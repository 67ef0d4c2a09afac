#!/usr/bin/env python3
"""Evidence-integrity gate: every ``BENCH_*.json`` / ``MULTICHIP_*.json``
record the prose cites must exist in the tree.

The ROADMAP carried the failure mode for four PRs: README/CHANGES/
COVERAGE cited worktree ledgers (``BENCH_AB_device_loop.json``,
``BENCH_AB_watch_frames.json``) that no commit ever added — the perf
record overstated its own evidence, and nothing failed.  This gate scans
the prose record for record names and exits 1, listing every offender
as ``path:line``, when a cited record is absent from the repo root.

A mention is NOT a citation when its line also says ``never committed``
or ``missing`` — an honest demotion is the record correcting itself,
and must stay expressible.

Run from anywhere: paths resolve against the repo root (this script's
parent's parent).
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LEDGER_RE = re.compile(r"(?:BENCH|MULTICHIP)_\w+\.json")
DEMOTION_RE = re.compile(r"never committed|missing", re.I)

PROSE_FILES = ["README.md", "CHANGES.md", "COVERAGE.md", "ROADMAP.md",
               "VERDICT.md"]


def check(root: str = ROOT) -> list[str]:
    """Every violation as ``path:line: <name> cited but absent``."""
    problems: list[str] = []
    for rel in PROSE_FILES:
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            continue
        with open(path, "r", encoding="utf-8") as f:
            for i, line in enumerate(f, start=1):
                if DEMOTION_RE.search(line):
                    continue
                for name in LEDGER_RE.findall(line):
                    if not os.path.exists(os.path.join(root, name)):
                        problems.append(
                            f"{rel}:{i}: {name} cited but absent from the "
                            f"repo root (commit the ledger, or demote the "
                            f"claim with 'never committed' on the citing "
                            f"line)")
    return problems


def main() -> int:
    problems = check()
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        print(f"check_ledgers: {len(problems)} phantom ledger citation(s) "
              f"— evidence-integrity gate FAILED", file=sys.stderr)
        return 1
    print("check_ledgers: every cited BENCH_*/MULTICHIP_*.json exists")
    return 0


if __name__ == "__main__":
    sys.exit(main())
