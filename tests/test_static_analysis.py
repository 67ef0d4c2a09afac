"""ktpu-analyze: the tier-1 gate plus the analyzer's own fixture tests.

``test_live_tree_clean`` is the commit gate: every future PR runs all
seven passes against the whole tree and fails on any unbaselined finding
(ISSUE 1 acceptance); ``test_analyzer_wall_time_budget`` keeps the gate
cheap enough to stay in tier 1.  The fixture tests pin the analyzer's
behavior to seeded violations with exact codes and locations, and pin
the exemptions (static bool flags, ``is None``, sorted() iteration,
lock-guarded writes, per-connection HTTP handlers, caller-held locks,
shadowed aliases, span-covered helpers, rebind-first donation use,
sanctioned sync sites, sticky-bucketed pads) so analyzer regressions
fail loudly in both directions.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from kubernetes_tpu.analysis import core as ana_core
from kubernetes_tpu.analysis.core import (
    BaselineError,
    load_baseline,
    repo_root,
    run_analysis,
)

ROOT = repo_root()
FIXTURES = "tests/analysis_fixtures"


def _fixture_line(rel_path: str, needle: str) -> int:
    """1-based line of the first source line containing ``needle`` — the
    'exact location' oracle that survives fixture reformatting."""
    with open(os.path.join(ROOT, rel_path), "r", encoding="utf-8") as f:
        for i, line in enumerate(f, start=1):
            if needle in line:
                return i
    raise AssertionError(f"{needle!r} not found in {rel_path}")


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live_report():
    baseline = load_baseline(ana_core.default_baseline_path())
    return run_analysis(root=ROOT, baseline=baseline)


def test_live_tree_clean(live_report):
    assert live_report.passes_run == list(ana_core.PASS_NAMES)
    assert live_report.findings == [], (
        "unbaselined static-analysis findings:\n"
        + "\n".join(f.format() for f in live_report.findings)
    )
    assert live_report.stale_suppressions == [], (
        "stale baseline entries (prune kubernetes_tpu/analysis/baseline.json):\n"
        + "\n".join(live_report.stale_suppressions)
    )


def test_analyzer_wall_time_budget(live_report):
    """The gate stays tier-1 only while it stays cheap: every pass must
    report a timing, and the whole seven-pass run must fit the budget
    (generous vs the ~7 s it takes today, tight enough to catch an
    accidental fixed-point blowup turning the lint quadratic)."""
    assert set(live_report.timings) == set(ana_core.PASS_NAMES)
    total = sum(live_report.timings.values())
    per_pass = {p: f"{t * 1000.0:.0f}ms" for p, t in live_report.timings.items()}
    assert total < 60.0, (
        f"ktpu-analyze took {total:.1f}s — over the tier-1 budget; "
        f"per-pass: {per_pass}"
    )


def test_every_baseline_entry_has_justification():
    baseline = load_baseline(ana_core.default_baseline_path())
    assert baseline, "baseline should exist (may be empty of entries)"
    for key, reason in baseline.items():
        assert reason.strip(), f"suppression {key} lacks a justification"


def test_cli_exit_codes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    # --no-baseline re-exposes whatever the baseline suppresses; the
    # expected exit derives from the baseline's CONTENT so a fully-fixed
    # tree (empty baseline) keeps this test green
    n_suppressed = len(load_baseline(ana_core.default_baseline_path()))
    as_json = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis", "--json", "--no-baseline"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    doc = json.loads(as_json.stdout)
    assert doc["passes"] == ["trace", "parity", "races", "metrics", "tracecov",
                             "device", "concurrency"]
    assert len(doc["findings"]) == n_suppressed, doc["findings"]
    assert as_json.returncode == (1 if n_suppressed else 0), as_json.stdout
    # stable key order: the emitted text IS the sorted serialization, so
    # CI can diff two runs' --json output textually
    assert as_json.stdout.strip() == json.dumps(doc, indent=2, sort_keys=True)
    # per-pass counts cover every requested pass, zeros included
    assert set(doc["counts"]) == set(ana_core.PASS_NAMES)
    for per in doc["counts"].values():
        assert set(per) == {"findings", "suppressed"}
        assert per["suppressed"] == 0  # --no-baseline suppresses nothing
    assert sum(per["findings"] for per in doc["counts"].values()) == n_suppressed
    assert set(doc["timings_ms"]) == set(ana_core.PASS_NAMES)


def test_cli_prune_baseline_round_trip(tmp_path):
    """--prune-baseline drops exactly the stale entries, preserving the
    _comment header and surviving entries' order and reasons; a second
    run against the pruned file is clean with no stale warnings."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(ana_core.default_baseline_path(), "r", encoding="utf-8") as f:
        doc = json.load(f)
    ghost = {"key": "RL999:nowhere.py:Ghost.method.attr", "reason": "points at nothing"}
    doc["suppressions"] = doc["suppressions"] + [ghost]
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(doc, indent=2) + "\n")

    # conflicting flags are a usage error, before any analysis runs
    conflict = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis",
         "--prune-baseline", "--no-baseline"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    assert conflict.returncode == 2, conflict.stderr

    pruned = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis",
         "--baseline", str(p), "--prune-baseline"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    assert pruned.returncode == 0, pruned.stdout + pruned.stderr
    # the prune report names the pass and code so retired entries are
    # auditable straight from the PR diff / CI log
    assert (f"pruned stale baseline entry [races RL999]: {ghost['key']}"
            in pruned.stderr)
    after = json.loads(p.read_text())
    assert after["_comment"] == doc["_comment"]
    assert after["suppressions"] == doc["suppressions"][:-1]  # order + reasons kept

    # round trip: the pruned file is now exactly the live baseline — a
    # --json re-run is clean, fully suppressed, and reports nothing stale
    rerun = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis",
         "--baseline", str(p), "--json", "--strict-baseline"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    assert rerun.returncode == 0, rerun.stdout + rerun.stderr
    redoc = json.loads(rerun.stdout)
    assert redoc["findings"] == []
    assert redoc["stale_suppressions"] == []
    assert len(redoc["suppressed"]) == len(after["suppressions"])
    assert (sum(per["suppressed"] for per in redoc["counts"].values())
            == len(after["suppressions"]))


def test_prune_baseline_function_edge_cases(tmp_path):
    from kubernetes_tpu.analysis.core import prune_baseline

    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"suppressions": [
        {"key": "TS101:a.py:f.float", "reason": "seeded"}]}))
    before = p.read_text()
    # no stale keys -> nothing removed, file not rewritten
    assert prune_baseline(str(p), []) == []
    assert prune_baseline(str(p), ["TS999:ghost.py:g.h"]) == []
    assert p.read_text() == before
    # malformed baselines raise rather than silently truncating
    p.write_text("not json")
    with pytest.raises(BaselineError):
        prune_baseline(str(p), ["TS101:a.py:f.float"])


# ---------------------------------------------------------------------------
# trace-safety fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trace_findings():
    report = run_analysis(
        root=ROOT,
        passes=["trace"],
        scopes={"trace": {"paths": [f"{FIXTURES}/fixture_trace_safety.py"]}},
    )
    return report.findings


def test_trace_fixture_codes_and_locations(trace_findings):
    path = f"{FIXTURES}/fixture_trace_safety.py"
    got = {(f.code, f.symbol): f.line for f in trace_findings}
    expected = {
        ("TS101", "bad_host_escape.float"): _fixture_line(path, "float(x[0])"),
        ("TS101", "bad_item_escape.item"): _fixture_line(path, "x.sum().item()"),
        ("TS101", "bad_np_call.np.argsort"): _fixture_line(path, "np.argsort(x)"),
        ("TS102", "bad_branch.if.total"): _fixture_line(path, "if total > 0:"),
        ("TS102", "bad_loop_body.if.state"): _fixture_line(path, "if state:"),
        ("TS103", "bad_set_feed.set-iter"): _fixture_line(path, "hash(k) for k in ids"),
        # interprocedural taint (ISSUE 4 satellite): helpers reached via
        # functools.partial (direct + module alias), bound-method
        # references, and self.method() calls from traced bodies
        ("TS102", "bad_partial_step.if.state"): _fixture_line(
            path, "if state:  # TS102 through the partial reference"),
        ("TS102", "bad_alias_step.if.state"): _fixture_line(
            path, "if state:  # TS102 through a module-level partial alias"),
        ("TS102", "MethodStepper._bad_method_step.if.state"): _fixture_line(
            path, "if state:  # TS102 through a bound-method reference"),
        ("TS101", "MethodStepper._bad_helper.float"): _fixture_line(
            path, "n = float(x.sum())"),
    }
    for key, line in expected.items():
        assert key in got, f"missing finding {key}; got {sorted(got)}"
        assert got[key] == line, f"{key}: reported line {got[key]}, expected {line}"


def test_trace_fixture_exemptions_stay_clean(trace_findings):
    flagged = {f.symbol for f in trace_findings}
    for clean_fn in ("clean_static_flag", "clean_is_none", "clean_sorted_feed"):
        assert not any(s.startswith(clean_fn) for s in flagged), (
            f"exempt pattern {clean_fn} was flagged: {sorted(flagged)}"
        )


# ---------------------------------------------------------------------------
# parity fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity_findings():
    report = run_analysis(
        root=ROOT,
        passes=["parity"],
        scopes={
            "parity": {
                "oracle_paths": [f"{FIXTURES}/fixture_parity_oracle.py"],
                "kernel_paths": [f"{FIXTURES}/fixture_parity_kernel.py"],
            }
        },
    )
    return report.findings


def test_parity_fixture_codes_and_locations(parity_findings):
    oracle = f"{FIXTURES}/fixture_parity_oracle.py"
    kernel = f"{FIXTURES}/fixture_parity_kernel.py"
    got = {(f.code, f.symbol): (f.path, f.line) for f in parity_findings}
    expected = {
        ("PC201", "unmapped.CheckBeta"): (oracle, _fixture_line(oracle, '"CheckBeta"')),
        ("PC201", "unmapped.make_fixture_factory"): (
            oracle, _fixture_line(oracle, "def make_fixture_factory"),
        ),
        ("PC202", "unmapped.UnmappedPriority"): (
            oracle, _fixture_line(oracle, "class UnmappedPriority"),
        ),
        ("PC203", "implements.CheckRenamedAway"): (
            kernel, _fixture_line(kernel, "implements CheckRenamedAway"),
        ),
        ("PC204", "fallback.CheckStale"): (oracle, _fixture_line(oracle, '"CheckStale"')),
        ("PC205", "fallback.CheckUnjustified"): (
            oracle, _fixture_line(oracle, '"CheckUnjustified"'),
        ),
        # reachability (ISSUE 3 satellite): ignored markers are reported
        # AND their entities revert to unmapped
        ("PC206", "marker.CheckFloating"): (
            kernel, _fixture_line(kernel, "implements CheckFloating"),
        ),
        ("PC206", "marker.CheckDead"): (
            kernel, _fixture_line(kernel, "implements CheckDead"),
        ),
        ("PC201", "unmapped.CheckFloating"): (
            oracle, _fixture_line(oracle, '"CheckFloating"'),
        ),
        ("PC201", "unmapped.CheckDead"): (
            oracle, _fixture_line(oracle, '"CheckDead"'),
        ),
    }
    assert got == expected


def test_parity_fixture_mapped_entities_stay_clean(parity_findings):
    symbols = {f.symbol for f in parity_findings}
    # CheckChained's marker sits in a PRIVATE helper reachable only
    # through the public fixture_entry; CheckCtor's sits in the __init__
    # of a private class the public entry instantiates — the call graph
    # must count both
    for clean in ("CheckAlpha", "MappedPriority", "CheckGamma", "CheckChained",
                  "CheckCtor"):
        assert not any(clean in s for s in symbols), sorted(symbols)


# ---------------------------------------------------------------------------
# race-lint fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def race_findings():
    report = run_analysis(
        root=ROOT,
        passes=["races"],
        scopes={"races": {"paths": [f"{FIXTURES}/fixture_races.py"]}},
    )
    return report.findings


def test_race_fixture_codes_and_locations(race_findings):
    path = f"{FIXTURES}/fixture_races.py"
    got = {(f.code, f.symbol) for f in race_findings}
    expected = {
        ("RL301", "UnlockedCounter._bump.count"),
        ("RL303", "UnlockedContainers._worker._pending"),
        ("RL303", "UnlockedContainers._worker._heap"),
        ("RL302", "LockOrderCycle.lockcycle._a-_b"),
        ("RL303", "HandlerCallbacks._on_add._index"),
        # ISSUE 5: mutations through single-assignment local aliases
        ("RL303", "AliasedMutations._worker._pending"),
        ("RL303", "AliasedMutations._worker._queue"),
        ("RL303", "AliasedMutations._worker._heap"),
        # ISSUE 6: chains of single-assignment aliases (fixed point)
        ("RL303", "TwoHopAliasedMutations._worker._twohop"),
        ("RL303", "TwoHopAliasedMutations._worker._threehop"),
        # ISSUE 10: aliases through calls and returns (per-function
        # return summaries — self-attr, argument, module function)
        ("RL303", "AliasThroughCall._worker._returned"),
        ("RL303", "AliasThroughCall._worker._arged"),
        ("RL303", "AliasThroughCall._worker._routed"),
        # ISSUE 10: captures by nested defs/lambdas, one-hop element
        # extraction, cross-object lock-order edges
        ("RL303", "NestedDefCapture._worker._items"),
        ("RL303", "ContainerExtraction._worker._slots"),
        ("RL302", "CrossObjectLockOrder.lockcycle._a-queue._mu"),
        # ISSUE 10: cross-object reachability — the unlocked collaborator
        # is flagged at ITS class, with the external entry in the message
        ("RL303", "UnlockedHelper.bump._stats"),
        # ISSUE 15: single-assignment tuple unpacking aliases pairwise
        ("RL303", "TupleUnpackAliases._worker._tup_a"),
        ("RL303", "TupleUnpackAliases._worker._tup_b"),
        ("RL303", "TupleUnpackAliases._worker._tup_elems"),
        # ISSUE 16: call-returned tuple summaries unpack positionally
        ("RL303", "CallTupleUnpackAliases._worker._ct_a"),
        ("RL303", "CallTupleUnpackAliases._worker._ct_b"),
        ("RL303", "CallTupleUnpackAliases._worker._ct_routed"),
        # ISSUE 16: one starred target aligns prefix and suffix
        ("RL303", "StarredUnpackAliases._worker._st_head"),
        ("RL303", "StarredUnpackAliases._worker._st_tail"),
    }
    assert got == expected, f"got {sorted(got)}"
    by_symbol = {f.symbol: f.line for f in race_findings}
    assert by_symbol["UnlockedCounter._bump.count"] == _fixture_line(
        path, "self.count = self.count + 1"
    )
    assert by_symbol["UnlockedContainers._worker._pending"] == _fixture_line(
        path, 'self._pending["k"] = 1'
    )
    assert by_symbol["HandlerCallbacks._on_add._index"] == _fixture_line(
        path, "self._index[obj.key] = obj"
    )
    assert by_symbol["TwoHopAliasedMutations._worker._twohop"] == _fixture_line(
        path, 'u["k"] = 1  # RL303 via two-hop alias chain'
    )
    assert by_symbol["AliasThroughCall._worker._returned"] == _fixture_line(
        path, 'q["k"] = 1  # RL303 via returns-self-attr summary'
    )
    assert by_symbol["AliasThroughCall._worker._arged"] == _fixture_line(
        path, 'r["k"] = 1  # RL303 via returns-argument summary'
    )
    assert by_symbol["AliasThroughCall._worker._routed"] == _fixture_line(
        path, 's["k"] = 1  # RL303 via module-function summary'
    )
    assert by_symbol["NestedDefCapture._worker._items"] == _fixture_line(
        path, 'self._items["k"] = 1  # RL303: captured by a nested def'
    )
    assert by_symbol["ContainerExtraction._worker._slots"] == _fixture_line(
        path, "slot.append(1)  # RL303 on _slots via one-hop element extraction"
    )
    assert by_symbol["UnlockedHelper.bump._stats"] == _fixture_line(
        path, "self._stats[k] = self._stats.get(k, 0) + 1"
    )
    assert by_symbol["TupleUnpackAliases._worker._tup_a"] == _fixture_line(
        path, 'a["k"] = 1  # RL303 on _tup_a via tuple unpacking'
    )
    assert by_symbol["TupleUnpackAliases._worker._tup_b"] == _fixture_line(
        path, 'b.append("k")  # RL303 on _tup_b via tuple unpacking'
    )
    assert by_symbol["TupleUnpackAliases._worker._tup_elems"] == _fixture_line(
        path, "e.append(1)  # RL303 on _tup_elems via element pair in an unpack"
    )
    assert by_symbol["CallTupleUnpackAliases._worker._ct_a"] == _fixture_line(
        path, 'a["k"] = 1  # RL303 on _ct_a via call-returned tuple unpacking'
    )
    assert by_symbol["CallTupleUnpackAliases._worker._ct_b"] == _fixture_line(
        path, 'b.append("k")  # RL303 on _ct_b via call-returned tuple unpacking'
    )
    assert by_symbol["CallTupleUnpackAliases._worker._ct_routed"] == _fixture_line(
        path, 'r["k"] = 1  # RL303 on _ct_routed via arg element of a tuple summary'
    )
    assert by_symbol["StarredUnpackAliases._worker._st_head"] == _fixture_line(
        path, 'head["k"] = 1  # RL303 on _st_head via starred-unpack prefix'
    )
    assert by_symbol["StarredUnpackAliases._worker._st_tail"] == _fixture_line(
        path, 'tail.append("k")  # RL303 on _st_tail via starred-unpack suffix'
    )
    messages = {f.symbol: f.message for f in race_findings}
    assert "via alias `u`" in messages["TwoHopAliasedMutations._worker._twohop"]
    assert "via alias `c`" in messages["TwoHopAliasedMutations._worker._threehop"]
    assert "via alias `q`" in messages["AliasThroughCall._worker._returned"]
    assert "in nested def `flush`" in messages["NestedDefCapture._worker._items"]
    assert ("via element `slot` of self._slots"
            in messages["ContainerExtraction._worker._slots"])
    # the cross-object finding names HOW the thread reaches the method
    assert ("entry: bump<-CrossObjectDriver._worker"
            in messages["UnlockedHelper.bump._stats"])
    # the cross-object cycle carries the dotted collaborator lock path
    cyc = messages["CrossObjectLockOrder.lockcycle._a-queue._mu"]
    assert "_a -> queue._mu -> _a" in cyc
    assert "CrossObjectLockOrder.forward" in cyc


def test_race_fixture_exemptions_stay_clean(race_findings):
    symbols = {f.symbol for f in race_findings}
    for clean in (
        "GuardedCounter",
        "PerRequestHandler",
        "AliasExemptions",
        # ISSUE 10 silences: the collaborator guarded by its own lock,
        # writes under the collaborator's lock (cross-object lock
        # identity), the driver itself (it only calls), caller-held-lock
        # propagation, and shadowed/locked alias shapes
        "LockedHelper",
        "CrossObjectDriver",
        "CrossObjectLockGuard",
        "CallerHeldHelper",
        "CrossShapeExemptions",
        # ISSUE 16 silences: arity-mismatched or disagreeing call
        # tuples, starred targets against calls, starred elements on
        # the value side, rebound unpacked names, and lock-guarded
        # unpacked aliases
        "TupleUnpackExemptions",
    ):
        assert not any(s.startswith(clean) for s in symbols), sorted(symbols)


# ---------------------------------------------------------------------------
# metrics-name lint fixtures (ISSUE 7)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def metrics_findings():
    report = run_analysis(
        root=ROOT,
        passes=["metrics"],
        scopes={"metrics": {"paths": [f"{FIXTURES}/fixture_metrics.py"]}},
    )
    return report.findings


def test_metrics_fixture_codes_and_locations(metrics_findings):
    path = f"{FIXTURES}/fixture_metrics.py"
    got = {(f.code, f.symbol) for f in metrics_findings}
    expected = {
        ("MN401", "build_bad_registry.BadCamel_total"),
        ("MN401", "build_bad_registry.scheduler-dashes-gauge"),
        ("MN402", "build_bad_registry.client_things_seen"),
        ("MN403", "build_bad_registry.scheduler_wait"),
        ("MN404", "duplicate_registrations.dup_metric_total"),
        # SLIs over unregistered metric names: keyword and positional
        ("MN405", "slo_specs.fixture_missing_latency_microseconds"),
        ("MN405", "slo_specs.fixture_missing_bad_total"),
        ("MN405", "slo_specs.fixture_missing_all_total"),
    }
    assert got == expected, f"got {sorted(got)}"
    by_key = {(f.code, f.symbol): f.line for f in metrics_findings}
    assert by_key[("MN402", "build_bad_registry.client_things_seen")] == (
        _fixture_line(path, 'Counter("client_things_seen")'))
    assert by_key[("MN404", "duplicate_registrations.dup_metric_total")] == (
        _fixture_line(path, 'second = Counter("dup_metric_total")'))
    messages = {f.symbol: f.message for f in metrics_findings}
    # the duplicate finding names the FIRST registration site
    assert "first registered at" in messages[
        "duplicate_registrations.dup_metric_total"]
    # the blind-SLO finding says what it means for the burn-rate engine
    assert "permanently blind" in messages[
        "slo_specs.fixture_missing_latency_microseconds"]


def test_metrics_fixture_exemptions_stay_clean(metrics_findings):
    symbols = {f.symbol for f in metrics_findings}
    # conforming names, and the stdlib collections.Counter (no metrics
    # import binds that name) must produce nothing
    assert not any(s.startswith("Clean") for s in symbols), sorted(symbols)


# ---------------------------------------------------------------------------
# trace-coverage fixtures (ISSUE 10)
# ---------------------------------------------------------------------------

TC_PATH = f"{FIXTURES}/fixture_tracecov.py"
TC_HOT_PATH = f"{FIXTURES}/fixture_tracecov_hot.py"
TC_PHASE_PATH = f"{FIXTURES}/fixture_tracecov_phase.py"
TC_SCOPE = {
    # the phase fixture is SCANNED but deliberately absent from
    # hot_modules: its wave-phase spans must trip TC504
    "paths": [TC_PATH, TC_HOT_PATH, TC_PHASE_PATH],
    "hot_modules": [TC_PATH, TC_HOT_PATH],
    "phase_files": [TC_PATH],
}


@pytest.fixture(scope="module")
def tracecov_findings():
    report = run_analysis(
        root=ROOT, passes=["tracecov"], scopes={"tracecov": TC_SCOPE}
    )
    return report.findings


def test_tracecov_fixture_codes_and_locations(tracecov_findings):
    got = {(f.code, f.path, f.symbol): f.line for f in tracecov_findings}
    expected = {
        # fault seams outside any span: module level, a function with no
        # marker and no callers, and a helper whose only caller is bare
        ("TC501", TC_PATH, "<module>.fixture.module"): _fixture_line(
            TC_PATH, 'faults.hit("fixture.module")'),
        ("TC501", TC_PATH, "unspanned_seam.fixture.unspanned"): _fixture_line(
            TC_PATH, 'faults.hit("fixture.unspanned")'),
        ("TC501", TC_PATH, "_orphan_helper.fixture.orphan"): _fixture_line(
            TC_PATH, 'faults.hit("fixture.orphan")'),
        # a phase timer with no .complete() twin in the same function
        ("TC502", TC_PATH, "PhaseTimers.bad_phase.bad_s"): _fixture_line(
            TC_PATH, 'self.stats["bad_s"] += t1 - t0'),
        # the marker-free hot-path module; the marker-BEARING hot module
        # (fixture_tracecov.py itself is in the hot scope) stays silent
        ("TC503", TC_HOT_PATH, "<module>"): 1,
        # wave-phase spans from outside the hot scope anchor at the FIRST
        # wave-phase marker — the .wave( call, NOT the earlier
        # cat="trace" complete (background categories are exempt)
        ("TC504", TC_PHASE_PATH, "<module>"): _fixture_line(
            TC_PHASE_PATH, "with (tr.wave(len(pods))"),
    }
    assert got == expected, f"got {sorted(got)}"
    messages = {f.path + ":" + f.symbol: f.message for f in tracecov_findings}
    assert "dump-on-fault here has no trace context" in messages[
        TC_PATH + ":unspanned_seam.fixture.unspanned"]
    assert "`.complete('bad', ...)`" in messages[
        TC_PATH + ":PhaseTimers.bad_phase.bad_s"]
    assert "the tracing layer is not even imported" in messages[
        TC_HOT_PATH + ":<module>"]
    assert "not listed in HOT_PATH_MODULES" in messages[
        TC_PHASE_PATH + ":<module>"]


def test_tracecov_fixture_exemptions_stay_clean(tracecov_findings):
    symbols = {f.symbol for f in tracecov_findings}
    for clean in (
        "spanned_seam",     # own span marker
        "_helper_seam",     # every caller covered (fixed-point rule)
        "covered_caller",
        "PhaseTimers.good_phase",  # timer mirrored via .complete("good")
    ):
        assert not any(s.startswith(clean) for s in symbols), sorted(symbols)


def test_tracecov_scope_mismatch_fails_loud():
    """A hot/phase scope entry naming a file outside the scanned set is a
    TC500 config finding, not a silent no-op."""
    report = run_analysis(
        root=ROOT,
        passes=["tracecov"],
        scopes={"tracecov": {
            "paths": [TC_PATH],
            "hot_modules": ["kubernetes_tpu/ops/renamed_away.py"],
            "phase_files": [],
        }},
    )
    got = {(f.code, f.path, f.symbol) for f in report.findings}
    assert ("TC500", "kubernetes_tpu/ops/renamed_away.py", "<scope>") in got, got


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"suppressions": [{"key": "TS101:a.py:f.float"}]}))
    with pytest.raises(BaselineError):
        load_baseline(str(p))
    p.write_text(
        json.dumps({"suppressions": [{"key": "TS101:a.py:f.float", "reason": "  "}]})
    )
    with pytest.raises(BaselineError):
        load_baseline(str(p))
    p.write_text("not json")
    with pytest.raises(BaselineError):
        load_baseline(str(p))


def test_baseline_suppresses_and_reports_stale():
    baseline = {
        "TS101:tests/analysis_fixtures/fixture_trace_safety.py:bad_host_escape.float": "seeded",
        "TS999:nowhere.py:ghost.symbol": "points at nothing",
    }
    report = run_analysis(
        root=ROOT,
        passes=["trace"],
        baseline=baseline,
        scopes={"trace": {"paths": [f"{FIXTURES}/fixture_trace_safety.py"]}},
    )
    suppressed = {f.symbol for f in report.suppressed}
    assert "bad_host_escape.float" in suppressed
    live = {f.symbol for f in report.findings}
    assert "bad_host_escape.float" not in live
    assert "bad_item_escape.item" in live  # others still reported
    assert report.stale_suppressions == ["TS999:nowhere.py:ghost.symbol"]


def test_finding_keys_are_line_independent():
    report = run_analysis(
        root=ROOT,
        passes=["trace"],
        scopes={"trace": {"paths": [f"{FIXTURES}/fixture_trace_safety.py"]}},
    )
    for f in report.findings:
        assert str(f.line) not in f.key.split(":")[-1], (
            "baseline keys must not embed line numbers (they'd rot on every "
            f"edit above the finding): {f.key}"
        )


# ---------------------------------------------------------------------------
# device-contract fixtures (ISSUE 15)
# ---------------------------------------------------------------------------

DC_PATH = f"{FIXTURES}/fixture_device_contracts.py"
DC_SCOPE = {"paths": [DC_PATH], "hot_modules": [DC_PATH]}


@pytest.fixture(scope="module")
def device_findings():
    report = run_analysis(
        root=ROOT, passes=["device"], scopes={"device": DC_SCOPE}
    )
    return report.findings


def test_device_fixture_codes_and_locations(device_findings):
    got = {(f.code, f.symbol): f.line for f in device_findings}
    ann_stale = _fixture_line(DC_PATH, "# device: sync — nothing materializes")
    ann_reasonless = _fixture_line(DC_PATH, "# device: sync\n")
    ann_static = _fixture_line(DC_PATH, "# device: static\n")
    expected = {
        # DC601: donated carry read after dispatch, before the rebind —
        # directly and through a one-hop callee
        ("DC601", "FixtureLoop.dispatch_bad._state"): _fixture_line(
            DC_PATH, "stale = self._state"),
        ("DC601", "FixtureLoop.dispatch_callee_bad._state._peek"): _fixture_line(
            DC_PATH, "self._peek()"),
        # DC602: unsanctioned host materialization of a device value
        ("DC602", "FixtureLoop.sync_bad._state"): _fixture_line(
            DC_PATH, "n = int(jnp.sum(self._state))"),
        ("DC602", "reasonless_sync.dev"): _fixture_line(
            DC_PATH, "n = int(jnp.sum(dev))"),
        # DC603: bare pad, pow2 width, un-normalized compile key
        ("DC603", "pad_bad._pad_to"): _fixture_line(
            DC_PATH, "return _pad_to(n, 8)"),
        ("DC603", "width_bad._pow2_width"): _fixture_line(
            DC_PATH, "return _pow2_width(n, 8)"),
        ("DC603", "factory_call_bad._fixture_runner.static.chunk"): _fixture_line(
            DC_PATH, "run = _fixture_runner(static.chunk)"),
        # DC604: snapshot NodeInfo mutated without mutable_info — mutator
        # through a local, a direct map subscript, and an attribute store
        ("DC604", "fixture_schedule.apply_bad.raw.add_pod"): _fixture_line(
            DC_PATH, "raw.add_pod(pod)"),
        ("DC604", "fixture_schedule.apply_bad.work_map.remove_pod"): _fixture_line(
            DC_PATH, "work_map[name].remove_pod(pod)"),
        ("DC604", "fixture_schedule.apply_bad.raw.node"): _fixture_line(
            DC_PATH, "raw.node = None"),
        # ... and a by-node batched write to an uncloned NodeInfo
        ("DC604", "fixture_schedule.place_bad.stale.add_pods_counted"):
            _fixture_line(DC_PATH, "stale.add_pods_counted(pods"),
        # DC605: stale sync, reasonless sync, unused static
        ("DC605", f"stale_sync_annotation.L{ann_stale}"): ann_stale,
        ("DC605", f"reasonless_sync.L{ann_reasonless}"): ann_reasonless,
        ("DC605", f"stale_static_annotation.L{ann_static}"): ann_static,
    }
    assert got == expected, f"got {sorted(got)}"
    messages = {f.symbol: f.message for f in device_findings}
    # the donation finding names the donated arg and the dispatch line
    assert "was donated" in messages["FixtureLoop.dispatch_bad._state"]
    assert "rebind" in messages["FixtureLoop.dispatch_bad._state"]
    # the callee-hop finding names the callee that reads the dead buffer
    assert "FixtureLoop._peek" in messages[
        "FixtureLoop.dispatch_callee_bad._state._peek"]
    # the sync finding teaches the annotation grammar
    assert "# device: sync — <reason>" in messages["FixtureLoop.sync_bad._state"]
    # the CoW finding names the sanctioned route
    assert "mutable_info" in messages["fixture_schedule.apply_bad.raw.add_pod"]


def test_device_fixture_exemptions_stay_clean(device_findings):
    symbols = {f.symbol for f in device_findings}
    for clean in (
        "FixtureLoop.dispatch_ok",   # rebind-first donation use
        "FixtureLoop.sync_ok",       # sanctioned sync site
        "pad_ok_sticky",             # pad routed through _sticky_pad
        "pad_ok_annotated",          # pad under a # device: static
        "width_ok",                  # width under a # device: static
        "factory_call_ok",           # int()-normalized compile key
        "fixture_schedule.apply_ok",  # mutation through mutable_info
        "fixture_schedule.place_ok",  # batched write through mutable_info
    ):
        assert not any(s.startswith(clean) for s in symbols), sorted(symbols)


def test_device_pass_catches_seeded_donation_bug(tmp_path):
    """Re-introducing the donated-carry-reuse bug into a copy of the real
    batch_kernel (reading self._state after the loop dispatch but before
    the rebind) is caught; the untouched copy is clean — so the finding
    is the seeded bug, not scanner noise."""
    from kubernetes_tpu.analysis import device_contracts as dc

    with open(os.path.join(ROOT, "kubernetes_tpu/ops/batch_kernel.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "bk_clean.py").write_text(src)
    assert dc.run(str(tmp_path), paths=["bk_clean.py"]) == []
    rebind = "self._state, self._buf = out[0], out[1]"
    assert rebind in src
    (tmp_path / "bk_bug.py").write_text(src.replace(
        rebind, "stale_probe = jnp.sum(self._state)\n            " + rebind, 1))
    got = {(f.code, f.symbol)
           for f in dc.run(str(tmp_path), paths=["bk_bug.py"])}
    assert ("DC601", "FrontierRun._dispatch_loop._state") in got, got


@pytest.mark.parametrize("sanctioned, bypass, mutator", [
    # the oracle path's per-pod write
    ("info = mutable_info(node_name)", "info = work_map.get(node_name)",
     "info.add_pod"),
    # the kernel path's by-node batched write
    ("node_info = mutable_info(node_names[c])",
     "node_info = work_map.get(node_names[c])",
     "node_info.add_pods_counted"),
], ids=["per-pod", "by-node"])
def test_device_pass_catches_seeded_cow_bypass(tmp_path, sanctioned, bypass,
                                               mutator):
    """Replacing one of backend.schedule_batch's `mutable_info(...)` with a
    raw `work_map.get(...)` — the exact regression the ROADMAP caveat
    warned about — is caught at that mutation site; the untouched copy is
    clean."""
    from kubernetes_tpu.analysis import device_contracts as dc

    with open(os.path.join(ROOT, "kubernetes_tpu/ops/backend.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "be_clean.py").write_text(src)
    assert dc.run(str(tmp_path), paths=["be_clean.py"]) == []
    assert src.count(sanctioned) == 1
    (tmp_path / "be_bug.py").write_text(src.replace(sanctioned, bypass))
    got = {(f.code, f.symbol)
           for f in dc.run(str(tmp_path), paths=["be_bug.py"])}
    symbols = {s for c, s in got if c == "DC604"}
    assert any(s.endswith(mutator) for s in symbols), got


def test_sanctioned_sync_sites_counts():
    """The static sync budget the runtime cross-check leans on: every
    live annotation in FrontierRun is counted under its function, and
    invalid (stale/reasonless) annotations never count."""
    from kubernetes_tpu.analysis.device_contracts import sanctioned_sync_sites

    sites = sanctioned_sync_sites(ROOT)
    bk = sites["kubernetes_tpu/ops/batch_kernel.py"]
    # 4th site: the per-shard alive snapshot rides the loop-exit
    # transfer (ISSUE 18 — sharded wave loop attribution)
    assert bk["FrontierRun._sync_loop"] == 4
    assert bk["FrontierRun._finalize_loop"] == 2
    assert bk["FrontierRun._maybe_compact"] == 2
    assert bk["FrontierRun.finalize"] == 2
    fx = sanctioned_sync_sites(ROOT, paths=[DC_PATH])[DC_PATH]
    assert fx == {"FixtureLoop.sync_ok": 1}


# ---------------------------------------------------------------------------
# --changed: git-diff-scoped reporting (ISSUE 15)
# ---------------------------------------------------------------------------


def test_changed_files_unit(tmp_path):
    from kubernetes_tpu.analysis.__main__ import _changed_files

    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    (tmp_path / "a.py").write_text("x = 1\n")
    subprocess.run(["git", "add", "a.py"], cwd=tmp_path, check=True)
    subprocess.run(
        ["git", "-c", "user.email=t@example.com", "-c", "user.name=t",
         "commit", "-q", "-m", "seed"],
        cwd=tmp_path, check=True,
    )
    (tmp_path / "a.py").write_text("x = 2\n")   # modified vs HEAD
    (tmp_path / "b.py").write_text("y = 1\n")   # untracked
    assert _changed_files(str(tmp_path), "HEAD") == {"a.py", "b.py"}
    with pytest.raises(ValueError):
        _changed_files(str(tmp_path), "definitely-not-a-ref")


def test_cli_changed_scopes_report_to_diff():
    """--changed filters the REPORT to files changed vs the ref (plus
    untracked), while the full scope still runs — all seven passes, full
    timings; a bad ref is exit 2, never a silently-empty green run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis",
         "--changed=definitely-not-a-ref"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    assert bad.returncode == 2, bad.stdout + bad.stderr
    assert "--changed" in bad.stderr

    full = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis", "--json",
         "--no-baseline"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    full_doc = json.loads(full.stdout)
    scoped = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis", "--json",
         "--no-baseline", "--changed=HEAD", "--profile"],
        cwd=ROOT, capture_output=True, text=True, env=env,
    )
    doc = json.loads(scoped.stdout)
    # compute the changed set exactly as the CLI does, so the expectation
    # is deterministic whatever state the working tree is in
    diff = subprocess.run(["git", "diff", "--name-only", "HEAD", "--"],
                          cwd=ROOT, capture_output=True, text=True)
    untracked = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard"],
        cwd=ROOT, capture_output=True, text=True)
    changed = {ln.strip() for ln in diff.stdout.splitlines() if ln.strip()}
    changed |= {ln.strip() for ln in untracked.stdout.splitlines() if ln.strip()}
    expected = [f for f in full_doc["findings"] if f["path"] in changed]
    assert doc["findings"] == expected
    assert scoped.returncode == (1 if expected else 0), scoped.stdout
    # the whole scope still ran: every pass reports, timings included,
    # and --profile output is preserved alongside --changed
    assert doc["passes"] == list(ana_core.PASS_NAMES)
    assert set(doc["timings_ms"]) == set(ana_core.PASS_NAMES)
    assert scoped.stderr.count("profile:") == len(ana_core.PASS_NAMES)


# ---------------------------------------------------------------------------
# concurrency-hazard fixtures (ISSUE 16)
# ---------------------------------------------------------------------------

CH_PATH = f"{FIXTURES}/fixture_concurrency.py"


@pytest.fixture(scope="module")
def concurrency_findings():
    report = run_analysis(
        root=ROOT,
        passes=["concurrency"],
        scopes={"concurrency": {"paths": [CH_PATH]}},
    )
    return report.findings


def test_concurrency_fixture_codes_and_locations(concurrency_findings):
    got = {(f.code, f.symbol) for f in concurrency_findings}
    expected = {
        # CH701: blocking shapes under a held lock — lexical, and in a
        # private helper the caller-held fixed point proves always-locked
        ("CH701", "BlockingUnderLock._worker.time.sleep"),
        ("CH701", "BlockingUnderLock._worker.self._evt.wait"),
        ("CH701", "BlockingUnderLock._worker.self._arr.item"),
        ("CH701", "BlockingUnderLock._drain.self._sock.sendall"),
        ("CH701", "BlockingUnderLock.shutdown.self._t.join"),
        ("CH701", "BlockingUnderLock.persist_bad.os.fsync"),
        # CH702: broad handlers whose body does nothing with the error
        ("CH702", "fixture_swallow_module.swallow1"),
        ("CH702", "SwallowedExceptions.poll.swallow1"),
        ("CH702", "SwallowedExceptions.drain.swallow1"),
        ("CH702", "SwallowedExceptions.quiet_return.swallow1"),
        # CH703: leaked threads / handles / armed context managers
        ("CH703", "fixture_leaky_thread.thread.t"),
        ("CH703", "fixture_fire_and_forget.thread.anonymous"),
        ("CH703", "fixture_leaky_open.open.fh"),
        ("CH703", "fixture_manual_enter.enter.plan"),
        ("CH703", "AttrThreadLeak.__init__.thread._t"),
        ("CH703", "ArmedPlanLeak.arm.enter._plan"),
        # CH704: third-party callbacks invoked under a held lock
        ("CH704", "CallbacksUnderLock.fire_direct.h.on_add"),
        ("CH704", "CallbacksUnderLock.fire_dispatch.h.on_add"),
        ("CH704", "CallbacksUnderLock.fire_param.callback"),
        ("CH704", "CallbacksUnderLock.fire_alias.h"),
        # CH705: unbounded growth on daemon paths
        ("CH705", "UnboundedGrowth.__init__._q"),
        ("CH705", "UnboundedGrowth.__init__._sq"),
        ("CH705", "UnboundedGrowth._worker._backlog"),
        ("CH705", "UnboundedGrowth._worker._seen"),
    }
    assert got == expected, f"got {sorted(got)}"
    by_symbol = {f.symbol: f.line for f in concurrency_findings}
    assert by_symbol["BlockingUnderLock._worker.time.sleep"] == _fixture_line(
        CH_PATH, "time.sleep(0.05)  # CH701: sleep while holding _mu"
    )
    assert by_symbol["BlockingUnderLock._drain.self._sock.sendall"] == _fixture_line(
        CH_PATH, 'self._sock.sendall(b"x")  # CH701: caller-held _mu blocks the send'
    )
    assert by_symbol["BlockingUnderLock.persist_bad.os.fsync"] == _fixture_line(
        CH_PATH, "os.fsync(self._fd)  # CH701: a reasonless annotation sanctions nothing"
    )
    assert by_symbol["SwallowedExceptions.poll.swallow1"] == _fixture_line(
        CH_PATH, "except:  # CH702: bare swallow in the poll loop"
    )
    assert by_symbol["fixture_leaky_open.open.fh"] == _fixture_line(
        CH_PATH, "fh = open(path)  # CH703: never closed, never escapes"
    )
    assert by_symbol["AttrThreadLeak.__init__.thread._t"] == _fixture_line(
        CH_PATH, "self._t = threading.Thread(target=self._run)  # CH703: no join anywhere in the class"
    )
    assert by_symbol["CallbacksUnderLock.fire_dispatch.h.on_add"] == _fixture_line(
        CH_PATH, "self._deliver(h.on_add, obj)  # CH704: bound method handed to a dispatcher under _mu"
    )
    assert by_symbol["UnboundedGrowth._worker._backlog"] == _fixture_line(
        CH_PATH, "self._backlog.append(item)  # CH705: grows and nothing ever shrinks it"
    )
    messages = {f.symbol: f.message for f in concurrency_findings}
    # the blocking finding names the held lock and teaches the annotation
    assert "_mu" in messages["BlockingUnderLock._worker.time.sleep"]
    assert "# blocking-ok — <reason>" in messages[
        "BlockingUnderLock._worker.time.sleep"]
    # the callback finding names the source and the sanctioned contract
    assert "self._handlers" in messages["CallbacksUnderLock.fire_direct.h.on_add"]
    assert "_deliver" in messages["CallbacksUnderLock.fire_direct.h.on_add"]
    assert "parameter `callback`" in messages["CallbacksUnderLock.fire_param.callback"]
    # the growth finding names the thread entry that makes it a daemon path
    assert "_worker" in messages["UnboundedGrowth._worker._backlog"]
    assert "# bounded: <reason>" in messages["UnboundedGrowth._worker._backlog"]


def test_concurrency_fixture_exemptions_stay_clean(concurrency_findings):
    symbols = {f.symbol for f in concurrency_findings}
    for clean in (
        # CH701 silences: Condition.wait releases the lock, str.join,
        # nested defs, a REASONED # blocking-ok annotation
        "BlockingUnderLock.persist.",
        "BlockingUnderLock.label",
        "BlockingUnderLock.spawn_later",
        "BlockingUnderLock.flush",
        # CH702 silences: counted / re-raised / logged / narrow handlers
        "SwallowedExceptions.counted",
        "SwallowedExceptions.reraise",
        "SwallowedExceptions.logged",
        "SwallowedExceptions.narrow",
        # CH703 silences: joined, daemon (both spellings), with-open,
        # closed-open, escaping handles, released __enter__
        "fixture_joined_thread",
        "fixture_daemon_thread",
        "fixture_with_open",
        "fixture_closed_open",
        "fixture_escaping_open",
        "fixture_handoff_socket",
        "fixture_manual_enter_released",
        "AttrThreadJoined",
        "ArmedPlanReleased",
        # CH704 silences: registration, deliver-outside-the-lock,
        # non-callbackish names
        "CallbacksUnderLock.add",
        "CallbacksUnderLock.deliver_outside",
        "CallbacksUnderLock.ping_watchers",
        "CallbacksUnderLock._deliver",
        # CH705 silences: bounded queue/deque, fixed vocabulary,
        # shrunk containers, annotated growth, non-worker growth,
        # entry-less classes
        "NoThreadGrowth",
    ):
        assert not any(s.startswith(clean) for s in symbols), sorted(symbols)
    for attr in ("_bounded_q", "_stats", "_buf", "_window", "_ledger", "_cold"):
        assert not any(s.endswith(attr) for s in symbols), sorted(symbols)


def _ch_codes(findings, code):
    return [(f.code, f.symbol) for f in findings if f.code == code]


def test_concurrency_pass_catches_seeded_blocking_under_lock(tmp_path):
    """Stripping the reasoned `# blocking-ok` annotation off the WAL
    append's fsync re-exposes the blocking-under-lock finding; the
    untouched copy is clean — the annotation is load-bearing."""
    from kubernetes_tpu.analysis import concurrency_hazards as ch

    with open(os.path.join(ROOT, "kubernetes_tpu/store/wal.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "wal_clean.py").write_text(src)
    assert _ch_codes(ch.run(str(tmp_path), paths=["wal_clean.py"]), "CH701") == []
    ann = "                # blocking-ok — WAL durability IS the commit point\n"
    assert ann in src
    (tmp_path / "wal_bug.py").write_text(src.replace(ann, "", 1))
    got = _ch_codes(ch.run(str(tmp_path), paths=["wal_bug.py"]), "CH701")
    assert ("CH701", "WriteAheadLog.append.os.fsync") in got, got


def test_concurrency_pass_catches_seeded_swallow(tmp_path):
    """Replacing RemoteWatch._run's counted close-failure handler with a
    bare `pass` — the exact pre-PR-16 shape — is caught; the untouched
    copy has no CH702 findings."""
    from kubernetes_tpu.analysis import concurrency_hazards as ch

    with open(os.path.join(ROOT, "kubernetes_tpu/client/remote.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "rw_clean.py").write_text(src)
    assert _ch_codes(ch.run(str(tmp_path), paths=["rw_clean.py"]), "CH702") == []
    counted = "self.metrics.watch_close_errors.inc()"
    assert counted in src
    (tmp_path / "rw_bug.py").write_text(src.replace(counted, "pass", 1))
    got = _ch_codes(ch.run(str(tmp_path), paths=["rw_bug.py"]), "CH702")
    assert ("CH702", "RemoteWatch._run.swallow1") in got, got


def test_concurrency_pass_catches_seeded_thread_leak(tmp_path):
    """Dropping `daemon=True` from the scheduler's fire-and-forget bind
    thread makes it unjoinable-and-non-daemon; the untouched copy has no
    CH703 findings."""
    from kubernetes_tpu.analysis import concurrency_hazards as ch

    with open(os.path.join(ROOT, "kubernetes_tpu/scheduler/scheduler.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "sched_clean.py").write_text(src)
    assert _ch_codes(ch.run(str(tmp_path), paths=["sched_clean.py"]), "CH703") == []
    daemonized = ", daemon=True).start()"
    assert daemonized in src
    (tmp_path / "sched_bug.py").write_text(
        src.replace(daemonized, ").start()", 1))
    got = _ch_codes(ch.run(str(tmp_path), paths=["sched_bug.py"]), "CH703")
    assert any(s.endswith(".thread.anonymous") for _c, s in got), got


def test_concurrency_pass_catches_seeded_callback_under_lock(tmp_path):
    """Re-indenting SharedInformer.add_handler's replay loop back inside
    `with self._mu:` — undoing the PR 16 fix — is caught; the untouched
    copy has no CH704 findings."""
    from kubernetes_tpu.analysis import concurrency_hazards as ch

    with open(os.path.join(ROOT, "kubernetes_tpu/client/informer.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "inf_clean.py").write_text(src)
    assert _ch_codes(ch.run(str(tmp_path), paths=["inf_clean.py"]), "CH704") == []
    outside = (
        "        for obj in replay:\n"
        "            self._deliver(handler.on_add, obj)\n"
    )
    assert outside in src
    inside = (
        "            for obj in replay:\n"
        "                self._deliver(handler.on_add, obj)\n"
    )
    (tmp_path / "inf_bug.py").write_text(src.replace(outside, inside, 1))
    got = _ch_codes(ch.run(str(tmp_path), paths=["inf_bug.py"]), "CH704")
    assert ("CH704", "SharedInformer.add_handler.handler.on_add") in got, got


def test_concurrency_pass_catches_seeded_unbounded_growth(tmp_path):
    """Stripping the `# bounded:` annotation off the time-series ring
    registration re-exposes the grow-without-shrink finding; the
    untouched copy has no CH705 findings."""
    from kubernetes_tpu.analysis import concurrency_hazards as ch

    with open(os.path.join(ROOT, "kubernetes_tpu/utils/timeseries.py"),
              encoding="utf-8") as f:
        src = f.read()
    (tmp_path / "ts_clean.py").write_text(src)
    assert _ch_codes(ch.run(str(tmp_path), paths=["ts_clean.py"]), "CH705") == []
    ann_line = [ln for ln in src.splitlines() if "# bounded:" in ln]
    assert len(ann_line) == 1, ann_line
    (tmp_path / "ts_bug.py").write_text(src.replace(ann_line[0] + "\n", "", 1))
    got = _ch_codes(ch.run(str(tmp_path), paths=["ts_bug.py"]), "CH705")
    assert ("CH705", "TimeSeriesStore._append._tracks") in got, got


def test_concurrency_annotations_require_reasons():
    """The annotation grammar itself: a reasoned marker sanctions its
    line and the line below; a reasonless one sanctions nothing."""
    from kubernetes_tpu.analysis.concurrency_hazards import (
        _annotated, _scan_annotations)

    blocking, bounded = _scan_annotations(
        "x = 1\n"
        "# blocking-ok — the lock hold IS the contract\n"
        "y = 2\n"
        "# blocking-ok\n"
        "z = 3\n"
        "q = 4  # bounded: evicted by the ring\n"
        "# bounded:\n"
        "r = 5\n"
    )
    assert _annotated(blocking, 3)       # reasoned, line above
    assert not _annotated(blocking, 5)   # reasonless marker
    assert _annotated(bounded, 6)        # reasoned, same line
    assert not _annotated(bounded, 8)    # reasonless marker


# ---------------------------------------------------------------------------
# evidence-integrity gate (ISSUE 16): scripts/check_ledgers.py
# ---------------------------------------------------------------------------

def _load_check_ledgers():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_ledgers", os.path.join(ROOT, "scripts", "check_ledgers.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_ledgers_live_tree_clean():
    """Every BENCH_*.json / MULTICHIP_*.json the record cites exists in
    the tree — the gate that would have caught the PR 6/11 phantom
    citations."""
    cl = _load_check_ledgers()
    assert cl.check() == []


def test_check_ledgers_flags_phantom_citation(tmp_path):
    """A prose citation of an absent ledger is a violation reported as
    path:line; the same line with 'never committed' on it is an honest
    demotion and stays expressible; a ledger present on disk is fine."""
    cl = _load_check_ledgers()
    (tmp_path / "README.md").write_text(
        "numbers in `BENCH_AB_ghost.json` prove it\n"
        "`BENCH_AB_demoted.json` was never committed — regenerate first\n"
        "`BENCH_AB_real.json` pins the overhead\n")
    (tmp_path / "BENCH_AB_real.json").write_text("{}")
    problems = cl.check(root=str(tmp_path))
    assert len(problems) == 1, problems
    assert problems[0].startswith("README.md:1: BENCH_AB_ghost.json")


@pytest.mark.parametrize("prose", ["README.md", "VERDICT.md"])
def test_check_ledgers_flags_plain_bench_record(tmp_path, prose):
    """Any ``BENCH_*.json`` is a record, not only the ``BENCH_AB_*`` ones,
    and VERDICT.md cites records like the other prose files."""
    cl = _load_check_ledgers()
    (tmp_path / prose).write_text(
        "shipped evidence: `BENCH_watch_fleet.json`\n"
        "the harness reads BENCHMARK.json, which is no record\n")
    problems = cl.check(root=str(tmp_path))
    assert len(problems) == 1, problems
    assert problems[0].startswith(f"{prose}:1: BENCH_watch_fleet.json")


def test_check_ledgers_wired_into_check_sh():
    """check.sh must actually run the gate — a gate nothing invokes is
    the original failure mode all over again."""
    with open(os.path.join(ROOT, "scripts", "check.sh"),
              encoding="utf-8") as f:
        sh = f.read()
    assert "check_ledgers.py" in sh
