"""Several runs of ``benchmark.run`` in one call, each a process of its own
(one process per chip at a time), their result lines kept in a file.

    python3 -m benchmark.sweep --tag knee \
        density-5k.arrivals:1:20:0:rate_pods_per_s=1500 \
        density-5k.arrivals:2:20:0:rate_pods_per_s=2000

A run is ``workload:seed:seconds:trace[:key=json,...][:control=N]``; the
optional keys override parameters of the traffic file (how the knee was
found, see README.md).  Results go to ``chiprun_out/bench/<tag>.jsonl``, one
object per run with the result line, the exit code, the wall seconds and the
end of stderr; a one-line summary per run goes to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out", "bench"))
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    out_dir = os.path.abspath(args.out_dir)
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    with open(os.path.join(out_dir, f"{args.tag}.jsonl"), "a") as out:
        for spec in args.runs:
            parts = spec.split(":")
            workload, seed, seconds, trace = parts[:4]
            cmd = [sys.executable, "-m", "benchmark.run", "--workload", workload,
                   "--seed", seed, "--seconds", seconds, "--trace", trace]
            for extra in parts[4:]:
                for kv in extra.split(","):
                    if kv.startswith("control="):
                        cmd += ["--control", kv.split("=", 1)[1]]
                    elif kv:
                        cmd += ["--traffic-set", kv]
            t = time.monotonic()
            try:
                proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                                      timeout=args.timeout)
                rc, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
            except subprocess.TimeoutExpired as e:
                rc, stdout, stderr = 124, e.stdout or "", e.stderr or ""
                if isinstance(stdout, bytes):
                    stdout, stderr = stdout.decode(), (stderr or b"").decode()
            wall = time.monotonic() - t
            lines = stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except ValueError:
                result = None
            out.write(json.dumps({"spec": spec, "rc": rc, "wall_s": wall,
                                  "result": result, "stderr": stderr[-6000:]}) + "\n")
            out.flush()
            worst = max(worst, rc)
            brief = {k: round(v["value"], 3) for k, v in
                     (result or {}).get("metrics", {}).items()}
            print(json.dumps({"spec": spec, "rc": rc, "wall_s": round(wall, 1),
                              "correct": (result or {}).get("correct"),
                              "failed": (result or {}).get("failed"),
                              "peak": (result or {}).get("device", {}).get("memory_peak_bytes"),
                              "metrics": brief}), flush=True)
            # the run's own account of its window (stderr), or why it failed
            report = [line for line in stderr.splitlines()
                      if line.startswith(("run:", "arrivals:", "per second", "waves:",
                                          "full collections", "kernel shapes",
                                          "control", "check:"))]
            print("\n".join(report) if rc == 0 else stderr[-3000:], flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
