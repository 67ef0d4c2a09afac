#!/usr/bin/env bash
# Local CI gate, in the order CI runs it:
#   1. ktpu-analyze — all seven passes over the live tree; exits 1 on
#      any unbaselined finding, 2 on config/baseline errors.
#   2. check_ledgers — evidence-integrity gate: every BENCH_*.json /
#      MULTICHIP_*.json cited by README/CHANGES/COVERAGE/ROADMAP/VERDICT
#      must exist in the tree (demote with "never committed" on the
#      citing line).
#   3. the tier-1 analyzer gate tests (fixture pins + live-tree-clean +
#      wall-time budget), so a pass regression fails even when the live
#      tree happens to be clean.
#   4. a fast smoke of the overload degradation-ladder unit tests (the
#      fake-clock ladder semantics — seconds, not the full suite).
#   5. a forced-8-device mesh smoke: the shard_map wave-loop parity
#      tests under XLA_FLAGS=--xla_force_host_platform_device_count=8
#      (virtual CPU devices — catches sharding regressions without
#      hardware; the forced-tie backend parity test plus the uneven-N
#      padding gate).
#   6. a hollow-watcher fleet smoke: ~200 watchers for a couple of
#      seconds through the serving tier (coalescing window + framed
#      delivery + shared encode vs per-event), gating fan-out liveness,
#      zero dropped-state clients, and the per-CLIENT staleness SLO
#      evaluator sampling (burn + recover + laggard dump).
#
# Usage: scripts/check.sh [ktpu-analyze args...]
# Extra args are forwarded to ktpu-analyze — e.g. `scripts/check.sh
# --changed` for a diff-scoped dev loop (full scope still scanned; only
# the report is filtered to files changed vs HEAD).
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== ktpu-analyze =="
python -m kubernetes_tpu.analysis --profile "$@"

echo "== check_ledgers =="
python scripts/check_ledgers.py

echo "== analyzer gate tests =="
python -m pytest tests/test_static_analysis.py -q -p no:cacheprovider

echo "== overload ladder smoke =="
python -m pytest tests/test_overload.py -q -p no:cacheprovider -k "ladder"

echo "== forced-8-device mesh smoke =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_mesh.py -q -p no:cacheprovider \
    -k "sharded_backend or uneven_width"

echo "== watch-fleet smoke =="
python -m pytest tests/test_watch_fleet.py -q -p no:cacheprovider
