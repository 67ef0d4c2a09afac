"""``benchmark.run`` end to end on the CPU at a tiny size, under the
rehearsal flag: the result line's shape, the refusal to time the CPU without
the flag, and ``correct`` coming out false for each fault planted under the
timed path."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(module: str, *argv: str, timeout: float = 420.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-m", *module.split(), *argv], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


# density-5k.arrivals has its files in place and no entry in BENCHMARK.json
# (PERF.md, Open questions): such a cell runs under the rehearsal flag only
@pytest.mark.parametrize("workload,trace", [
    ("perf-2k.backlog", "0"), ("perf-2k.backlog", "1"),
    ("density-2k.backlog", "0"), ("density-2k.backlog", "1"),
    ("density-5k.arrivals", "0"), ("density-5k.arrivals", "1")])
def test_rehearsal_prints_one_result_object(workload, trace):
    code, out, err = _run("benchmark.run", "--workload", workload, "--seed",
                          str(2**31 + 11), "--seconds", "4", "--trace", trace,
                          "--rehearse-cpu", "80,500")
    assert code == 0, err[-3000:]
    result = json.loads(out[-1])
    keys = list(result)
    assert keys[:5] == RESULT_KEYS and keys[-1] == "checks"
    assert "breakdown" not in result            # no device trace on the CPU
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace == "1" else "end_to_end"
    mine = {m["name"]: m for m in bench[group]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) <= set(mine)
    device_metrics = {n for n, m in mine.items() if m["source"] == "device_trace"}
    assert not device_metrics & set(result["metrics"])
    if trace == "0":
        assert set(result["metrics"]) == set(mine)
    else:
        assert set(result["metrics"]) == set(mine) - device_metrics
    for name, m in result["metrics"].items():
        assert m["unit"] == mine[name]["unit"] and m["value"] > 0
    assert result["attempted"] > 0
    # every number compared is printed beside its limit, on stderr too
    for name, entry in result["checks"].items():
        assert f"{name}: {entry['value']} (limit {entry['limit']})" in err
    assert result["correct"] is True, err[-3000:]


def test_without_the_flag_and_without_a_chip_there_is_no_result():
    code, out, err = _run("benchmark.run", "--workload", "perf-2k.backlog",
                          "--seed", "1", "--seconds", "4", "--trace", "0")
    assert code != 0
    assert out == []
    assert "no accelerator" in err


def test_a_cell_that_is_not_in_benchmark_json_is_never_measured():
    code, out, err = _run("benchmark.run", "--workload", "density-5k.arrivals",
                          "--seed", "1", "--seconds", "4", "--trace", "0")
    assert code != 0
    assert out == []
    assert "unknown workload" in err


@pytest.mark.parametrize("fault,workload,number", [
    ("answer_altered", "perf-2k.backlog", "choice_mismatches"),
    ("half_batch_left_out", "perf-2k.backlog", "undecided"),
    ("state_unchanged", "density-5k.arrivals", "tie_counter_gap")])
def test_a_broken_timed_path_is_not_correct(fault, workload, number):
    code, out, err = _run("tests.benchmark.faults", fault, "--workload", workload,
                          "--seed", "7", "--seconds", "4", "--trace", "0",
                          "--rehearse-cpu", "80,400")
    assert code == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result["correct"] is False
    assert result["checks"][number]["value"] > result["checks"][number]["limit"]
