"""chip_smoke.py on the CPU: the parent stays off JAX, a run without a
chip fails, and the three legs' functions hold at a tiny size with the
rung expectation passed in by the test (product code reads no
environment variable for it)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")

# on the forced-CPU platform TPUBatchBackend() picks the XLA scan (rung
# "interpret" in breaker.LEVELS); 64 nodes x 256 pods sits below the
# chunked gate, so the XLA run leaves no frontier entry
CPU = dict(platform="cpu", rung="interpret", xla_mode=None)
NODES, PODS = 64, 256


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(REPO)


def test_parent_module_imports_without_jax():
    code = ("import sys; import chip_smoke; "
            "assert 'jax' not in sys.modules, 'chip_smoke imported jax'; "
            "import kubernetes_tpu.testutil; "
            "assert 'jax' not in sys.modules, 'testutil imported jax'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.timeout(120)
def test_without_a_chip_the_script_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, SCRIPT], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=110)
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.mark.timeout(60)
def test_alone_in_a_directory_the_script_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SCRIPT, "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=50)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.timeout(120)
def test_drain_leg_tiny(smoke):
    report = smoke.leg_drain(NODES, PODS, 0, smoke.Expect(**CPU))
    assert report["device"]["platform"] == "cpu"
    assert report["bound"] + report["failed"] == PODS
    assert report["prefix_parity"] == {"checked": PODS, "mismatches": 0,
                                       "sample": []}
    assert report["stats"]["pallas_segments"] == 0
    assert report["natives"] == {"labelmatch": True, "fastcopy": True}
    json.dumps(report)  # the leg's object is what the child prints
    # the parent's last line carries exactly the contract's keys; what it
    # collects of the legs goes on the line before
    summary, result = smoke._result_lines({"drain": report}, {"seed": 0})
    assert "\n" not in summary and "\n" not in result
    assert json.loads(result) == {"ok": True, "device": {
        "platform": "cpu", "kind": report["device"]["kind"],
        "count": report["device"]["count"]}}
    assert json.loads(summary)["legs"]["drain"]["prefix_parity"] == {
        "checked": PODS, "mismatches": 0}


@pytest.mark.timeout(120)
def test_rungs_leg_tiny(smoke):
    report = smoke.leg_rungs(NODES, PODS, 0, smoke.Expect(**CPU))
    assert report["compare"]["checked"] == PODS
    assert report["compare"]["mismatches"] == 0
    assert report["compare"]["round_robin_equal"] is True
    json.dumps(report)


def test_legs_refuse_the_wrong_platform_and_rung(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no chip found"):
        smoke.leg_drain(NODES, PODS, 0, smoke.Expect())  # expects a tpu
    # a run that finished on another rung than expected is a failure,
    # whatever its bindings
    with pytest.raises(smoke.SmokeFailure, match="expected rung 'pallas'"):
        smoke.leg_drain(NODES, PODS, 0,
                        smoke.Expect(platform="cpu", rung="pallas"))
    stats = {"segments": 3, "pallas_segments": 3, "frontier_fallback_modes": {},
             **{k: 0 for k in smoke.FALLBACK_COUNTERS}}
    smoke._check_rung(stats, "pallas", "ok")
    with pytest.raises(smoke.SmokeFailure, match="a rung degraded"):
        smoke._check_rung({**stats, "breaker_transitions": 1}, "pallas", "x")
    with pytest.raises(smoke.SmokeFailure, match="a rung degraded"):
        smoke._check_rung({**stats, "frontier_fallback_modes": {"mesh": 1}},
                          "pallas", "x")
    with pytest.raises(smoke.SmokeFailure, match="pallas_segments=2"):
        smoke._check_rung({**stats, "pallas_segments": 2}, "pallas", "x")


@pytest.mark.timeout(180)
def test_serve_leg_tiny(smoke, tmp_path):
    report = smoke.leg_serve(NODES, PODS, 2, 0, smoke.Expect(**CPU),
                             str(tmp_path), timeout=150.0)
    assert report["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": report["device"]["count"]}
    assert report["bound"] + report["unschedulable"] == PODS
    assert report["dispatch_spans"]["rungs"] == ["interpret"]
    assert report["exit_codes"] == {"scheduler": 0, "apiserver": 0}
    # the launcher kept the children's output
    log = (tmp_path / "serve-scheduler.log").read_text()
    assert "backend tpu: platform=cpu" in log
    json.dumps(report)


# -- the persistent compile cache (utils/platform.py) ------------------------


def test_compile_cache_placement(monkeypatch):
    from kubernetes_tpu.utils import platform

    # set from outside: the code names no directory — JAX reads the
    # variable itself, so the cache is there and nowhere else
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert platform.compile_cache_dir() is None
    # not set: <checkout>/.jax_cache, the same path every time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = platform.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    assert platform.compile_cache_dir() == first


def test_compile_cache_is_not_configured_on_the_forced_cpu_platform():
    import jax

    import kubernetes_tpu.ops  # noqa: F401 - configures the cache at import
    from kubernetes_tpu.utils import platform

    assert jax.config.jax_platforms == "cpu"  # conftest forced it
    platform.configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_configuration_off_the_forced_cpu_platform(tmp_path):
    """What an accelerator process gets, observed in a child whose
    platform is not forced.  Only the configuration is read: importing
    the utility module runs no computation, so nothing is written."""
    code = (
        "import json, os, jax\n"
        "from kubernetes_tpu.utils.platform import configure_compile_cache\n"
        "configure_compile_cache()\n"
        "print(json.dumps({'dir': jax.config.jax_compilation_cache_dir,\n"
        "  'min_s': jax.config.jax_persistent_cache_min_compile_time_secs,\n"
        "  'min_b': jax.config.jax_persistent_cache_min_entry_size_bytes,\n"
        "  'flags': os.environ.get('XLA_FLAGS', '')}))\n")
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                         "XLA_FLAGS")}
    base["PYTHONPATH"] = REPO

    def run(env):
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        return json.loads(proc.stdout.strip().splitlines()[-1])

    got = run(base)
    assert got["dir"] == os.path.join(REPO, ".jax_cache")
    # thresholds opened: the ~1 s fused-kernel compile must be kept
    assert got["min_s"] == 0.0 and got["min_b"] == -1
    assert got["flags"] == ""
    got = run({**base, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert got["dir"] == str(tmp_path)


def test_importing_ops_leaves_xla_flags_alone():
    import kubernetes_tpu.ops  # noqa: F401

    # conftest's device-count flag is all there is: no CPU-runtime flag
    assert "xla_cpu_use_thunk_runtime" not in os.environ.get("XLA_FLAGS", "")
