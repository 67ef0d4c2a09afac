"""chip_smoke.py — does the scheduler's main path still start on the chip?

Run from the repo root on a machine with one TPU chip::

    python chip_smoke.py [--seed 0]

The parent process NEVER imports jax: an accelerator belongs to one
process at a time, so every leg runs as a child process, one after the
other, each the only holder of the chip while it lives.

- **drain**  — in process: Store →
  Clientset → Scheduler + ``TPUBatchBackend()`` (default arguments) →
  ``schedule_pending_batch()`` over the north-star cluster (5,000 nodes ×
  150,000 mixed pods, ``BASELINE.json``), then the oracle replay of the
  first 2,000 drain-order pods (``run_prefix_parity``).
- **rungs**  — the fused Pallas rung and the first fallback rung (the XLA
  device loop) schedule the same 5,000 × 20,000 cluster in one child;
  bindings and the round-robin tie counter must be equal pod for pod.
- **serve**  — the daemons as real processes over HTTP
  (``python -m kubernetes_tpu.apiserver`` + ``python -m
  kubernetes_tpu.scheduler --backend tpu --trace``): 5,000 nodes, 20,000
  mixed pods in 10 waves through a remote Clientset; the rung of every
  dispatch is read back from the daemon's ``/debug/traces``.

A leg fails — and the script exits non-zero without printing a result —
when JAX finds no accelerator, when any segment ran on another rung than
expected (every fallback and breaker counter must be zero), when a native
engine fell back to Python, or when a child process fails.  The native
engines are rebuilt from ``csrc/`` first, so only what was just built is
loaded.  Children's output is kept under ``chiprun_out/chip_smoke/``.

The numbers printed are set-up and correctness facts (compile seconds,
cache hits, segments by rung, parity counts), not benchmark results.

Stdout is two lines.  The first collects the legs (also written to
``chiprun_out/chip_smoke/summary.json``); the last is one JSON object with
exactly these keys, the device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass
from typing import Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# the deployment the repo names as its north star (BASELINE.json)
NORTH_NODES = 5_000
NORTH_PODS = 150_000
RUNG_PODS = 20_000
SERVE_WAVES = 10
# the contract's limit is 1200 s, compilation included
DEADLINE_S = 1_150.0

# counters of TPUBatchBackend.stats that must stay zero: each one is a
# way a run can finish with identical bindings on a slower rung
FALLBACK_COUNTERS = (
    "pallas_fallbacks", "interpret_fallbacks", "oracle_segments",
    "oracle_pods", "breaker_transitions", "frontier_fallbacks",
    "frontier_loop_fallbacks",
)


class SmokeFailure(Exception):
    """A leg's check did not hold."""


@dataclass(frozen=True)
class Expect:
    """What a leg asserts about where the work ran.  The chip run uses
    the defaults; the CPU tests pass their own."""

    platform: str = "tpu"
    # rung of TPUBatchBackend() with default arguments (breaker.LEVELS)
    rung: str = "pallas"
    # last_frontier mode of the kernel_impl="xla" run; None = the size is
    # below the chunked gate, no frontier entry is expected
    xla_mode: Optional[str] = "loop"


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- what every leg reports -------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    return {pkg: metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")}


def _device_report(expect: Expect) -> dict:
    """The device as JAX reports it; first JAX touch of a leg's process."""
    import jax

    devices = jax.devices()
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "default_device": str(devices[0]),
        "versions": _versions(),
    }
    _require(report["platform"] == expect.platform,
             f"no chip found: JAX runs on platform {report['platform']!r} "
             f"({report['kind']}), expected {expect.platform!r}")
    return report


class _CompileWatch:
    """Counts compilations and persistent-cache traffic through
    ``jax.monitoring`` while entered.  ``compile_s`` is the backend
    compile, the part the persistent cache saves; tracing the kernels'
    Python and lowering it (Mosaic included) is paid by every fresh
    process and reported beside it (nested jits are counted once per
    level, so those two can overstate)."""

    _PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
               "/jax/core/compile/backend_compile_duration": "compile_s"}
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.seconds = dict.fromkeys(self._PHASES.values(), 0.0)
        self.hits = 0
        self.misses = 0

    def __enter__(self):
        import jax.monitoring as monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *_exc) -> None:
        import jax.monitoring as monitoring

        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        phase = self._PHASES.get(event)
        if phase is not None:
            self.seconds[phase] += duration
            self.compiles += phase == "compile_s"

    def snapshot(self) -> dict:
        import jax

        return {"compiles": self.compiles,
                **{k: round(v, 3) for k, v in self.seconds.items()},
                "cache_hits": self.hits, "cache_misses": self.misses,
                "cache_dir": jax.config.jax_compilation_cache_dir}


def _natives() -> dict:
    from kubernetes_tpu import native

    loaded = {"labelmatch": native.get_lib() is not None,
              "fastcopy": native.get_fastcopy() is not None}
    _require(all(loaded.values()),
             f"a native engine fell back to Python: {loaded}")
    return loaded


def _degraded(stats: dict) -> dict:
    """The non-zero fallback counters and frontier fallback modes."""
    bad = {k: stats[k] for k in FALLBACK_COUNTERS if stats[k]}
    bad.update(stats["frontier_fallback_modes"])
    return bad


def _check_rung(stats: dict, rung: str, what: str) -> None:
    """Every segment on ``rung``, every fallback counter zero."""
    want_pallas = stats["segments"] if rung == "pallas" else 0
    _require(stats["segments"] > 0, f"{what}: no kernel segment ran")
    _require(stats["pallas_segments"] == want_pallas,
             f"{what}: pallas_segments={stats['pallas_segments']} of "
             f"{stats['segments']} segments, expected rung {rung!r}")
    _require(not _degraded(stats),
             f"{what}: a rung degraded: {_degraded(stats)}")


# -- the in-process legs ----------------------------------------------------


def _mixed_workload(n_nodes: int, n_pods: int, seed: int) -> tuple:
    """``testutil``'s mixed workload: (nodes, services, pods) from ``seed``."""
    import random

    from kubernetes_tpu.testutil import make_nodes, make_pods, make_services

    rng = random.Random(seed)
    return (make_nodes(n_nodes, rng, "mixed"), make_services(),
            make_pods(n_pods, rng, "mixed"))


def _odd_request_workload(n_nodes: int, n_pods: int, seed: int) -> tuple:
    """Requests bf16 cannot hold (1,001m, 257Mi, ...), filling the fleet
    to ~80 %.  The Pallas rung gathers per-signature requests through f32
    one-hot matmuls, exact only at ``precision=HIGHEST``: Mosaic's default
    rounds them to 8 bits of mantissa (1,001 -> 1,000), scores shift and
    bindings diverge from the XLA rung.  The interpreter cannot show it,
    and neither can the mixed workload, whose requests all fit 8 bits."""
    import random

    from kubernetes_tpu.testutil import make_node, make_pod

    rng = random.Random(seed)
    nodes = [make_node(f"node-{i:05d}", cpu="8", memory="16Gi", pods=110,
                       labels={"kubernetes.io/hostname": f"node-{i:05d}"})
             for i in range(n_nodes)]
    cpus = ("257m", "513m", "999m", "1001m", "1100m", "1131m")
    mems = ("257Mi", "513Mi", "1001Mi", "1131Mi")
    per_node = 8_000 / (sum(int(c[:-1]) for c in cpus) / len(cpus))
    pods = [make_pod(f"odd-{i:06d}", cpu=rng.choice(cpus),
                     memory=rng.choice(mems), labels={"app": "odd"})
            for i in range(min(n_pods, int(0.8 * per_node * n_nodes)))]
    return nodes, [], pods


def _schedule_cluster(workload: tuple, **backend_kw) -> dict:
    """One batch drain through the normal entry points, with the
    backend's own account of it."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    t0 = time.perf_counter()
    nodes, services, pods = workload
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (len(nodes) + len(pods)))))
    for node in nodes:
        cs.nodes.create(node)
    for svc in services:
        cs.services.create(svc)
    for pod in pods:
        cs.pods.create(pod)
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo, **backend_kw)
    sched = Scheduler(cs, algorithm=algo, backend=backend, emit_events=False)
    sched.start()
    drain_order: list = []
    orig_drain = sched.queue.drain

    def recording_drain(max_n=None):
        drained = orig_drain(max_n)
        drain_order.extend(p.meta.key for p in drained)
        return drained

    sched.queue.drain = recording_drain
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    bound, failed = sched.schedule_pending_batch()
    elapsed = time.perf_counter() - t0
    pods, _ = cs.pods.list()
    return {
        "bound": bound, "failed": failed,
        "setup_s": round(setup_s, 3), "schedule_s": round(elapsed, 3),
        "stats": dict(backend.stats),
        "last_frontier": [dict(seg) for seg in backend.last_frontier],
        "round_robin": int(algo._round_robin),
        "assignments": {p.meta.key: p.spec.node_name or None for p in pods},
        "batch_order": drain_order,
    }


PREFIX_PARITY_K = 2_000


def run_prefix_parity(backend_res: dict, n_nodes: int, n_pods: int,
                      seed: int, k: int = PREFIX_PARITY_K) -> dict:
    """At-scale parity certification without at-scale oracle cost.

    Sequential-greedy is prefix-closed: pod i's placement depends only on
    the initial cluster and the pods scheduled before it (pending pods
    never influence predicates or priorities — only scheduled pods do).
    So the oracle replayed over just the FIRST ``k`` pods of the batch,
    in batch order, must match the kernel's first ``k`` assignments
    binding-for-binding.  This is exact, not statistical.

    ``backend_res`` is ``_schedule_cluster``'s result over
    ``_mixed_workload(n_nodes, n_pods, seed)``.  Batch order is its
    RECORDED queue-drain order, not creation order (the queue is fed from
    the store's name-sorted LIST).  A replay cluster holding exactly
    those ``k`` pods queues them in the same relative order — a
    restriction of a sorted sequence is sorted — so the oracle's ``k``
    decisions are directly comparable.
    """
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    nodes, services, pods = _mixed_workload(n_nodes, n_pods, seed)
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (n_nodes + k))))
    for node in nodes:
        cs.nodes.create(node)
    for svc in services:
        cs.services.create(svc)
    pods_by_key = {p.meta.key: p for p in pods}
    for key in backend_res["batch_order"][:k]:
        cs.pods.create(pods_by_key[key])
    sched = Scheduler(cs, algorithm=GenericScheduler(), backend=None)
    sched.start()
    sched.run_pending()
    replayed, _ = cs.pods.list()
    o = {p.meta.key: p.spec.node_name or None for p in replayed}
    b = backend_res["assignments"]
    mismatches = [(key, o[key], b.get(key)) for key in o if o[key] != b.get(key)]
    return {"checked": len(o), "mismatches": len(mismatches),
            "sample": mismatches[:5]}


def leg_drain(n_nodes: int, n_pods: int, seed: int, expect: Expect) -> dict:
    report = {"leg": "drain", "nodes": n_nodes, "pods": n_pods, "seed": seed,
              "device": _device_report(expect), "natives": _natives()}
    with _CompileWatch() as watch:
        res = _schedule_cluster(_mixed_workload(n_nodes, n_pods, seed))
    report["compile"] = watch.snapshot()
    report.update({k: res[k] for k in ("bound", "failed", "setup_s",
                                       "schedule_s", "stats")})
    # the first segments pay the compiles; what is left is the run
    report["run_s"] = round(res["schedule_s"] - report["compile"]["compile_s"], 3)
    _require(res["bound"] + res["failed"] == n_pods,
             f"drain: {res['bound']} bound + {res['failed']} failed "
             f"!= {n_pods} pods")
    _require(res["bound"] > 0, "drain: nothing was bound")
    _check_rung(res["stats"], expect.rung, "drain")
    parity = run_prefix_parity(res, n_nodes, n_pods, seed)
    report["prefix_parity"] = parity
    _require(parity["checked"] == min(PREFIX_PARITY_K, n_pods)
             and parity["mismatches"] == 0,
             f"drain: prefix parity {parity['mismatches']} mismatches of "
             f"{parity['checked']}: {parity['sample']}")
    return report


def _compare_rungs(workload: tuple, expect: Expect, what: str) -> dict:
    """Schedule ``workload`` twice — ``TPUBatchBackend()`` and
    ``TPUBatchBackend(kernel_impl="xla")`` — and hold the two runs to
    equal bindings and an equal round-robin tie counter."""
    n_pods = len(workload[2])
    report, runs = {}, {}
    for name, kw in (("default", {}), ("xla", {"kernel_impl": "xla"})):
        with _CompileWatch() as watch:
            res = _schedule_cluster(workload, **kw)
        runs[name] = res
        report[name] = {k: res[k] for k in (
            "bound", "failed", "setup_s", "schedule_s", "stats",
            "last_frontier", "round_robin")}
        report[name]["compile"] = watch.snapshot()
        _require(res["bound"] + res["failed"] == n_pods and res["bound"] > 0,
                 f"{what}/{name}: {res['bound']} bound + {res['failed']} "
                 f"failed of {n_pods} pods")
    _check_rung(runs["default"]["stats"], expect.rung, f"{what}/default")
    # kernel_impl="xla" runs no Pallas segment by design
    _check_rung(runs["xla"]["stats"], "interpret", f"{what}/xla")
    a, b = runs["default"]["assignments"], runs["xla"]["assignments"]
    diff = [(k, a[k], b.get(k)) for k in a if a[k] != b.get(k)]
    report["compare"] = {
        "checked": len(a), "mismatches": len(diff), "sample": diff[:5],
        "round_robin_equal":
            runs["default"]["round_robin"] == runs["xla"]["round_robin"],
    }
    _require(len(a) == len(b) == n_pods and not diff,
             f"{what}: {len(diff)} bindings differ between the default "
             f"backend and the XLA rung: {diff[:5]}")
    _require(report["compare"]["round_robin_equal"],
             f"{what}: tie counters differ: {runs['default']['round_robin']} "
             f"vs {runs['xla']['round_robin']}")
    return report


def leg_rungs(n_nodes: int, n_pods: int, seed: int, expect: Expect) -> dict:
    report = {"leg": "rungs", "nodes": n_nodes, "pods": n_pods, "seed": seed,
              "device": _device_report(expect), "natives": _natives()}
    report.update(_compare_rungs(_mixed_workload(n_nodes, n_pods, seed),
                                 expect, "rungs"))
    modes = [seg["mode"] for seg in report["xla"]["last_frontier"]]
    if expect.xla_mode is None:
        _require(not modes, f"rungs/xla: unexpected frontier modes {modes}")
    else:
        _require(bool(modes) and all(m == expect.xla_mode for m in modes),
                 f"rungs/xla: frontier modes {modes}, expected every "
                 f"segment on {expect.xla_mode!r}")
    # a tenth of the fleet is enough to tell a rounded gather from an
    # exact one
    odd = _odd_request_workload(max(n_nodes // 10, 8), n_pods, seed)
    report["odd_requests"] = {
        "nodes": len(odd[0]), "pods": len(odd[2]),
        **_compare_rungs(odd, expect, "rungs/odd")}
    return report


# -- the daemon leg ---------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _wait_healthz(url: str, proc: subprocess.Popen, what: str,
                  timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _require(proc.poll() is None,
                 f"serve: {what} exited with code {proc.returncode} at start-up")
        try:
            if _http_json(f"{url}/healthz", timeout=2).get("status") == "ok":
                return
        except (OSError, ValueError):
            time.sleep(0.2)
    raise SmokeFailure(f"serve: {what} never answered {url}/healthz")


def _metrics(url: str) -> dict:
    """Unlabelled samples of a Prometheus text exposition."""
    with urllib.request.urlopen(f"{url}/metrics", timeout=30) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        m = re.fullmatch(r"([A-Za-z_:][\w:]*) ([-+.\deE]+|NaN|[+-]?Inf)", line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def _dispatch_spans(url: str) -> dict:
    """{(ts, dur): rung} of the dispatch spans in the daemon's wave ring."""
    doc = _http_json(f"{url}/debug/traces", timeout=60)
    _require("traceEvents" in doc, f"serve: tracing is off: {doc}")
    return {(ev["ts"], ev.get("dur")): ev["args"].get("rung")
            for ev in doc["traceEvents"] if ev["name"] == "dispatch"}


def _daemon_device(log_path: str, proc: subprocess.Popen, expect: Expect,
                   timeout: float = 120.0) -> dict:
    """The device from the scheduler daemon's start-up log line (its
    first JAX touch), checked before any work is sent to it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with open(log_path) as f:
            m = re.search(r"backend tpu: platform=(\S+) device_kind=(.+?) "
                          r"devices=(\d+)", f.read())
        if m is not None:
            _require(m.group(1) == expect.platform,
                     f"no chip found: the scheduler daemon runs on platform "
                     f"{m.group(1)!r}, expected {expect.platform!r}")
            return {"platform": m.group(1), "kind": m.group(2),
                    "count": int(m.group(3))}
        _require(proc.poll() is None, "serve: the scheduler exited with code "
                 f"{proc.returncode} before it reached the device")
        time.sleep(0.2)
    raise SmokeFailure("serve: the scheduler logged no device line")


def _stop(proc: subprocess.Popen, what: str) -> int:
    """SIGTERM, then the exit code (SIGKILL only if it does not stop)."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise SmokeFailure(f"serve: {what} ignored SIGTERM")
    return proc.returncode


def _cache_entries(cache_dir: Optional[str]) -> int:
    return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0


def leg_serve(n_nodes: int, n_pods: int, waves: int, seed: int,
              expect: Expect, log_dir: str, timeout: float = 600.0) -> dict:
    """This process stays off JAX: the scheduler daemon is the only
    process of the leg that touches the accelerator."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.utils.platform import compile_cache_dir

    os.makedirs(log_dir, exist_ok=True)
    report = {"leg": "serve", "nodes": n_nodes, "pods": n_pods,
              "waves": waves, "seed": seed, "versions": _versions()}
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or compile_cache_dir()
    cache_before = _cache_entries(cache_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    api_port, health_port = _free_port(), _free_port()
    api_url = f"http://127.0.0.1:{api_port}"
    health_url = f"http://127.0.0.1:{health_port}"
    logs = {name: os.path.join(log_dir, f"serve-{name}.log")
            for name in ("apiserver", "scheduler")}
    procs: dict = {}
    deadline = time.monotonic() + timeout
    try:
        with open(logs["apiserver"], "w") as f:
            procs["apiserver"] = subprocess.Popen(
                [sys.executable, "-m", "kubernetes_tpu.apiserver",
                 "--host", "127.0.0.1", "--port", str(api_port),
                 "--event-log-window", str(max(300_000, 4 * (n_nodes + n_pods)))],
                env=env, cwd=ROOT, stdout=f, stderr=subprocess.STDOUT)
        _wait_healthz(api_url, procs["apiserver"], "apiserver")
        with open(logs["scheduler"], "w") as f:
            procs["scheduler"] = subprocess.Popen(
                [sys.executable, "-m", "kubernetes_tpu.scheduler",
                 "--apiserver", api_url, "--backend", "tpu", "--trace",
                 "--healthz-port", str(health_port)],
                env=env, cwd=ROOT, stdout=f, stderr=subprocess.STDOUT)
        _wait_healthz(health_url, procs["scheduler"], "scheduler")
        report["device"] = _daemon_device(logs["scheduler"],
                                          procs["scheduler"], expect)

        remote = RemoteStore(api_url)
        cs = Clientset(remote)
        nodes, services, pods = _mixed_workload(n_nodes, n_pods, seed)
        for i in range(0, len(nodes), 1_000):
            cs.nodes.create_many_nowait(nodes[i:i + 1_000])
        for svc in services:
            cs.services.create(svc)
        per_wave = -(-n_pods // waves)

        def alive() -> None:
            _require(time.monotonic() < deadline,
                     f"serve: not scheduled within {timeout:.0f}s")
            for name, proc in procs.items():
                _require(proc.poll() is None,
                         f"serve: {name} died with code {proc.returncode}")

        spans: dict = {}
        wave_s = []
        created = 0
        for w in range(waves):
            wave = pods[w * per_wave:(w + 1) * per_wave]
            t0 = time.monotonic()
            cs.pods.create_many_nowait(wave)
            created += len(wave)
            # the next wave follows once the scheduler has decided this
            # one; a retried pod counts twice, so this only paces — the
            # exact check comes after the last wave
            while _metrics(health_url).get(
                    "scheduler_schedule_attempts_total", 0.0) < created:
                alive()
                time.sleep(0.05)
            wave_s.append(round(time.monotonic() - t0, 3))
            spans.update(_dispatch_spans(health_url))  # the ring holds 16 waves

        # exact: every pod is bound, or carries a FailedScheduling event
        # (the event sink writes behind the scheduler)
        while True:
            unbound, _ = remote.list("Pod", field_selector="spec.nodeName=")
            unbound_keys = {f"{p['metadata'].get('namespace', 'default')}/"
                            f"{p['metadata']['name']}" for p in unbound}
            if not unbound_keys:
                break
            events, _ = cs.events.list()
            marked = {e.involved_key for e in events
                      if e.reason == "FailedScheduling"}
            if unbound_keys <= marked:
                break
            alive()
            time.sleep(1.0)
        spans.update(_dispatch_spans(health_url))
        report["bound"] = n_pods - len(unbound_keys)
        report["unschedulable"] = len(unbound_keys)
        _require(report["bound"] > 0, "serve: nothing was bound")
        # the first wave pays the compiles; the others are the run
        report["first_wave_s"] = wave_s[0]
        report["later_waves_s"] = wave_s[1:]

        metrics = _metrics(health_url)
        report["metrics"] = {k: metrics.get(k) for k in (
            "scheduler_pallas_fallback_total",
            "scheduler_kernel_breaker_transitions_total",
            "scheduler_schedule_attempts_total",
            "scheduler_schedule_failures_total",
            "scheduler_bind_failures_total")}
        rungs = sorted(set(spans.values()), key=str)
        report["dispatch_spans"] = {"count": len(spans), "rungs": rungs}
        _require(len(spans) > 0 and rungs == [expect.rung],
                 f"serve: dispatch spans on rungs {rungs}, expected every "
                 f"one on {expect.rung!r}")
        for name in ("scheduler_pallas_fallback_total",
                     "scheduler_kernel_breaker_transitions_total"):
            _require(metrics.get(name) == 0.0,
                     f"serve: {name} = {metrics.get(name)}")
    finally:
        codes = {}
        errors = []
        for name in ("scheduler", "apiserver"):
            if name in procs:
                try:
                    codes[name] = _stop(procs[name], name)
                except SmokeFailure as e:
                    errors.append(str(e))
        report["exit_codes"] = codes
    _require(not errors, "; ".join(errors))
    _require(all(c == 0 for c in codes.values()),
             f"serve: daemons exited with codes {codes}")

    fell_back = []
    for path in logs.values():
        with open(path) as f:
            fell_back += [line for line in f if "Python fallback" in line]
    report["natives"] = {"python_fallback_lines": len(fell_back)}
    _require(not fell_back,
             f"serve: a native engine fell back to Python: {fell_back[:2]}")
    report["compile"] = {"cache_dir": cache_dir,
                         "cache_entries_before": cache_before,
                         "cache_entries_after": _cache_entries(cache_dir)}
    return report


# -- the parent -------------------------------------------------------------


def build_native() -> dict:
    """Build the native engines from the committed sources and load only
    what was just built: ``csrc/*.so`` and ``csrc/ktpu-pause`` are
    git-ignored, and ``native._compile_cached`` trusts any artifact newer
    than its source."""
    from kubernetes_tpu import native

    for stale in glob.glob(os.path.join(ROOT, "csrc", "*.so")) + [
            os.path.join(ROOT, "csrc", "ktpu-pause")]:
        if os.path.exists(stale):
            os.unlink(stale)
    built = {"labelmatch": native.get_lib() is not None,
             "fastcopy": native.get_fastcopy() is not None,
             "pause": native.pause_binary() is not None}
    _require(all(built.values()), f"native build failed: {built}")
    return built


def _run_leg(name: str, seed: int, timeout: float) -> dict:
    """One leg as a child process in its own session; its output is kept,
    its exit code checked, and nothing it started outlives it."""
    out_path = os.path.join(OUT_DIR, f"{name}.json")
    log_path = os.path.join(OUT_DIR, f"{name}.log")
    with open(out_path, "w") as out, open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--leg", name,
             "--seed", str(seed)],
            cwd=ROOT, stdout=out, stderr=log, start_new_session=True)
        try:
            code = proc.wait(timeout=max(timeout, 1.0))
        except subprocess.TimeoutExpired:
            code = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)  # leftovers of the session
            except ProcessLookupError:
                pass
            proc.wait()
    with open(log_path) as f:
        tail = f.read()[-4_000:]
    _require(code is not None,
             f"leg {name} did not finish within {timeout:.0f}s\n{tail}")
    _require(code == 0, f"leg {name} exited with code {code}\n{tail}")
    with open(out_path) as f:
        lines = f.read().strip().splitlines()
    _require(bool(lines), f"leg {name} printed no result")
    report = json.loads(lines[-1])
    _require(report.get("ok") is True, f"leg {name} reported {report}")
    return report


def _leg_main(name: str, seed: int) -> int:
    expect = Expect()
    try:
        if name == "drain":
            report = leg_drain(NORTH_NODES, NORTH_PODS, seed, expect)
        elif name == "rungs":
            report = leg_rungs(NORTH_NODES, RUNG_PODS, seed, expect)
        else:
            report = leg_serve(NORTH_NODES, RUNG_PODS, SERVE_WAVES, seed,
                               expect, OUT_DIR)
    except SmokeFailure as e:
        print(f"chip_smoke leg {name} FAILED: {e}", file=sys.stderr)
        return 1
    report["ok"] = True
    print(json.dumps(report))
    return 0


def _summary(report: dict) -> dict:
    """What the summary line keeps of a leg (the full object is in
    ``chiprun_out/chip_smoke/<leg>.json``)."""
    keep = ("pods", "bound", "failed", "unschedulable", "schedule_s", "run_s",
            "first_wave_s", "dispatch_spans", "exit_codes")
    out = {k: report[k] for k in keep if k in report}
    if "stats" in report:
        out.update(_rung_stats(report["stats"]))
        out["compile"] = _compile_summary(report["compile"])
        out["prefix_parity"] = {k: report["prefix_parity"][k]
                                for k in ("checked", "mismatches")}
    for run in ("default", "xla"):
        if run in report:
            out[run] = {**_rung_stats(report[run]["stats"]),
                        "modes": [s["mode"] for s in report[run]["last_frontier"]],
                        "compile": _compile_summary(report[run]["compile"])}
    if "compare" in report:
        out["compare"] = _compare_summary(report["compare"])
        odd = report["odd_requests"]
        out["odd_requests"] = {"nodes": odd["nodes"], "pods": odd["pods"],
                               **_compare_summary(odd["compare"])}
    if "metrics" in report:
        out["fallback_metrics"] = [
            report["metrics"]["scheduler_pallas_fallback_total"],
            report["metrics"]["scheduler_kernel_breaker_transitions_total"]]
    return out


def _rung_stats(stats: dict) -> dict:
    return {"segments": stats["segments"],
            "pallas_segments": stats["pallas_segments"],
            "degraded": _degraded(stats)}


def _compile_summary(compile_: dict) -> dict:
    return {k: compile_[k] for k in ("compile_s", "cache_hits", "cache_misses")}


def _compare_summary(compare: dict) -> dict:
    return {k: compare[k] for k in ("checked", "mismatches", "round_robin_equal")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated cluster and pods")
    ap.add_argument("--leg", choices=["drain", "rungs", "serve"],
                    help=argparse.SUPPRESS)  # child mode, used by the parent
    args = ap.parse_args(argv)
    if args.leg:
        return _leg_main(args.leg, args.seed)

    start = time.monotonic()
    os.makedirs(OUT_DIR, exist_ok=True)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run the finallys
    legs = {}
    try:
        natives = build_native()
        for name in ("drain", "rungs", "serve"):
            t0 = time.monotonic()
            print(f"chip_smoke: leg {name} ...", file=sys.stderr, flush=True)
            legs[name] = _run_leg(name, args.seed,
                                  DEADLINE_S - (time.monotonic() - start))
            print(f"chip_smoke: leg {name} ok in "
                  f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True)
        _require("jax" not in sys.modules, "the parent imported jax")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    summary, result = _result_lines(legs, {
        "seed": args.seed, "natives_built": natives,
        "elapsed_s": round(time.monotonic() - start, 1)})
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(summary + "\n")
    print(summary)
    print(result, flush=True)
    return 0


def _result_lines(legs: dict, facts: dict) -> tuple:
    """The parent's two lines of stdout.  The first collects the legs; the
    last is the contract's object and carries exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) — the driver refuses any other key."""
    device = legs["drain"]["device"]
    summary = {"chip_smoke": "summary", "versions": device["versions"], **facts,
               "legs": {name: _summary(rep) for name, rep in legs.items()}}
    result = {"ok": True,
              "device": {"platform": str(device["platform"]),
                         "kind": str(device["kind"]),
                         "count": int(device["count"])}}
    return json.dumps(summary), json.dumps(result)


if __name__ == "__main__":
    sys.exit(main())
