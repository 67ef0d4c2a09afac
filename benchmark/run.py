"""One run of one cell of ``BENCHMARK.json``.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process **is** the scheduler process and the only one that touches JAX.
It wires the scheduler as the daemon's payload does
(``kubernetes_tpu/scheduler/__main__.py`` ``run``) and drives the daemon's
own entry, ``Scheduler.run_batch_loop``, on a thread.  The apiserver
(``python -m kubernetes_tpu.apiserver``) and the load generator
(``python -m benchmark.loadgen``) are child processes that never import JAX.

Set-up (process start -> window start) is: apiserver up, the deployment
created over HTTP, the scheduler wired with its loop held, and every shape
bucket the window will use warmed.  The window, the metrics and what decides
``correct`` are set out in PERF.md sections 2 and 4 and in ``check.py``.

The last line of stdout is the result object.  A run that finds no TPU (or
fewer chips than the cell asks for), in which a rung degraded or a native
engine fell back to Python, exits non-zero and prints no result.
``--rehearse-cpu NODES,PODS`` is the only way onto the CPU: a tiny rehearsal
whose device says ``cpu`` and which prints no device metric.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import queue
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import urllib.request
from types import SimpleNamespace

from . import check, cluster, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# counters of TPUBatchBackend.stats that must stay zero: each is a way a run
# can finish with identical bindings on a slower rung (copied from
# chip_smoke.py FALLBACK_COUNTERS)
FALLBACK_COUNTERS = (
    "pallas_fallbacks", "interpret_fallbacks", "oracle_segments",
    "oracle_pods", "breaker_transitions", "frontier_fallbacks",
    "frontier_loop_fallbacks",
)


class RunFailure(Exception):
    """The run cannot stand as a measurement; no result line is printed."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise RunFailure(msg)


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


# -- BENCHMARK.json ---------------------------------------------------------


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, rehearse: bool = False) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    # a cell that PERF.md keeps for later (its files in place, no entry in
    # BENCHMARK.json) can be rehearsed on the CPU, never measured
    config, _, mix = name.partition(".")
    if rehearse and all(os.path.isfile(os.path.join(HERE, d, f"{n}.json"))
                        for d, n in (("configs", config), ("traffic", mix))):
        return {"name": name, "config": config, "traffic": mix, "chips": 1,
                "why": "not in BENCHMARK.json: rehearsal only"}
    raise SystemExit(f"unknown workload {name!r}; known: "
                     f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# -- compile counting (method of chip_smoke._CompileWatch) -------------------


class CompileWatch:
    """Counts backend compilations and persistent-cache traffic through
    ``jax.monitoring`` from ``start()`` on."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        # a new shape whose executable the persistent cache holds compiles
        # nothing, yet pays seconds of Python tracing and Mosaic lowering:
        # lowerings are counted too
        self.lowerings = 0
        self.lower_s = 0.0
        self.hits = 0
        self.misses = 0

    def start(self) -> "CompileWatch":
        import jax.monitoring as monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration
        elif event == self._LOWER:
            self.lowerings += 1
            self.lower_s += duration


# -- children ---------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """A child process in its own session, its stderr in a log file; the
    load generator's stdout is read as JSON lines."""

    def __init__(self, name: str, argv: list, workdir: str, pipe: bool):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "w")
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        # a child never needs the chip; if it imported jax by accident it
        # must not take the device from this process
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, start_new_session=True, text=True,
            stdin=subprocess.PIPE if pipe else subprocess.DEVNULL,
            stdout=subprocess.PIPE if pipe else self._log,
            stderr=self._log)
        self.lines: "queue.Queue" = queue.Queue()
        if pipe:
            threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise RunFailure(f"{self.name}: no {event!r} within {timeout:.0f}s"
                                 f"\n{self.tail()}") from None
            if line is None:
                raise RunFailure(f"{self.name} exited (code {self.proc.poll()}) "
                                 f"before {event!r}\n{self.tail()}")
            msg = json.loads(line)
            if msg.get("event") == event:
                return msg

    def poll_event(self, event: str) -> bool:
        """Has ``event`` arrived?  (Consumes lines up to it.)"""
        while True:
            try:
                line = self.lines.get_nowait()
            except queue.Empty:
                return False
            if line is None:
                raise RunFailure(f"{self.name} exited early\n{self.tail()}")
            if json.loads(line).get("event") == event:
                return True

    def tail(self) -> str:
        self._log.flush()
        with open(self.log_path) as f:
            return f.read()[-3000:]

    def kill(self) -> None:
        import signal

        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        for stream in (self.proc.stdin, self.proc.stdout):
            if stream is not None:
                try:
                    stream.close()
                except OSError:      # the child died with a line unflushed
                    pass
        self._log.close()


def _wait_healthz(url: str, child: Child, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        _require(child.proc.poll() is None,
                 f"apiserver exited at start-up\n{child.tail()}")
        try:
            with urllib.request.urlopen(f"{url}/healthz", timeout=2) as r:
                if json.loads(r.read()).get("status") == "ok":
                    return
        except (OSError, ValueError):
            time.sleep(0.05)
    raise RunFailure(f"apiserver never answered {url}/healthz")


# -- the device -------------------------------------------------------------


def find_device(chips: int, rehearse: bool) -> dict:
    """First JAX touch of the process.  No chip, no run."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearse:
        _require(device["platform"] == "cpu", f"rehearsal on {device}")
        return device
    _require(device["platform"] == "tpu",
             f"no accelerator: JAX runs on {device}; this benchmark never "
             f"times the CPU")
    _require(device["count"] >= chips,
             f"the cell asks for {chips} chip(s), JAX finds {device['count']}")
    return device


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# -- trace annotations around the layer calls --------------------------------


def annotate_layers(sched, backend, dispatched: list) -> None:
    """Put the program's layers on the profiler's clock: wrap the calls into
    each layer with ``jax.profiler.TraceAnnotation`` from here, the
    benchmark's side (the Pallas dispatch has no annotation of its own).
    Each kernel dispatch is noted in ``dispatched`` with the sizes the
    roofline counts from, read from the segment's fields by name."""
    from jax.profiler import TraceAnnotation

    import kubernetes_tpu.ops.pallas_kernel as pk

    def wrap(obj, attr: str, name: str, note=None) -> None:
        fn = getattr(obj, attr)

        def annotated(*a, **kw):
            if note is not None:
                note(*a)
            with TraceAnnotation(name):
                return fn(*a, **kw)

        setattr(obj, attr, annotated)

    def note_dispatch(static, _init) -> None:
        dispatched.append({
            "t": time.perf_counter(), "pods": len(static.group_of_pod),
            "terms": static.term_matches_sig.shape[0] if static.terms else 0,
            "volume_slots": static.pod_vol_ids.shape[1] if static.use_vols else 0})

    wrap(sched.queue, "drain", "bench.queue.drain")
    wrap(sched, "snapshot", "bench.snapshot")
    wrap(backend.tensorizer, "build_static", "bench.tensorize.build_static")
    wrap(backend.tensorizer, "initial_state", "bench.tensorize.initial_state")
    wrap(pk, "_pack", "bench.dispatch.pack")
    wrap(pk, "dispatch_batch_pallas", "bench.dispatch", note_dispatch)
    wrap(pk, "finalize_batch_pallas", "bench.device_wait")
    wrap(sched.cache, "assume_many", "bench.commit.assume_many")
    wrap(sched.clientset.pods, "bind_many", "bench.commit.bind_many")
    wrap(sched.cache, "finish_binding_many", "bench.commit.finish_binding")
    wrap(sched, "pump", "bench.ingest.pump")
    wrap(sched._recorder, "event_batch", "bench.events.enqueue")


class GcWatch:
    """Full (oldest-generation) collections of this process, the scheduler's:
    when each began and how long it took."""

    def __init__(self):
        self.pauses: list = []          # (began at, seconds)
        self._began = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._began = time.monotonic()
        elif self._began is not None:
            self.pauses.append((self._began, time.monotonic() - self._began))
            self._began = None


# -- the run, step by step ----------------------------------------------------


def run_cell(args, hooks: dict) -> tuple:
    """Returns (result object, lines for stderr).  ``hooks`` lets a test
    break the timed path underneath (``after_wiring(sched, backend)``)."""
    bench = load_benchmark()
    cell = find_cell(bench, args.workload, args.rehearse_cpu is not None)
    config, mix = cluster.resolve(cell["config"], cell["traffic"], args.seconds,
                                  args.rehearse_cpu, args.traffic_set)
    r = SimpleNamespace(
        args=args, hooks=hooks, bench=bench, cell=cell, config=config, mix=mix,
        plan=traffic.plan(mix, config, args.seed, args.seconds),
        rehearse=args.rehearse_cpu is not None,
        workdir=tempfile.mkdtemp(prefix="bench-run-"), children=[])
    try:
        start_children(r)
        wire_scheduler(r)
        warm_up(r)
        measure_window(r)
        stop_and_read_back(r)
        end_to_end(r)
        metrics, breakdown = (layer_metrics(r) if args.trace else
                              ({m["name"]: {"value": r.e2e[m["name"]], "unit": m["unit"]}
                                for m in metrics_of(bench, "end_to_end", cell["name"])},
                               None))
        return decide_correct(r, metrics, breakdown)
    finally:
        for child in r.children:
            child.kill()
        shutil.rmtree(r.workdir, ignore_errors=True)


def start_children(r) -> None:
    """The apiserver, the client (which creates the deployment at once) and,
    where the window has creates, their sender."""
    n_objects = (r.config["nodes"]["count"] + r.plan["preload"] + r.plan["window_pods"]
                 + sum(w["pods"] for w in r.plan["warm_waves"]))
    r.api_url = f"http://127.0.0.1:{_free_port()}"
    r.apiserver = Child("apiserver", [
        sys.executable, "-m", "kubernetes_tpu.apiserver", "--host", "127.0.0.1",
        "--port", r.api_url.rsplit(":", 1)[1],
        "--event-log-window", str(max(300_000, 4 * n_objects))], r.workdir, pipe=False)
    r.children.append(r.apiserver)
    _wait_healthz(r.api_url, r.apiserver)
    world_args = [
        "--url", r.api_url, "--config", r.cell["config"], "--traffic",
        r.cell["traffic"], "--seed", str(r.args.seed), "--seconds", str(r.args.seconds),
        *(["--rehearse-cpu", r.args.rehearse_cpu] if r.rehearse else []),
        *(x for kv in r.args.traffic_set for x in ("--traffic-set", kv))]
    r.loadgen = Child("loadgen", [sys.executable, "-m", "benchmark.loadgen",
                                  *world_args], r.workdir, pipe=True)
    r.children.append(r.loadgen)
    r.loadgen.send(cmd="populate")
    r.sender = None
    if r.plan["window_pods"]:
        r.sender = Child("sender", [sys.executable, "-m", "benchmark.sender",
                                    *world_args], r.workdir, pipe=True)
        r.children.append(r.sender)


def wire_scheduler(r) -> None:
    """The scheduler, wired as the daemon's payload wires it, its loop held."""
    # the chip is looked for while the client creates the deployment: both
    # take many seconds, in different processes.  No chip, no run.
    r.device = find_device(r.cell["chips"], r.rehearse)
    _log(f"device {r.device}")
    from kubernetes_tpu import native
    from kubernetes_tpu.daemon import remote_clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.utils import tracing
    from kubernetes_tpu.utils.features import SchedulerConfiguration
    import kubernetes_tpu.ops.pallas_kernel as pallas_kernel

    natives = {"labelmatch": native.get_lib() is not None,
               "fastcopy": native.get_fastcopy() is not None}
    _require(all(natives.values()),
             f"a native engine fell back to Python: {natives}")
    r.watch = CompileWatch().start()
    r.gc_watch = GcWatch()
    populated = r.loadgen.expect("populated", timeout=900.0)
    _log(f"populated {populated}")

    r.tracer = tracing.enable(ring_waves=8_192) if r.args.trace else None
    cs = remote_clientset(r.api_url)
    r.algo = algo = GenericScheduler()
    r.backend = backend = TPUBatchBackend(algorithm=algo)
    r.sched = sched = Scheduler(cs, algorithm=algo, backend=backend)
    if "after_wiring" in r.hooks:
        r.hooks["after_wiring"](sched, backend)
    # the queue drains, in order: what the reference replays, and when each
    # wave began
    r.drains, r.drain_times = [], []
    orig_drain = sched.queue.drain

    def recording_drain(max_n=None):
        drained = orig_drain(max_n)
        if drained:
            r.drain_times.append(time.monotonic())
            r.drains.append([p.meta.key for p in drained])
        return drained

    sched.queue.drain = recording_drain
    r.dispatched = []
    if r.args.trace:
        annotate_layers(sched, backend, r.dispatched)
    sched.start(manual=False)
    n_nodes = r.config["nodes"]["count"]
    deadline = time.monotonic() + 300.0
    while (len(sched.queue) < r.plan["preload"]
           or len(sched.snapshot()) < n_nodes):
        _require(time.monotonic() < deadline,
                 f"informers never synced: queue {len(sched.queue)} of "
                 f"{r.plan['preload']}, nodes {len(sched.snapshot())} of {n_nodes}")
        time.sleep(0.02)
    _log(f"informers synced: queue {len(sched.queue)}, nodes {n_nodes}")

    # which kernel shapes are asked for, and when: a shape first seen inside
    # the window is a warm-up that fell short
    r.shapes_seen = []
    runner = pallas_kernel._pallas_runner

    def noting_runner(*key):
        r.shapes_seen.append(str(key))
        return runner(*key)

    pallas_kernel._pallas_runner = noting_runner
    r.stop = threading.Event()
    interval = SchedulerConfiguration().batch_interval
    r.loop_error = []

    def loop() -> None:
        try:
            # the daemon's call; a backlog cell stops after the wave that
            # decides the backlog once (``loop_max_waves`` in its traffic
            # file): the unschedulable pods' retries are not its subject
            sched.run_batch_loop(min_batch=backend.max_segment_pods,
                                 max_wait=interval, stop=r.stop,
                                 poll_interval=min(0.05, interval),
                                 max_waves=r.mix.get("loop_max_waves"))
        except BaseException as e:  # noqa: BLE001 - reported by the main thread
            r.loop_error.append(e)
            raise

    r.loop_thread = threading.Thread(target=loop, name="batch-loop", daemon=True)


def warm_up(r) -> None:
    """Every shape bucket the window will use, and no other."""
    if r.mix["kind"] == "backlog":
        _warm_backlog(r)
    else:
        r.loop_thread.start()
        for wave in r.plan["warm_waves"]:
            r.loadgen.send(cmd="wave", wave=wave, timeout=300.0)
            got = r.loadgen.expect("wave", timeout=330.0)
            _require(got["bound"] == got["pods"], f"warm-up wave not bound: {got}")
            _log(f"warm wave {got}")
        r.sender.expect("ready", timeout=300.0)
        # let the accumulation settle so the window starts on an idle loop
        time.sleep(0.2)
    # what the set-up leaves behind is collected now, not inside the window
    gc.collect()
    r.before = SimpleNamespace(
        shapes=set(r.shapes_seen), compiles=r.watch.compiles,
        lowerings=r.watch.lowerings, lower_s=r.watch.lower_s,
        stats=dict(r.backend.stats), drains=len(r.drains),
        attempts=r.sched.metrics.schedule_attempts.value)


def _warm_backlog(r) -> None:
    """Dry-run the whole backlog through a second backend on fresh lazy
    views of the generator's own wire pods, against the scheduler's
    snapshot, and throw the result away.  It compiles (or loads from the
    persistent cache) exactly the shape buckets the first wave will use;
    the real backend, its sticky buckets, its host state, the tie counter
    and the informer's pod objects are untouched, so no per-pod work moves
    out of the window."""
    from kubernetes_tpu.api import lazy
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler

    t = time.perf_counter()
    world = cluster.World(r.config, r.args.seed, r.plan)
    pods = [lazy.wrap(api.Pod, p) for p in sorted(world.preload, key=cluster.pod_key)]
    snapshot = r.sched.snapshot()
    pctx = r.sched.priority_context(snapshot)
    warm = TPUBatchBackend(algorithm=GenericScheduler())
    warm.schedule_batch(pods, snapshot, pctx)
    _log(f"warm-up dry run: {warm.stats['segments']} segments in "
         f"{time.perf_counter() - t:.2f}s")


def measure_window(r) -> None:
    import jax

    kind = r.mix["kind"]
    slice_cfg = r.mix["trace_slice"]
    r.profile_dir = os.path.join(r.workdir, "profile")
    r.prof = prof = {"on": False, "t_start": None, "t_stop": None, "t_marker": None}

    def start_profile() -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(r.profile_dir, profiler_options=opts)
        prof["on"] = True
        prof["t_start"] = time.perf_counter()
        # a marker that ties the profiler's clock to perf_counter
        with jax.profiler.TraceAnnotation("bench.clock"):
            prof["t_marker"] = time.perf_counter()

    def stop_profile() -> None:
        if prof["on"]:
            prof["t_stop"] = time.perf_counter()
            jax.profiler.stop_trace()
            prof["on"] = False

    if r.args.trace and slice_cfg["start_s"] <= 0:
        start_profile()
    r.t_open = time.monotonic() + (0.0 if kind == "backlog" else 0.25)
    r.t_open_perf = time.perf_counter() + (r.t_open - time.monotonic())
    r.setup_s = r.t_open_perf - _T_START
    r.loadgen.send(cmd="go", t0=r.t_open)
    if r.sender is not None:
        r.sent_path = os.path.join(r.workdir, "sent.json")
        r.sender.send(t0=r.t_open, out=r.sent_path)
    if not r.loop_thread.is_alive():
        r.loop_thread.start()
    _log(f"window open after {r.setup_s:.2f}s of set-up")
    r.t_deadline = r.t_open + r.args.seconds
    decided_at = None
    compiles_at_close = None
    # every wake-up takes the interpreter lock from the scheduler for a
    # moment: 10 ms reads a backlog's 3 s window to a thousandth of its
    # length, and an arrivals window closes by the clock, not by this loop
    nap = 0.01 if kind == "backlog" else 0.05
    while True:
        now = time.monotonic()
        _require(not r.loop_error, f"the batch loop died: {r.loop_error}")
        if r.args.trace and not prof["on"] and prof["t_stop"] is None \
                and now - r.t_open >= slice_cfg["start_s"]:
            start_profile()
        if prof["on"] and time.perf_counter() - prof["t_start"] >= slice_cfg["length_s"]:
            stop_profile()
        if kind == "backlog":
            decided = r.sched.metrics.schedule_attempts.value - r.before.attempts
            # the window closes when every pod of the backlog has been
            # decided once (bound, or failed once and sent to back-off)
            if decided >= r.plan["preload"]:
                decided_at = now
                compiles_at_close = r.watch.compiles
                break
        if now >= r.t_deadline:
            break
        time.sleep(nap)
    r.unbound_at_close = None
    if kind == "backlog":
        stop_profile()            # a backlog's traced slice ends with its window
        from kubernetes_tpu.client.remote import RemoteStore

        # how many are bound, as the apiserver says at the close: no first-
        # pass binding follows the last decision, so after it the count
        # stands; a window cut at --seconds ends when this answer is in
        left, _ = RemoteStore(r.api_url, timeout=120.0).list(
            "Pod", field_selector="spec.nodeName=")
        r.unbound_at_close = len(left)
        r.t_close = decided_at if decided_at is not None else time.monotonic()
        _log(f"backlog decided once: {decided_at is not None} at "
             f"{r.t_close - r.t_open:.2f}s, {r.unbound_at_close} unbound")
    else:
        r.t_close = r.t_deadline
        grace_until = r.t_deadline + r.mix["grace_s"]
        while time.monotonic() < grace_until and not r.loadgen.poll_event("all_bound"):
            _require(not r.loop_error, f"the batch loop died: {r.loop_error}")
            time.sleep(0.02)
    stop_profile()
    if compiles_at_close is None:
        compiles_at_close = r.watch.compiles
    r.compiles_in_window = compiles_at_close - r.before.compiles
    r.lowerings_in_window = r.watch.lowerings - r.before.lowerings
    r.lower_s_in_window = r.watch.lower_s - r.before.lower_s
    r.shapes_in_window = sorted(set(r.shapes_seen) - r.before.shapes)


def stop_and_read_back(r) -> None:
    """No further wave: what was decided is what is checked.  Then where the
    work ran, the apiserver's own word on every binding, and the client's
    samples."""
    from kubernetes_tpu.client.remote import RemoteStore

    r.stop.set()
    r.loop_thread.join(timeout=300.0)
    r.t_stopped_perf = time.perf_counter()
    _require(not r.loop_thread.is_alive(), "the batch loop did not stop")
    _require(not r.loop_error, f"the batch loop died: {r.loop_error}")
    r.peak = memory_peak_bytes()
    after = dict(r.backend.stats)
    r.tie_counter = int(r.algo._round_robin)
    r.window_stats = {k: after[k] - r.before.stats.get(k, 0)
                      for k, v in after.items() if isinstance(v, (int, float))}
    bad = {k: after[k] for k in FALLBACK_COUNTERS if after[k]}
    bad.update(after.get("frontier_fallback_modes") or {})
    _require(not bad, f"a rung degraded: {bad}")
    _require(after["segments"] > 0, "no kernel segment ran")
    if not r.rehearse:
        _require(after["pallas_segments"] == after["segments"],
                 f"{after['pallas_segments']} of {after['segments']} "
                 f"segments on the Pallas rung")

    # the client's watches may trail the scheduler: they are read once they
    # have seen what the apiserver holds, or after 90 s
    _log("loop stopped; reading every pod back")
    items, _ = RemoteStore(r.api_url, timeout=300.0).list("Pod")
    _log(f"read {len(items)} pods back")
    r.bindings = {cluster.pod_key(p): (p["spec"].get("nodeName") or None)
                  for p in items}
    drained = {k for batch in r.drains for k in batch}
    samples_path = os.path.join(r.workdir, "samples.json")
    r.loadgen.send(cmd="stop", out=samples_path,
                   expect_bound=sum(1 for v in r.bindings.values() if v is not None),
                   expect_marked=sum(1 for k, v in r.bindings.items()
                                     if v is None and k in drained),
                   catch_up_s=90.0)
    r.loadgen.expect("stopped", timeout=150.0)
    with open(samples_path) as f:
        r.samples = json.load(f)
    if r.sender is not None:
        r.sender.expect("sent", timeout=150.0)
        with open(r.sent_path) as f:
            r.samples.update(json.load(f))
        _require(not r.samples["errors"], f"creates failed: {r.samples['errors']}")
    else:
        r.samples.update(window_keys=[], due=[], sent=[], acked=[])
    _log("the client's samples are in")
    with open(r.apiserver.log_path) as f:
        _require("Python fallback" not in f.read(),
                 "a native engine of the apiserver fell back to Python")
    # the children are reaped now; this process's informer and event-sink
    # threads are daemons and end with it (a graceful stop waits out watch
    # time-outs and drains the event queue: 14 s that serve no measurement)
    for child in r.children:
        child.kill()


def end_to_end(r) -> None:
    """The end-to-end metrics, from the client's side, and a timeline of the
    window for stderr."""
    samples, bound_at = r.samples, r.samples["bound_at"]
    r.report = []
    if r.mix["kind"] == "backlog":
        r.window_s = r.t_close - r.t_open
        r.n_bound = r.plan["preload"] - r.unbound_at_close
        r.attempted, r.failed = r.plan["preload"], 0
        r.seen = []
        r.e2e = {"bound_pods_per_s": stats.rate(r.n_bound, r.window_s)}
    else:
        keys = samples["window_keys"]
        r.seen = [bound_at.get(k) for k in keys]
        _require(len(keys) == r.plan["window_pods"] and None not in samples["due"],
                 f"the sender sent {sum(d is not None for d in samples['due'])} "
                 f"of {r.plan['window_pods']} pods")
        latencies, never = stats.bind_latencies_ms(samples["due"], r.seen,
                                                   samples["stopped_at"])
        refused = sum(1 for a in samples["acked"] if a is None)
        r.window_s = r.args.seconds
        r.n_bound = stats.bound_in_window(r.seen, r.t_open, r.t_deadline)
        r.attempted, r.failed = len(keys), never
        r.e2e = {"bound_pods_per_s": stats.rate(r.n_bound, r.window_s),
                 "bind_p50_ms": stats.percentile(latencies, 50),
                 "bind_p95_ms": stats.percentile(latencies, 95)}
        r.report += [f"arrivals: {len(keys)} created, {refused} refused, {never} "
                     f"never bound, {r.n_bound} bound inside the window"]
        r.report += stats.timeline(samples["due"], samples["sent"], samples["acked"],
                                   r.seen, r.t_open, r.window_s)
    r.e2e["setup_s"] = r.setup_s
    waves = [(t - r.t_open, len(d)) for t, d in
             zip(r.drain_times[r.before.drains:], r.drains[r.before.drains:])]
    gaps = sorted(((b[0] - a[0], a[0], a[1]) for a, b in zip(waves, waves[1:])),
                  reverse=True)[:3]
    r.report.append(
        f"waves: {len(waves)}; the longest from one drain to the next (seconds, "
        f"began at, pods): " + json.dumps([[round(g, 3), round(at, 2), n]
                                           for g, at, n in gaps]))
    pauses = [(at - r.t_open, s) for at, s in r.gc_watch.pauses
              if r.t_open <= at <= r.t_close]
    r.report.append(f"full collections of the scheduler's process in the window: "
                    f"{len(pauses)}, longest {max([s for _, s in pauses], default=0):.3f}s"
                    f" at {[round(at, 2) for at, _ in pauses][:8]}")


def layer_metrics(r) -> tuple:
    """(per-layer metrics of the traced run, breakdown or None)."""
    from kubernetes_tpu.utils import tracing

    from . import trace_reduce

    tracing.disable()
    t_close_perf = r.t_open_perf + (r.t_close - r.t_open)
    facts = {
        "cell": r.cell, "config": r.config, "traffic": r.mix, "plan": r.plan,
        "samples": r.samples, "seen": r.seen, "window_s": r.window_s,
        "stats": r.window_stats, "device": r.device, "profile": None,
        "n_nodes": r.config["nodes"]["count"], "dispatched": r.dispatched,
        # spans from the window's opening until the loop stood still: the
        # informer applies the last confirmations after the last decision
        "spans": trace_reduce.window_spans(r.tracer, r.t_open_perf, t_close_perf,
                                           r.t_stopped_perf),
    }
    breakdown = None
    if not r.rehearse:
        profile = trace_reduce.reduce_profile(
            r.profile_dir, t_marker=r.prof["t_marker"],
            t_start=r.prof["t_start"], t_stop=r.prof["t_stop"])
        facts["profile"] = r.profile = profile
        breakdown = {"device_ops": profile["device_ops"][:10],
                     "idle_gaps": profile["idle_gaps"][:10]}
    long_spans = [(sp["name"], round(sp["t0"] - r.t_open_perf, 3), round(sp["dur"], 3),
                   sp["attrs"].get("pods", sp["attrs"].get("events")))
                  for sp in facts["spans"]
                  if sp["dur"] >= 0.2 and sp["cat"] in ("phase", "ingest", "wave")]
    r.report.append("spans of 0.2 s and more (name, start, seconds, pods): "
                    + json.dumps(sorted(long_spans, key=lambda x: x[1])))
    metrics = {}
    for m in metrics_of(r.bench, "per_layer", r.cell["name"]):
        if r.rehearse and m["source"] == "device_trace":
            continue              # a rehearsal prints no device metric
        value = read_layer_metric(m["name"], facts)
        # BENCHMARK.json lists the metric for this cell, so its span, counter
        # or kernel has to be there: a reader that finds nothing fails the run
        _require(value is not None, f"per-layer metric {m['name']}: nothing to "
                 f"read in {r.cell['name']} (a span, counter or kernel is missing)")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, breakdown


def read_layer_metric(name: str, facts: dict):
    """``layer_metrics/<name>.py`` ``read(facts)``; None: nothing to read."""
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_layer_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(facts)


def decide_correct(r, metrics: dict, breakdown) -> tuple:
    """``correct``: the plain reference, once the window has closed, the peak
    has been read and the program's state is no longer needed."""
    drains = r.drains
    del r.sched, r.backend, r.algo
    t_check = time.perf_counter()
    world = cluster.World(r.config, r.args.seed, r.plan)
    for wave in r.plan["warm_waves"]:
        world.warm_wave(wave)
    numbers = check.compare(world, drains, r.bindings, set(r.samples["marked_failed"]),
                            r.tie_counter, r.samples["rebinds"], seed=r.args.seed)
    check_s = time.perf_counter() - t_check
    lines = [
        f"run: window {r.window_s:.3f}s, {r.attempted} attempted, {r.n_bound} bound in "
        f"window, {r.failed} failed, waves {len(drains) - r.before.drains}, "
        f"compiles in window {r.compiles_in_window}, lowerings in window "
        f"{r.lowerings_in_window} ({r.lower_s_in_window:.2f}s), cache hits "
        f"{r.watch.hits} misses {r.watch.misses}, kernel segments "
        f"{r.window_stats.get('segments')}",
        *r.report,
        f"check: {numbers['decisions']} decisions, {numbers['scored']} scored, "
        f"{numbers['bound']} bound, reference took {check_s:.2f}s",
    ]
    if r.shapes_in_window:
        lines.append("kernel shapes first seen inside the window: "
                     + "; ".join(r.shapes_in_window))
    if r.args.control:
        lines += _control(world, drains, r.args)
    lines += [f"  {name}: {v['value']} (limit {v['limit']})"
              for name, v in check.report(numbers).items()]
    device = dict(r.device, memory_peak_bytes=r.peak)
    if breakdown is not None:
        device.update(busy_s=r.profile["busy_s"], window_s=r.profile["window_s"])
    result = {"correct": check.verdict(numbers), "attempted": r.attempted,
              "failed": r.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = check.report(numbers)
    return result, lines


def _control(world, drains, args) -> list:
    """Readings of the control (never part of a benchmark run): the
    reference in bfloat16's 8 bits of mantissa put in the program's place."""
    t = time.perf_counter()
    cut, bindings, rr = check.control_bindings(world, drains, 8, args.control)
    numbers = check.compare(world, cut, bindings, set(bindings), rr, 0, seed=args.seed)
    keep = ("decisions", "scored", "choice_mismatches", "infeasible_bindings",
            "verdict_mismatches", "tie_counter_gap")
    return ["control (8-bit gather): " + json.dumps({k: numbers[k] for k in keep})
            + f" in {time.perf_counter() - t:.1f}s"]


def main(argv=None, hooks=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", default=None, metavar="NODES,PODS",
                    help="tiny CPU rehearsal; the device says cpu and no "
                         "device metric is printed")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=JSON", help="override one parameter of the "
                    "traffic file (the knee sweep; never a benchmark run)")
    ap.add_argument("--control", type=int, default=0, metavar="DECISIONS",
                    help="also print the control's readings over the first "
                         "DECISIONS decisions (not part of a benchmark run)")
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(args, hooks or {})
    except RunFailure as e:
        print(f"benchmark.run FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    code = main()
    # the TPU runtime's teardown takes seconds and frees nothing this process
    # still needs; every child has been reaped above
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
