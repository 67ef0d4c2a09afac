"""Benchmark: batched TPU scheduling throughput vs the reference scheduler.

The harness mirrors ``test/integration/scheduler_perf`` (SURVEY.md §4.4):
fake nodes + a flood of pending pods through the REAL scheduling path
(store → informers → cache snapshot → backend → bind writes), measuring
pods-scheduled/sec.  The reference's expected throughput on this harness is
100 pods/s (warn threshold, ``scheduler_perf/scheduler_test.go:35``; hard
floor 30) — ``vs_baseline`` is measured-value / 100.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Presets:
  smoke  —   200 nodes ×   1k pods (fast sanity)
  basic  —   500 nodes ×   2k pods (BASELINE.json configs[0])
  dense  —  1000 nodes ×  10k pods
  mixed  —  1000 nodes ×  10k pods, mixed workload (default: ~20% affinity
            pods, ~10% volume pods, taints/zones/services — the honest
            preset; the phase-B kernel keeps all of it on device)
  north  —  5000 nodes × 150k pods (the north-star scale)

``--parity`` additionally runs the pure sequential CPU oracle over an
identical cluster and asserts assignment-for-assignment equality (the
"identical bindings" half of the north star), reporting it in the JSON.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import random
import sys
import time


PRESETS = {
    "smoke": (200, 1_000, "plain"),
    "basic": (500, 2_000, "plain"),
    "dense": (1_000, 10_000, "plain"),
    "mixed": (1_000, 10_000, "mixed"),
    "north": (5_000, 150_000, "mixed"),
}

ZONE = "failure-domain.beta.kubernetes.io/zone"


def make_nodes(n_nodes: int, rng: random.Random, workload: str):
    from kubernetes_tpu.api import Taint
    from kubernetes_tpu.testutil import make_node

    nodes = []
    for i in range(n_nodes):
        labels = {
            "kubernetes.io/hostname": f"node-{i:05d}",
            ZONE: f"zone-{i % 3}",
        }
        taints = []
        if workload == "mixed":
            if rng.random() < 0.3:
                labels["disk"] = rng.choice(["ssd", "hdd"])
            if rng.random() < 0.1:
                taints.append(Taint(key="dedicated", value="special", effect="NoSchedule"))
        nodes.append(
            make_node(
                f"node-{i:05d}",
                cpu=rng.choice(["8", "16", "32"]),
                memory=rng.choice(["16Gi", "32Gi", "64Gi"]),
                pods=110,
                labels=labels,
                taints=taints,
            )
        )
    return nodes


def make_services():
    from kubernetes_tpu.api import ObjectMeta, Service

    return [
        Service(meta=ObjectMeta(name=app), selector={"app": app})
        for app in ("web", "api", "db")
    ]


def make_pods(n_pods: int, rng: random.Random, workload: str):
    """Pending-pod flood.  ``plain``: 4 homogeneous RC-style templates.
    ``mixed``: adds ~20% affinity-bearing pods (soft zone co-location +
    required hostname anti-affinity — the reference's own hot spot,
    predicates.go:982), ~10% disk-volume pods, node selectors, and
    toleration-bearing pods for the tainted capacity."""
    from kubernetes_tpu.api import (
        Affinity,
        LabelSelector,
        PodAffinityTerm,
        Toleration,
        Volume,
        WeightedPodAffinityTerm,
    )
    from kubernetes_tpu.testutil import make_pod

    plain_templates = [
        dict(cpu="100m", memory="128Mi", labels={"app": "web"}),
        dict(cpu="250m", memory="256Mi", labels={"app": "api"}),
        dict(cpu="500m", memory="512Mi", labels={"app": "db"}),
        dict(cpu="1", memory="1Gi", labels={"app": "batch"}),
    ]
    if workload == "plain":
        return [
            make_pod(f"pod-{i:06d}", **plain_templates[i % len(plain_templates)])
            for i in range(n_pods)
        ]

    soft = Affinity(
        pod_affinity_preferred=[
            WeightedPodAffinityTerm(
                weight=10,
                term=PodAffinityTerm(
                    selector=LabelSelector.from_match_labels({"app": "web"}),
                    topology_key=ZONE,
                ),
            )
        ]
    )
    anti = Affinity(
        pod_anti_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "lonely"}),
                topology_key="kubernetes.io/hostname",
            )
        ]
    )
    pods = []
    for i in range(n_pods):
        r = rng.random()
        if r < 0.10:
            pods.append(
                make_pod(f"soft-{i:06d}", cpu="100m", memory="128Mi",
                         labels={"app": "web"}, affinity=soft)
            )
        elif r < 0.20:
            pods.append(
                make_pod(f"lonely-{i:06d}", cpu="100m", memory="128Mi",
                         labels={"app": "lonely"}, affinity=anti)
            )
        elif r < 0.30:
            pods.append(
                make_pod(
                    f"vol-{i:06d}", cpu="100m", memory="128Mi", labels={"app": "api"},
                    volumes=[Volume(name="v", disk_id=f"pd-{rng.randrange(2 * n_pods)}",
                                    disk_kind=rng.choice(["gce-pd", "aws-ebs"]))],
                )
            )
        elif r < 0.35:
            pods.append(
                make_pod(f"ssd-{i:06d}", cpu="250m", memory="256Mi",
                         labels={"app": "db"}, node_selector={"disk": "ssd"})
            )
        elif r < 0.40:
            pods.append(
                make_pod(
                    f"tol-{i:06d}", cpu="200m", memory="128Mi", labels={"app": "batch"},
                    tolerations=[Toleration(key="dedicated", operator="Exists")],
                )
            )
        else:
            pods.append(
                make_pod(f"pod-{i:06d}", **plain_templates[i % len(plain_templates)])
            )
    return pods


def _failure_reasons(cs, sched, assignments: dict, sample_cap: int = 500) -> dict:
    """Why pods stayed unbound: re-evaluate a sample of them against the
    final cluster state and histogram each pod's dominant predicate-failure
    reason (the per-node detail the oracle's FitError carries).  Off-clock;
    explains the unbound tail in the artifact instead of leaving it mute."""
    from kubernetes_tpu.scheduler.predicates import PredicateContext

    unbound = [k for k, v in assignments.items() if v is None]
    if not unbound:
        return {"unbound_total": 0, "sampled": 0, "reasons": {}}
    pods_by_key = {p.meta.key: p for p in cs.pods.list()[0]}
    snapshot = sched.snapshot()
    pctx = sched.priority_context(snapshot)
    ctx = PredicateContext(snapshot, pvcs=pctx.pvcs, pvs=pctx.pvs,
                           services=pctx.services)
    node_names = sorted(n for n, i in snapshot.items() if i.node is not None)
    hist: dict[str, int] = {}
    sample = unbound[:sample_cap]
    for key in sample:
        pod = pods_by_key.get(key)
        if pod is None:
            continue
        feasible, failures = sched.algorithm.find_nodes_that_fit(
            pod, node_names, snapshot, ctx
        )
        if feasible:
            # fits now (space freed since the run); call it out as such
            hist["fits-now (state changed since attempt)"] = (
                hist.get("fits-now (state changed since attempt)", 0) + 1)
            continue
        per_reason: dict[str, int] = {}
        for reasons in failures.values():
            for r in reasons:
                per_reason[r] = per_reason.get(r, 0) + 1
        if per_reason:
            dominant = max(per_reason, key=per_reason.get)
            hist[dominant] = hist.get(dominant, 0) + 1
    return {
        "unbound_total": len(unbound),
        "sampled": len(sample),
        "reasons": dict(sorted(hist.items(), key=lambda kv: -kv[1])),
    }


def run_once(
    n_nodes: int,
    n_pods: int,
    use_backend: bool,
    workload: str,
    seed: int = 0,
    emit_events: bool = False,
    want_failure_reasons: bool = False,
) -> dict:
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    rng = random.Random(seed)
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (n_nodes + n_pods))))
    for node in make_nodes(n_nodes, rng, workload):
        cs.nodes.create(node)
    if workload == "mixed":
        for svc in make_services():
            cs.services.create(svc)
    for pod in make_pods(n_pods, rng, workload):
        cs.pods.create(pod)

    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo) if use_backend else None
    sched = Scheduler(cs, algorithm=algo, backend=backend, emit_events=emit_events)
    sched.start()
    drain_order: list[str] = []
    if use_backend:
        # record the queue-drain order for the prefix-parity gate (the
        # queue is fed from the store's name-sorted LIST, not creation
        # order); one list-extend per batch — negligible on the timed path
        orig_drain = sched.queue.drain

        def _recording_drain(max_n=None):
            drained = orig_drain(max_n)
            drain_order.extend(p.meta.key for p in drained)
            return drained

        sched.queue.drain = _recording_drain
    if emit_events:
        # production shape: the hot loop enqueues, the sink thread
        # correlates + writes concurrently with the timed work
        sched.broadcaster.start()

    start = time.perf_counter()
    if use_backend:
        bound, failed = sched.schedule_pending_batch()
    else:
        bound = sched.run_pending()
        failed = 0
    elapsed = time.perf_counter() - start
    result = {
        "bound": bound,
        "failed": failed,
        "elapsed_s": elapsed,
        "pods_per_sec": bound / elapsed if elapsed > 0 else 0.0,
    }
    if use_backend:
        result["backend_stats"] = dict(backend.stats)
    if emit_events:
        # drain the remaining queue off-clock, then report what the
        # correlator actually did during the run
        sched.broadcaster.stop(drain=True)
        result["event_stats"] = dict(sched.broadcaster.correlator.stats)
    # the three reference SLIs (metrics/metrics.go:26-50), p50/p99 in ms
    m = sched.metrics

    def _pq(h, q):
        v = h.quantile(q)
        return round(v / 1e3, 3) if v != float("inf") else None

    result["sli"] = {
        "e2e_scheduling_ms": {"p50": _pq(m.e2e_scheduling_latency, 0.5),
                              "p99": _pq(m.e2e_scheduling_latency, 0.99)},
        "binding_ms": {"p50": _pq(m.binding_latency, 0.5),
                       "p99": _pq(m.binding_latency, 0.99)},
    }
    # final pod→node assignment map, for parity comparison across runs
    pods, _ = cs.pods.list()
    result["assignments"] = {p.meta.key: p.spec.node_name or None for p in pods}
    if use_backend:
        result["batch_order"] = drain_order
    if want_failure_reasons:
        result["failure_reasons"] = _failure_reasons(cs, sched, result["assignments"])
    return result


def run_parity(backend_res: dict, n_nodes: int, n_pods: int, workload: str, seed: int) -> dict:
    """The north star's 'identical bindings' gate: the oracle runs over an
    identical cluster (same seed) through the full store→bind path; its
    assignment map must match the timed backend run key-for-key."""
    oracle_res = run_once(n_nodes, n_pods, use_backend=False, workload=workload, seed=seed)
    b, o = backend_res["assignments"], oracle_res["assignments"]
    assert set(b) == set(o), "pod sets diverged"
    mismatches = [(k, o[k], b[k]) for k in o if o[k] != b[k]]
    return {
        "checked": len(o),
        "mismatches": len(mismatches),
        "sample": mismatches[:5],
        "oracle_pods_per_sec": round(oracle_res["pods_per_sec"], 1),
        "backend_pods_per_sec": round(backend_res["pods_per_sec"], 1),
    }


CHURN_SLO_P99_MS = 5_000.0  # reference pod-startup SLO (metrics_util.go:46)
# regression floor for the NORTH-scale churn preset (5k nodes).  ISSUE 3's
# pipeline reached ~1282 pods/s; ISSUE 4's zero-copy ingest ~1434.7; the
# ISSUE 5 frontier scan (monotone prefilter + chunked still_ok + axis
# tightening + batched arrival/event txns + clone-on-write work map)
# lifted same-box medians to 1788.4 pods/s (BENCH_AB_frontier_scan.json:
# old 1390.2 -> new 1788.4, 4/4 interleaved pairs both orders, worktree
# method, per-wave oracle parity exact on both arms).  1300 sits ~27%
# under the demonstrated new level (this bench has ~±15-20% day drift)
# and 30% above the previous floor, so a regression to any pre-ISSUE-3/4
# path fails the gate loudly.
CHURN_FLOOR_PODS_PER_SEC = 1_300.0


def _oracle_replay_waves(drain_batches: list, final_assignments: dict,
                         n_nodes: int, total_pods: int, workload: str,
                         seed: int) -> dict:
    """Off-clock per-wave oracle parity for a churn run: replay the
    RECORDED drain batches, in drain order, through the per-pod CPU
    oracle on an identically seeded cluster, and compare each wave's
    bindings against the timed run's final map.  Exact by prefix-closure
    (sequential-greedy: pod i's placement depends only on the initial
    cluster and the pods scheduled before it) as long as no key was
    drained twice — a requeue re-decides under different queue state, so
    the exact replay degrades honestly to 'skipped'."""
    flat = [k for b in drain_batches for k in b]
    if len(set(flat)) != len(flat):
        return {"mode": "skipped (requeues present)",
                "checked": 0, "mismatches": -1, "round_robin": None}
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    rng = random.Random(seed)
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (n_nodes + total_pods))))
    for node in make_nodes(n_nodes, rng, workload):
        cs.nodes.create(node)
    if workload == "mixed":
        for svc in make_services():
            cs.services.create(svc)
    pods_by_key = {p.meta.key: p for p in make_pods(total_pods, rng, workload)}
    sched = Scheduler(cs, algorithm=GenericScheduler(), backend=None,
                      emit_events=False)
    sched.start()
    checked = mismatches = 0
    sample = []
    for batch in drain_batches:
        for key in batch:
            cs.pods.create(pods_by_key[key])
        sched.pump()
        sched.run_pending()
        sched.pump()
        pods_now, _ = cs.pods.list()
        got = {p.meta.key: p.spec.node_name or None for p in pods_now}
        for key in batch:
            checked += 1
            if got.get(key) != final_assignments.get(key):
                mismatches += 1
                if len(sample) < 5:
                    sample.append((key, got.get(key),
                                   final_assignments.get(key)))
    # the oracle's final select_host tie-rotation counter: the sharded
    # loop's cross-shard tie-break must leave the timed run's counter at
    # exactly this value or every later tied choice lands one rotation
    # off (the --multichip ledger gates on the comparison)
    return {"mode": "exact per-wave replay", "checked": checked,
            "mismatches": mismatches, "sample": sample,
            "round_robin": sched.algorithm._round_robin}


def run_churn(n_nodes: int = 5_000, total_pods: int = 20_000, waves: int = 10,
              workload: str = "mixed", seed: int = 0, warmup: bool = True,
              pipeline: bool = True, lazy_ingest: bool = True,
              frontier: bool = True, watch_frames: bool = True,
              device_loop: bool = True, frontier_chunk: int = 512,
              verify_oracle: bool = False, trace=None,
              telemetry=None, mesh: bool = False,
              coalesce: float = 0.0) -> dict:
    """Steady-state arrival load (``test/e2e/scalability/density.go:
    316-318,474-475``): pods arrive from an ARRIVAL THREAD — wave w+1 is
    created the moment wave w leaves the queue, the density.go shape
    where creation clients are not the scheduler — and the scheduler
    serves them through ``Scheduler.run_batch_loop`` (min-batch/max-wait
    policy), so per-pod e2e scheduling latency is measured under
    continuous creation along with saturation throughput.

    Per-wave phase timers (pump / tensorize / dispatch / device-wait /
    commit / overlapped prep) and the overlap fraction (prep hidden in
    the device's shadow over total device wait) ride the result.

    ``pipeline=False`` is the A/B arm: lock-step ingest (no overlapped
    prep, no persistent node-static rows, no sticky shape buckets, no
    device-resident node state) on the SAME harness, isolating the
    ISSUE-3 pipeline from everything else.

    ``lazy_ingest=False`` is the ISSUE-4 A/B arm (``--ab-pump``): eager
    per-event ``from_dict`` and the classic item LIST (the dict
    compatibility oracle) instead of lazy decode-on-access views and the
    columnar store emit.  ``frontier=False`` is the ISSUE-5 A/B arm
    (``--ab-frontier``): the full-width plain scan instead of the
    frontier scan (monotone prefilter + chunked still_ok + mid-segment
    node-axis compaction).  ``watch_frames=False`` is the ISSUE-6 A/B
    arm (``--ab-watch``): per-event watch delivery and per-pod cache
    apply/bind confirm instead of column-packed frames, one-lock batch
    apply, and the columnar wave confirm.  ``device_loop=False`` is the
    ISSUE-11 A/B arm (``--ab-loop``): the chunked HOST loop (one
    blocking sync per chunk) instead of the device-resident
    ``lax.while_loop`` with donated carries and on-device compaction
    decisions; ``frontier_chunk`` sets the chunk width for both modes
    (the chunk-count axis of the host-sync scaling evidence).
    ``verify_oracle=True`` additionally replays
    the recorded drain batches through the per-pod CPU oracle off-clock
    and reports per-wave binding parity (``oracle_parity``).

    ``trace`` (ISSUE 7): truthy enables the wave tracer + flight
    recorder for the TIMED run only (the warm-up compiles untraced); a
    string value additionally writes the Chrome trace-event JSON
    artifact there (load into chrome://tracing / Perfetto), and the
    result carries a ``trace`` summary block either way.

    ``telemetry`` (ISSUE 13): truthy enables the continuous-telemetry
    stack for the TIMED run — the time-series scraper over the
    scheduler registry, the burn-rate SLO monitor over DEFAULT_SLOS,
    and the off-box shipper.  A string value ships the run's records
    (JSON-lines) to that path; truthy-non-string ships to ``os.devnull``
    (the A/B arm: full pipeline cost, no artifact).  The result carries
    a ``telemetry`` summary block with per-SLO burn-rate verdicts.

    The default preset is NORTH-scale churn (5,000 nodes — VERDICT r4
    directive 4): the returned dict carries an SLO verdict
    (``slo_pass``) gating e2e p99 ≤ 5s (the reference pod-startup SLO)
    and throughput ≥ the recorded floor; ``main`` exits 1 on failure."""
    import threading

    from kubernetes_tpu.api import lazy as lazy_mod
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.models.snapshot import Tensorizer
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    from kubernetes_tpu.store import frames as frames_mod

    if warmup:  # compile the wave-sized segment buckets off the clock
        run_churn(n_nodes, 2 * (total_pods // waves), 2, workload, seed + 1,
                  warmup=False, pipeline=pipeline, lazy_ingest=lazy_ingest,
                  frontier=frontier, watch_frames=watch_frames,
                  device_loop=device_loop, frontier_chunk=frontier_chunk,
                  mesh=mesh)

    lazy_was = lazy_mod.ENABLED
    frames_was = frames_mod.ENABLED
    lazy_mod.ENABLED = lazy_ingest
    frames_mod.ENABLED = watch_frames
    tracer = None
    if trace:
        from kubernetes_tpu.utils import tracing

        tracer = tracing.enable(ring_waves=waves + 2)
    try:
        r = _run_churn_timed(n_nodes, total_pods, waves, workload, seed,
                             pipeline, lazy_ingest, frontier,
                             watch_frames, device_loop, frontier_chunk,
                             verify_oracle, telemetry, mesh, coalesce)
    finally:
        lazy_mod.ENABLED = lazy_was
        frames_mod.ENABLED = frames_was
        if tracer is not None:
            from kubernetes_tpu.utils import tracing

            tracing.disable()
        if telemetry:
            # belt and braces: the timed run disables these itself on
            # the happy path; a raise mid-run must not leak the globals
            from kubernetes_tpu.utils import telemetry as telemetry_mod
            from kubernetes_tpu.utils import timeseries as timeseries_mod

            telemetry_mod.disable()
            timeseries_mod.disable()
    if tracer is not None:
        doc = tracer.chrome_trace()
        r["trace"] = {
            "enabled": True,
            "events": len(doc["traceEvents"]),
            "waves_recorded": len(tracer.ring),
            "flight_dumps": len(tracer.dumps),
            "dump_reasons": sorted({d["reason"] for d in tracer.dumps}),
        }
        if isinstance(trace, str):
            with open(trace, "w") as f:
                json.dump(doc, f)
                f.write("\n")
            r["trace"]["artifact"] = trace
    return r


def _run_churn_timed(n_nodes, total_pods, waves, workload, seed, pipeline,
                     lazy_ingest, frontier, watch_frames, device_loop,
                     frontier_chunk, verify_oracle, telemetry=None,
                     mesh=False, coalesce=0.0) -> dict:
    import threading

    from kubernetes_tpu.api import lazy as lazy_mod
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.models.snapshot import Tensorizer
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    rng = random.Random(seed)
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (n_nodes + total_pods)),
                         coalesce_window_s=coalesce))
    for node in make_nodes(n_nodes, rng, workload):
        cs.nodes.create(node)
    if workload == "mixed":
        for svc in make_services():
            cs.services.create(svc)
    all_pods = make_pods(total_pods, rng, workload)

    algo = GenericScheduler()
    # mesh=True forces the sharded wave loop (frontier_mesh default is
    # "auto", which stays single-device on the CPU backend); everything
    # else about the harness is identical, so a mesh run is A/B-comparable
    backend = TPUBatchBackend(algorithm=algo, frontier=frontier,
                              frontier_device_loop=device_loop,
                              frontier_chunk=frontier_chunk,
                              frontier_mesh=(True if mesh else "auto"))
    if not pipeline:
        backend.tensorizer = Tensorizer(sticky_buckets=False,
                                        persistent_rows=False)
    sched = Scheduler(cs, algorithm=algo, backend=backend, emit_events=True)
    sched.overlap_ingest = pipeline
    sched.start()
    sched.broadcaster.start()

    # continuous telemetry over the TIMED run (ISSUE 13): scraper over
    # the scheduler registry, burn-rate monitor on the standing SLOs,
    # shipper to the artifact path (or devnull for the cost-only arm).
    # run_churn's finally tears these globals down on any raise.
    ts_store = slo_ev = shipper = None
    if telemetry:
        from kubernetes_tpu.utils import slo as slo_mod
        from kubernetes_tpu.utils import telemetry as telemetry_mod
        from kubernetes_tpu.utils import timeseries as timeseries_mod

        ts_store = timeseries_mod.enable(sched.metrics.registry,
                                         interval_s=0.25)
        slo_ev = slo_mod.monitor(store=ts_store)
        sink = telemetry_mod.FileSink(
            telemetry if isinstance(telemetry, str) else os.devnull)
        shipper = telemetry_mod.enable(sink,
                                       registry=sched.metrics.registry)
        ts_store.add_observer(telemetry_mod.timeseries_observer(shipper))

    per_wave = total_pods // waves
    # per-wave pump timing (the loop pumps internally; wrap to attribute)
    pump_acc = [0.0]
    orig_pump = sched.pump

    def timed_pump():
        t = time.perf_counter()
        n = orig_pump()
        pump_acc[0] += time.perf_counter() - t
        return n

    sched.pump = timed_pump

    # wave-drain detection feeds the arrival thread: wave w+1 is created
    # the moment wave w left the queue, so creation overlaps scheduling
    drained = [0]
    drain_batches: list[list[str]] = []  # per drain call, keys in order
    wave_drained = [threading.Event() for _ in range(waves)]
    orig_drain = sched.queue.drain

    def recording_drain(max_n=None):
        out = orig_drain(max_n)
        if out:
            drain_batches.append([p.meta.key for p in out])
        drained[0] += len(out)
        for w in range(waves):
            if drained[0] >= (w + 1) * per_wave:
                wave_drained[w].set()
        return out

    sched.queue.drain = recording_drain

    def arrivals():
        for w in range(waves):
            # ONE batch-create txn per wave (Store.create_many): the
            # arrival client's per-pod lock/fanout round-trips leave the
            # host budget; event order (and therefore queue/drain order
            # and binding parity) is identical to per-item creates
            cs.pods.create_many_nowait(all_pods[w * per_wave:(w + 1) * per_wave])
            if not wave_drained[w].wait(timeout=300):
                return  # scheduler wedged: the SLO gate will fail loudly

    lazy_pre = lazy_mod.stats_snapshot()
    t0 = time.perf_counter()
    arr = threading.Thread(target=arrivals, daemon=True)
    arr.start()
    bound = 0
    phase_timers: list[dict] = []
    for w in range(waves):
        pump_before = pump_acc[0]
        # pump-APPLICATION bracket at the wave level (ISSUE 6): the
        # bind-confirm frame of wave w is often digested by wave w+1's
        # pre-drain pumps, so per-wave apply time is deltaed around the
        # whole serving call, not just schedule_pending_batch
        apply_before = sched._pump_apply_stats()
        fb_before = sched.metrics.confirm_fallbacks.value
        b = sched.run_batch_loop(min_batch=per_wave, max_wait=30.0,
                                 max_waves=1, poll_interval=0.002)
        bound += b
        ph = {k: round(sched.last_batch_phases.get(k, 0.0), 4)
              for k in ("tensorize_s", "dispatch_s", "device_wait_s",
                        "commit_s", "prep_s", "decode_s")}
        ph["promotions"] = int(sched.last_batch_phases.get("promotions", 0))
        ph["pump_s"] = round(pump_acc[0] - pump_before, 4)
        apply_after = sched._pump_apply_stats()
        ph["apply_s"] = round(apply_after[0] - apply_before[0], 4)
        ph["frames"] = apply_after[1] - apply_before[1]
        ph["frame_events"] = apply_after[2] - apply_before[2]
        ph["confirm_fallbacks"] = int(
            sched.metrics.confirm_fallbacks.value - fb_before)
        # blocking device→host round-trips of the wave (ISSUE 11): fed by
        # the same backend seam device_wait uses; O(compactions + 1) per
        # segment in loop mode, O(chunks) in the chunked host loop
        ph["host_syncs"] = int(sched.last_batch_phases.get("host_syncs", 0))
        ph["bound"] = b
        fr = sched.last_batch_phases.get("frontier")
        if fr:
            # per-wave alive-union trajectory (the ISSUE 5 artifact):
            # prefilter width + per-chunk alive fractions per segment
            ph["frontier"] = fr
        mw = sched.last_batch_phases.get("mesh")
        if mw:
            # sharded-wave attribution (ISSUE 18): shard count, per-shard
            # upload fractions, and the alive-fraction skew of the wave
            ph["mesh"] = mw
        phase_timers.append(ph)
    elapsed = time.perf_counter() - t0
    arr.join(timeout=10)
    sched.broadcaster.stop(drain=True)
    # unbound from FINAL state, not failure events: a pod that failed a
    # wave re-queues after backoff and would be double-counted by events
    pods_final, _ = cs.pods.list()
    unbound = sum(1 for p in pods_final if not p.spec.node_name)
    m = sched.metrics

    def _pq(h, q):
        v = h.quantile(q)
        return round(v / 1e3, 3) if v != float("inf") else None

    pps = round(bound / elapsed, 1) if elapsed > 0 else 0.0
    p99 = _pq(m.e2e_scheduling_latency, 0.99)
    prep_total = sum(p["prep_s"] for p in phase_timers)
    wait_total = sum(p["device_wait_s"] for p in phase_timers)
    ncache = backend.device_node_cache.stats
    lazy_post = lazy_mod.stats_snapshot()
    pod_inf = sched.informers.informer("Pod").stats
    telem_block = None
    if ts_store is not None:
        from kubernetes_tpu.utils import telemetry as telemetry_mod
        from kubernetes_tpu.utils import timeseries as timeseries_mod

        ts_store.sample_once()  # one final scrape so the tail is in-ring
        telemetry_mod.disable()  # drains the queue through the sink
        timeseries_mod.disable()
        verdicts = {}
        for s in (slo_ev.slos if slo_ev is not None else []):
            fast = s.sli.bad_fraction(ts_store, s.fast_window_s)
            slow = s.sli.bad_fraction(ts_store, s.slow_window_s)
            verdicts[s.name] = {
                "breached": slo_ev.state(s.name)["breached"],
                "objective": s.objective,
                "fast_burn": round(fast / s.error_budget, 2)
                if fast is not None else None,
                "slow_burn": round(slow / s.error_budget, 2)
                if slow is not None else None,
            }
        telem_block = {
            "enabled": True,
            "artifact": telemetry if isinstance(telemetry, str) else None,
            "scrapes": ts_store.scrapes,
            "tracks": len(ts_store.tracks()),
            "shipper": shipper.stats(),
            "breaches_fired": slo_ev.breaches_fired if slo_ev else 0,
            "slo_verdicts": verdicts,
        }

    oracle_parity = None
    if verify_oracle:
        oracle_parity = _oracle_replay_waves(
            drain_batches, {p.meta.key: p.spec.node_name or None
                            for p in pods_final},
            n_nodes, total_pods, workload, seed)
        # rr tie-counter parity: the deterministic cross-shard tie-break
        # must advance the timed run's rotation counter exactly as the
        # sequential oracle does
        oracle_parity["round_robin_timed"] = algo._round_robin
        oracle_parity["round_robin_match"] = (
            oracle_parity["round_robin"] is not None
            and oracle_parity["round_robin"] == algo._round_robin)
    return {
        "nodes": n_nodes,
        "pods": total_pods,
        "waves": waves,
        "bound": bound,
        "unbound": unbound,
        "pods_per_sec": pps,
        "pipeline": pipeline,
        "e2e_scheduling_ms": {"p50": _pq(m.e2e_scheduling_latency, 0.5),
                              "p99": p99},
        "binding_ms": {"p50": _pq(m.binding_latency, 0.5),
                       "p99": _pq(m.binding_latency, 0.99)},
        "queue_wait_ms": {"p50": _pq(m.batch_queue_wait, 0.5),
                          "p99": _pq(m.batch_queue_wait, 0.99)},
        "phase_timers": phase_timers,
        # fraction of total device wait filled with overlapped host prep
        "overlap_fraction": round(prep_total / (prep_total + wait_total), 3)
        if prep_total + wait_total > 0 else 0.0,
        # device-resident node state: how much of the node axis was
        # actually re-uploaded (0 dirty cols on a quiet fleet)
        "node_upload": {
            "reuses": ncache["reuses"], "uploads": ncache["uploads"],
            "col_updates": ncache["col_updates"],
            "dirty_fraction": round(
                ncache["dirty_cols"] / max(ncache["cols_total"], 1), 4),
            # per-shard cumulative upload attribution (ISSUE 18): only
            # populated when the node cache served a sharded mesh
            **({"shard_dirty_cols": list(ncache["shard_dirty_cols"]),
                "shard_cols_total": list(ncache["shard_cols_total"]),
                "shard_upload_fractions": [
                    round(d / max(c, 1), 4)
                    for d, c in zip(ncache["shard_dirty_cols"],
                                    ncache["shard_cols_total"])]}
               if ncache.get("shard_cols_total") else {}),
        },
        # frontier scan (ISSUE 5): segments served, device compactions,
        # tensorize-time column drops, full-width retries
        "frontier": {
            "enabled": frontier,
            "segments": backend.stats["frontier_segments"],
            "compactions": backend.stats["frontier_compactions"],
            "prefilter_cols": backend.stats["frontier_prefilter_cols"],
            "fallbacks": backend.stats["frontier_fallbacks"],
            "loop_fallbacks": backend.stats["frontier_loop_fallbacks"],
            "fallback_modes": dict(backend.stats["frontier_fallback_modes"]),
        },
        # sharded wave loop (ISSUE 18): requested mode, observed shard
        # count, and the per-wave attribution attrs (also on each
        # phase_timers[w]["mesh"])
        "mesh": {
            "requested": bool(mesh),
            "n_shards": max((p["mesh"]["n_shards"] for p in phase_timers
                             if p.get("mesh")), default=0),
            "waves_sharded": sum(1 for p in phase_timers if p.get("mesh")),
        },
        # device-resident wave loop (ISSUE 11): blocking device→host
        # round-trips the run actually paid, per wave and in total
        "host_syncs": {
            "device_loop": device_loop,
            "chunk": frontier_chunk,
            "total": backend.stats["host_syncs"],
            "per_wave": [p["host_syncs"] for p in phase_timers],
        },
        "row_cache": dict(backend.tensorizer.node_rows_stats or {}),
        # zero-copy ingest (ISSUE 4): what the decode path actually did
        "ingest": {
            "lazy": lazy_ingest,
            "decoded_events": pod_inf["decoded_events"],
            "decode_s": round(pod_inf["decode_s"], 4),
            "decode_errors": pod_inf["decode_errors"],
            "wrapped": lazy_post["wrapped"] - lazy_pre["wrapped"],
            "promotions": (lazy_post["promotions"] + lazy_post["sections"]
                           - lazy_pre["promotions"] - lazy_pre["sections"]),
        },
        # batched watch frames (ISSUE 6): delivery + one-lock apply +
        # columnar confirm volume of the run
        "watch": {
            "frames_enabled": watch_frames,
            "frames": pod_inf["frames"],
            "frame_events": pod_inf["frame_events"],
            "batch_errors": pod_inf["batch_errors"],
            "apply_s": round(pod_inf["apply_s"], 4),
            "confirm_fallbacks": int(sched.metrics.confirm_fallbacks.value),
        },
        "oracle_parity": oracle_parity,
        # continuous-telemetry summary (ISSUE 13): scrape/ship counters
        # and per-SLO burn-rate verdicts; None when the stack was off
        "telemetry": telem_block,
        "slo_p99_ms": CHURN_SLO_P99_MS,
        "floor_pods_per_sec": CHURN_FLOOR_PODS_PER_SEC,
        "slo_pass": bool(p99 is not None and p99 <= CHURN_SLO_P99_MS
                         and pps >= CHURN_FLOOR_PODS_PER_SEC),
    }


def run_churn_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                 waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B of the steady-state pipeline: B (new) =
    overlapped ingest + persistent rows + sticky buckets + device-resident
    node state; A (old) = all four off, same harness, same seeds.  Writes
    the BENCH_AB_churn_pipeline.json ledger shape."""
    # pay each arm's XLA compiles off the books
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, pipeline=True)
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, pipeline=False)

    def one(pipe: bool) -> dict:
        return run_churn(n_nodes, total_pods, waves, seed=seed,
                         warmup=False, pipeline=pipe)

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    bounds = set()
    for _ in range(pairs):
        b = one(True)
        a = one(False)
        ab_pairs.append({"B_new": b["pods_per_sec"], "A_old": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-churn AB: B={b['pods_per_sec']} A={a['pods_per_sec']} "
              f"overlap={b['overlap_fraction']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_old": a["pods_per_sec"], "B_new": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-churn BA: A={a['pods_per_sec']} B={b['pods_per_sec']}",
              file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    won = sum(1 for p in ab_pairs + ba_pairs if p["B_new"] > p["A_old"])
    return {
        "claim": ("Steady-state scheduling pipeline: overlapped wave ingest "
                  "(prep in the device's shadow), incremental tensorize "
                  "(persistent node-static rows), sticky shape buckets (no "
                  "mid-run recompiles), device-resident node state"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, per-arm warm-up compiles "
                   "paid up front; A = pipeline seams off (pre-ISSUE-3 "
                   "behavior), B = pipeline on"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_old_all": a_all,
        "B_new_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "b_won_pairs": f"{won}/{len(ab_pairs) + len(ba_pairs)} (both orders)",
        "bound_counts": sorted(bounds),
    }


def run_pump_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B of the zero-copy ingest path (ISSUE 4):
    B (new) = lazy decode-on-access watch/LIST views + the columnar store
    emit; A (old) = eager per-event ``from_dict`` + classic item LIST (the
    dict compatibility oracle), same harness, same seeds.  The first run
    of EACH arm additionally replays the recorded drain batches through
    the per-pod CPU oracle (off-clock) and reports per-wave binding
    parity.  Writes the BENCH_AB_pump_ingest.json ledger shape."""
    # pay the XLA compiles off the books (shape buckets are identical in
    # both arms — one warm-up covers the process-wide compile cache)
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, lazy_ingest=True)

    parity = {}

    def one(lazy: bool, verify: bool = False) -> dict:
        r = run_churn(n_nodes, total_pods, waves, seed=seed, warmup=False,
                      lazy_ingest=lazy, verify_oracle=verify)
        if verify:
            parity["lazy" if lazy else "eager"] = r["oracle_parity"]
        return r

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    bounds = set()
    for i in range(pairs):
        b = one(True, verify=(i == 0))
        a = one(False, verify=(i == 0))
        ab_pairs.append({"B_new": b["pods_per_sec"], "A_old": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-pump AB: B={b['pods_per_sec']} A={a['pods_per_sec']} "
              f"decode_s A={a['ingest']['decode_s']} "
              f"B={b['ingest']['decode_s']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_old": a["pods_per_sec"], "B_new": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-pump BA: A={a['pods_per_sec']} B={b['pods_per_sec']}",
              file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    won = sum(1 for p in ab_pairs + ba_pairs if p["B_new"] > p["A_old"])
    return {
        "claim": ("Zero-copy ingest: lazy decode-on-access watch/LIST views "
                  "(typed fields materialize only when touched) + columnar "
                  "store LIST emit (shared-subtree views, identity/request/"
                  "signature columns) between store and tensorizer"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, warm-up compiles paid up "
                   "front; A = eager from_dict per event + item LIST "
                   "(pre-ISSUE-4), B = lazy + columnar; first run of each "
                   "arm replayed off-clock through the per-pod CPU oracle "
                   "per drained wave"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_old_all": a_all,
        "B_new_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "b_won_pairs": f"{won}/{len(ab_pairs) + len(ba_pairs)} (both orders)",
        "bound_counts": sorted(bounds),
        "oracle_parity": parity,
    }


def run_frontier_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                    waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B of the frontier scan (ISSUE 5):
    B (new) = frontier mode on (tensorize-time monotone prefilter,
    chunked still_ok scan, mid-segment node-axis compaction); A (old) =
    the full-width plain scan, same harness, same seeds.  The first pair
    replays both arms' recorded drain batches through the per-pod CPU
    oracle (off-clock) and reports per-wave binding parity.  Writes the
    BENCH_AB_frontier_scan.json ledger shape."""
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, frontier=True)
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, frontier=False)

    parity = {}

    def one(frontier: bool, verify: bool = False) -> dict:
        r = run_churn(n_nodes, total_pods, waves, seed=seed, warmup=False,
                      frontier=frontier, verify_oracle=verify)
        if verify:
            parity["frontier" if frontier else "plain"] = r["oracle_parity"]
        return r

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    bounds = set()
    trajectories = None
    for i in range(pairs):
        b = one(True, verify=(i == 0))
        a = one(False, verify=(i == 0))
        if trajectories is None:
            trajectories = [p.get("frontier") for p in b["phase_timers"]]
        ab_pairs.append({"B_new": b["pods_per_sec"], "A_old": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-frontier AB: B={b['pods_per_sec']} A={a['pods_per_sec']} "
              f"frontier={b['frontier']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_old": a["pods_per_sec"], "B_new": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-frontier BA: A={a['pods_per_sec']} B={b['pods_per_sec']}",
              file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    won = sum(1 for p in ab_pairs + ba_pairs if p["B_new"] > p["A_old"])
    return {
        "claim": ("Frontier scan: tensorize-time monotone node prefilter, "
                  "per-signature still_ok carry plane, and mid-segment "
                  "device node-axis compaction on the XLA scan path "
                  "(bit-exact oracle parity by construction)"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, per-arm warm-up compiles "
                   "paid up front; A = frontier off (full-width plain "
                   "scan), B = frontier on; first pair of each arm "
                   "replayed off-clock through the per-pod CPU oracle per "
                   "drained wave"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_old_all": a_all,
        "B_new_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "b_won_pairs": f"{won}/{len(ab_pairs) + len(ba_pairs)} (both orders)",
        "bound_counts": sorted(bounds),
        "oracle_parity": parity,
        "alive_trajectories_first_run": trajectories,
    }


def run_watch_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                 waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B of batched watch frames (ISSUE 6):
    B (new) = column-packed watch frames + one-lock informer batch apply
    + the scheduler's columnar wave confirm; A (old) = per-event watch
    delivery and per-pod cache apply/bind confirm, same harness, same
    seeds.  The first pair replays both arms' recorded drain batches
    through the per-pod CPU oracle (off-clock) and reports per-wave
    binding parity.  Writes the BENCH_AB_watch_frames.json ledger shape
    (the recorded ledger uses the worktree method; this flag A/B
    isolates the feature seam on one tree)."""
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, watch_frames=True)
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, watch_frames=False)

    parity = {}

    def one(framed: bool, verify: bool = False) -> dict:
        r = run_churn(n_nodes, total_pods, waves, seed=seed, warmup=False,
                      watch_frames=framed, verify_oracle=verify)
        if verify:
            parity["frames" if framed else "per_event"] = r["oracle_parity"]
        return r

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    a_apply, b_apply = [], []
    bounds = set()
    for i in range(pairs):
        b = one(True, verify=(i == 0))
        a = one(False, verify=(i == 0))
        ab_pairs.append({"B_new": b["pods_per_sec"], "A_old": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        b_apply.append(b["watch"]["apply_s"])
        a_apply.append(a["watch"]["apply_s"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-watch AB: B={b['pods_per_sec']} A={a['pods_per_sec']} "
              f"apply_s A={a['watch']['apply_s']} B={b['watch']['apply_s']}",
              file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_old": a["pods_per_sec"], "B_new": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        a_apply.append(a["watch"]["apply_s"])
        b_apply.append(b["watch"]["apply_s"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-watch BA: A={a['pods_per_sec']} B={b['pods_per_sec']}",
              file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    won = sum(1 for p in ab_pairs + ba_pairs if p["B_new"] > p["A_old"])
    return {
        "claim": ("Batched watch frames: column-packed event delivery "
                  "(one frame per correlated store txn), one-lock informer "
                  "batch apply, and the scheduler's columnar wave confirm "
                  "(prev-revision fence) from store to bind confirm"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, per-arm warm-up compiles "
                   "paid up front; A = frames seam off (per-event delivery "
                   "+ per-pod apply/confirm, pre-ISSUE-6), B = frames on; "
                   "first pair of each arm replayed off-clock through the "
                   "per-pod CPU oracle per drained wave"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_old_all": a_all,
        "B_new_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "b_won_pairs": f"{won}/{len(ab_pairs) + len(ba_pairs)} (both orders)",
        "bound_counts": sorted(bounds),
        "apply_s_per_run": {"A_old": a_apply, "B_new": b_apply},
        "oracle_parity": parity,
    }


def _rss_mb() -> float:
    """Current resident set (VmRSS) in MiB — current, not peak, so the
    second arm of an A/B is not poisoned by the first arm's high-water
    mark the way ``ru_maxrss`` would be."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except OSError:
        pass
    return 0.0


def _fleet_arm(arm_b: bool, n_watchers: int, seed_pods: int, churn_ops: int,
               http_watchers: int, selector_watchers: int, n_informers: int,
               pump_threads: int, coalesce_window_s: float, seed: int,
               slo_probe: bool, drain_timeout_s: float = 120.0) -> dict:
    """One arm of the hollow-watcher fleet bench: B = coalescing window +
    framed delivery + shared encode, A = per-event delivery (the
    pre-serving-tier broadcaster), same harness, same seeded churn.

    The fleet is kubemark applied to the WATCH axis: ``n_watchers``
    in-process hollow watchers (no thread each — a pump pool drives
    slices), a small HTTP cohort on real apiserver streams (selector
    watchers among them exercising column-level sub-frame packing), and
    a few real ``SharedInformer``s with ``compact_on_resync`` for the
    RSS point.  Throughput is LOGICAL fan-out: every churn event must
    reach every full watcher (a coalesced fold counts — the client holds
    the newest state that event produced), so events/s =
    churn_ops x full_watchers / drain wall."""
    import dataclasses
    import threading

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.client.informer import SharedInformer
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.kubelet.hollow import HollowWatcher, HollowWatcherFleet
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.store import frames as frames_mod
    from kubernetes_tpu.utils import tracing
    from kubernetes_tpu.utils.fanout import WatchFanoutTracker
    from kubernetes_tpu.utils.metrics import (DEFAULT_STORE_METRICS,
                                              ClientMetrics, Registry)
    from kubernetes_tpu.utils.slo import BurnRateEvaluator, serving_slos
    from kubernetes_tpu.utils.timeseries import TimeSeriesStore

    frames_was, shenc_was = frames_mod.ENABLED, frames_mod.SHARED_ENCODE
    frames_mod.ENABLED = arm_b
    frames_mod.SHARED_ENCODE = arm_b
    sm = DEFAULT_STORE_METRICS
    sm0 = (sm.coalesce_flushes.value, sm.coalesced_events.value,
           sm.coalesce_fallbacks.value)
    store = Store(event_log_window=max(200_000, 8 * (seed_pods + churn_ops)),
                  coalesce_window_s=(coalesce_window_s if arm_b else 0.0))
    server = None
    stop = threading.Event()
    stall = threading.Event()
    threads: list[threading.Thread] = []
    tracer = tracing.enable(ring_waves=4) if slo_probe else None
    try:
        rng = random.Random(seed)
        cs = Clientset(store)

        def pod(i):
            return {"metadata": {"name": f"fp-{i:05d}", "namespace": "default",
                                 "labels": {"tier": "hot" if i % 2 == 0
                                            else "cold"}},
                    "spec": {}, "status": {"phase": "Pending"}}

        for i in range(seed_pods):
            store.create("Pod", pod(i))
        seed_head = store.revision

        metrics = ClientMetrics(Registry())
        tracker = WatchFanoutTracker(metrics)
        fleet = HollowWatcherFleet(store, n_watchers, kind="Pod",
                                   frames=arm_b, tracker=tracker,
                                   from_revision=seed_head)
        server = APIServer(store)
        server.start()
        remote = RemoteStore(server.url)
        http_fleet = HollowWatcherFleet(remote, http_watchers, kind="Pod",
                                        frames=arm_b, tracker=tracker,
                                        prefix="http",
                                        from_revision=seed_head)
        sel_watchers = [
            HollowWatcher(
                f"sel-{i:03d}",
                remote.watch("Pod", from_revision=seed_head, frames=arm_b,
                             label_selector="tier=hot"))
            for i in range(selector_watchers)
        ]
        informers = [SharedInformer(cs.pods, compact_on_resync=True)
                     for _ in range(n_informers)]
        for inf in informers:
            inf.start_manual()

        # -- pump pool: slices of the hollow fleet + one aux driver --------
        def pump_slice(ws):
            while not stop.is_set():
                if stall.is_set():
                    time.sleep(0.002)
                    continue
                n = 0
                for w in ws:
                    n += w.pump()
                if n == 0:
                    time.sleep(0.001)

        def pump_aux():
            while not stop.is_set():
                if stall.is_set():
                    time.sleep(0.002)
                    continue
                n = http_fleet.pump_all()
                for w in sel_watchers:
                    n += w.pump()
                for inf in informers:
                    n += inf.pump()
                if n == 0:
                    time.sleep(0.001)

        step = max(1, n_watchers // pump_threads)
        for j in range(0, n_watchers, step):
            t = threading.Thread(target=pump_slice,
                                 args=(fleet.watchers[j:j + step],),
                                 daemon=True, name=f"fleet-pump-{j}")
            threads.append(t)
        threads.append(threading.Thread(target=pump_aux, daemon=True,
                                        name="fleet-pump-aux"))

        # staleness sampler: per-tick p50/p99 revision lag across the
        # hollow fleet (plain int reads — watcher applied_rev is a word)
        lag_p50: list[int] = []
        lag_p99: list[int] = []

        def sampler():
            while not stop.is_set():
                head = store.revision
                tracker.observe_head(head)
                lags = sorted(head - w.applied_rev for w in fleet.watchers)
                lag_p50.append(lags[len(lags) // 2])
                lag_p99.append(lags[(len(lags) * 99) // 100])
                tracker.sample()
                time.sleep(0.02)

        threads.append(threading.Thread(target=sampler, daemon=True,
                                        name="fleet-sampler"))
        for t in threads:
            t.start()

        # -- the measured churn: singles (the coalescer's diet) ------------
        alive = set(range(seed_pods))
        hot = list(range(0, seed_pods, 2))
        touched: set = set()
        t0 = time.perf_counter()
        for op in range(churn_ops):
            i = rng.choice(hot)
            touched.add(i)
            r = rng.random()
            if i in alive and r < 0.12:
                store.delete("Pod", "default", f"fp-{i:05d}")
                alive.discard(i)
            elif i not in alive:
                store.create("Pod", pod(i))
                alive.add(i)
            else:
                obj = store.get("Pod", "default", f"fp-{i:05d}")
                obj["status"] = {"phase": f"Running-{op}"}
                store.update("Pod", obj)
        head = store.revision
        deadline = time.perf_counter() + drain_timeout_s
        while (fleet.converged(head) < n_watchers
               or http_fleet.converged(head) < http_watchers):
            if time.perf_counter() > deadline:
                break
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        # grace for the selector cohort (its applied_rev tops out at the
        # last MATCHING revision, not head) and the informers
        time.sleep(0.25)

        full_clients = n_watchers + http_watchers
        logical = churn_ops * full_clients
        delivered = sum(w.event_units for w in fleet.watchers + http_fleet.watchers)
        deliveries = sum(w.deliveries for w in fleet.watchers + http_fleet.watchers)

        # -- state-equivalence gate (over the keys the watchers SAW:
        # the fleet watches from the seed head, so only churned keys
        # have deliveries to agree on) --------------------------------------
        expected = {}
        for i in touched:
            key = f"default/fp-{i:05d}"
            if i in alive:
                expected[key] = int(
                    store.get("Pod", "default", f"fp-{i:05d}")
                    ["metadata"]["resourceVersion"])
            else:
                expected[key] = None
        mismatches = gapped = 0
        for w in fleet.watchers + http_fleet.watchers:
            if w.gaps:
                gapped += 1
                continue
            for key, rev in expected.items():
                if w.cache.get(key) != rev and not (rev is None
                                                    and key not in w.cache):
                    mismatches += 1
                    break
        sel_bad = sel_mismatch = 0
        for w in sel_watchers:
            if any(not k.split("/", 1)[1].startswith("fp-") or
                   int(k.split("fp-")[1]) % 2 != 0 for k in w.cache):
                sel_bad += 1
            for key, rev in expected.items():
                if rev is not None and w.cache.get(key) != rev:
                    sel_mismatch += 1
                    break
        for inf in informers:
            inf.relist()  # resync -> compact_on_resync sweep (the RSS point)
        inf_lag = [head - inf.last_revision for inf in informers]
        rss = _rss_mb()

        # -- SLO probe: stall the pumps, burn, drain, recover --------------
        slo_block = None
        if slo_probe:
            tracker.attach_breach_context()
            clk = [0.0]
            ts = TimeSeriesStore(metrics.registry, interval_s=0.5,
                                 capacity=600, clock=lambda: clk[0])
            slos = [dataclasses.replace(s, fast_window_s=1.0,
                                        slow_window_s=3.0, recovery_evals=2)
                    for s in serving_slos(worst_lag_revisions=40.0)]
            ev = BurnRateEvaluator(slos=slos, store=ts)
            events: list[dict] = []

            def tick():
                clk[0] += 0.5
                tracker.observe_head(store.revision)
                tracker.sample()
                ts.sample_once()
                events.extend(ev.evaluate())

            stall.set()
            for op in range(120):  # lag builds while nobody pumps
                i = rng.choice(hot)
                if i in alive:
                    obj = store.get("Pod", "default", f"fp-{i:05d}")
                    obj["status"] = {"phase": f"stall-{op}"}
                    store.update("Pod", obj)
            store.flush_coalesced()
            for _ in range(30):
                tick()
                if any(e["type"] == "breach" for e in events):
                    break
                time.sleep(0.02)
            stall.clear()
            shead = store.revision
            sdl = time.perf_counter() + 30.0
            while (fleet.converged(shead) < n_watchers
                   and time.perf_counter() < sdl):
                time.sleep(0.005)
            for _ in range(40):
                tick()
                if any(e["type"] == "recovered" for e in events):
                    break
                time.sleep(0.02)
            dump_ctx = None
            for d in (tracer.dumps if tracer is not None else []):
                if d["reason"].startswith("slo:watch_fanout_worst_client"):
                    dump_ctx = d["attrs"].get("context")
            slo_block = {
                "slo": "watch_fanout_worst_client_staleness",
                "breached": any(e["type"] == "breach" for e in events),
                "recovered": any(e["type"] == "recovered" for e in events),
                "breach_dump_top_laggards": (
                    len(dump_ctx["top_laggards"]) if dump_ctx else 0),
                "events": events,
            }

        return {
            "arm": "B_coalesced_shared" if arm_b else "A_per_event",
            "wall_s": round(wall, 3),
            "fanout_events_per_s": int(logical / wall) if wall else None,
            "logical_events": logical,
            "delivered_units": delivered,
            "deliveries": deliveries,
            "staleness_p50_revisions": (sorted(lag_p50)[len(lag_p50) // 2]
                                        if lag_p50 else 0),
            "staleness_p99_revisions": (sorted(lag_p99)[len(lag_p99) // 2]
                                        if lag_p99 else 0),
            "rss_mb": rss,
            "coalesce": {
                "flushes": int(sm.coalesce_flushes.value - sm0[0]),
                "folded": int(sm.coalesced_events.value - sm0[1]),
                "fallbacks": int(sm.coalesce_fallbacks.value - sm0[2]),
            },
            "equiv": {"clients": full_clients, "mismatches": mismatches,
                      "gapped": gapped},
            "selector": {"clients": selector_watchers,
                         "non_matching_keys": sel_bad,
                         "mismatches": sel_mismatch},
            "informers": {"count": n_informers,
                          "compact_on_resync": True,
                          "lag_after_relist": inf_lag,
                          "compactions": sum(i.stats["compactions"]
                                             for i in informers)},
            "slo": slo_block,
        }
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        try:
            fleet.stop_all()
            http_fleet.stop_all()
            for w in sel_watchers:
                w.stop()
            for inf in informers:
                inf.stop()
        except Exception:
            pass
        if server is not None:
            server.stop()
        store.close()
        if tracer is not None:
            tracing.disable()
        frames_mod.ENABLED = frames_was
        frames_mod.SHARED_ENCODE = shenc_was


def run_watch_fleet(n_watchers: int = 10_000, seed_pods: int = 400,
                    churn_ops: int = 600, http_watchers: int = 24,
                    selector_watchers: int = 8, n_informers: int = 4,
                    pump_threads: int = 8, coalesce_window_s: float = 0.005,
                    seed: int = 0, parity: bool = True) -> dict:
    """The hollow-watcher fleet bench (ISSUE 19): ``n_watchers``
    concurrent watch clients against ONE broadcaster under single-event
    churn, A/B-ing the serving tier (B = time-window coalescing + framed
    delivery + single-encode fan-out; A = per-event delivery).

    Ships the BENCH_watch_fleet.json evidence: logical fan-out events/s
    per arm (every churn event reaching every client), per-client
    staleness p50/p99 in revisions, RSS with ``compact_on_resync``
    informers riding along, a zero-mismatch state-equivalence gate over
    every client's final cache, the per-CLIENT staleness SLO burning and
    recovering mid-run (with the top-K laggard breach dump), and — with
    ``parity`` — the north-preset churn replayed through the per-pod CPU
    oracle with the coalescing window ON."""
    a = _fleet_arm(False, n_watchers, seed_pods, churn_ops, http_watchers,
                   selector_watchers, n_informers, pump_threads,
                   coalesce_window_s, seed, slo_probe=False)
    print(f"# watch-fleet A: {a['fanout_events_per_s']} ev/s "
          f"wall={a['wall_s']}s equiv={a['equiv']}", file=sys.stderr)
    b = _fleet_arm(True, n_watchers, seed_pods, churn_ops, http_watchers,
                   selector_watchers, n_informers, pump_threads,
                   coalesce_window_s, seed, slo_probe=True)
    print(f"# watch-fleet B: {b['fanout_events_per_s']} ev/s "
          f"wall={b['wall_s']}s equiv={b['equiv']} slo={b['slo']}",
          file=sys.stderr)
    ratio = (round(b["fanout_events_per_s"] / a["fanout_events_per_s"], 2)
             if a["fanout_events_per_s"] else None)

    parity_block = None
    if parity:
        print("# watch-fleet: north-preset oracle parity with coalescing on",
              file=sys.stderr)
        r = run_churn(5_000, 20_000, 10, seed=seed, verify_oracle=True,
                      coalesce=coalesce_window_s)
        parity_block = dict(r["oracle_parity"],
                            coalesce_window_s=coalesce_window_s,
                            pods_per_sec=r["pods_per_sec"])

    mism = (a["equiv"]["mismatches"] + b["equiv"]["mismatches"]
            + a["selector"]["mismatches"] + b["selector"]["mismatches"]
            + a["selector"]["non_matching_keys"]
            + b["selector"]["non_matching_keys"])
    gapped = a["equiv"]["gapped"] + b["equiv"]["gapped"]
    slo_ok = bool(b["slo"] and b["slo"]["breached"] and b["slo"]["recovered"]
                  and b["slo"]["breach_dump_top_laggards"] > 0)
    verdict = {
        "pass": bool(ratio is not None and ratio >= 3.0 and mism == 0
                     and gapped == 0 and slo_ok
                     and (parity_block is None
                          or parity_block["mismatches"] == 0)),
        "fanout_ratio_B_over_A": ratio,
        "min_ratio": 3.0,
        "state_mismatches": mism,
        "dropped_state_clients": gapped,
        "slo_burned_and_recovered": slo_ok,
        "oracle_parity_mismatches": (parity_block["mismatches"]
                                     if parity_block else None),
    }
    return {
        "claim": ("Heavy-traffic serving tier: a bounded time-window "
                  "coalescing seam at the broadcaster (per-key latest-wins "
                  "folds into synthetic watch frames), column-level "
                  "selector sub-frames, and single-encode fan-out — "
                  "measured as logical fan-out throughput against a "
                  "kubemark-style hollow-watcher fleet"),
        "method": (f"{n_watchers} hollow in-process watchers + "
                   f"{http_watchers} HTTP stream clients "
                   f"(+{selector_watchers} selector watchers, "
                   f"{n_informers} compact_on_resync informers) on one "
                   f"store; {churn_ops} single-object churn ops over "
                   f"{seed_pods} seeded pods; both arms same seeds, same "
                   "pump pool; throughput is logical fan-out (churn_ops x "
                   "full clients / drain wall); equivalence gates every "
                   "client's final cache against the store; the B arm "
                   "additionally stalls the pumps to burn and recover the "
                   "per-CLIENT staleness SLO"),
        "watchers": {"hollow": n_watchers, "http": http_watchers,
                     "selector": selector_watchers,
                     "informers": n_informers},
        "churn": {"seed_pods": seed_pods, "ops": churn_ops,
                  "coalesce_window_s": coalesce_window_s},
        "A": a,
        "B": b,
        "oracle_parity_coalesced": parity_block,
        "verdict": verdict,
    }


def run_loop_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B of the device-resident wave loop
    (ISSUE 11): B (new) = the chunked frontier scan driven as ONE
    ``lax.while_loop`` dispatch per segment (donated carries, on-device
    compaction flag, all-G ``still_ok`` refresh at chunk boundaries);
    A (old) = the chunked HOST loop (one blocking sync per chunk), same
    frontier plane, same harness, same seeds.  The first pair replays
    both arms' recorded drain batches through the per-pod CPU oracle
    (off-clock) and reports per-wave binding parity.  An off-clock
    chunk-width sweep (512 → 128, a 4x chunk-count increase) records
    per-wave ``host_syncs`` for both modes — the loop's must stay flat
    (O(compactions + 1)) while the host loop's grow with chunk count.
    Writes the BENCH_AB_device_loop.json ledger shape (the recorded
    ledger uses the worktree method; this flag A/B isolates the loop
    seam on one tree)."""
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, device_loop=True)
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False, device_loop=False)

    parity = {}
    syncs_first = {}

    def one(loop: bool, verify: bool = False) -> dict:
        r = run_churn(n_nodes, total_pods, waves, seed=seed, warmup=False,
                      device_loop=loop, verify_oracle=verify)
        if verify:
            parity["loop" if loop else "chunked_host"] = r["oracle_parity"]
            syncs_first["loop" if loop else "chunked_host"] = r["host_syncs"]
        return r

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    bounds = set()
    for i in range(pairs):
        b = one(True, verify=(i == 0))
        a = one(False, verify=(i == 0))
        ab_pairs.append({"B_new": b["pods_per_sec"], "A_old": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-loop AB: B={b['pods_per_sec']} A={a['pods_per_sec']} "
              f"syncs B={b['host_syncs']['total']} "
              f"A={a['host_syncs']['total']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_old": a["pods_per_sec"], "B_new": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-loop BA: A={a['pods_per_sec']} B={b['pods_per_sec']}",
              file=sys.stderr)
    # off-clock sync-scaling sweep: same workload, chunk 512 then 128
    # (4x the chunks per segment) in both modes — the recorded per-wave
    # host_syncs are the O(compactions + 1) flatness evidence
    sync_scaling = {}
    for label, loop_on, chunk in (("loop_chunk512", True, 512),
                                  ("loop_chunk128", True, 128),
                                  ("chunked_chunk512", False, 512),
                                  ("chunked_chunk128", False, 128)):
        r = run_churn(n_nodes, total_pods, waves, seed=seed, warmup=False,
                      device_loop=loop_on, frontier_chunk=chunk)
        sync_scaling[label] = {
            "per_wave_host_syncs": r["host_syncs"]["per_wave"],
            "total_host_syncs": r["host_syncs"]["total"],
            "segments": r["frontier"]["segments"],
            "compactions": r["frontier"]["compactions"],
        }
        print(f"# ab-loop sweep {label}: total={r['host_syncs']['total']} "
              f"per_wave={r['host_syncs']['per_wave']}", file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    won = sum(1 for p in ab_pairs + ba_pairs if p["B_new"] > p["A_old"])
    return {
        "claim": ("Device-resident wave loop: the chunked frontier scan "
                  "runs as ONE lax.while_loop dispatch per segment with "
                  "donated ScanState carries, an on-device compaction "
                  "flag (host re-entered only when a compaction fires), "
                  "and the all-G still_ok refresh at chunk boundaries — "
                  "host syncs per wave drop from O(chunks) to "
                  "O(compactions + 1)"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, per-arm warm-up compiles "
                   "paid up front; A = chunked host loop (device_loop off, "
                   "pre-ISSUE-11), B = device-resident while_loop; first "
                   "pair of each arm replayed off-clock through the "
                   "per-pod CPU oracle per drained wave; off-clock chunk "
                   "sweep 512/128 records host-sync scaling in both modes"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_old_all": a_all,
        "B_new_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "b_won_pairs": f"{won}/{len(ab_pairs) + len(ba_pairs)} (both orders)",
        "bound_counts": sorted(bounds),
        "oracle_parity": parity,
        "host_syncs_first_run": syncs_first,
        "host_sync_scaling": sync_scaling,
    }


def run_trace_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                 waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B pricing the wave tracer (ISSUE 7):
    A = tracing disabled (the production default — instrumented sites
    cost one global load + None check), B = tracer + flight recorder
    ENABLED for the whole timed run.  This is an overhead PRICE report,
    not a win claim: ``win_pct`` is the measured cost of enabling (≈0
    means the enabled path is free too; the DISABLED path's "within
    noise of pre-PR" claim uses the worktree ledger, not this flag A/B,
    because the instrumentation exists in both arms here)."""
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False)

    def one(traced: bool) -> dict:
        return run_churn(n_nodes, total_pods, waves, seed=seed,
                         warmup=False, trace=traced)

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    trace_stats = []
    bounds = set()
    for i in range(pairs):
        b = one(True)
        a = one(False)
        ab_pairs.append({"B_on": b["pods_per_sec"], "A_off": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        trace_stats.append(b["trace"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-trace AB: on={b['pods_per_sec']} off={a['pods_per_sec']} "
              f"events={b['trace']['events']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_off": a["pods_per_sec"], "B_on": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        trace_stats.append(b["trace"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-trace BA: off={a['pods_per_sec']} on={b['pods_per_sec']}",
              file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    return {
        "claim": ("Wave tracing + flight recorder: per-wave span trees, "
                  "store-txn correlation ids, dump-on-fault — priced "
                  "ENABLED vs disabled on the same tree (the disabled "
                  "path's no-regression claim is the worktree ledger)"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop serving "
                   "(both arms), events on; interleaved pairs in BOTH "
                   "orders, one shared process, warm-up compiles paid up "
                   "front; A = tracing disabled, B = tracer + flight "
                   "recorder enabled for the whole timed run"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_off_all": a_all,
        "B_on_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        # the sign convention matches the other ledgers (B vs A), so a
        # NEGATIVE value here is the enabled-tracing slowdown
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "bound_counts": sorted(bounds),
        "trace_stats": trace_stats,
    }


def run_telemetry_ab(n_nodes: int = 5_000, total_pods: int = 20_000,
                     waves: int = 10, pairs: int = 2, seed: int = 0) -> dict:
    """Both-orders interleaved A/B pricing continuous telemetry (ISSUE
    13): A = scraper/monitor/shipper disabled (the production default —
    producer sites cost one global load + None check), B = the full
    stack ENABLED for the whole timed run: 0.25 s scrape cadence over
    the scheduler registry, burn-rate evaluation of the standing SLOs
    on every scrape, and the shipper draining every scrape delta through
    a devnull file sink.  Like ``--ab-trace`` this is an overhead PRICE
    report, not a win claim: the DISABLED path's "within noise of
    pre-PR" claim is the worktree ledger
    (BENCH_AB_telemetry_overhead.json), because the instrumentation
    exists in both arms here."""
    run_churn(n_nodes, 2 * (total_pods // waves), 2, seed=seed + 1,
              warmup=False)

    def one(enabled: bool) -> dict:
        return run_churn(n_nodes, total_pods, waves, seed=seed,
                         warmup=False, telemetry=enabled)

    ab_pairs, ba_pairs = [], []
    a_all, b_all = [], []
    telemetry_stats = []
    bounds = set()
    for _ in range(pairs):
        b = one(True)
        a = one(False)
        ab_pairs.append({"B_on": b["pods_per_sec"], "A_off": a["pods_per_sec"]})
        b_all.append(b["pods_per_sec"])
        a_all.append(a["pods_per_sec"])
        telemetry_stats.append(b["telemetry"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-telemetry AB: on={b['pods_per_sec']} "
              f"off={a['pods_per_sec']} "
              f"scrapes={b['telemetry']['scrapes']}", file=sys.stderr)
    for _ in range(pairs):
        a = one(False)
        b = one(True)
        ba_pairs.append({"A_off": a["pods_per_sec"], "B_on": b["pods_per_sec"]})
        a_all.append(a["pods_per_sec"])
        b_all.append(b["pods_per_sec"])
        telemetry_stats.append(b["telemetry"])
        bounds.update((a["bound"], b["bound"]))
        print(f"# ab-telemetry BA: off={a['pods_per_sec']} "
              f"on={b['pods_per_sec']}", file=sys.stderr)
    a_med = sorted(a_all)[len(a_all) // 2]
    b_med = sorted(b_all)[len(b_all) // 2]
    return {
        "claim": ("Continuous telemetry: registry scraper + burn-rate "
                  "SLO monitor + off-box shipper — priced ENABLED vs "
                  "disabled on the same tree (the disabled path's "
                  "no-regression claim is the worktree ledger)"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves, arrival thread + run_batch_loop "
                   "serving (both arms), events on; interleaved pairs in "
                   "BOTH orders, one shared process, warm-up compiles "
                   "paid up front; A = telemetry disabled, B = scraper "
                   "(0.25 s cadence) + SLO monitor + devnull shipper "
                   "enabled for the whole timed run"),
        "pairs_order_AB_first": ab_pairs,
        "pairs_order_BA_first": ba_pairs,
        "A_off_all": a_all,
        "B_on_all": b_all,
        "A_median": a_med,
        "B_median": b_med,
        # sign convention matches the other ledgers (B vs A): a NEGATIVE
        # value here is the enabled-telemetry slowdown
        "win_pct": round((b_med - a_med) / a_med * 100, 1) if a_med else None,
        "bound_counts": sorted(bounds),
        "telemetry_stats": telemetry_stats,
    }


def run_preemption(n_nodes: int = 2_000) -> dict:
    """Priority-preemption workload (VERDICT r4 directive 6: measure
    preemption cost at all).  Saturate every node's CPU with priority-0
    fillers, then flood one batch of priority-100 preemptors that each
    need a victim evicted: the batch fails wholesale, the cohort
    PostFilter (scheduler._preempt_cohort — prefilter kernel + exact
    reprieve on the survivors) evicts minimal victim sets, and the next
    batch binds every preemptor into the freed space.

    Reports preemption throughput and per-attempt latency; parity of the
    decisions themselves is pinned by tests/test_preemption_batch.py's
    oracle table."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.testutil import make_node, make_pod

    n_fillers = 4 * n_nodes  # 4 x 2cpu fills each 8-cpu node
    n_preemptors = n_nodes // 2
    cs = Clientset(Store(event_log_window=max(200_000, 4 * (n_nodes + n_fillers))))
    for i in range(n_nodes):
        cs.nodes.create(make_node(
            f"node-{i:05d}", cpu="8", memory="32Gi", pods=110,
            labels={"kubernetes.io/hostname": f"node-{i:05d}",
                    ZONE: f"zone-{i % 3}"}))
    algo = GenericScheduler()
    sched = Scheduler(cs, algorithm=algo,
                      backend=TPUBatchBackend(algorithm=algo),
                      emit_events=True)
    sched.start()
    sched.broadcaster.start()
    for i in range(n_fillers):
        cs.pods.create(make_pod(f"filler-{i:06d}", cpu="2", memory="256Mi",
                                labels={"app": "filler"}))
    sched.pump()
    sched.schedule_pending_batch()
    for i in range(n_preemptors):
        p = make_pod(f"vip-{i:06d}", cpu="2", memory="256Mi",
                     labels={"app": "vip"})
        p.spec.priority = 100
        cs.pods.create(p)
    sched.pump()
    t0 = time.perf_counter()
    sched.schedule_pending_batch()  # fails -> cohort preemption
    preempt_elapsed = time.perf_counter() - t0
    m = sched.metrics
    # snapshot the counters HERE: the freed-space batch may run its own
    # cohort for stragglers, and those attempts are outside the window
    attempts = m.preemption_attempts.value
    victims = m.preemption_victims.value
    sched.pump()
    bound_after, _ = sched.schedule_pending_batch()  # into freed space
    total_elapsed = time.perf_counter() - t0
    sched.broadcaster.stop(drain=True)

    def _pq(h, q):
        v = h.quantile(q)
        return round(v / 1e3, 3) if v != float("inf") else None

    return {
        "nodes": n_nodes,
        "preemptors": n_preemptors,
        "attempts": attempts,
        "victims": victims,
        "preemptor_bound_after": bound_after,
        "preemptions_per_sec": round(attempts / preempt_elapsed, 1)
        if preempt_elapsed > 0 else 0.0,
        "e2e_preempt_and_bind_s": round(total_elapsed, 3),
        "preemption_latency_ms": {"p50": _pq(m.preemption_latency, 0.5),
                                  "p99": _pq(m.preemption_latency, 0.99)},
    }


def run_overload(n_nodes: int = 320, surge_mult: float = 3.0,
                 surge_pods_cap: int = 60_000, max_surge_s: float = 20.0,
                 goodput_deadline_s: float = 5.0, seed: int = 0,
                 fast_window_s: float = 0.5, slow_window_s: float = 1.5,
                 step_hold_s: float = 0.5) -> dict:
    """Overload-control surge bench (ISSUE 17): drive arrivals at
    ``surge_mult``x the measured drain capacity through the apiserver's
    create path and record what the degradation ladder does about it.

    Phases:

    1. **calibrate** — two direct-store batches through the serving loop
       (the first warms the wave-shape compiles); the second's rate is
       the drain capacity every other number is relative to.
    2. **surge** — three arrival threads (batch prio 0 / standard 5 /
       critical 9, at 50/30/20%) pace paced batch-creates through
       per-tier ``RemoteStore`` clients at ``surge_mult``x capacity.
       The ladder engages off the queue-depth gauge; rung 3 throttles
       the batch tier at the apiserver (429 + Retry-After, honored by
       the client, rejected when the budget runs out).  Per-pod e2e is
       stamped create-attempt -> bind (the wave-relative e2e histogram
       can't see queue backlog or throttle delay).
    3. **recover** — arrivals stop; the backlog drains; the run clocks
       how long the ladder takes to walk back to rung 0 (the gauge SLI
       keeps sampling at zero traffic, so recovery needs no probes).
    4. **steady-state parity** — a tail batch binds at rung 0 and is
       replayed through the per-pod CPU oracle seeded with the live
       world's bound state AND its select_host tie counter (scores are
       fixed-point integers, so ties are routine and the rotation
       offset matters), so the tail must match the oracle exactly —
       occupancy invariants are the verdict gate, the exact map rides
       along as evidence.

    The verdict block gates: ladder engaged (rung > 0), top-tier p99
    and goodput strictly better than the batch tier's, full recovery
    to rung 0, and post-recovery occupancy parity."""
    import threading

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.client.remote import RemoteStore, RetryExhaustedError
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.testutil import make_node, make_pod
    from kubernetes_tpu.utils import timeseries as timeseries_mod
    from kubernetes_tpu.utils.overload import (AdmissionThrottle,
                                               DegradationLadder,
                                               overload_slos)

    store = Store(event_log_window=400_000)
    server = APIServer(store)
    server.start()
    cs = Clientset(store)
    # distinct memories do NOT break score ties (scores are fixed-point
    # integers); exact replay instead relies on seeding the oracle with
    # the live select_host tie counter, captured at tail time below.
    # Generous per-node pod caps stretch the slot budget so the surge
    # can outlast the SLO windows even at high drain rates.
    pods_per_node = 200
    for i in range(n_nodes):
        cs.nodes.create(make_node(
            f"node-{i:05d}", cpu="8", memory=f"{16_384 + i}Mi",
            pods=pods_per_node,
            labels={"kubernetes.io/hostname": f"node-{i:05d}",
                    ZONE: f"zone-{i % 3}"}))
    algo = GenericScheduler()
    sched = Scheduler(cs, algorithm=algo,
                      backend=TPUBatchBackend(algorithm=algo),
                      emit_events=False)
    sched.start()

    t_create: dict[str, float] = {}
    t_bind: dict[str, float] = {}
    rejected: set[str] = set()
    drain_batches: list[list[str]] = []
    orig_drain = sched.queue.drain

    def recording_drain(max_n=None):
        out = orig_drain(max_n)
        if out:
            drain_batches.append([p.meta.name for p in out])
        return out

    sched.queue.drain = recording_drain
    orig_spb = sched.schedule_pending_batch

    def stamping_spb(max_batch=None):
        # probe only the pods this wave drained (a full list() per wave
        # holds the store lock long enough to starve the HTTP handlers
        # and the arrival threads behind them); failed pods re-queue and
        # get re-probed when a later wave re-drains them
        mark = len(drain_batches)
        r = orig_spb(max_batch)
        now = time.perf_counter()
        for batch in drain_batches[mark:]:
            for n in batch:
                if n in t_bind:
                    continue
                p = cs.pods.get(n)
                if p is not None and p.spec.node_name:
                    t_bind[n] = now
        return r

    sched.schedule_pending_batch = stamping_spb

    stop = threading.Event()
    max_batch = 384
    serve = threading.Thread(
        target=lambda: sched.run_batch_loop(
            min_batch=32, max_wait=0.05, poll_interval=0.002,
            max_batch=max_batch, stop=stop),
        daemon=True)
    serve.start()

    def _tmpl(name, prio=0):
        p = make_pod(name, cpu="10m", memory="16Mi")
        if prio:
            p.spec.priority = prio
        return p

    def _wait_all_bound(names, timeout):
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if all(n in t_bind for n in names):
                return True
            time.sleep(0.02)
        return False

    try:
        # -- phase 1: calibrate drain capacity (first batch warms XLA) --
        cal_rate = None
        for attempt in range(2):
            names = [f"cal{attempt}-{i:05d}" for i in range(768)]
            t0 = time.perf_counter()
            for n in names:
                t_create[n] = t0
            cs.pods.create_many_nowait([_tmpl(n) for n in names])
            assert _wait_all_bound(names, 120), "calibration never drained"
            cal_rate = len(names) / (max(t_bind[n] for n in names) - t0)
        print(f"# overload: drain capacity {cal_rate:.0f} pods/s",
              file=sys.stderr)

        # -- wire the ladder + throttle (absent during calibration) -----
        pending_threshold = max(32.0, cal_rate * 0.5)
        ts_store = timeseries_mod.enable(sched.metrics.registry,
                                         interval_s=0.1, capacity=4_096)
        ladder = DegradationLadder(
            slos=overload_slos(pending_threshold=pending_threshold,
                               fast_window_s=fast_window_s,
                               slow_window_s=slow_window_s,
                               recovery_evals=2),
            step_hold_s=step_hold_s, recover_hold_s=1.0)
        sched.attach_overload(ladder)
        ladder.attach(ts_store)
        server.admission_throttle = AdmissionThrottle(ladder,
                                                      retry_after_s=0.75)

        # -- phase 2: the surge ----------------------------------------
        # sized from a DURATION target, not a pod count: the gauge SLI
        # only breaches once the windowed means sustain past the slow
        # window plus the step holds, so a pod cap that silently
        # shortens the surge below that never engages the ladder.  The
        # per-node pod cap bounds how many arrivals can ever bind (the
        # calibration pods and the tail are already on the nodes).
        arrival_rate = surge_mult * cal_rate
        slot_budget = n_nodes * pods_per_node - 2 * 768 - 600
        surge_s_target = min(max_surge_s, slot_budget / arrival_rate)
        surge_pods = min(surge_pods_cap,
                         max(900, int(arrival_rate * surge_s_target)))
        print(f"# overload: surge {surge_pods} pods @ {arrival_rate:.0f}"
              f"/s (~{surge_pods / arrival_rate:.1f}s, slow window"
              f" {slow_window_s}s)", file=sys.stderr)
        tiers = {
            "batch": dict(prio=0, frac=0.5),
            "standard": dict(prio=5, frac=0.3),
            "critical": dict(prio=9, frac=0.2),
        }
        clients = {}
        per_tier_chunks = {}
        for tname, cfg in tiers.items():
            n = int(surge_pods * cfg["frac"])
            rs = RemoteStore(
                server.url, max_retries=2, retry_backoff=0.05,
                retry_backoff_max=1.0, retry_seed=seed + cfg["prio"])
            clients[tname] = rs
            rcs = Clientset(rs)
            pods = [_tmpl(f"{tname}-{i:05d}", cfg["prio"]) for i in range(n)]
            cfg["names"] = [p.meta.name for p in pods]
            per_tier_chunks[tname] = (rcs, [pods[i:i + 25]
                                            for i in range(0, n, 25)])
        # largest-deficit interleave: one shared chunk schedule keeps
        # the tier mix constant across the whole surge.  Per-tier
        # arrival threads don't — the un-throttled tiers flood in
        # early and eat the deepest backlog while the throttled tier's
        # retry sleeps push its pods into the drained aftermath, which
        # INVERTS the ordering the throttle exists to produce.
        schedule = []
        emitted = {t: 0 for t in tiers}
        total_chunks = sum(len(c) for _, c in per_tier_chunks.values())
        for k in range(total_chunks):
            pick = max(
                (t for t in tiers if emitted[t] < len(per_tier_chunks[t][1])),
                key=lambda t: tiers[t]["frac"] * (k + 1) - emitted[t])
            rcs, chunks = per_tier_chunks[pick]
            schedule.append((rcs, chunks[emitted[pick]]))
            emitted[pick] += 1
        next_idx = [0]
        idx_lock = threading.Lock()
        surge_t0 = time.perf_counter()

        def worker():
            while True:
                with idx_lock:
                    k = next_idx[0]
                    if k >= len(schedule):
                        return
                    next_idx[0] = k + 1
                rcs, chunk = schedule[k]
                target = surge_t0 + (k * 25) / arrival_rate
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                stamp = time.perf_counter()
                for p in chunk:
                    t_create[p.meta.name] = stamp
                try:
                    rcs.pods.create_many(chunk)
                except RetryExhaustedError:
                    # throttled past the retry budget: load shed
                    for p in chunk:
                        rejected.add(p.meta.name)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        surge_end = time.perf_counter()

        # -- phase 3: recovery -----------------------------------------
        recovery_s = None
        deadline = surge_end + 180
        while time.perf_counter() < deadline:
            if ladder.rung == 0 and len(sched.queue) == 0:
                recovery_s = round(time.perf_counter() - surge_end, 2)
                break
            time.sleep(0.05)
        accepted = [n for cfg in tiers.values() for n in cfg["names"]
                    if n not in rejected]
        _wait_all_bound(accepted, 60)

        # -- phase 4: post-recovery steady state + oracle replay -------
        tail_mark = len(drain_batches)
        # all scores are fixed-point integers, so ties are common and
        # select_host rotates through them with a persistent counter
        # (reference lastNodeIndex).  The oracle must start its replay
        # from the live counter or every tied choice lands one rotation
        # off — captured here, before the tail waves advance it.
        rr_at_tail = algo._round_robin
        tail_names = [f"tail-{i:05d}" for i in range(300)]
        t0 = time.perf_counter()
        for n in tail_names:
            t_create[n] = t0
        cs.pods.create_many_nowait([_tmpl(n) for n in tail_names])
        tail_bound = _wait_all_bound(tail_names, 60)
        pods_live, _ = cs.pods.list()
        live_map = {p.meta.name: p.spec.node_name for p in pods_live}
    finally:
        stop.set()
        sched.queue.close()
        serve.join(timeout=30)
        timeseries_mod.disable()
        server.stop()

    # oracle replay of the tail waves over the live pre-tail state
    cs_o = Clientset(Store())
    for i in range(n_nodes):
        cs_o.nodes.create(make_node(
            f"node-{i:05d}", cpu="8", memory=f"{16_384 + i}Mi",
            pods=pods_per_node,
            labels={"kubernetes.io/hostname": f"node-{i:05d}",
                    ZONE: f"zone-{i % 3}"}))
    tail_set = set(tail_names)
    prebound = [(n, node) for n, node in live_map.items()
                if node and n not in tail_set]
    cs_o.pods.create_many_nowait(
        [make_pod(n, cpu="10m", memory="16Mi", node_name=node)
         for n, node in prebound])
    algo_o = GenericScheduler()
    algo_o._round_robin = rr_at_tail
    sched_o = Scheduler(cs_o, algorithm=algo_o, emit_events=False)
    sched_o.start()
    for batch in drain_batches[tail_mark:]:
        cs_o.pods.create_many_nowait(
            [_tmpl(n) for n in batch if n in tail_set])
        sched_o.pump()
        sched_o.run_pending()
    pods_o, _ = cs_o.pods.list()
    oracle_tail = {p.meta.name: p.spec.node_name for p in pods_o
                   if p.meta.name in tail_set}
    live_tail = {n: live_map.get(n) for n in tail_names}
    tail_counts = collections.Counter(live_tail.values())
    oracle_counts = collections.Counter(oracle_tail.values())
    occupancy_parity = (tail_bound and all(live_tail.values())
                        and tail_counts == oracle_counts)
    exact_parity = live_tail == oracle_tail

    def _tier_stats(cfg):
        names = cfg["names"]
        e2e = sorted(t_bind[n] - t_create[n] for n in names if n in t_bind)
        good = sum(1 for n in names
                   if n in t_bind
                   and t_bind[n] - t_create[n] <= goodput_deadline_s)
        return {
            "arrivals": len(names),
            "rejected": sum(1 for n in names if n in rejected),
            "bound": len(e2e),
            "goodput": round(good / max(len(names), 1), 4),
            "e2e_ms": {
                "p50": round(e2e[len(e2e) // 2] * 1e3, 1) if e2e else None,
                "p99": round(e2e[int(len(e2e) * 0.99)] * 1e3, 1)
                if e2e else None,
            },
        }

    tier_stats = {t: _tier_stats(cfg) for t, cfg in tiers.items()}
    crit, batch = tier_stats["critical"], tier_stats["batch"]
    tier_p99_ok = (crit["e2e_ms"]["p99"] is not None
                   and batch["e2e_ms"]["p99"] is not None
                   and crit["e2e_ms"]["p99"] < batch["e2e_ms"]["p99"])
    verdict = {
        "ladder_engaged": ladder.max_rung_seen > 0,
        "max_rung": ladder.max_rung_seen,
        "reached_throttle_rung": ladder.max_rung_seen >= 3,
        "tier_p99_ok": tier_p99_ok,
        "tier_goodput_ok": crit["goodput"] > batch["goodput"],
        "recovered": recovery_s is not None,
        "recovery_s": recovery_s,
        "post_recovery_occupancy_parity": occupancy_parity,
        "post_recovery_exact_parity": exact_parity,
    }
    verdict["pass"] = all((
        verdict["ladder_engaged"], verdict["tier_p99_ok"],
        verdict["tier_goodput_ok"], verdict["recovered"],
        verdict["post_recovery_occupancy_parity"]))
    throttle = server.admission_throttle.stats()
    return {
        "nodes": n_nodes,
        "drain_capacity_pods_per_sec": round(cal_rate, 1),
        "surge_mult": surge_mult,
        "surge_pods": surge_pods,
        "surge_s": round(surge_end - surge_t0, 2),
        "pending_threshold": pending_threshold,
        "goodput_deadline_s": goodput_deadline_s,
        "tiers": tier_stats,
        "rung_timeline": [(round(t, 3), r) for t, r in ladder.history()],
        "transitions": ladder.transitions,
        "degradation_transitions_total":
            sched.metrics.degradation_transitions.value,
        "score_plane_sheds": sched.metrics.score_plane_sheds.value,
        "admission": {
            "admitted": throttle["admitted"],
            "throttled": throttle["throttled"],
            "throttled_by_tier": {str(k): v for k, v in
                                  throttle["throttled_by_tier"].items()},
            "server_throttled_total": server.admission_throttled.value,
            "retry_after_honored": {
                t: clients[t].metrics.retry_after_honored.value
                for t in tiers},
        },
        "tail": {
            "pods": len(tail_names),
            "bound": sum(1 for v in live_tail.values() if v),
            "exact_mismatches": sum(1 for n in tail_names
                                    if live_tail.get(n) != oracle_tail.get(n)),
        },
        "verdict": verdict,
    }


MULTICHIP_DEVICE_COUNTS = (1, 2, 4, 8)


def run_multichip_child(cfg: dict) -> dict:
    """One ``--multichip`` measurement in a FRESH process: force an
    ``n_devices``-way virtual CPU platform before jax initializes (the
    parent also sets ``XLA_FLAGS``/``JAX_PLATFORMS`` in the child env —
    belt and braces), run the churn harness with the sharded wave loop
    forced on (n >= 2; n = 1 is the single-device loop baseline), and
    report the parity / host-sync / upload-attribution evidence the
    ledger gates on.  One process per device count is mandatory: the
    device count is fixed at jax initialization."""
    from kubernetes_tpu.utils.platform import force_virtual_cpu

    nd = int(cfg["n_devices"])
    force_virtual_cpu(nd)
    r = run_churn(n_nodes=int(cfg["nodes"]), total_pods=int(cfg["pods"]),
                  waves=int(cfg["waves"]),
                  workload=cfg.get("workload", "mixed"),
                  seed=int(cfg.get("seed", 0)),
                  frontier_chunk=int(cfg.get("chunk", 128)),
                  verify_oracle=True, mesh=(nd > 1))
    par = r["oracle_parity"] or {}
    return {
        "n_devices": nd,
        "pods_per_sec": r["pods_per_sec"],
        "bound": r["bound"],
        "unbound": r["unbound"],
        "mesh": r["mesh"],
        "host_syncs": r["host_syncs"],
        "frontier": r["frontier"],
        "node_upload": r["node_upload"],
        "oracle_parity": {k: par.get(k) for k in (
            "mode", "checked", "mismatches", "round_robin",
            "round_robin_timed", "round_robin_match")},
        "per_wave_mesh": [p.get("mesh") for p in r["phase_timers"]],
    }


def run_multichip(device_counts=MULTICHIP_DEVICE_COUNTS, n_nodes: int = 512,
                  total_pods: int = 4_000, waves: int = 5, chunk: int = 128,
                  seed: int = 0) -> dict:
    """The sharded-wave-loop churn ledger (ISSUE 18): run the churn
    harness at each device count in ``device_counts`` — one subprocess
    each, with ``--xla_force_host_platform_device_count=N`` on the CPU
    backend — and gate a single verdict on what the sharded loop must
    preserve:

    - **per-wave oracle parity, exact**, at every shard count, including
      the select_host tie-rotation counter (the deterministic cross-shard
      tie-break's observable);
    - **host syncs O(compactions + 1)** per segment (<= 2 per segment +
      1 per compaction — dispatch and the loop-exit cursor read), never
      O(chunks), at every shard count;
    - **per-shard upload attribution** present on every >= 2-device
      config (shard count == device count, non-empty per-shard upload
      fractions, zero mesh-mode fallbacks).

    This graduates MULTICHIP from the compile-and-collective dryrun
    shapes of earlier rounds to a real sharded *churn* ledger: the full
    store -> informer -> backend -> bind path under the mesh."""
    import subprocess

    configs = []
    for nd in device_counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={nd}").strip()
        cfg = {"n_devices": nd, "nodes": n_nodes, "pods": total_pods,
               "waves": waves, "chunk": chunk, "seed": seed}
        print(f"# multichip: {nd}-device child ({n_nodes} nodes x "
              f"{total_pods} pods x {waves} waves)", file=sys.stderr)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--multichip-child", json.dumps(cfg)],
            env=env, capture_output=True, text=True, timeout=3_600)
        entry = {"n_devices": nd, "rc": proc.returncode,
                 "ok": proc.returncode == 0}
        if proc.returncode == 0:
            try:
                entry.update(json.loads(proc.stdout.strip().splitlines()[-1]))
            except (ValueError, IndexError) as e:
                entry["ok"] = False
                entry["tail"] = f"unparseable child stdout: {e}"
        else:
            entry["tail"] = proc.stderr[-2_000:]
        configs.append(entry)
        if entry["ok"]:
            par = entry["oracle_parity"]
            print(f"# multichip {nd}-device: {entry['pods_per_sec']} pods/s, "
                  f"parity {par['mismatches']}/{par['checked']} mismatches "
                  f"rr_match={par['round_robin_match']}, host_syncs="
                  f"{entry['host_syncs']['total']} (segments="
                  f"{entry['frontier']['segments']}, compactions="
                  f"{entry['frontier']['compactions']}), n_shards="
                  f"{entry['mesh']['n_shards']}", file=sys.stderr)
        else:
            print(f"# multichip {nd}-device: FAILED rc={entry['rc']}",
                  file=sys.stderr)

    def _gate(c: dict) -> list:
        if not c["ok"]:
            return ["child failed"]
        bad = []
        par = c["oracle_parity"]
        if par["mode"] != "exact per-wave replay" or par["mismatches"] != 0:
            bad.append("oracle parity not exact")
        if not par["round_robin_match"]:
            bad.append("rr tie counter diverged")
        fr = c["frontier"]
        if c["host_syncs"]["total"] > 2 * fr["segments"] + fr["compactions"]:
            bad.append("host syncs exceed O(compactions+1) budget")
        if c["n_devices"] >= 2:
            if c["mesh"]["n_shards"] != c["n_devices"]:
                bad.append("shard count != device count")
            if not c["node_upload"].get("shard_upload_fractions"):
                bad.append("no per-shard upload attribution")
            if "mesh" in fr["fallback_modes"]:
                bad.append("mesh-mode fallbacks fired")
        return bad

    failures = {str(c["n_devices"]): _gate(c) for c in configs}
    failures = {k: v for k, v in failures.items() if v}
    verdict = {
        "device_counts": list(device_counts),
        "parity_exact_all": all(
            c["ok"] and c["oracle_parity"]["mismatches"] == 0
            and c["oracle_parity"]["round_robin_match"] for c in configs),
        "host_sync_budget_all": all(
            c["ok"] and c["host_syncs"]["total"]
            <= 2 * c["frontier"]["segments"] + c["frontier"]["compactions"]
            for c in configs),
        "sharded_attribution_all": all(
            bool(c["ok"] and c["node_upload"].get("shard_upload_fractions")
                 and c["mesh"]["n_shards"] == c["n_devices"])
            for c in configs if c["n_devices"] >= 2),
        "failures": failures,
        "pass": not failures,
    }
    return {
        "claim": ("Sharded node axis: the device-resident wave loop runs "
                  "under shard_map over a 1-D node-axis mesh with in-loop "
                  "cross-shard reductions (psum/pmax alive + score "
                  "reduces, deterministic (score, global index) tie-break "
                  "with the cross-shard rotation prefix) — per-wave "
                  "bindings and the rr tie counter EXACT vs the CPU "
                  "oracle at every shard count, host syncs still "
                  "O(compactions + 1), per-shard upload attribution on "
                  "the node cache"),
        "method": (f"Churn {n_nodes} nodes / {total_pods} mixed pods / "
                   f"{waves} waves (arrival thread + run_batch_loop, "
                   f"events on, chunk {chunk}), one FRESH subprocess per "
                   f"device count {list(device_counts)} with "
                   "--xla_force_host_platform_device_count=N on the CPU "
                   "backend (mesh forced on at N >= 2; N = 1 is the "
                   "single-device loop baseline); every run's drained "
                   "waves replayed off-clock through the per-pod CPU "
                   "oracle"),
        "configs": configs,
        "verdict": verdict,
    }


PREFIX_PARITY_K = 2_000


def run_prefix_parity(backend_res: dict, n_nodes: int, n_pods: int,
                      workload: str, seed: int, k: int = PREFIX_PARITY_K) -> dict:
    """At-scale parity certification without at-scale oracle cost.

    Sequential-greedy is prefix-closed: pod i's placement depends only on
    the initial cluster and the pods scheduled before it (pending pods
    never influence predicates or priorities — only scheduled pods do).
    So the oracle replayed over just the FIRST ``k`` pods of the batch,
    in batch order, must match the kernel's first ``k`` assignments
    binding-for-binding.  This is exact, not statistical, and turns the
    north-scale "identical bindings" claim from extrapolated (certified
    at 10k) into certified at the timed scale itself.

    Batch order is the RECORDED queue-drain order of the timed run, not
    creation order (the queue is fed from the store's name-sorted LIST).
    A replay cluster holding exactly those ``k`` pods queues them in the
    same relative order — a restriction of a sorted sequence is sorted —
    so the oracle's ``k`` decisions are directly comparable.  Gates the
    exit code like the certify path
    (scheduler_perf/scheduler_test.go:83-88 fails, it doesn't just print).
    """
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store

    prefix_keys = backend_res["batch_order"][:k]
    rng = random.Random(seed)
    cs = Clientset(Store(event_log_window=max(200_000, 2 * (n_nodes + k))))
    for node in make_nodes(n_nodes, rng, workload):
        cs.nodes.create(node)
    if workload == "mixed":
        for svc in make_services():
            cs.services.create(svc)
    pods_by_key = {p.meta.key: p for p in make_pods(n_pods, rng, workload)}
    for key in prefix_keys:
        cs.pods.create(pods_by_key[key])
    sched = Scheduler(cs, algorithm=GenericScheduler(), backend=None)
    sched.start()
    t0 = time.perf_counter()
    bound = sched.run_pending()
    elapsed = time.perf_counter() - t0
    pods, _ = cs.pods.list()
    o = {p.meta.key: p.spec.node_name or None for p in pods}
    b = backend_res["assignments"]
    mismatches = [(key, o[key], b.get(key)) for key in o if o[key] != b.get(key)]
    return {
        "checked": len(o),
        "mismatches": len(mismatches),
        "sample": mismatches[:5],
        "oracle_pods_per_sec": round(bound / elapsed, 1) if elapsed > 0 else 0.0,
    }


def run_micro() -> dict:
    """Scheduler microbenchmark matrix (reference
    ``scheduler_perf/scheduler_bench_test.go:32-51``): latency of ONE
    ``Schedule()`` call over {100, 1000 nodes} x {0, 1000 scheduled
    pods}, for the CPU oracle, plus the TPU batch path's amortized
    per-pod cost at each cell (its per-call floor is the kernel launch,
    so the honest number is batched)."""
    from kubernetes_tpu.ops.backend import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, PriorityContext
    from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
    from kubernetes_tpu.testutil import make_node, make_pod

    results = {}
    for n_nodes in (100, 1000):
        for n_scheduled in (0, 1000):
            node_info_map = {}
            for i in range(n_nodes):
                node = make_node(
                    f"node-{i:04d}", cpu="32", memory="64Gi", pods=110,
                    labels={"kubernetes.io/hostname": f"node-{i:04d}",
                            ZONE: f"zone-{i % 3}"},
                )
                node_info_map[node.meta.name] = NodeInfo(node)
            for i in range(n_scheduled):
                pod = make_pod(f"sched-{i:05d}", cpu="100m", memory="128Mi",
                               labels={"app": "web"},
                               node_name=f"node-{i % n_nodes:04d}")
                node_info_map[pod.spec.node_name].add_pod(pod)
            algo = GenericScheduler()
            pctx = PriorityContext(node_info_map)
            probe = make_pod("probe", cpu="100m", memory="128Mi",
                             labels={"app": "web"})
            algo.schedule(probe, node_info_map, pctx)  # warm caches
            iters = 30 if n_nodes == 100 else 10
            t0 = time.perf_counter()
            for _ in range(iters):
                algo.schedule(probe, node_info_map, pctx)
            oracle_us = (time.perf_counter() - t0) / iters * 1e6

            # TPU path: amortized per-pod over a 1k-pod batch
            pending = [make_pod(f"p-{i:05d}", cpu="100m", memory="128Mi",
                                labels={"app": "web"}) for i in range(1000)]
            backend = TPUBatchBackend(algorithm=algo)
            backend.schedule_batch(pending, node_info_map, pctx)  # compile
            t0 = time.perf_counter()
            backend.schedule_batch(pending, node_info_map, pctx)
            tpu_us = (time.perf_counter() - t0) / len(pending) * 1e6
            key = f"{n_nodes}nodes/{n_scheduled}pods"
            results[key] = {"oracle_us_per_schedule": round(oracle_us, 1),
                            "tpu_us_per_pod_batched": round(tpu_us, 2)}
            print(f"# micro {key}: oracle {oracle_us:.0f}us/Schedule, "
                  f"tpu {tpu_us:.2f}us/pod (batched)", file=sys.stderr)
    return results


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", choices=PRESETS, default="north")
    parser.add_argument("--nodes", type=int, default=None)
    parser.add_argument("--pods", type=int, default=None)
    parser.add_argument("--workload", choices=["plain", "mixed"], default=None)
    parser.add_argument("--events", dest="events", action="store_true", default=True,
                        help="emit Scheduled/FailedScheduling events on the timed run "
                        "(DEFAULT — the reference scheduler always emits them)")
    parser.add_argument("--no-events", dest="events", action="store_false")
    parser.add_argument("--trials", type=int, default=None,
                        help="timed-run repetitions; the MEDIAN is reported "
                        "with the min..max spread in the JSON (default 3 for "
                        "north, 1 otherwise) — this bench has ~±20%% "
                        "observed noise, a single trial proves nothing")
    parser.add_argument("--no-churn", dest="churn", action="store_false",
                        default=True,
                        help="skip the steady-state churn measurement that "
                        "rides along with the north preset")
    parser.add_argument("--no-preempt", dest="preempt", action="store_false",
                        default=True,
                        help="skip the priority-preemption workload that "
                        "rides along with the north preset")
    parser.add_argument("--no-certify", dest="certify", action="store_false",
                        default=True,
                        help="skip the default parity certification sub-run "
                        "(dense-mixed 1000 nodes x 10k pods vs the oracle)")
    parser.add_argument("--oracle", action="store_true", help="bench the CPU oracle path instead")
    parser.add_argument(
        "--parity",
        action="store_true",
        help="also run the sequential oracle over an identical cluster and "
        "assert identical bindings (reported in the JSON line)",
    )
    parser.add_argument(
        "--compare", action="store_true", help="also run the oracle and report speedup to stderr"
    )
    parser.add_argument(
        "--micro", action="store_true",
        help="Schedule()-latency matrix ({100,1000} nodes x {0,1000} pods)",
    )
    parser.add_argument(
        "--ab-churn", nargs="?", const="BENCH_AB_churn_pipeline.json",
        default=None, metavar="PATH",
        help="run the both-orders churn pipeline A/B (on vs off) and write "
        "the ledger JSON to PATH (default BENCH_AB_churn_pipeline.json); "
        "--nodes/--pods/--trials override scale and pair count",
    )
    parser.add_argument(
        "--ab-pump", nargs="?", const="BENCH_AB_pump_ingest.json",
        default=None, metavar="PATH",
        help="run the both-orders zero-copy-ingest A/B (lazy+columnar vs "
        "eager from_dict) and write the ledger JSON to PATH (default "
        "BENCH_AB_pump_ingest.json); --nodes/--pods/--trials override "
        "scale and pair count",
    )
    parser.add_argument(
        "--ab-frontier", nargs="?", const="BENCH_AB_frontier_scan.json",
        default=None, metavar="PATH",
        help="run the both-orders frontier-scan A/B (monotone prefilter + "
        "mid-segment node-axis compaction vs the full-width plain scan) "
        "and write the ledger JSON to PATH (default "
        "BENCH_AB_frontier_scan.json); --nodes/--pods/--trials override "
        "scale and pair count",
    )
    parser.add_argument(
        "--ab-watch", nargs="?", const="BENCH_AB_watch_frames.json",
        default=None, metavar="PATH",
        help="run the both-orders batched-watch-frames A/B (column-packed "
        "frames + one-lock batch apply + columnar confirm vs per-event "
        "delivery) and write the ledger JSON to PATH (default "
        "BENCH_AB_watch_frames.json); --nodes/--pods/--trials override "
        "scale and pair count",
    )
    parser.add_argument(
        "--ab-loop", nargs="?", const="BENCH_AB_device_loop.json",
        default=None, metavar="PATH",
        help="run the both-orders device-resident-wave-loop A/B "
        "(lax.while_loop with donated carries + on-device compaction "
        "decisions vs the chunked host loop) and write the ledger JSON "
        "to PATH (default BENCH_AB_device_loop.json); includes an "
        "off-clock chunk-width sweep recording host-sync scaling; "
        "--nodes/--pods/--trials override scale and pair count",
    )
    parser.add_argument(
        "--trace", nargs="?", const="BENCH_trace_churn.json",
        default=None, metavar="PATH",
        help="enable the wave tracer + flight recorder for the churn "
        "measurement and write its Chrome trace-event JSON to PATH "
        "(default BENCH_trace_churn.json); load into chrome://tracing "
        "or Perfetto",
    )
    parser.add_argument(
        "--ab-trace", nargs="?", const="BENCH_AB_trace_enabled.json",
        default=None, metavar="PATH",
        help="run the both-orders tracing-overhead A/B (tracer + flight "
        "recorder enabled vs disabled, same tree) and write the ledger "
        "JSON to PATH (default BENCH_AB_trace_enabled.json); a negative "
        "win_pct is the enabled-tracing slowdown — the disabled path's "
        "no-regression claim is the worktree ledger "
        "(BENCH_AB_trace_overhead.json); --nodes/--pods/--trials "
        "override scale and pair count",
    )
    parser.add_argument(
        "--telemetry", nargs="?", const="BENCH_telemetry_churn.ndjson",
        default=None, metavar="PATH",
        help="enable continuous telemetry for the churn measurement "
        "(time-series scraper + burn-rate SLO monitor + off-box "
        "shipper) and ship the run's records as JSON-lines to PATH "
        "(default BENCH_telemetry_churn.ndjson); the churn block gains "
        "per-SLO burn-rate verdicts, only quotable with the artifact "
        "behind them",
    )
    parser.add_argument(
        "--ab-telemetry", nargs="?",
        const="BENCH_AB_telemetry_enabled.json",
        default=None, metavar="PATH",
        help="run the both-orders telemetry-overhead A/B (scraper + SLO "
        "monitor + shipper enabled vs disabled, same tree) and write "
        "the ledger JSON to PATH (default "
        "BENCH_AB_telemetry_enabled.json); a negative win_pct is the "
        "enabled-telemetry slowdown — the disabled path's no-regression "
        "claim is the worktree ledger (BENCH_AB_telemetry_overhead."
        "json); --nodes/--pods/--trials override scale and pair count",
    )
    parser.add_argument(
        "--overload", nargs="?", const="BENCH_overload.json",
        default=None, metavar="PATH",
        help="run the overload-control surge bench (ISSUE 17): arrivals "
        "at 2-5x measured drain capacity through the apiserver, the "
        "degradation ladder engaging rung by rung, per-tier goodput/p99, "
        "post-surge recovery time, and a post-recovery oracle parity "
        "check; writes the artifact JSON to PATH (default "
        "BENCH_overload.json) — verdicts are only printed with the "
        "artifact behind them; --nodes overrides scale",
    )
    parser.add_argument(
        "--watch-fleet", nargs="?", const="BENCH_watch_fleet.json",
        default=None, metavar="PATH",
        help="run the hollow-watcher fleet bench (ISSUE 19): 10k+ "
        "concurrent watch clients against one broadcaster under churn, "
        "A/B-ing the serving tier (coalescing window + framed delivery "
        "+ single-encode fan-out vs per-event), with a zero-mismatch "
        "state-equivalence gate, the per-CLIENT staleness SLO burning "
        "and recovering mid-run, and a north-preset oracle-parity leg "
        "with coalescing on; writes the ledger JSON to PATH (default "
        "BENCH_watch_fleet.json) — verdicts only print with the "
        "artifact behind them",
    )
    parser.add_argument(
        "--fleet-watchers", type=int, default=10_000, metavar="N",
        help="hollow-watcher count for --watch-fleet (default 10000; "
        "the committed ledger requires >= 10000)",
    )
    parser.add_argument(
        "--fleet-no-parity", dest="fleet_parity", action="store_false",
        default=True,
        help="skip --watch-fleet's north-preset oracle-parity leg "
        "(minutes of churn) — fleet-only iteration",
    )
    parser.add_argument(
        "--multichip", nargs="?", const="MULTICHIP_churn.json",
        default=None, metavar="PATH",
        help="a CPU correctness record, never a chip run: the parent "
        "forks children forced onto 1/2/4/8 virtual CPU devices, so "
        "its pods/s are not device numbers.  Runs the sharded-wave-loop "
        "churn ledger (ISSUE 18): the churn preset at each device count "
        "(one subprocess each), gating per-wave oracle parity (incl. the rr tie "
        "counter), the O(compactions+1) host-sync budget, and per-shard "
        "upload attribution at every shard count; writes the ledger "
        "JSON to PATH (default MULTICHIP_churn.json) — verdicts are "
        "only printed with the artifact behind them; --nodes/--pods "
        "override scale",
    )
    parser.add_argument(
        "--multichip-child", default=None, metavar="JSON",
        help=argparse.SUPPRESS,  # internal: one forced-device-count run
    )
    parser.add_argument(
        "--overload-mult", type=float, default=3.0, metavar="X",
        help="surge arrival rate as a multiple of measured drain "
        "capacity for --overload (default 3.0; the verdict requires "
        ">= 2.0)",
    )
    args = parser.parse_args()

    if args.multichip_child is not None:
        # internal half of --multichip: ONE forced-device-count churn run
        # in this (fresh) process; the parent parses the JSON line below
        print(json.dumps(run_multichip_child(json.loads(args.multichip_child))))
        return

    if args.multichip is not None:
        import datetime

        kw = {}
        if args.nodes:
            kw["n_nodes"] = args.nodes
        if args.pods:
            kw["total_pods"] = args.pods
        ledger = run_multichip(**kw)
        ledger["date"] = datetime.date.today().isoformat()
        # the no-artifact-no-verdict guard (same contract as --overload
        # and --telemetry): if the JSON cannot be written, refuse to
        # print the verdict block and exit non-zero
        try:
            with open(args.multichip, "w") as f:
                json.dump(ledger, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"# REFUSING to print multichip verdicts: artifact "
                  f"write to {args.multichip!r} failed ({e})",
                  file=sys.stderr)
            sys.exit(1)
        v = ledger["verdict"]
        print(json.dumps({
            "metric": "multichip-churn-verdict",
            "value": 1 if v["pass"] else 0,
            "unit": "pass",
            "vs_baseline": 1,
            "device_counts": v["device_counts"],
            "verdict": v,
            "artifact": args.multichip,
        }))
        sys.exit(0 if v["pass"] else 1)

    if args.watch_fleet is not None:
        import datetime

        ledger = run_watch_fleet(n_watchers=args.fleet_watchers,
                                 parity=args.fleet_parity)
        ledger["date"] = datetime.date.today().isoformat()
        # the no-artifact-no-verdict guard (same contract as --overload
        # and the A/B ledgers): if the JSON cannot be written, refuse to
        # print the verdict block and exit non-zero
        try:
            with open(args.watch_fleet, "w") as f:
                json.dump(ledger, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"# REFUSING to print watch-fleet verdicts: artifact "
                  f"write to {args.watch_fleet!r} failed ({e})",
                  file=sys.stderr)
            sys.exit(1)
        v = ledger["verdict"]
        print(json.dumps({
            "metric": "watch-fleet-fanout-ratio",
            "value": v["fanout_ratio_B_over_A"],
            "unit": "x (B logical fan-out events/s vs A)",
            "vs_baseline": v["min_ratio"],
            "verdict": v,
            "artifact": args.watch_fleet,
        }))
        sys.exit(0 if v["pass"] else 1)

    if args.overload is not None:
        if args.overload_mult < 2.0:
            parser.error("--overload-mult must be >= 2.0 (the ladder "
                         "verdict is only meaningful past drain capacity)")
        res = run_overload(n_nodes=args.nodes or 320,
                           surge_mult=args.overload_mult)
        # the no-artifact-no-verdict guard (same contract as --telemetry
        # and the A/B ledgers): if the JSON cannot be written, refuse to
        # print the verdict block and exit non-zero — a quoted verdict
        # with nothing on disk behind it is not evidence
        try:
            with open(args.overload, "w") as f:
                json.dump(res, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"# REFUSING to print overload verdicts: artifact "
                  f"write to {args.overload!r} failed ({e})",
                  file=sys.stderr)
            sys.exit(1)
        v = res["verdict"]
        t = res["tiers"]
        print(f"# overload: capacity={res['drain_capacity_pods_per_sec']} "
              f"pods/s, surge {res['surge_mult']}x for {res['surge_s']}s "
              f"({res['surge_pods']} pods), max_rung={v['max_rung']}, "
              f"recovery={v['recovery_s']}s", file=sys.stderr)
        for name in ("critical", "standard", "batch"):
            s = t[name]
            print(f"# overload tier {name}: goodput={s['goodput']} "
                  f"p99={s['e2e_ms']['p99']}ms rejected={s['rejected']}",
                  file=sys.stderr)
        print(f"# overload admission: throttled="
              f"{res['admission']['throttled']} "
              f"retry_after_honored={res['admission']['retry_after_honored']}",
              file=sys.stderr)
        print(json.dumps({
            "metric": "overload-verdict",
            "value": 1 if v["pass"] else 0,
            "unit": "pass",
            "vs_baseline": 1,
            "max_rung": v["max_rung"],
            "recovery_s": v["recovery_s"],
            "verdict": v,
            "artifact": args.overload,
        }))
        sys.exit(0 if v["pass"] else 1)

    if (args.ab_churn or args.ab_pump or args.ab_frontier or args.ab_watch
            or args.ab_loop or args.ab_trace or args.ab_telemetry):
        import datetime

        kw = {}
        if args.nodes:
            kw["n_nodes"] = args.nodes
        if args.pods:
            kw["total_pods"] = args.pods
        if args.trials:
            kw["pairs"] = args.trials
        runner = (run_telemetry_ab if args.ab_telemetry
                  else run_trace_ab if args.ab_trace
                  else run_loop_ab if args.ab_loop
                  else run_watch_ab if args.ab_watch
                  else run_frontier_ab if args.ab_frontier
                  else run_pump_ab if args.ab_pump else run_churn_ab)
        path = (args.ab_telemetry or args.ab_trace or args.ab_loop
                or args.ab_watch or args.ab_frontier or args.ab_pump
                or args.ab_churn)
        metric = ("telemetry-enabled-overhead-pct" if args.ab_telemetry
                  else "trace-enabled-overhead-pct" if args.ab_trace
                  else "device-loop-win-pct" if args.ab_loop
                  else "watch-frames-win-pct" if args.ab_watch
                  else "frontier-scan-win-pct" if args.ab_frontier
                  else "pump-ingest-win-pct" if args.ab_pump
                  else "churn-pipeline-win-pct")
        ledger = runner(**kw)
        ledger["date"] = datetime.date.today().isoformat()
        # the medians below are only quotable WITH the ledger artifact
        # behind them (ISSUE 11): if the JSON cannot be written, refuse
        # to print them and exit non-zero instead of reporting numbers
        # that nothing on disk substantiates
        try:
            with open(path, "w") as f:
                json.dump(ledger, f, indent=1)
                f.write("\n")
        except OSError as e:
            print(f"# REFUSING to print A/B medians: ledger write to "
                  f"{path!r} failed ({e})", file=sys.stderr)
            sys.exit(1)
        print(json.dumps({
            "metric": metric,
            "value": ledger["win_pct"],
            "unit": "% (B_median vs A_median)",
            "vs_baseline": round(ledger["B_median"] / 100.0, 2),
            "A_median": ledger["A_median"],
            "B_median": ledger["B_median"],
            "ledger": path,
        }))
        return

    if args.micro:
        matrix = run_micro()
        cell = matrix["1000nodes/1000pods"]
        print(json.dumps({
            "metric": "schedule-latency-us",
            "value": cell["oracle_us_per_schedule"],
            "unit": "us/Schedule@1000nodes/1000pods",
            "vs_baseline": 0,
            "matrix": matrix,
        }))
        return
    n_nodes, n_pods, workload = PRESETS[args.preset]
    if args.nodes:
        n_nodes = args.nodes
    if args.pods:
        n_pods = args.pods
    if args.workload:
        workload = args.workload

    if args.trials is not None and args.trials < 1:
        parser.error("--trials must be >= 1")  # before the minutes-long warm-up
    trials = args.trials or (3 if args.preset == "north" and not args.oracle else 1)

    # warm-up at the same scale (different seed): triggers XLA compilation of
    # every segment-shape bucket the timed run will hit, so the timed run
    # measures steady-state throughput (first TPU compile is ~5s per bucket)
    if not args.oracle:
        run_once(n_nodes, n_pods, use_backend=True, workload=workload, seed=1)
    runs = []
    for t in range(trials):
        runs.append(run_once(
            n_nodes, n_pods, use_backend=not args.oracle, workload=workload,
            seed=0, emit_events=args.events,
            want_failure_reasons=not args.oracle,
        ))
        if trials > 1:
            print(f"# trial {t + 1}/{trials}: "
                  f"{runs[-1]['pods_per_sec']:.1f} pods/s", file=sys.stderr)
    runs.sort(key=lambda r: r["pods_per_sec"])
    result = runs[len(runs) // 2]  # the median trial is the reported one
    if result["bound"] == 0:
        print(json.dumps({"metric": "pods-scheduled/sec", "value": 0, "unit": "pods/s", "vs_baseline": 0}))
        sys.exit(1)

    parity = None
    if args.parity:
        parity = run_parity(result, n_nodes, n_pods, workload, seed=0)
        print(
            f"# parity: {parity['checked']} pods checked, "
            f"{parity['mismatches']} mismatches "
            f"(oracle {parity['oracle_pods_per_sec']} pods/s)",
            file=sys.stderr,
        )

    if args.compare:
        oracle = run_once(
            n_nodes, min(n_pods, 2_000), use_backend=False, workload=workload, seed=0
        )
        print(
            f"# oracle: {oracle['pods_per_sec']:.1f} pods/s on {min(n_pods, 2000)} pods; "
            f"backend speedup {result['pods_per_sec'] / max(oracle['pods_per_sec'], 1e-9):.1f}x",
            file=sys.stderr,
        )

    # parity CERTIFICATION (default): dense-mixed preset, backend vs oracle
    # over identical clusters — the artifact carries the north star's
    # "identical bindings" evidence on every recorded run
    # (scheduler_perf/scheduler_test.go:83-88 gates, it doesn't just print)
    certify = None
    at_cert_scale = (n_nodes, n_pods, workload) == PRESETS["mixed"]
    if args.certify and not args.oracle and not (args.parity and at_cert_scale):
        cert_nodes, cert_pods, cert_workload = PRESETS["mixed"]
        # the timed run already IS the certification workload when the
        # preset matches — don't re-run identical multi-minute work
        cert_backend = result if at_cert_scale else run_once(
            cert_nodes, cert_pods, use_backend=True,
            workload=cert_workload, seed=0)
        certify = run_parity(cert_backend, cert_nodes, cert_pods, cert_workload, seed=0)
        print(
            f"# certify[dense-mixed]: {certify['checked']} pods checked, "
            f"{certify['mismatches']} mismatches "
            f"(backend {certify['backend_pods_per_sec']} vs oracle "
            f"{certify['oracle_pods_per_sec']} pods/s)",
            file=sys.stderr,
        )

    # north-prefix parity gate: when the timed run is BIGGER than the
    # certification scale, full-set oracle replay is infeasible (~45 min at
    # 150k) — replay the oracle over the first PREFIX_PARITY_K pods of the
    # SAME batch instead (prefix-closure makes this exact; docstring above)
    # churn: steady-state arrival-load measurement rides along with the
    # north preset (density.go's saturation throughput + per-pod latency
    # under continuous creation; VERDICT r3 Missing #5)
    churn = None
    if not args.oracle and args.preset == "north" and args.churn:
        churn = run_churn(seed=0, trace=args.trace,
                          telemetry=args.telemetry)
        if args.trace:
            tr = churn["trace"]
            print(f"# trace: {tr['events']} events over "
                  f"{tr['waves_recorded']} waves -> {tr['artifact']} "
                  f"({tr['flight_dumps']} flight dumps)", file=sys.stderr)
        if args.telemetry:
            # the no-ledger-no-numbers guard, extended to the SLO
            # verdict block (ISSUE 13): burn-rate verdicts are only
            # quotable with the shipped JSON-lines artifact behind them
            tb = churn.get("telemetry") or {}
            art = tb.get("artifact")
            shipped = (tb.get("shipper") or {}).get("shipped", 0)
            if not art or not os.path.exists(art) or shipped == 0:
                churn["telemetry"] = None
                print(f"# REFUSING to print SLO verdicts: telemetry "
                      f"artifact {art!r} missing or empty "
                      f"(shipped={shipped})", file=sys.stderr)
                sys.exit(1)
            verdicts = ", ".join(
                f"{name}={'BREACH' if v['breached'] else 'ok'}"
                for name, v in sorted(tb["slo_verdicts"].items()))
            print(f"# telemetry: {tb['scrapes']} scrapes over "
                  f"{tb['tracks']} tracks -> {art} (shipped {shipped}, "
                  f"dead {tb['shipper']['dead_lettered']}); "
                  f"verdicts: {verdicts}", file=sys.stderr)
        print(
            f"# churn[{churn['nodes']} nodes]: {churn['bound']} bound / "
            f"{churn['unbound']} unbound over "
            f"{churn['waves']} waves at {churn['pods_per_sec']} pods/s, "
            f"e2e p50={churn['e2e_scheduling_ms']['p50']}ms "
            f"p99={churn['e2e_scheduling_ms']['p99']}ms, "
            f"SLO(p99<={churn['slo_p99_ms']:.0f}ms, "
            f">={churn['floor_pods_per_sec']:.0f} pods/s): "
            f"{'PASS' if churn['slo_pass'] else 'FAIL'}",
            file=sys.stderr,
        )

    preemption = None
    if not args.oracle and args.preset == "north" and args.preempt:
        preemption = run_preemption()
        print(
            f"# preemption: {preemption['attempts']} attempts -> "
            f"{preemption['victims']} victims, "
            f"{preemption['preemptor_bound_after']}/{preemption['preemptors']} "
            f"preemptors bound, {preemption['preemptions_per_sec']} "
            f"preemptions/s, latency p50="
            f"{preemption['preemption_latency_ms']['p50']}ms p99="
            f"{preemption['preemption_latency_ms']['p99']}ms",
            file=sys.stderr,
        )

    prefix = None
    if not args.oracle and n_pods > PRESETS["mixed"][1]:
        prefix = run_prefix_parity(result, n_nodes, n_pods, workload, seed=0)
        print(
            f"# prefix-parity[{args.preset}]: oracle replay of the first "
            f"{prefix['checked']} batch pods, {prefix['mismatches']} mismatches "
            f"(oracle {prefix['oracle_pods_per_sec']} pods/s)",
            file=sys.stderr,
        )

    stats = result.get("backend_stats", {})
    print(
        f"# {args.preset}[{workload}]: {result['bound']} bound / {result['failed']} failed "
        f"in {result['elapsed_s']:.2f}s on {n_nodes} nodes "
        f"(kernel={stats.get('kernel_pods', 0)} oracle={stats.get('oracle_pods', 0)} "
        f"segments={stats.get('segments', 0)} "
        f"pallas_segments={stats.get('pallas_segments', 0)} "
        f"events={'on' if args.events else 'off'})",
        file=sys.stderr,
    )
    # baseline: the reference harness's expected throughput (100 pods/s).
    # The preset/scale ride along so recorded results across rounds are
    # comparable on their own terms (r1 default was 'basic'; the default
    # is now the north-star scale itself).
    line = {
        "metric": "pods-scheduled/sec",
        "value": round(result["pods_per_sec"], 1),
        "unit": "pods/s",
        "vs_baseline": round(result["pods_per_sec"] / 100.0, 2),
        "preset": args.preset,
        "nodes": n_nodes,
        "pods": result["bound"] + result["failed"],
        "workload": workload,
        "events": "on" if args.events else "off",
        "pallas_segments": stats.get("pallas_segments", 0),
        "kernel_pods": stats.get("kernel_pods", 0),
        "oracle_pods": stats.get("oracle_pods", 0),
        "sli": result.get("sli"),
    }
    if trials > 1:
        vals = [round(r["pods_per_sec"], 1) for r in runs]
        line["trials"] = trials
        line["trial_pods_per_sec"] = vals  # sorted; median is `value`
        line["spread_pct"] = round(
            (vals[-1] - vals[0]) / max(vals[len(vals) // 2], 1e-9) * 100, 1)
    if churn is not None:
        line["churn"] = churn
    if preemption is not None:
        line["preemption"] = preemption
    if "event_stats" in result:
        line["event_stats"] = result["event_stats"]
    if "failure_reasons" in result:
        line["failure_reasons"] = result["failure_reasons"]
    if certify is not None:
        line["parity_checked"] = certify["checked"]
        line["parity_mismatches"] = certify["mismatches"]
        line["parity_preset"] = "mixed"
    if parity is not None:
        # --parity: at-scale parity at the TIMED preset overrides the
        # certification sub-run's numbers
        line["parity_checked"] = parity["checked"]
        line["parity_mismatches"] = parity["mismatches"]
        line["parity_preset"] = args.preset
    if prefix is not None:
        # the at-scale prefix replay is the headline parity evidence; the
        # dense-mixed full-set certification rides along under its own keys
        if certify is not None:
            line["certify_checked"] = certify["checked"]
            line["certify_mismatches"] = certify["mismatches"]
            line["certify_preset"] = "mixed"
        if parity is None:
            line["parity_checked"] = prefix["checked"]
            line["parity_mismatches"] = prefix["mismatches"]
            line["parity_preset"] = f"{args.preset}-prefix"
        else:
            # an explicit --parity full-set run outranks the prefix gate
            # in the parity_* keys; keep the prefix result alongside
            line["prefix_checked"] = prefix["checked"]
            line["prefix_mismatches"] = prefix["mismatches"]
    print(json.dumps(line))
    mism = [p["mismatches"] for p in (parity, certify, prefix) if p is not None]
    if churn is not None and not churn["slo_pass"]:
        # the reference's pod-startup SLO, enforced at north scale — a
        # round that regresses past the floor must FAIL loudly
        print("# churn SLO gate FAILED", file=sys.stderr)
        sys.exit(1)
    if preemption is not None and (
            preemption["preemptor_bound_after"] < preemption["preemptors"]):
        # the workload is constructed so every preemptor has a victim set;
        # anything unbound means the PostFilter lost someone — gate on it
        print("# preemption gate FAILED: "
              f"{preemption['preemptor_bound_after']} of "
              f"{preemption['preemptors']} preemptors bound", file=sys.stderr)
        sys.exit(1)
    if any(mism):
        sys.exit(1)


if __name__ == "__main__":
    main()
