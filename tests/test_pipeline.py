"""Steady-state scheduling pipeline (ISSUE 3): overlapped wave ingest,
incremental tensorize, and device-resident node state.

The parity discipline, now asserted PER WAVE: pods arriving in waves
against the running pipelined scheduler must bind exactly as the
fault-free CPU oracle replayed over the same committed states — with the
cross-wave row cache, sticky shape buckets, device-resident node arrays,
and the overlapped prep (including the ``scheduler.pipeline.prep`` fault
fired mid-wave) all active.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from kubernetes_tpu import faults
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.models.snapshot import Tensorizer
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
from kubernetes_tpu.store import Store
from kubernetes_tpu.testutil import make_node, make_pod

ZONE = "failure-domain.beta.kubernetes.io/zone"


def _make_world(n_nodes=24, backend=True):
    cs = Clientset(Store())
    for i in range(n_nodes):
        cs.nodes.create(make_node(
            f"node-{i:03d}",
            cpu=["4", "8", "16"][i % 3],
            memory=["8Gi", "16Gi", "32Gi"][i % 3],
            pods=30,
            labels={"kubernetes.io/hostname": f"node-{i:03d}",
                    ZONE: f"zone-{i % 3}"},
        ))
    algo = GenericScheduler()
    b = TPUBatchBackend(algorithm=algo) if backend else None
    sched = Scheduler(cs, algorithm=algo, backend=b, emit_events=False)
    sched.start()
    return cs, sched


def _wave_pods(w: int, n: int):
    """Mixed wave: plain RC-style templates + anti-affinity + volumes, so
    the kernel's terms and vols paths are live across waves."""
    from kubernetes_tpu.api import Affinity, LabelSelector, PodAffinityTerm, Volume

    anti = Affinity(pod_anti_affinity_required=[PodAffinityTerm(
        selector=LabelSelector.from_match_labels({"app": "lonely"}),
        topology_key="kubernetes.io/hostname")])
    pods = []
    for i in range(n):
        name = f"w{w}-p{i:03d}"
        if i % 10 == 7:
            pods.append(make_pod(name, cpu="100m", memory="128Mi",
                                 labels={"app": "lonely"}, affinity=anti))
        elif i % 10 == 3:
            pods.append(make_pod(
                name, cpu="100m", memory="128Mi", labels={"app": "api"},
                volumes=[Volume(name="v", disk_id=f"pd-{w}-{i % 4}",
                                disk_kind="gce-pd")]))
        else:
            pods.append(make_pod(name, cpu=["100m", "250m"][i % 2],
                                 memory="128Mi",
                                 labels={"app": ["web", "db"][i % 2]}))
    return pods


def _assignments(cs):
    pods, _ = cs.pods.list()
    return {p.meta.key: p.spec.node_name or None for p in pods}


def _run_waves_with_parity(n_waves=4, per_wave=50, plan=None,
                           use_batch_loop=False):
    """Drive the pipelined backend scheduler and a per-pod oracle world
    through identical waves; assert identical bindings AFTER EVERY WAVE."""
    cs_b, sched_b = _make_world()
    cs_o, sched_o = _make_world(backend=False)
    for w in range(n_waves):
        for pod in _wave_pods(w, per_wave):
            cs_b.pods.create(pod)
            cs_o.pods.create(pod)
        if plan is not None:
            with plan.armed():
                if use_batch_loop:
                    sched_b.run_batch_loop(min_batch=per_wave, max_wait=5.0,
                                           max_waves=1)
                else:
                    sched_b.pump()
                    sched_b.schedule_pending_batch()
        elif use_batch_loop:
            sched_b.run_batch_loop(min_batch=per_wave, max_wait=5.0,
                                   max_waves=1)
        else:
            sched_b.pump()
            sched_b.schedule_pending_batch()
        sched_o.pump()
        sched_o.run_pending()
        got, want = _assignments(cs_b), _assignments(cs_o)
        assert got == want, (
            f"wave {w}: pipelined bindings diverged from the oracle replay "
            f"({sum(1 for k in want if got.get(k) != want[k])} mismatches)")
    return sched_b, sched_o


# -- per-wave oracle parity (the acceptance gate) ---------------------------


def test_wave_by_wave_parity_with_pipeline_active():
    sched_b, _ = _run_waves_with_parity()
    # the pipeline actually ran: cross-wave row cache hits, device node
    # arrays reused, overlapped prep recorded
    rows = sched_b.backend.tensorizer.node_rows_stats
    assert rows is not None and rows["hits"] > 0
    cache = sched_b.backend.device_node_cache
    assert cache.stats["reuses"] > 0
    assert sched_b.metrics.pipeline_prep_latency.count > 0


def test_wave_by_wave_parity_through_run_batch_loop():
    sched_b, _ = _run_waves_with_parity(use_batch_loop=True)
    assert sched_b.metrics.batch_queue_wait.count > 0


def test_wave_parity_with_prep_fault_fired_mid_wave():
    """The acceptance criterion's fault case: the pipeline fault point
    fires mid-wave and bindings still match the oracle wave for wave."""
    plan = FaultPlan(seed=7).on("scheduler.pipeline.prep", mode="error",
                                first_n=2)
    sched_b, _ = _run_waves_with_parity(plan=plan)
    assert plan.fired.get("scheduler.pipeline.prep", 0) > 0
    assert sched_b.metrics.pipeline_prep_failures.value > 0


def test_overlap_off_is_bit_identical():
    """The A/B seam: overlap_ingest=False (lock-step prep) must produce
    the same bindings as the pipelined default."""
    cs_a, sched_a = _make_world()
    cs_b, sched_b = _make_world()
    sched_b.overlap_ingest = False
    sched_b.backend.tensorizer = Tensorizer(sticky_buckets=False,
                                            persistent_rows=False)
    for w in range(3):
        for pod in _wave_pods(w, 40):
            cs_a.pods.create(pod)
            cs_b.pods.create(pod)
        for s in (sched_a, sched_b):
            s.pump()
            s.schedule_pending_batch()
        assert _assignments(cs_a) == _assignments(cs_b)


# -- incremental tensorize: persistent rows + dirty-node invalidation -------


def test_node_static_rows_track_node_object_changes():
    """A node update between waves (label/taint/condition change) must be
    reflected in the cached rows — compare against a fresh tensorizer."""
    from kubernetes_tpu.scheduler.priorities import PriorityContext

    cs, sched = _make_world(n_nodes=8)
    pods = [make_pod(f"a{i}", cpu="100m", memory="128Mi",
                     labels={"app": "web"},
                     node_selector={"disk": "ssd"} if i % 2 else None)
            for i in range(6)]
    tz = sched.backend.tensorizer
    snap = sched.snapshot()
    pctx = PriorityContext(snap)
    s1 = tz.build_static(pods, snap, pctx)
    assert s1.node_token is not None

    # label one node ssd: its column must flip for the selector signature
    node = cs.nodes.get("node-003")
    node.meta.labels["disk"] = "ssd"
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    s2 = tz.build_static(pods, snap, pctx)
    assert s2.node_dirty == [3]
    fresh = Tensorizer(persistent_rows=False).build_static(pods, snap, pctx)
    np.testing.assert_array_equal(s2.static_ok, fresh.static_ok)
    np.testing.assert_array_equal(s2.static_score, fresh.static_score)
    np.testing.assert_array_equal(s2.node_aff_raw, fresh.node_aff_raw)
    np.testing.assert_array_equal(s2.taint_intol_raw, fresh.taint_intol_raw)
    # unchanged fleet afterwards: pure cache hit, no dirty columns
    s3 = tz.build_static(pods, snap, pctx)
    assert s3.node_dirty == [] and s3.node_token == s2.node_token


def test_node_static_rows_prefer_avoid_annotation_flip():
    """The interaction-class edge: annotating a node to avoid controller U
    re-keys U's signature without corrupting the shared unannotated row."""
    from kubernetes_tpu.api import OwnerReference
    from kubernetes_tpu.scheduler.priorities import (
        PREFER_AVOID_PODS_ANNOTATION,
        PriorityContext,
    )

    cs, sched = _make_world(n_nodes=6)
    tz = sched.backend.tensorizer

    def rc_pod(name, uid):
        p = make_pod(name, cpu="100m", memory="128Mi", labels={"app": "web"})
        p.meta.owner_references = [OwnerReference(
            kind="ReplicaSet", name=f"rs-{uid}", uid=uid, controller=True)]
        return p

    pods = [rc_pod("u1", "uid-1"), rc_pod("v1", "uid-2")]
    snap = sched.snapshot()
    pctx = PriorityContext(snap)
    tz.build_static(pods, snap, pctx)

    node = cs.nodes.get("node-000")
    node.meta.annotations[PREFER_AVOID_PODS_ANNOTATION] = "uid-1"
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    s2 = tz.build_static(pods, snap, pctx)
    fresh = Tensorizer(persistent_rows=False).build_static(pods, snap, pctx)
    np.testing.assert_array_equal(s2.static_score, fresh.static_score)
    # and back off again: the un-annotated class must recover too
    node = cs.nodes.get("node-000")
    node.meta.annotations.pop(PREFER_AVOID_PODS_ANNOTATION)
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    s3 = tz.build_static(pods, snap, pctx)
    fresh = Tensorizer(persistent_rows=False).build_static(pods, snap, pctx)
    np.testing.assert_array_equal(s3.static_score, fresh.static_score)


def test_sticky_buckets_stabilize_shapes_across_waves():
    """A wave that needs a bigger term/vol bucket must not shrink back on
    the next wave — compiled kernel shapes stay reusable."""
    from kubernetes_tpu.scheduler.priorities import PriorityContext

    cs, sched = _make_world(n_nodes=8)
    tz = sched.backend.tensorizer
    snap = sched.snapshot()
    pctx = PriorityContext(snap)

    plain = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(4)]
    s1 = tz.build_static(plain, snap, pctx)
    assert s1.v_state == 8  # no conflict vols yet

    from kubernetes_tpu.api import Volume
    shared = [make_pod(f"v{i}", cpu="100m", memory="128Mi",
                       volumes=[Volume(name="v", disk_id="pd-shared",
                                       disk_kind="gce-pd")])
              for i in range(3)]
    s2 = tz.build_static(shared, snap, pctx)
    assert s2.v_state >= 32  # conflict-capable disk entered the vocab

    s3 = tz.build_static(plain, snap, pctx)
    assert s3.v_state == s2.v_state, "sticky bucket must not shrink"
    # the non-sticky tensorizer DOES shrink (the pre-PR behavior)
    loose = Tensorizer(sticky_buckets=False)
    l2 = loose.build_static(shared, snap, pctx)
    l3 = loose.build_static(plain, snap, pctx)
    assert l2.v_state >= 32 and l3.v_state == 8


# -- device-resident node state ---------------------------------------------


def _expect_alloc(static):
    """What the device-side node_alloc should hold: the host array sliced
    by the segment's resource-axis selection (ISSUE 5 tightening)."""
    if static.r_sel is None:
        return static.node_alloc
    return static.node_alloc[:, static.r_sel]


def test_device_node_cache_reuses_and_updates_columns():
    from kubernetes_tpu.ops.batch_kernel import DeviceNodeCache, to_device
    from kubernetes_tpu.scheduler.priorities import PriorityContext

    cs, sched = _make_world(n_nodes=8)
    tz = sched.backend.tensorizer
    pods = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(4)]
    snap = sched.snapshot()
    pctx = PriorityContext(snap)
    cache = DeviceNodeCache()

    s1 = tz.build_static(pods, snap, pctx)
    d1 = to_device(s1, node_cache=cache)
    assert cache.stats["uploads"] == 1
    d2 = to_device(s1, node_cache=cache)
    assert cache.stats["reuses"] == 1
    assert d2.node_alloc is d1.node_alloc  # same device buffer, no upload

    # dirty one node: only its columns are written
    node = cs.nodes.get("node-002")
    node.status.allocatable["cpu"] = "2"
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    s2 = tz.build_static(pods, snap, pctx)
    assert s2.node_dirty == [2]
    d3 = to_device(s2, node_cache=cache)
    assert cache.stats["col_updates"] == 1
    np.testing.assert_array_equal(
        np.asarray(d3.node_alloc), _expect_alloc(s2))
    np.testing.assert_array_equal(np.asarray(d3.node_exists), s2.node_exists)


def test_device_node_cache_zone_vocab_shift():
    """One node's zone relabel can renumber EVERY column's zone id (the
    vocab is first-occurrence over sorted nodes): the cache must diff the
    host arrays, not trust the dirty-node list, or stale ids poison the
    zone-spread scores."""
    from kubernetes_tpu.ops.batch_kernel import DeviceNodeCache, to_device
    from kubernetes_tpu.scheduler.priorities import PriorityContext

    cs, sched = _make_world(n_nodes=6)
    tz = sched.backend.tensorizer
    pods = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(3)]
    cache = DeviceNodeCache()

    # make node-000 the sole member of a zone that heads the vocab
    node = cs.nodes.get("node-000")
    node.meta.labels[ZONE] = "zone-solo"
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    pctx = PriorityContext(snap)
    s1 = tz.build_static(pods, snap, pctx)
    to_device(s1, node_cache=cache)

    # collapse it back: only column 0 is "dirty" per the node list, but
    # every other column's zone id shifts by one
    node = cs.nodes.get("node-000")
    node.meta.labels[ZONE] = "zone-0"
    cs.nodes.update(node)
    sched.pump()
    snap = sched.snapshot()
    s2 = tz.build_static(pods, snap, pctx)
    assert s2.node_dirty == [0]
    assert not np.array_equal(s1.node_zone, s2.node_zone)
    d2 = to_device(s2, node_cache=cache)
    np.testing.assert_array_equal(np.asarray(d2.node_zone), s2.node_zone)
    np.testing.assert_array_equal(
        np.asarray(d2.node_alloc), _expect_alloc(s2))


def test_device_node_cache_survives_tensorizer_swap():
    """A swapped-in tensorizer restarts its epoch/version counters; the
    instance nonce in the token must keep its fresh (epoch 1, version 0)
    from aliasing the previous tensorizer's cached device arrays."""
    from kubernetes_tpu.ops.batch_kernel import DeviceNodeCache, to_device
    from kubernetes_tpu.scheduler.priorities import PriorityContext

    cs1, sched1 = _make_world(n_nodes=4)
    pods = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(2)]
    cache = DeviceNodeCache()
    snap1 = sched1.snapshot()
    s1 = Tensorizer().build_static(pods, snap1, PriorityContext(snap1))
    to_device(s1, node_cache=cache)

    # a different same-size fleet through a FRESH tensorizer: same
    # (epoch, version) lineage, different nonce, different node_alloc
    cs2 = Clientset(Store())
    for i in range(4):
        cs2.nodes.create(make_node(f"node-{i:03d}", cpu="2", memory="4Gi",
                                   pods=10,
                                   labels={"kubernetes.io/hostname": f"node-{i:03d}"}))
    sched2 = Scheduler(cs2, algorithm=GenericScheduler(),
                       backend=TPUBatchBackend(algorithm=GenericScheduler()),
                       emit_events=False)
    sched2.start()
    snap2 = sched2.snapshot()
    s2 = Tensorizer().build_static(pods, snap2, PriorityContext(snap2))
    assert s1.node_token != s2.node_token  # nonce differs
    d2 = to_device(s2, node_cache=cache)
    np.testing.assert_array_equal(
        np.asarray(d2.node_alloc), _expect_alloc(s2))


# -- _idiv exactness ---------------------------------------------------------


def test_idiv_bit_exact_over_scoring_ranges():
    """f32+fixup floor division must equal int32 // on every lane the
    scoring formulas can select (divisors <= 2^24, |quotients| < 2^23),
    including negatives and boundary-adjacent values."""
    import jax.numpy as jnp

    from kubernetes_tpu.ops.batch_kernel import _idiv

    rng = np.random.default_rng(0)
    a = np.concatenate([
        rng.integers(-(2**27), 2**27, size=20000),
        np.array([0, 1, -1, 655360 * 110, -655360 * 110, 2**27 - 1]),
    ]).astype(np.int32)
    b = np.concatenate([
        rng.integers(1, 2**24, size=20000),
        np.array([1, 2, 3, 110, 65536, 2**24 - 1]),
    ]).astype(np.int32)
    n = min(len(a), len(b))
    a, b = a[:n], b[:n]
    got = np.asarray(_idiv(jnp.asarray(a), jnp.asarray(b)))
    want = a // b
    np.testing.assert_array_equal(got, want)
    # adversarial: exact-multiple boundaries, where a naive float floor
    # is most likely to land one off
    q = rng.integers(-(2**22), 2**22, size=5000).astype(np.int64)
    d = rng.integers(1, 2**9, size=5000).astype(np.int64)
    for delta in (-1, 0, 1):
        aa = (q * d + delta).astype(np.int32)
        bb = d.astype(np.int32)
        got = np.asarray(_idiv(jnp.asarray(aa), jnp.asarray(bb)))
        np.testing.assert_array_equal(got, aa // bb)


# -- run_batch_loop policy ---------------------------------------------------


def test_run_batch_loop_accumulates_to_min_batch():
    """Arrivals landing while the loop waits accumulate into one wave
    instead of N tiny ones."""
    cs, sched = _make_world(n_nodes=8)
    n = 30
    started = threading.Event()

    def arrivals():
        started.wait()
        for i in range(n):
            cs.pods.create(make_pod(f"p{i:03d}", cpu="100m", memory="128Mi"))

    t = threading.Thread(target=arrivals, daemon=True)
    t.start()
    started.set()
    bound = sched.run_batch_loop(min_batch=n, max_wait=10.0, max_waves=1,
                                 poll_interval=0.002)
    t.join(timeout=5)
    assert bound == n
    assert sched.metrics.batch_size.count == 1  # ONE wave, not n
    assert sched.metrics.batch_queue_wait.count == 1


def test_run_batch_loop_max_wait_fires_partial_wave():
    cs, sched = _make_world(n_nodes=8)
    for i in range(5):
        cs.pods.create(make_pod(f"p{i}", cpu="100m", memory="128Mi"))
    bound = sched.run_batch_loop(min_batch=1000, max_wait=0.05, max_waves=1)
    assert bound == 5  # max_wait elapsed; the partial wave ran


def test_run_batch_loop_idle_timeout_returns():
    _, sched = _make_world(n_nodes=4)
    bound = sched.run_batch_loop(min_batch=1, idle_timeout=0.05,
                                 poll_interval=0.01)
    assert bound == 0


def test_batch_phase_timers_recorded():
    cs, sched = _make_world(n_nodes=8)
    for i in range(20):
        cs.pods.create(make_pod(f"p{i:02d}", cpu="100m", memory="128Mi"))
    sched.pump()
    sched.schedule_pending_batch()
    phases = sched.last_batch_phases
    for key in ("tensorize_s", "dispatch_s", "device_wait_s", "commit_s",
                "prep_s", "decode_s"):
        assert key in phases and phases[key] >= 0.0
    assert "promotions" in phases
    assert sched.metrics.tensorize_upload_fraction.count > 0
    assert sched.metrics.ingest_decode_seconds.count > 0


def test_full_window_poll_gate_is_platform_checked(monkeypatch):
    """ROADMAP open item (ISSUE 4 satellite): a real accelerator always
    polls for the whole device window — only the XLA CPU 'device', which
    shares the host cores, still requires a spare core."""
    import os

    import kubernetes_tpu.scheduler.scheduler as sched_mod

    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    monkeypatch.setattr(sched_mod, "_ACCEL_PLATFORM", "tpu")
    assert sched_mod._poll_full_device_window() is True
    monkeypatch.setattr(sched_mod, "_ACCEL_PLATFORM", "gpu")
    assert sched_mod._poll_full_device_window() is True
    # the CPU 'device' computes ON the host cores: 1 core -> no polling
    monkeypatch.setattr(sched_mod, "_ACCEL_PLATFORM", "cpu")
    assert sched_mod._poll_full_device_window() is False
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    assert sched_mod._poll_full_device_window() is True


def test_unreadable_device_is_an_error_not_an_unknown_platform(monkeypatch):
    """A chip that fails to initialize must not quietly become the XLA
    rung: both platform probes let ``jax.devices()`` raise."""
    import jax

    import kubernetes_tpu.ops.backend as backend_mod
    import kubernetes_tpu.scheduler.scheduler as sched_mod

    def no_devices():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_devices)
    monkeypatch.setattr(sched_mod, "_ACCEL_PLATFORM", None)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        backend_mod._device_platform()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        sched_mod._accel_platform()
