"""The wave planner reads each pod once (``plan_segments``) and hands every
kernel segment its columns: signature ids, keys and disk rows, which
``build_static`` indexes instead of walking the pods again.

The references kept here: the backend's segmenter as it was before the
planner moved into the tensorize module (one walk of ``pod_disk_vols``,
``pod_signature_key`` and ``count_affinity_terms`` per pod), the per-pod
derivation of each column, and ``build_static`` of the bare pod list,
which must give the same ``BatchStatic`` field for field."""

import copy
import dataclasses
import random

import numpy as np
import pytest

from benchmark import cluster as bench_cluster
from kubernetes_tpu.api import Affinity, LabelSelector, PodAffinityTerm, Volume
from kubernetes_tpu.api import lazy as lazy_mod
from kubernetes_tpu.api import types as api
from kubernetes_tpu.models import Tensorizer
from kubernetes_tpu.models.snapshot import (
    BatchStatic,
    SegmentColumns,
    _disk_refs,
    count_affinity_terms,
    plan_segments,
    pod_disk_vols,
    pod_signature_key,
)
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, PriorityContext
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
from kubernetes_tpu.testutil import make_node, make_pod
from kubernetes_tpu.utils import tracing

from tests.test_parity import build_cluster, oracle_batch

HOSTNAME = "kubernetes.io/hostname"


def parent_segments(pods, mounted, max_pods, tz):
    """The backend's segmenter before the planner (kept as the reference
    for where the cuts fall): ``[(kind, [(i, pod), ...]), ...]``."""
    out = []
    cur = []
    sigs = set()
    vols_once = set()
    vols_conflict = set()
    n_terms = 0

    def flush():
        nonlocal cur, sigs, vols_once, vols_conflict, n_terms
        if cur:
            out.append(("kernel", cur))
        cur, sigs, vols_once, vols_conflict, n_terms = [], set(), set(), set(), 0

    for i, pod in enumerate(pods):
        pv = pod_disk_vols(pod)
        if len(pv) > tz.vols_per_pod:
            flush()
            out.append(("oracle", [(i, pod)]))
            continue
        pv_conflict = {d for d in pv if d in mounted or d in vols_once}
        key = pod_signature_key(pod)
        t_new = count_affinity_terms(pod) if key not in sigs else 0
        if cur and (
            len(cur) >= max_pods
            or (key not in sigs and len(sigs) >= tz.max_groups)
            or n_terms + t_new > tz.max_terms
            or len(vols_conflict | pv_conflict) > tz.max_vols
        ):
            flush()
            t_new = count_affinity_terms(pod)
            pv_conflict = {d for d in pv if d in mounted}
        sigs.add(key)
        n_terms += t_new
        vols_conflict |= pv_conflict
        vols_once |= pv
        cur.append((i, pod))
    flush()
    return out


def per_pod_columns(pods):
    """Each column of a segment derived pod by pod."""
    ids: dict = {}
    reps, groups = [], []
    for pod in pods:
        key = pod_signature_key(pod)
        if key not in ids:
            ids[key] = len(reps)
            reps.append(pod)
        groups.append(ids[key])
    rows = [(k, _disk_refs(pod)) for k, pod in enumerate(pods)
            if _disk_refs(pod)]
    return {"group_of_pod": groups, "reps": reps,
            "n_terms": sum(count_affinity_terms(r) for r in reps),
            "keys": [pod.meta.key for pod in pods],
            "disk_rows": [k for k, _ in rows],
            "disk_refs": [refs for _, refs in rows]}


def assert_static_equal(a: BatchStatic, b: BatchStatic) -> None:
    for f in dataclasses.fields(BatchStatic):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name == "node_token":
            # (instance nonce, epoch, version): two tensorizers' row caches
            # never share a nonce, by design
            x, y = x[1:], y[1:]
        if isinstance(x, np.ndarray):
            assert isinstance(y, np.ndarray), f.name
            assert x.dtype == y.dtype and x.shape == y.shape, f.name
            assert np.array_equal(x, y), f.name
        else:
            assert x == y, f.name


# -- worlds ----------------------------------------------------------------

def _bench_world(name, n_nodes, n_pods, seed):
    """A deployment of the benchmark's generator, cut to a few dozen nodes,
    its objects lazy views over the wire dicts as the informer hands them
    over (the spec stays undecoded)."""
    config = copy.deepcopy(bench_cluster.load_config(name))
    config["nodes"]["count"] = n_nodes
    config["pods"]["count"] = n_pods
    rng = random.Random(seed)
    nim = {}
    for raw in bench_cluster.make_nodes(config, rng):
        node = lazy_mod.wrap(api.Node, raw)
        nim[node.meta.name] = NodeInfo(node)
    pods = [lazy_mod.wrap(api.Pod, raw)
            for raw in bench_cluster.make_pods(config, rng, n_pods)]
    return nim, pods


def _gce(disk_id, read_only=False):
    return Volume(name=f"v-{disk_id}", disk_kind="gce-pd", disk_id=disk_id,
                  read_only=read_only)


def _anti(app):
    return Affinity(pod_anti_affinity_required=[PodAffinityTerm(
        selector=LabelSelector.from_match_labels({"app": app}),
        topology_key=HOSTNAME)])


def _disk_pods(rng, n, disk_ids, per_pod=1, tag="d", read_only=False):
    return [make_pod(f"{tag}-{i:04d}", cpu="100m", memory="64Mi",
                     labels={"app": "web"},
                     volumes=[_gce(rng.choice(disk_ids), read_only)
                              for _ in range(per_pod)])
            for i in range(n)]


def _mixed(rng, n, apps=6, tag="m"):
    """Templates of several signatures, some with hostname anti-affinity,
    a tenth with a disk from a small pool."""
    pods = []
    for i in range(n):
        app = f"a{rng.randrange(apps)}"
        kw = dict(cpu=rng.choice(["100m", "250m"]), memory="64Mi",
                  labels={"app": app})
        if rng.random() < 0.2:
            kw["affinity"] = _anti(app)
        if rng.random() < 0.1:
            kw["volumes"] = [_gce(f"pool-{rng.randrange(12)}")]
        pods.append(make_pod(f"{tag}-{i:04d}", **kw))
    return pods


def _mount(nim, disk_ids):
    """Existing pods holding ``disk_ids``, one a node, round robin."""
    names = sorted(nim)
    for k, disk_id in enumerate(disk_ids):
        name = names[k % len(names)]
        nim[name].add_pod(make_pod(f"holder-{k}", cpu="10m",
                                   volumes=[_gce(disk_id)], node_name=name))


def world_perf_2k(seed):
    nim, pods = _bench_world("perf-2k", 40, 700, seed)
    return nim, pods, {}, 256


def world_density_5k(seed):
    nim, pods = _bench_world("density-5k", 60, 900, seed)
    return nim, pods, {}, 256


def world_count_only_disks(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    pods = [make_pod(f"solo-{i:04d}", cpu="100m", volumes=[_gce(f"own-{i}")])
            for i in range(120)] + _mixed(rng, 60)
    rng.shuffle(pods)
    return nim, pods, {}, 64


def world_mounted_disks(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    held = [f"held-{k}" for k in range(10)]
    _mount(nim, held)
    pods = _disk_pods(rng, 80, held + [f"free-{k}" for k in range(30)])
    pods += _mixed(rng, 80)
    rng.shuffle(pods)
    return nim, pods, {}, 64


def world_shared_disk(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    pods = (_disk_pods(rng, 40, ["one", "two"], tag="rw")
            + _disk_pods(rng, 40, ["ro"], tag="ro", read_only=True)
            + _disk_pods(rng, 40, [f"x{k}" for k in range(8)], per_pod=3,
                         tag="many")
            + _mixed(rng, 60))
    rng.shuffle(pods)
    return nim, pods, {}, 96


def world_over_vols_per_pod(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    pods = (_disk_pods(rng, 30, [f"x{k}" for k in range(40)], per_pod=3,
                       tag="three")
            + _disk_pods(rng, 30, [f"x{k}" for k in range(40)], per_pod=2,
                         tag="two")
            + _mixed(rng, 60))
    rng.shuffle(pods)
    return nim, pods, {"vols_per_pod": 2}, 64


def world_small_max_groups(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    return nim, _mixed(rng, 200, apps=9), {"max_groups": 4}, 1024


def world_small_max_terms(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    return nim, _mixed(rng, 200, apps=9), {"max_terms": 2}, 1024


def world_small_max_vols(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    held = [f"held-{k}" for k in range(6)]
    _mount(nim, held)
    pods = _disk_pods(rng, 90, held + [f"free-{k}" for k in range(6)],
                      per_pod=2) + _mixed(rng, 60)
    rng.shuffle(pods)
    return nim, pods, {"max_vols": 3}, 1024


def world_small_max_segment_pods(seed):
    rng = random.Random(seed)
    nim = build_cluster(rng, 16, existing_per_node=1)
    return nim, _mixed(rng, 150), {}, 7


WORLDS = {f.__name__[len("world_"):]: f for f in (
    world_perf_2k, world_density_5k, world_count_only_disks,
    world_mounted_disks, world_shared_disk, world_over_vols_per_pod,
    world_small_max_groups, world_small_max_terms, world_small_max_vols,
    world_small_max_segment_pods)}


def _mounted(nim) -> set:
    return {d for info in nim.values() for q in info.pods
            for d in pod_disk_vols(q)}


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("world", WORLDS.values(), ids=WORLDS.keys())
def test_the_plan_cuts_as_the_parent_and_its_columns_equal_the_per_pod_walk(
        world, seed):
    nim, pods, limits, max_pods = world(seed)
    mounted = _mounted(nim)
    tz = Tensorizer(**limits)
    plan = plan_segments(pods, mounted, max_pods, tz.max_groups,
                         tz.max_terms, tz.max_vols, tz.vols_per_pod)
    want = parent_segments(pods, mounted, max_pods, tz)
    assert [(kind, [i for i, _ in (p.segment if kind == "kernel" else p)])
            for kind, p in plan] == [
        (kind, [i for i, _ in seg]) for kind, seg in want]
    kernel = [p for kind, p in plan if kind == "kernel"]
    assert len(kernel) >= 2
    for cols in kernel:
        assert isinstance(cols, SegmentColumns)
        assert cols.pods == [pod for _, pod in cols.segment]
        assert cols.group_of_pod.dtype == np.int32
        derived = per_pod_columns(cols.pods)
        assert cols.group_of_pod.tolist() == derived["group_of_pod"]
        assert [id(p) for p in cols.reps] == [id(p) for p in derived["reps"]]
        for name in ("n_terms", "keys", "disk_rows", "disk_refs"):
            assert getattr(cols, name) == derived[name], name


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("world", WORLDS.values(), ids=WORLDS.keys())
def test_build_static_with_the_plans_columns_equals_it_of_the_bare_pods(
        world, seed):
    """Two tensorizers take the same segments in the same order (their
    sticky buckets and row caches then agree): one is handed each
    segment's columns, the other the bare pod list.  Segments build_static
    rejects are rejected by both."""
    nim, pods, limits, max_pods = world(seed)
    mounted = _mounted(nim)
    pctx = PriorityContext(nim)
    with_columns, bare = Tensorizer(**limits), Tensorizer(**limits)
    plan = plan_segments(pods, mounted, max_pods, with_columns.max_groups,
                         with_columns.max_terms, with_columns.max_vols,
                         with_columns.vols_per_pod)
    built = 0
    for kind, cols in plan:
        if kind != "kernel":
            continue
        a = with_columns.build_static(cols.pods, nim, pctx,
                                      mounted_disks=mounted, columns=cols)
        b = bare.build_static(list(cols.pods), nim, pctx,
                              mounted_disks=mounted)
        assert (a is None) == (b is None)
        if a is not None:
            assert_static_equal(a, b)
            built += 1
    assert built >= 2


@pytest.mark.parametrize("limit", ["max_groups", "max_terms", "max_vols",
                                   "vols_per_pod"])
def test_build_static_keeps_each_rejection_of_a_bare_list(limit):
    """A list over a budget, handed over whole, is planned as one segment
    and rejected as before; one pod under it is not."""
    rng = random.Random(5)
    nim = build_cluster(rng, 8, existing_per_node=0)
    pctx = PriorityContext(nim)
    if limit == "vols_per_pod":
        pods = _disk_pods(rng, 3, [f"x{k}" for k in range(30)], per_pod=3)
        tz = Tensorizer(vols_per_pod=2)
    elif limit == "max_vols":
        pods = [make_pod(f"s-{k}", cpu="100m", volumes=[_gce(f"s{k // 2}")])
                for k in range(4)]
        tz = Tensorizer(max_vols=1)
    else:
        pods = _mixed(rng, 60, apps=6)
        pods += [make_pod(f"anti-{k}", labels={"app": f"b{k}"},
                          affinity=_anti(f"b{k}")) for k in range(3)]
        tz = Tensorizer(**{limit: 2})
    assert tz.build_static(pods, nim, pctx) is None
    if limit != "vols_per_pod":
        assert tz.build_static(pods[:1], nim, pctx) is not None


def _pinned_cluster():
    nim = {}
    for k in range(4):
        node = make_node(f"n{k}", cpu="4", memory="8Gi", pods=20,
                         labels={HOSTNAME: f"n{k}"})
        nim[node.meta.name] = NodeInfo(node)
    return nim


def _capture_build_static(backend):
    seen = []
    build = backend.tensorizer.build_static

    def wrapped(pods, *a, **kw):
        static = build(pods, *a, **kw)
        seen.append((len(pods), kw.get("columns") is not None, static))
        return static

    backend.tensorizer.build_static = wrapped
    return seen


def test_a_disk_mounted_by_an_earlier_segment_is_a_conflict_row_later():
    """The plan is made before any segment runs, so it cannot see the disk
    segment 1 mounts; segment 2 references it once, and build_static, which
    judges conflicts against the disks mounted when it runs, must give it
    an identity row there."""
    nim = _pinned_cluster()
    pods = ([make_pod("first", cpu="100m", volumes=[_gce("pd-x")])]
            + [make_pod(f"fill-{k}", cpu="100m") for k in range(3)]
            + [make_pod("second", cpu="100m", volumes=[_gce("pd-x")])]
            + [make_pod(f"tail-{k}", cpu="100m") for k in range(3)])
    algo_a, algo_b = GenericScheduler(), GenericScheduler()
    want = oracle_batch(pods, nim, PriorityContext(nim), algo_a)
    backend = TPUBatchBackend(algorithm=algo_b, max_segment_pods=4)
    seen = _capture_build_static(backend)
    try:
        got = backend.schedule_batch(pods, nim, PriorityContext(nim))
    finally:
        backend._host_state.close()
    assert got == want and algo_a._round_robin == algo_b._round_robin
    assert [(n, planned) for n, planned, _ in seen] == [(4, True), (4, True)]
    first, second = (static for _, _, static in seen)
    assert first.vol_vocab == [] and first.pod_vol_count_only[0, 0]
    assert second.vol_vocab == [("gce-pd", "pd-x")]
    assert not second.pod_vol_count_only[0, 0]
    assert backend.stats["planned_pods"] == backend.stats["kernel_pods"] == 8


def test_a_segment_build_static_rejects_splits_to_the_oracles_bindings():
    """Segment 1 mounts two singleton disks; segment 2 references each once
    more, which the plan counted as no conflict.  At tensorize time both
    are conflicts, over ``max_vols`` = 1, so build_static rejects the
    segment and the split path tensorizes its halves, each planned by
    build_static itself: bindings and tie counter stay the oracle's."""
    nim = _pinned_cluster()
    pods = [make_pod("a", cpu="100m", volumes=[_gce("pd-x")]),
            make_pod("b", cpu="100m", volumes=[_gce("pd-y")]),
            make_pod("c", cpu="100m", volumes=[_gce("pd-x")]),
            make_pod("d", cpu="100m", volumes=[_gce("pd-y")])]
    algo_a, algo_b = GenericScheduler(), GenericScheduler()
    want = oracle_batch(pods, nim, PriorityContext(nim), algo_a)
    backend = TPUBatchBackend(algorithm=algo_b, max_segment_pods=2,
                              tensorizer=Tensorizer(max_vols=1))
    seen = _capture_build_static(backend)
    try:
        got = backend.schedule_batch(pods, nim, PriorityContext(nim))
    finally:
        backend._host_state.close()
    assert got == want and algo_a._round_robin == algo_b._round_robin
    assert None not in got
    # the sync split path tensorizes the rejected segment whole once more,
    # as a bare list, before it halves it
    assert [(n, planned, static is None) for n, planned, static in seen] == [
        (2, True, False), (2, True, True), (2, False, True),
        (1, False, False), (1, False, False)]
    assert backend.stats["oracle_pods"] == 0
    assert backend.stats["kernel_pods"] == 4
    assert backend.stats["planned_pods"] == 4  # 2 placed, 2 rejected


def test_the_wave_reports_its_disk_pods_and_planned_pods():
    """In an in-process wave, ``segment_plan`` counts the pods with a disk
    row and each ``tensorize`` the pods whose columns the plan made."""
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store import Store

    tr = tracing.enable()
    try:
        cs = Clientset(Store())
        for k in range(4):
            cs.nodes.create(make_node(f"n{k}", cpu="8", memory="16Gi"))
        algo = GenericScheduler()
        backend = TPUBatchBackend(algorithm=algo, max_segment_pods=8)
        sched = Scheduler(cs, algorithm=algo, backend=backend)
        sched.start()
        cs.pods.create_many(
            [make_pod(f"p{k}", cpu="100m") for k in range(9)]
            + [make_pod(f"v{k}", cpu="100m", volumes=[_gce(f"pd-{k}")])
               for k in range(3)])
        sched.pump()
        assert sched.schedule_pending_batch() == (12, 0)
        wave = tr.ring[-1]
    finally:
        tracing.disable()
    by = {}
    for c in wave.children:
        by.setdefault(c.name, []).append(c)
    (plan,) = by["segment_plan"]
    assert plan.attrs == {"pods": 12, "segments": 2, "disk_pods": 3}
    assert [t.attrs["pods"] for t in by["tensorize"]] == [8, 4]
    assert [t.attrs["planned"] for t in by["tensorize"]] == [8, 4]
    assert backend.stats["planned_pods"] == 12
