"""Oracle ↔ TPU-kernel binding parity.

The framework's core claim (BASELINE.json): the batched device path
produces *identical bindings* to the sequential oracle.  These tests run
both paths over randomized clusters and assert assignment-for-assignment
equality, including the round-robin tie counter.
"""

import random

import pytest

from kubernetes_tpu.api import (
    Affinity,
    LabelSelector,
    ObjectMeta,
    OwnerReference,
    PodAffinityTerm,
    ReplicaSet,
    Service,
    Taint,
    Toleration,
    Volume,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.models import Tensorizer
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import (
    FitError,
    GenericScheduler,
    PriorityContext,
    cluster_autoscaler_priorities,
)
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo
from kubernetes_tpu.testutil import make_node, make_pod

ZONE = "failure-domain.beta.kubernetes.io/zone"


def oracle_batch(pods, node_info_map, pctx, algorithm):
    """Reference behavior: pure sequential oracle with cache feedback."""
    work = {n: i.clone() for n, i in node_info_map.items()}
    wctx = PriorityContext(
        work, services=pctx.services, replicasets=pctx.replicasets,
        hard_pod_affinity_weight=pctx.hard_pod_affinity_weight,
        pvcs=pctx.pvcs, pvs=pctx.pvs,
    )
    out = []
    for pod in pods:
        try:
            res = algorithm.schedule(pod, work, wctx)
            out.append(res.node_name)
            work[res.node_name].add_pod(pod)
        except FitError:
            out.append(None)
    return out


def build_cluster(rng, n_nodes, zones=3, tainted_frac=0.1, existing_per_node=2):
    node_info_map = {}
    for i in range(n_nodes):
        labels = {"kubernetes.io/hostname": f"node-{i:04d}"}
        if zones:
            labels[ZONE] = f"zone-{i % zones}"
        if rng.random() < 0.3:
            labels["disk"] = rng.choice(["ssd", "hdd"])
        taints = []
        if rng.random() < tainted_frac:
            taints.append(Taint(key="dedicated", value="special", effect="NoSchedule"))
        node = make_node(
            f"node-{i:04d}",
            cpu=rng.choice(["4", "8", "16"]),
            memory=rng.choice(["8Gi", "16Gi", "32Gi"]),
            pods=rng.choice([50, 110]),
            labels=labels,
            taints=taints,
        )
        info = NodeInfo(node)
        for j in range(rng.randrange(existing_per_node + 1)):
            p = make_pod(
                f"existing-{i}-{j}",
                cpu=rng.choice(["100m", "500m", "1"]),
                memory=rng.choice(["128Mi", "512Mi", "1Gi"]),
                labels={"app": rng.choice(["web", "db", "cache"])},
                node_name=node.meta.name,
            )
            info.add_pod(p)
        node_info_map[node.meta.name] = info
    return node_info_map


def make_batch(rng, n_pods, templates=None):
    templates = templates or [
        dict(cpu="100m", memory="128Mi", labels={"app": "web"}),
        dict(cpu="500m", memory="512Mi", labels={"app": "db"}),
        dict(cpu="1", memory="1Gi", labels={"app": "cache"}),
        dict(cpu="250m", memory="256Mi", labels={"app": "web"},
             node_selector={"disk": "ssd"}),
        dict(cpu="200m", memory="128Mi", labels={"app": "batch"},
             tolerations=[Toleration(key="dedicated", operator="Exists")]),
    ]
    pods = []
    for i in range(n_pods):
        t = dict(rng.choice(templates))
        pods.append(make_pod(f"pend-{i:05d}", **t))
    return pods


def assert_parity(pods, node_info_map, pctx, priorities=None, check_kernel_used=True):
    algo_a = GenericScheduler(priorities=priorities)
    algo_b = GenericScheduler(priorities=priorities)
    want = oracle_batch(pods, node_info_map, pctx, algo_a)
    backend = TPUBatchBackend(algorithm=algo_b)
    got = backend.schedule_batch(pods, node_info_map, pctx)
    mismatches = [
        (p.meta.name, w, g) for p, w, g in zip(pods, want, got) if w != g
    ]
    assert not mismatches, f"{len(mismatches)} binding mismatches; first 10: {mismatches[:10]}"
    assert algo_a._round_robin == algo_b._round_robin, "tie-break counter diverged"
    if check_kernel_used:
        assert backend.stats["kernel_pods"] > 0, "kernel path was never exercised"
    return backend


def test_parity_basic_resources():
    rng = random.Random(1)
    m = build_cluster(rng, 24, zones=0, tainted_frac=0, existing_per_node=2)
    pods = make_batch(rng, 120, templates=[
        dict(cpu="100m", memory="128Mi"),
        dict(cpu="2", memory="4Gi"),
        dict(cpu="500m", memory="1Gi"),
    ])
    assert_parity(pods, m, PriorityContext(m))


def test_parity_zones_spread_services():
    rng = random.Random(2)
    m = build_cluster(rng, 30, zones=3)
    svcs = [Service(meta=ObjectMeta(name=a), selector={"app": a}) for a in ("web", "db", "cache")]
    pctx = PriorityContext(m, services=svcs)
    pods = make_batch(rng, 150)
    assert_parity(pods, m, pctx)


def test_parity_replicaset_owners_and_spread():
    rng = random.Random(3)
    m = build_cluster(rng, 20, zones=2)
    rs = ReplicaSet(
        meta=ObjectMeta(name="rs-web"),
        selector=LabelSelector.from_match_labels({"app": "web"}),
    )
    pctx = PriorityContext(m, replicasets=[rs])
    ref = OwnerReference(kind="ReplicaSet", name="rs-web", uid="uid-rs-web", controller=True)
    pods = [
        make_pod(f"w-{i}", cpu="200m", memory="256Mi", labels={"app": "web"}, owner_refs=[ref])
        for i in range(80)
    ]
    assert_parity(pods, m, pctx)


def test_parity_most_requested_binpack():
    rng = random.Random(4)
    m = build_cluster(rng, 16, zones=0)
    pods = make_batch(rng, 100)
    assert_parity(pods, m, PriorityContext(m), priorities=cluster_autoscaler_priorities())


def test_parity_taints_and_node_affinity():
    rng = random.Random(5)
    m = build_cluster(rng, 25, zones=3, tainted_frac=0.3)
    # add PreferNoSchedule taints to some nodes (exercises TaintToleration prio)
    for i, (name, info) in enumerate(sorted(m.items())):
        if i % 4 == 0:
            info.node.spec.taints.append(Taint(key="soft", value="x", effect="PreferNoSchedule"))
            info.set_node(info.node)
    pods = make_batch(rng, 120)
    assert_parity(pods, m, PriorityContext(m))


def test_parity_host_ports():
    rng = random.Random(6)
    m = build_cluster(rng, 10, zones=0, existing_per_node=0)
    pods = [make_pod(f"p-{i}", cpu="100m", host_ports=[8080]) for i in range(15)]
    backend = assert_parity(pods, m, PriorityContext(m))
    # only 10 nodes -> 10 pods land, 5 unschedulable on both paths


def test_parity_unschedulable_overflow():
    rng = random.Random(7)
    m = build_cluster(rng, 6, zones=0, existing_per_node=0)
    pods = make_batch(rng, 120, templates=[dict(cpu="2", memory="4Gi")])
    backend = assert_parity(pods, m, PriorityContext(m))


def test_parity_mixed_affinity_volume_batch_stays_on_kernel():
    # phase B: pods carrying their own anti-affinity terms and disk volumes
    # are kernel-expressible — the whole mixed batch runs on device
    rng = random.Random(8)
    m = build_cluster(rng, 15, zones=2)
    aff = Affinity(
        pod_anti_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "solo"}),
                topology_key="kubernetes.io/hostname",
            )
        ]
    )
    pods = []
    for i in range(90):
        if i % 10 == 5:
            pods.append(make_pod(f"solo-{i}", cpu="100m", labels={"app": "solo"}, affinity=aff))
        elif i % 17 == 3:
            pods.append(
                make_pod(
                    f"vol-{i}", cpu="100m",
                    volumes=[Volume(name="v", disk_id=f"pd-{i % 4}", disk_kind="gce-pd")],
                )
            )
        else:
            pods.append(make_pod(f"plain-{i}", cpu="200m", memory="256Mi", labels={"app": "web"}))
    backend = assert_parity(pods, m, PriorityContext(m))
    assert backend.stats["oracle_pods"] == 0
    assert backend.stats["kernel_pods"] == 90
    assert backend.stats["segments"] == 1


def test_parity_existing_affinity_pods_affect_eligible_batch():
    # existing pods carry required anti-affinity + preferred affinity; the
    # (affinity-less) batch pods must respect the symmetry rules on both paths
    rng = random.Random(9)
    m = build_cluster(rng, 12, zones=3, existing_per_node=0)
    names = sorted(m.keys())
    anti = Affinity(
        pod_anti_affinity_required=[
            PodAffinityTerm(selector=LabelSelector.from_match_labels({"app": "web"}), topology_key=ZONE)
        ]
    )
    pref = Affinity(
        pod_affinity_preferred=[
            WeightedPodAffinityTerm(
                weight=7,
                term=PodAffinityTerm(selector=LabelSelector.from_match_labels({"app": "web"}), topology_key=ZONE),
            )
        ]
    )
    lonely = make_pod("lonely", cpu="100m", labels={"app": "db"}, affinity=anti, node_name=names[0])
    m[names[0]].add_pod(lonely)
    friendly = make_pod("friendly", cpu="100m", labels={"app": "cache"}, affinity=pref, node_name=names[1])
    m[names[1]].add_pod(friendly)
    pods = [make_pod(f"web-{i}", cpu="100m", labels={"app": "web"}) for i in range(24)]
    backend = assert_parity(pods, m, PriorityContext(m))
    assert backend.stats["kernel_pods"] == 24  # affinity-less pods stay eligible


def test_parity_large_randomized():
    rng = random.Random(10)
    m = build_cluster(rng, 60, zones=4, tainted_frac=0.15, existing_per_node=3)
    svcs = [Service(meta=ObjectMeta(name=a), selector={"app": a}) for a in ("web", "db")]
    pctx = PriorityContext(m, services=svcs)
    pods = make_batch(rng, 400)
    assert_parity(pods, m, pctx)


def test_backend_in_scheduler_end_to_end():
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store import Store

    cs = Clientset(Store())
    for i in range(8):
        cs.nodes.create(make_node(f"n{i}", cpu="8", memory="16Gi"))
    for i in range(40):
        cs.pods.create(make_pod(f"p{i}", cpu="500m", memory="512Mi"))
    algo = GenericScheduler()
    sched = Scheduler(cs, algorithm=algo, backend=TPUBatchBackend(algorithm=algo))
    sched.start()
    bound, failed = sched.schedule_pending_batch()
    assert (bound, failed) == (40, 0)
    pods, _ = cs.pods.list()
    assert all(p.spec.node_name for p in pods)
    # batch respects capacity exactly like the per-pod path would
    from collections import Counter
    counts = Counter(p.spec.node_name for p in pods)
    assert max(counts.values()) <= 110


# ---------------------------------------------------------------------------
# Phase B: pending pods carry their OWN (anti)affinity terms and volumes —
# all of it must run on the kernel with oracle-identical bindings
# ---------------------------------------------------------------------------


def _assert_all_kernel(backend, n):
    assert backend.stats["oracle_pods"] == 0
    assert backend.stats["kernel_pods"] == n


def test_parity_batch_required_anti_affinity_self():
    # every pod anti-affines with its own label on hostname -> at most one
    # per node; later pods respect earlier batch placements on both paths
    rng = random.Random(20)
    m = build_cluster(rng, 10, zones=2, existing_per_node=0)
    aff = Affinity(
        pod_anti_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "solo"}),
                topology_key="kubernetes.io/hostname",
            )
        ]
    )
    pods = [
        make_pod(f"solo-{i}", cpu="100m", labels={"app": "solo"}, affinity=aff)
        for i in range(14)
    ]
    backend = assert_parity(pods, m, PriorityContext(m))
    _assert_all_kernel(backend, 14)


def test_parity_batch_required_affinity_first_pod_rule():
    # required affinity to own label: the first pod lands anywhere (first-pod
    # rule, predicates.go:1196-1216), the rest pack into its zone
    rng = random.Random(21)
    m = build_cluster(rng, 12, zones=3, existing_per_node=0)
    aff = Affinity(
        pod_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "herd"}),
                topology_key=ZONE,
            )
        ]
    )
    pods = [
        make_pod(f"herd-{i}", cpu="100m", labels={"app": "herd"}, affinity=aff)
        for i in range(9)
    ]
    backend = assert_parity(pods, m, PriorityContext(m))
    _assert_all_kernel(backend, 9)
    # all placed in one zone
    algo = GenericScheduler()
    got = TPUBatchBackend(algorithm=algo).schedule_batch(pods, m, PriorityContext(m))
    zones = {m[n].node.meta.labels[ZONE] for n in got}
    assert len(zones) == 1


def test_parity_batch_required_affinity_unsatisfiable():
    # required affinity to a label no pod has (and the pod itself lacks):
    # every pod unschedulable on both paths
    rng = random.Random(22)
    m = build_cluster(rng, 6, zones=2, existing_per_node=0)
    aff = Affinity(
        pod_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "ghost"}),
                topology_key=ZONE,
            )
        ]
    )
    pods = [make_pod(f"p-{i}", cpu="100m", labels={"app": "real"}, affinity=aff) for i in range(4)]
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo)
    got = backend.schedule_batch(pods, m, PriorityContext(m))
    want = oracle_batch(pods, m, PriorityContext(m), GenericScheduler())
    assert got == want == [None] * 4


def test_parity_batch_preferred_affinity_scoring():
    # soft co-location with earlier batch pods must shift scores identically
    rng = random.Random(23)
    m = build_cluster(rng, 9, zones=3, existing_per_node=1)
    pref = Affinity(
        pod_affinity_preferred=[
            WeightedPodAffinityTerm(
                weight=50,
                term=PodAffinityTerm(
                    selector=LabelSelector.from_match_labels({"app": "web"}),
                    topology_key=ZONE,
                ),
            )
        ]
    )
    anti = Affinity(
        pod_anti_affinity_preferred=[
            WeightedPodAffinityTerm(
                weight=30,
                term=PodAffinityTerm(
                    selector=LabelSelector.from_match_labels({"app": "web"}),
                    topology_key=ZONE,
                ),
            )
        ]
    )
    pods = []
    for i in range(30):
        if i % 3 == 0:
            pods.append(make_pod(f"seed-{i}", cpu="100m", labels={"app": "web"}))
        elif i % 3 == 1:
            pods.append(make_pod(f"follow-{i}", cpu="100m", labels={"app": "f"}, affinity=pref))
        else:
            pods.append(make_pod(f"avoid-{i}", cpu="100m", labels={"app": "a"}, affinity=anti))
    backend = assert_parity(pods, m, PriorityContext(m))
    _assert_all_kernel(backend, 30)


def test_parity_batch_symmetric_required_affinity_weight():
    # a placed batch pod's REQUIRED affinity term scores symmetrically onto
    # later matching pods via hard_pod_affinity_weight
    rng = random.Random(24)
    m = build_cluster(rng, 8, zones=2, existing_per_node=0)
    req = Affinity(
        pod_affinity_required=[
            PodAffinityTerm(
                selector=LabelSelector.from_match_labels({"app": "web"}),
                topology_key=ZONE,
            )
        ]
    )
    pods = [make_pod("web-seed", cpu="100m", labels={"app": "web"})]
    pods.append(make_pod("clingy", cpu="100m", labels={"app": "clingy"}, affinity=req))
    pods += [make_pod(f"web-{i}", cpu="100m", labels={"app": "web"}) for i in range(10)]
    pctx = PriorityContext(m, hard_pod_affinity_weight=40)
    backend = assert_parity(pods, m, pctx)
    _assert_all_kernel(backend, 12)


def test_parity_volume_disk_conflict_and_limits():
    from kubernetes_tpu.scheduler.predicates import VOLUME_COUNT_LIMITS

    rng = random.Random(25)
    m = build_cluster(rng, 8, zones=2, existing_per_node=0)
    pods = []
    for i in range(40):
        if i % 4 == 0:
            # exclusive EBS disk: two pods sharing an id conflict
            pods.append(
                make_pod(
                    f"ebs-{i}", cpu="50m",
                    volumes=[Volume(name="v", disk_id=f"ebs-{i % 6}", disk_kind="aws-ebs")],
                )
            )
        elif i % 4 == 1:
            # read-only gce-pd: sharable across pods
            pods.append(
                make_pod(
                    f"pd-ro-{i}", cpu="50m",
                    volumes=[Volume(name="v", disk_id="pd-shared", disk_kind="gce-pd", read_only=True)],
                )
            )
        elif i % 4 == 2:
            # writable gce-pd: NOT sharable
            pods.append(
                make_pod(
                    f"pd-rw-{i}", cpu="50m",
                    volumes=[Volume(name="v", disk_id=f"pd-rw-{i % 5}", disk_kind="gce-pd")],
                )
            )
        else:
            pods.append(make_pod(f"plain-{i}", cpu="100m", memory="128Mi"))
    backend = assert_parity(pods, m, PriorityContext(m))
    _assert_all_kernel(backend, 40)


def test_parity_max_volume_count_enforced():
    # one tiny node; azure-disk limit is 16: the 17th distinct disk pod must
    # fail on both paths
    m = {}
    node = make_node("only", cpu="64", memory="128Gi", pods=110)
    m["only"] = NodeInfo(node)
    pods = [
        make_pod(
            f"az-{i}", cpu="10m",
            volumes=[Volume(name="v", disk_id=f"az-{i}", disk_kind="azure-disk")],
        )
        for i in range(18)
    ]
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo)
    got = backend.schedule_batch(pods, m, PriorityContext(m))
    want = oracle_batch(pods, m, PriorityContext(m), GenericScheduler())
    assert got == want
    assert got.count(None) == 2  # 16 fit, 2 spill


def test_parity_pvc_zone_and_node_affinity():
    from kubernetes_tpu.api import PersistentVolume, PersistentVolumeClaim
    from kubernetes_tpu.api.selectors import NodeSelector, NodeSelectorTerm, Requirement

    rng = random.Random(26)
    m = build_cluster(rng, 9, zones=3, existing_per_node=0)
    names = sorted(m.keys())
    pvs = {
        "pv-z1": PersistentVolume(meta=ObjectMeta(name="pv-z1"), zone="zone-1", phase="Bound"),
        "pv-local": PersistentVolume(
            meta=ObjectMeta(name="pv-local"),
            phase="Bound",
            node_affinity=NodeSelector(
                terms=[NodeSelectorTerm(match_expressions=[
                    Requirement("kubernetes.io/hostname", "In", [names[4]])
                ])]
            ),
        ),
    }
    pvcs = {
        "default/claim-z1": PersistentVolumeClaim(
            meta=ObjectMeta(name="claim-z1"), volume_name="pv-z1", phase="Bound"
        ),
        "default/claim-local": PersistentVolumeClaim(
            meta=ObjectMeta(name="claim-local"), volume_name="pv-local", phase="Bound"
        ),
        "default/claim-unbound": PersistentVolumeClaim(meta=ObjectMeta(name="claim-unbound")),
    }
    pctx = PriorityContext(m, pvcs=pvcs, pvs=pvs)
    pods = []
    for i in range(24):
        if i % 4 == 0:
            pods.append(make_pod(f"zonal-{i}", cpu="50m",
                                 volumes=[Volume(name="v", pvc_name="claim-z1")]))
        elif i % 4 == 1:
            pods.append(make_pod(f"local-{i}", cpu="50m",
                                 volumes=[Volume(name="v", pvc_name="claim-local")]))
        elif i % 4 == 2:
            pods.append(make_pod(f"lost-{i}", cpu="50m",
                                 volumes=[Volume(name="v", pvc_name="claim-unbound")]))
        else:
            pods.append(make_pod(f"plain-{i}", cpu="100m"))
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo)
    got = backend.schedule_batch(pods, m, pctx)
    want = oracle_batch(pods, m, pctx, GenericScheduler())
    assert got == want
    # zonal pods in zone-1, local pods on names[4], unbound-claim pods fail
    for pod, node in zip(pods, got):
        if pod.meta.name.startswith("zonal"):
            assert m[node].node.meta.labels[ZONE] == "zone-1"
        elif pod.meta.name.startswith("local"):
            assert node == names[4]
        elif pod.meta.name.startswith("lost"):
            assert node is None


def test_parity_large_randomized_with_affinity_and_volumes():
    # the honest mixed workload: ~20% affinity-bearing, ~10% volume-bearing
    rng = random.Random(27)
    m = build_cluster(rng, 40, zones=4, tainted_frac=0.1, existing_per_node=2)
    svcs = [Service(meta=ObjectMeta(name=a), selector={"app": a}) for a in ("web", "db")]
    pctx = PriorityContext(m, services=svcs)
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
    for i in range(300):
        r = rng.random()
        if r < 0.1:
            pods.append(make_pod(f"soft-{i}", cpu="100m", labels={"app": "web"}, affinity=soft))
        elif r < 0.2:
            pods.append(make_pod(f"lonely-{i}", cpu="100m", labels={"app": "lonely"}, affinity=anti))
        elif r < 0.3:
            pods.append(
                make_pod(
                    f"vol-{i}", cpu="100m",
                    volumes=[Volume(name="v", disk_id=f"pd-{rng.randrange(30)}",
                                    disk_kind=rng.choice(["gce-pd", "aws-ebs"]))],
                )
            )
        else:
            t = rng.choice([
                dict(cpu="100m", memory="128Mi", labels={"app": "web"}),
                dict(cpu="500m", memory="512Mi", labels={"app": "db"}),
            ])
            pods.append(make_pod(f"plain-{i}", **t))
    backend = assert_parity(pods, m, pctx)
    _assert_all_kernel(backend, 300)


def test_prefix_parity_gate_small_scale():
    """chip_smoke.run_prefix_parity: the oracle replaying the first k pods
    of the batch's recorded drain order matches the kernel's first k
    assignments exactly (prefix-closure of sequential greedy)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    import chip_smoke

    backend_res = chip_smoke._schedule_cluster(
        chip_smoke._mixed_workload(80, 600, 3))
    assert len(backend_res["batch_order"]) == 600
    gate = chip_smoke.run_prefix_parity(backend_res, 80, 600, seed=3, k=150)
    assert gate["checked"] == 150
    assert gate["mismatches"] == 0, gate["sample"]


def test_build_static_row_cache_equivalence(monkeypatch):
    """The interaction-key row cache must be invisible: build_static with
    the cache ON produces arrays IDENTICAL to a full per-signature sweep
    (cache OFF) — including prefer-avoid controller refs and annotated
    nodes, the fragmentation-prone corner (r4 review)."""
    import numpy as np

    import kubernetes_tpu.models.snapshot as snap
    from kubernetes_tpu.scheduler.priorities import PREFER_AVOID_PODS_ANNOTATION

    rng = random.Random(11)
    m = build_cluster(rng, 40, zones=3)
    # one node prefers to avoid pods of controller "rs-avoided"
    first = m[sorted(m)[0]].node
    first.meta.annotations[PREFER_AVOID_PODS_ANNOTATION] = "uid-avoided"
    pods = make_batch(rng, 200)
    # owner refs: one avoided controller, several benign distinct ones
    for i, p in enumerate(pods[:40]):
        uid = "uid-avoided" if i % 4 == 0 else f"uid-{i}"
        p.meta.owner_references = [OwnerReference(
            kind="ReplicaSet", name=f"rs{i}", uid=uid, controller=True)]
    pctx = PriorityContext(m)
    tz = Tensorizer(pad_multiple=64)

    monkeypatch.setattr(snap, "_DISABLE_ROW_CACHE", True)
    plain = tz.build_static(pods, m, pctx, prefer_avoid_weight=10000)
    monkeypatch.setattr(snap, "_DISABLE_ROW_CACHE", False)
    cached = tz.build_static(pods, m, pctx, prefer_avoid_weight=10000)

    for fieldname in ("static_ok", "node_aff_raw", "taint_intol_raw",
                      "static_score", "interpod_raw"):
        a = getattr(plain, fieldname)
        b = getattr(cached, fieldname)
        assert np.array_equal(a, b), f"{fieldname} diverged under the cache"
