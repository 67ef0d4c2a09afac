"""A kernel segment's results go onto the working snapshot by node and by
signature (ISSUE 25): one clone and one aggregate add per touched node,
one host-state ingest per segment.

The per-pod calls (``NodeInfo.add_pod_counted``, ``HostBatchState.add_pod``)
are the reference, kept here: the batched calls must leave every field as
the per-pod calls in pod order leave it, ``generation`` and index order
included, and the backend's bindings, tie counter, commit entries and final
host state must not change."""

import random

import pytest

from kubernetes_tpu.api import (
    Affinity,
    LabelSelector,
    PodAffinityTerm,
    Volume,
)
from kubernetes_tpu.models.snapshot import (
    HostBatchState,
    _disk_refs,
    pod_signature_key,
)
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, PriorityContext
from kubernetes_tpu.scheduler.nodeinfo import NodeInfo, pod_has_affinity
from kubernetes_tpu.scheduler.units import (
    ResourceVec,
    pod_nonzero_request_vec,
    pod_request_vec,
)
from kubernetes_tpu.testutil import make_node, make_pod
from kubernetes_tpu.utils import tracing

from tests.test_parity import build_cluster, oracle_batch

HOSTNAME = "kubernetes.io/hostname"


def _anti(app="lonely"):
    return Affinity(pod_anti_affinity_required=[PodAffinityTerm(
        selector=LabelSelector.from_match_labels({"app": app}),
        topology_key=HOSTNAME)])


def _gce(disk_id, read_only=False):
    return Volume(name=f"v-{disk_id}", disk_kind="gce-pd", disk_id=disk_id,
                  read_only=read_only)


def _ebs(disk_id):
    return Volume(name=f"v-{disk_id}", disk_kind="aws-ebs", disk_id=disk_id)


# templates: name -> make_pod kwargs.  Pods of one template share a
# scheduling signature; direct disks are NOT in the signature, so the
# "disk-*" templates share theirs with a disk-less twin
TEMPLATES = {
    "plain": dict(cpu="100m", memory="128Mi", labels={"app": "web"}),
    "odd": dict(cpu="257m", memory="513Mi", labels={"app": "db"}),
    "affinity": dict(cpu="100m", memory="64Mi", labels={"app": "lonely"},
                     affinity=_anti()),
    "port": dict(cpu="50m", memory="64Mi", labels={"app": "edge"},
                 host_ports=[8080]),
    "ports2": dict(cpu="50m", memory="64Mi", labels={"app": "edge"},
                   host_ports=[8080, 9090]),
    "gpu": dict(cpu="1", memory="1Gi", gpu=1, labels={"app": "train"}),
    "bare": dict(),
    "disk-ro": dict(cpu="100m", memory="128Mi", labels={"app": "web"},
                    volumes=[_gce("shared", read_only=True)]),
    "disk-rw": dict(cpu="100m", memory="128Mi", labels={"app": "web"},
                    volumes=[_ebs("mine")]),
    "disk-two": dict(cpu="100m", memory="128Mi", labels={"app": "web"},
                     volumes=[_gce("shared", read_only=True), _ebs("other")]),
}


def _pods(kinds, tag="p"):
    return [make_pod(f"{tag}-{k:03d}-{kind}", **TEMPLATES[kind])
            for k, kind in enumerate(kinds)]


def _info_fields(info: NodeInfo) -> dict:
    return {
        "pods": [p.meta.key for p in info.pods],
        "pod_ids": [id(p) for p in info.pods],
        "pods_with_affinity": [p.meta.key for p in info.pods_with_affinity],
        "requested": list(info.requested.units),
        "nonzero_requested": list(info.nonzero_requested.units),
        "used_ports": set(info.used_ports),
        "generation": info.generation,
    }


def _sum_vec(vecs) -> ResourceVec:
    total = ResourceVec()
    for v in vecs:
        total.add(v)
    return total


NODEINFO_CASES = {
    "plain": ["plain"] * 5,
    "two-signatures": ["plain", "odd", "plain", "odd", "odd"],
    "affinity-between-plain": ["plain", "affinity", "plain", "affinity"],
    "two-pods-share-a-host-port": ["port", "plain", "port"],
    "port-sets-overlap": ["ports2", "port", "plain"],
    "gpu-and-bare": ["gpu", "bare", "gpu"],
    "disks": ["disk-ro", "plain", "disk-rw", "disk-two"],
    "everything": ["plain", "affinity", "port", "odd", "disk-ro", "gpu",
                   "bare", "ports2", "affinity", "disk-rw"],
    "one-pod": ["odd"],
    "no-pods": [],
}


@pytest.mark.parametrize("kinds", NODEINFO_CASES.values(),
                         ids=NODEINFO_CASES.keys())
def test_add_pods_counted_equals_the_per_pod_calls(kinds):
    node = make_node("n0", cpu="64", memory="256Gi", pods=110)
    base = NodeInfo(node)
    for p in _pods(["plain", "port", "affinity"], tag="resident"):
        base.add_pod(p)
    pods = _pods(kinds)
    req = [pod_request_vec(p) for p in pods]
    nz = [pod_nonzero_request_vec(p) for p in pods]

    want = base.clone()
    for p, r, z in zip(pods, req, nz):
        want.add_pod_counted(p, r, z)

    got = base.clone()
    got.add_pods_counted(
        pods, _sum_vec(req), _sum_vec(nz),
        [p for p in pods if pod_has_affinity(p)],
        [port for p in pods for port in p.host_ports()])

    assert _info_fields(got) == _info_fields(want)
    # and the base was not written through the clone
    assert base.generation == 3 and len(base.pods) == 3


def _state_fields(hs: HostBatchState) -> dict:
    return {
        "pod_lids": list(hs.pod_lids),
        "pod_node_j": list(hs.pod_node_j),
        "pod_keys": list(hs.pod_keys),
        "pod_content": list(hs.pod_content),
        "pod_disks": list(hs.pod_disks),
        "node_pods": [dict(d) for d in hs.node_pods],
        "disk_locations": {k: {j: list(rc) for j, rc in v.items()}
                           for k, v in hs.disk_locations.items()},
        "nk_counts": hs.nk_counts.tolist(),
        "content_rc": dict(hs._content_rc),
        "node_j_array": hs.node_j_array().tolist(),
    }


def _host_cluster():
    """Four real nodes (one with a resident pod that mounts a disk) and one
    NodeInfo whose node object is gone: absent from ``node_index``."""
    infos = {f"n{j}": NodeInfo(make_node(f"n{j}", cpu="64", memory="256Gi"))
             for j in range(4)}
    infos["n1"].add_pod(make_pod("resident", labels={"app": "web"},
                                 volumes=[_gce("shared", read_only=True)],
                                 node_name="n1"))
    infos["gone"] = NodeInfo()
    return infos


# (template, node) per pod, in pod order; node None = unplaced (chosen -1)
HOSTSTATE_CASES = {
    "plain-round-robin": [("plain", f"n{k % 4}") for k in range(12)],
    "two-signatures-one-labelmap": [("plain", "n0"), ("disk-ro", "n0"),
                                    ("plain", "n1"), ("odd", "n1")],
    "read-only-disk-shared": [("disk-ro", "n1"), ("disk-ro", "n1"),
                              ("disk-ro", "n2")],
    "exclusive-disks": [("disk-rw", "n0"), ("plain", "n0"),
                        ("disk-two", "n3"), ("disk-two", "n1")],
    "unplaced-pods": [("plain", None), ("odd", "n2"), ("disk-rw", None),
                      ("plain", "n2"), ("odd", None)],
    "node-missing-from-node-index": [("plain", "gone"), ("plain", "n0"),
                                     ("disk-rw", "gone"), ("odd", "nowhere")],
    "everything": [("plain", "n0"), ("affinity", "n1"), ("disk-ro", "n1"),
                   ("port", None), ("odd", "gone"), ("disk-two", "n2"),
                   ("plain", "n3"), ("gpu", "n0"), ("bare", "n0"),
                   ("disk-rw", "n0"), ("affinity", "n2")],
    "nothing-placed": [("plain", None), ("odd", None)],
}


def _signature_groups(pods):
    gid: dict = {}
    return [gid.setdefault(pod_signature_key(p), len(gid)) for p in pods]


@pytest.mark.parametrize("already", [False, True],
                         ids=["fresh", "one-pod-already-ingested"])
@pytest.mark.parametrize("placed", HOSTSTATE_CASES.values(),
                         ids=HOSTSTATE_CASES.keys())
def test_host_state_add_pods_equals_add_pod(placed, already):
    pods = _pods([kind for kind, _ in placed])
    names = [name for _, name in placed]
    want, got = HostBatchState(_host_cluster()), HostBatchState(_host_cluster())
    try:
        if already:
            # the first placed pod is there before the segment's results
            # land (an earlier segment of the batch put it there)
            k = next((k for k, n in enumerate(names) if n in want.node_index),
                     None)
            if k is not None:
                want.add_pod(pods[k], names[k])
                got.add_pod(pods[k], names[k])
        for pod, name in zip(pods, names):
            if name is not None:
                want.add_pod(pod, name)
        got.add_pods(pods, [p.meta.key for p in pods], names,
                     _signature_groups(pods),
                     [bool(_disk_refs(p)) for p in pods])
        assert _state_fields(got) == _state_fields(want)
    finally:
        want.close()
        got.close()


# -- the backend: same results, same host state, snapshot untouched ---------


def _mixed_batch(rng, n):
    kinds = list(TEMPLATES)
    pods = []
    for k in range(n):
        kind = rng.choice(kinds)
        kw = dict(TEMPLATES[kind])
        if kind.startswith("disk-"):
            # a few distinct disks, so some are shared and some conflict
            kw["volumes"] = [_gce(f"ro-{rng.randrange(3)}", read_only=True)
                             if kind == "disk-ro"
                             else _ebs(f"rw-{rng.randrange(6)}")]
        pods.append(make_pod(f"pend-{k:04d}-{kind}", **kw))
    return pods


def _per_pod_add_pods_counted(self, pods, req_sum, nz_sum, affinity_pods,
                              ports):
    """The tree before the change, per pod, reading each pod itself."""
    for pod in pods:
        self.add_pod_counted(pod, pod_request_vec(pod),
                             pod_nonzero_request_vec(pod))


def _per_pod_add_pods(self, pods, keys, node_names, groups, has_disks):
    for pod, name in zip(pods, node_names):
        if name is not None:
            self.add_pod(pod, name)


def _run_batch(monkeypatch, backend_kw, per_pod_reference: bool):
    rng = random.Random(25)
    node_info_map = build_cluster(rng, 24, zones=3, tainted_frac=0.1,
                                  existing_per_node=2)
    # one NodeInfo without a node object rides along, as after a node
    # delete with pods still draining
    node_info_map["ghost"] = NodeInfo()
    pods = _mixed_batch(rng, 90)
    before = {n: _info_fields(i) for n, i in node_info_map.items()}
    identity = dict(node_info_map)

    clones: dict = {}
    real_clone = NodeInfo.clone

    def recording_clone(self):
        c = real_clone(self)
        clones[self.node.meta.name] = c
        return c

    with monkeypatch.context() as m:
        m.setattr(NodeInfo, "clone", recording_clone)
        if per_pod_reference:
            m.setattr(NodeInfo, "add_pods_counted", _per_pod_add_pods_counted)
            m.setattr(HostBatchState, "add_pods", _per_pod_add_pods)
        algo = GenericScheduler()
        backend = TPUBatchBackend(algorithm=algo, max_segment_pods=32,
                                  **backend_kw)
        segments: list = []
        got = backend.schedule_batch(
            pods, node_info_map, PriorityContext(node_info_map),
            on_segment=lambda entries: segments.append(
                [(p.meta.key, n, None if r is None else list(r.units),
                  None if z is None else list(z.units))
                 for p, n, r, z in entries]))
    # the snapshot passed in is untouched: same objects, same fields
    assert node_info_map == identity
    assert {n: _info_fields(i) for n, i in node_info_map.items()} == before
    out = {
        "assignments": got,
        "round_robin": algo._round_robin,
        "segments": segments,
        "host_state": _state_fields(backend._host_state),
        "working": {n: {k: v for k, v in _info_fields(c).items()
                        if k != "pod_ids"} for n, c in clones.items()},
        "stats": dict(backend.stats),
        "pods": pods,
        "node_info_map": node_info_map,
    }
    backend._host_state.close()
    return out


RUNGS = {
    "xla-device-loop": dict(),
    "xla-plain-scan": dict(frontier=False),
    "xla-chunked-compacting": dict(frontier_chunk=8, frontier_min_width=8,
                                   frontier_device_loop=False),
    "pallas-interpret": dict(kernel_impl="pallas"),
}


@pytest.mark.timeout(300)
@pytest.mark.parametrize("backend_kw", RUNGS.values(), ids=RUNGS.keys())
def test_three_segments_of_mixed_pods_place_as_the_per_pod_loop_did(
        monkeypatch, backend_kw):
    got = _run_batch(monkeypatch, backend_kw, per_pod_reference=False)
    want = _run_batch(monkeypatch, backend_kw, per_pod_reference=True)
    assert got["stats"]["segments"] >= 3
    assert got["stats"]["oracle_pods"] == 0
    for field in ("assignments", "round_robin", "segments", "host_state",
                  "working"):
        assert got[field] == want[field], field
    # every kind of pod was placed, so the batched calls carried affinity
    # pods, host ports and disks
    placed = {p.meta.name.rsplit("-", 1)[-1]
              for p, n in zip(got["pods"], got["assignments"]) if n}
    assert {"affinity", "port", "ro", "rw", "plain"} <= placed
    assert any(c["pods_with_affinity"] for c in got["working"].values())
    assert any(c["used_ports"] for c in got["working"].values())
    assert got["host_state"]["disk_locations"]
    # and the sequential oracle agrees, tie counter included
    algo = GenericScheduler()
    m = got["node_info_map"]
    assert got["assignments"] == oracle_batch(
        got["pods"], m, PriorityContext(m), algo)
    assert got["round_robin"] == algo._round_robin
    n_placed = sum(1 for n in got["assignments"] if n)
    assert got["stats"]["place_batched_pods"] == n_placed
    assert got["stats"]["kernel_pods"] == len(got["pods"])


# -- the shape of the work --------------------------------------------------


@pytest.mark.timeout(120)
def test_a_plain_segment_makes_one_clone_and_one_add_per_touched_node(
        monkeypatch):
    """One ``clone()`` per touched node, no per-pod ``add_pod_counted`` or
    ``HostBatchState.add_pod``; the ``place`` span says how many nodes took
    a batched call and how many groups the segment had."""
    nodes = {f"n{j:02d}": NodeInfo(make_node(f"n{j:02d}", cpu="64",
                                             memory="256Gi", pods=110))
             for j in range(8)}
    pods = _pods(["plain", "odd", "plain", "bare"] * 25)

    calls = {"clone": 0, "add_pods_counted": 0, "add_pods": 0}

    def counting(owner, name):
        real = getattr(owner, name)

        def wrapper(self, *a, **kw):
            calls[name] += 1
            return real(self, *a, **kw)
        monkeypatch.setattr(owner, name, wrapper)

    def forbidden(name):
        def wrapper(self, *a, **kw):
            raise AssertionError(f"per-pod {name} on the kernel path")
        return wrapper

    counting(NodeInfo, "clone")
    counting(NodeInfo, "add_pods_counted")
    counting(HostBatchState, "add_pods")
    monkeypatch.setattr(NodeInfo, "add_pod_counted",
                        forbidden("add_pod_counted"))
    monkeypatch.setattr(HostBatchState, "add_pod", forbidden("add_pod"))

    tr = tracing.enable()
    try:
        backend = TPUBatchBackend(algorithm=GenericScheduler())
        with tr.span("wave", cat="wave") as root:
            got = backend.schedule_batch(pods, nodes, PriorityContext(nodes))
    finally:
        tracing.disable()
        if backend._host_state is not None:
            backend._host_state.close()
    assert all(got)
    touched = len(set(got))
    assert touched == 8
    assert calls == {"clone": touched, "add_pods_counted": touched,
                     "add_pods": 1}
    place = [c for c in root.children if c.name == "place"]
    assert [s.attrs for s in place] == [
        {"pods": 100, "cloned_nodes": touched, "nodes": touched, "groups": 3}]
    assert backend.stats["place_batched_pods"] == 100
    assert backend.stats["kernel_pods"] == 100
    # the reader the benchmark has still reads the span: dur over its pods
    assert place[0].attrs["pods"] == len(pods)


@pytest.mark.timeout(120)
def test_the_oracle_path_still_places_pod_by_pod(monkeypatch):
    """An oracle segment has its results one at a time and reads the
    working map between them: it keeps the per-pod ``apply``."""
    nodes = {f"n{j}": NodeInfo(make_node(f"n{j}", cpu="4", memory="8Gi"))
             for j in range(3)}
    pods = _pods(["plain", "odd"] * 4)
    backend = TPUBatchBackend(algorithm=GenericScheduler())
    monkeypatch.setattr(backend, "_config_supported", lambda: None)
    got = backend.schedule_batch(pods, nodes, PriorityContext(nodes))
    assert all(got)
    assert backend.stats["oracle_pods"] == 8
    assert backend.stats["place_batched_pods"] == 0
    assert all(i.generation == 0 for i in nodes.values())
