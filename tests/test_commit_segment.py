"""The commit consumes a segment's placement as ``place`` grouped it (ISSUE
30): by node for the cache, by column for the bind, the keys, the events.

One wave through ``schedule_pending_batch`` with everything a commit can
meet — a kernel segment, an oracle segment behind it in the same commit, an
unplaced pod, a second kernel segment on the same nodes and one binding the
store refuses — must give the bindings, cache state, requeues and events
(types, reasons, order) the per-pod commit gave: stated here outright, and
equal to a run whose segments arrive stripped of their groups."""

import pytest

from kubernetes_tpu.api import BindingColumns, Volume
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
from kubernetes_tpu.scheduler.nodeinfo import PlacedSegment
from kubernetes_tpu.store import Store
from kubernetes_tpu.testutil import make_node, make_pod
from kubernetes_tpu.utils import tracing

UNPLACED, REFUSED, ORACLE = "p-03", "p-06", "p-10"


def _wave():
    pods = []
    for i in range(19):
        name = f"p-{i:02d}"
        if name == UNPLACED:
            pods.append(make_pod(name, cpu="999", memory="1Gi"))
        elif name == ORACLE:
            # more distinct disks than a kernel pod can carry: the
            # backend hands this one to the oracle, a segment of its own
            pods.append(make_pod(name, cpu="100m", memory="128Mi", volumes=[
                Volume(name=f"v{k}", disk_kind="gce-pd", disk_id=f"pd-{k}")
                for k in range(9)]))
        elif i % 5 == 2:
            pods.append(make_pod(name, cpu="50m", memory="64Mi",
                                 labels={"app": "edge"}, host_ports=[8080]))
        else:
            pods.append(make_pod(name, cpu=["100m", "257m"][i % 2],
                                 memory="128Mi", labels={"app": "web"}))
    return pods


def _run(strip_groups: bool):
    cs = Clientset(Store())
    for j in range(6):
        cs.nodes.create(make_node(f"n{j}", cpu="8", memory="16Gi", pods=30))
    for pod in _wave():
        cs.pods.create(pod)
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo)
    sched = Scheduler(cs, algorithm=algo, backend=backend)
    sched.start()

    commits: list = []
    schedule_batch = backend.schedule_batch

    def watched(pods, snapshot, pctx, on_segment=None, **kw):
        def seen(entries):
            commits.append((type(entries), getattr(entries, "grouped", 0),
                            [e[0].meta.name for e in entries]))
            on_segment(list(entries) if strip_groups else entries)
        return schedule_batch(pods, snapshot, pctx, on_segment=seen, **kw)

    backend.schedule_batch = watched
    events: list = []
    event_batch = sched._recorder.event_batch

    def recorded(items):
        items = list(items)
        events.extend((pod.meta.name, etype, reason) for pod, etype, reason, _
                      in items)
        event_batch(items)

    sched._recorder.event_batch = recorded
    bind_many = cs.pods.bind_many
    sent: list = []

    def sending(bindings):
        sent.append(bindings)
        return bind_many(bindings)

    cs.pods.bind_many = sending

    plan = FaultPlan().on("scheduler.bind", mode="drop", first_n=1,
                          match={"via": "bind_many", "pod": f"default/{REFUSED}"})
    tr = tracing.enable()
    try:
        with plan.armed():
            result = sched.schedule_pending_batch()
        wave = tr.ring[-1]
    finally:
        tracing.disable()
        backend._host_state.close()
    pods, _ = cs.pods.list()
    return {
        "result": result,
        "commits": commits,
        "sent": sent,
        "events": events,
        "bindings": {p.meta.name: p.spec.node_name or None for p in pods},
        "assumed": {key.split("/")[1]: (node, state) for key, (_, node, state)
                    in sched.cache._pod_states.items()},
        "nodes": {name: ([p.meta.name for p in info.pods],
                         list(info.requested.units),
                         list(info.nonzero_requested.units),
                         set(info.used_ports), info.generation)
                  for name, info in sched.cache._nodes.items()},
        "deadlines": sorted(k.split("/")[1]
                            for k in sched.cache._assume_deadlines),
        "queued": len(sched.queue),
        "backoff": sorted(k.split("/")[1] for k in sched.backoff._entries),
        "round_robin": algo._round_robin,
        "metrics": {name: getattr(sched.metrics, name).value for name in (
            "schedule_failures", "bind_failures", "bind_requeues",
            "assume_batched_pods")},
        "assume_spans": [s.attrs for s in wave.iter_spans()
                         if s.name == "commit.assume"],
        "commit_spans": [s.attrs for s in wave.iter_spans()
                         if s.name == "commit"],
    }


@pytest.fixture(scope="module")
def by_node():
    return _run(strip_groups=False)


@pytest.mark.timeout(300)
def test_the_wave_commits_in_two_calls_and_the_first_mixes_kernel_and_oracle(
        by_node):
    names = [f"p-{i:02d}" for i in range(19)]
    assert by_node["commits"] == [(PlacedSegment, 10, names[:11]),
                                  (PlacedSegment, 8, names[11:])]
    # the transport's own two columns, in pod order: plain lists of the
    # store's keys and the node names beside them, nothing wrapped
    sent = by_node["sent"]
    assert all(type(b) is BindingColumns for b in sent)
    assert [(len(b), len(b.keys), len(b.node_names)) for b in sent] == [
        (10, 10, 10), (8, 8, 8)]
    assert all(type(col) is list and all(type(v) is str for v in col)
               for b in sent for col in (b.keys, b.node_names))
    assert [key for b in sent for key in b.keys] == [
        f"default/{n}" for n in names if n != UNPLACED]
    assert {node for b in sent for node in b.node_names} <= {
        f"n{j}" for j in range(6)}


@pytest.mark.timeout(300)
def test_bindings_cache_requeues_and_events_are_the_per_pod_commits(by_node):
    got = by_node
    names = [f"p-{i:02d}" for i in range(19)]
    assert got["result"] == (17, 2)
    bound = {n: node for n, node in got["bindings"].items() if node}
    assert sorted(bound) == [n for n in names if n not in (UNPLACED, REFUSED)]
    # the cache holds exactly the bound pods, assumed on the node they
    # were bound to; the refused pod was forgotten, the unplaced never in
    assert got["assumed"] == {n: (node, "assumed") for n, node in bound.items()}
    assert got["deadlines"] == sorted(bound)
    on_nodes = {n: node for node, (pods, *_rest) in got["nodes"].items()
                for n in pods}
    assert on_nodes == bound
    assert sum(gen for *_f, gen in got["nodes"].values()) == 6 + 18 + 1
    # both segments wrote the same nodes
    assert all(len(pods) >= 2 for pods, *_rest in got["nodes"].values())
    assert any(ports for _p, _r, _z, ports, _g in got["nodes"].values())
    # one pod each: unschedulable and requeued, refused and requeued
    assert got["metrics"] == {"schedule_failures": 1, "bind_failures": 1,
                              "bind_requeues": 1, "assume_batched_pods": 17}
    assert got["backoff"] == sorted([UNPLACED, REFUSED])
    # events: per commit the unplaced first, then the placed in pod order
    first = [n for n in names[:11] if n != UNPLACED]
    assert got["events"] == (
        [(UNPLACED, "Warning", "FailedScheduling")]
        + [(n, "Warning", "FailedBinding") if n == REFUSED
           else (n, "Normal", "Scheduled") for n in first]
        + [(n, "Normal", "Scheduled") for n in names[11:]])


@pytest.mark.timeout(300)
def test_the_spans_say_how_much_of_the_assume_went_by_node(by_node):
    first, second = by_node["assume_spans"]
    # 9 kernel pods by node and the oracle's pod behind them, one at a time
    assert (first["pods"], first["batched"]) == (10, 9)
    assert (second["pods"], second["batched"]) == (8, 8)
    assert 1 <= first["nodes"] <= 6 and 1 <= second["nodes"] <= 6
    assert by_node["commit_spans"] == [{"pods": 11, "bound": 9},
                                       {"pods": 8, "bound": 8}]


@pytest.mark.timeout(300)
def test_segments_stripped_of_their_groups_commit_to_the_same_state(by_node):
    per_pod = _run(strip_groups=True)
    assert [s["batched"] for s in per_pod["assume_spans"]] == [0, 0]
    assert [s["nodes"] for s in per_pod["assume_spans"]] == [0, 0]
    assert per_pod["metrics"]["assume_batched_pods"] == 0
    for field in ("result", "sent", "events", "bindings", "assumed", "nodes",
                  "deadlines", "queued", "backoff", "round_robin",
                  "commit_spans"):
        assert per_pod[field] == by_node[field], field


def test_pod_client_bind_many_takes_key_and_node_columns():
    cs = Clientset(Store())
    cs.nodes.create(make_node("n0"))
    for name in ("a", "b", "c"):
        cs.pods.create(make_pod(name, cpu="100m", memory="64Mi"))
    assert cs.pods.bind_many(BindingColumns(
        ["default/a", "default/b", "default/missing", "c"],
        ["n0", "n0", "n0", "n0"])) == [None, None, "not found", "not found"]
    pods, _ = cs.pods.list()
    assert {p.meta.name: p.spec.node_name for p in pods} == {
        "a": "n0", "b": "n0", "c": ""}
    with pytest.raises(ValueError):
        cs.pods.bind_many(BindingColumns(["default/c"], []))
    assert cs.pods.get("c").spec.node_name == ""
