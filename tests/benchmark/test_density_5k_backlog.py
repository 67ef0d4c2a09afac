"""Cell ``density-5k.backlog`` (ISSUE 31): the rehearsal prints one correct
result object with the cell's three readers in the traced line; each reader
on hand-made facts; and a wave of several segments whose pod watch is cut
after every segment's bind binds as the plain reference does."""

import copy
import json
import os

import pytest

from benchmark import check, cluster, run
from tests.benchmark.test_rehearsal import ROOT, _run

CELL = "density-5k.backlog"
NEW = {"watch_framed_share": "%", "bind_rtt_worst_us_per_pod": "us/pod",
       "segments_per_wave": "segments"}


def test_the_cell_and_its_readers_are_listed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "density-5k", "backlog", 1)
    config = next(c for c in bench["configs"] if c["name"] == "density-5k")
    assert config["reduced"] == [] and config["file"].endswith("density-5k.json")
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert {n: m["unit"] for n, m in mine.items()} == NEW
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "bound_pods_per_s"
        assert m["source"] == "program_span"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_to_one_correct_result_object(trace):
    code, out, err = _run("benchmark.run", "--workload", CELL, "--seed",
                          str(2**31 + 31), "--seconds", "4", "--trace", trace,
                          "--rehearse-cpu", "80,500")
    assert code == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result["correct"] is True, err[-3000:]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["attempted"] == 500 and result["failed"] == 0
    metrics = result["metrics"]
    if trace == "0":
        assert set(metrics) == {"bound_pods_per_s", "setup_s"}
        return
    assert {n: metrics[n]["unit"] for n in NEW} == NEW
    # every bind of the rehearsal's one segment was confirmed from a frame
    assert metrics["watch_framed_share"]["value"] == 100.0
    assert metrics["segments_per_wave"]["value"] == 1.0
    assert metrics["bind_rtt_worst_us_per_pod"]["value"] > 0


def _s(name, dur=0.01, parent="wave-1", cat="phase", **attrs):
    return {"name": name, "cat": cat, "t0": 1.0, "t1": 1.0 + dur, "dur": dur,
            "self_s": dur, "attrs": attrs, "wave": 1, "parent": parent}


# a backlog window of one wave in three segments: 1,000 bound, the first
# segment's 400 confirmed from two frames, the rest line by line (no span)
FACTS = {"spans": [
    _s("wave-1", 2.0, parent=None, cat="wave", pods=1100),
    _s("dispatch"), _s("dispatch"), _s("dispatch"),
    _s("commit", pods=450, bound=400), _s("commit", pods=450, bound=400),
    _s("commit", pods=200, bound=200),
    _s("remote.request", 0.004, parent="commit.bind", cat="client", items=400),
    _s("remote.request", 0.030, parent="commit.bind", cat="client", items=400),
    _s("remote.request", 0.003, parent="commit.bind", cat="client", items=200),
    _s("remote.request", 0.5, parent=None, cat="client"),      # a LIST
    _s("scheduler.confirm", parent=None, cat="ingest", kind="Pod", txn="bind_many-1",
       events=256, fallbacks=0),
    _s("scheduler.confirm", parent=None, cat="ingest", kind="Pod", txn="bind_many-1",
       events=144, fallbacks=0),
    # the event sink's frames are not pods
    _s("scheduler.confirm", parent=None, cat="ingest", kind="Event", events=900),
]}


@pytest.mark.parametrize("name,want,needs", [
    ("watch_framed_share", 40.0, ("commit",)),
    ("bind_rtt_worst_us_per_pod", 75.0, ("remote.request",)),
    ("segments_per_wave", 3.0, ("dispatch",)),
])
def test_new_reader_reads_what_it_says_and_nothing_without_its_span(name, want, needs):
    assert run.read_layer_metric(name, FACTS) == pytest.approx(want, rel=1e-9)
    without = dict(FACTS, spans=[s for s in FACTS["spans"] if s["name"] not in needs])
    assert run.read_layer_metric(name, without) is None
    assert run.read_layer_metric(name, {"spans": []}) is None


def test_watch_framed_share_is_0_with_no_frame_and_100_with_every_bind_framed():
    lines_only = [s for s in FACTS["spans"] if s["name"] != "scheduler.confirm"]
    assert run.read_layer_metric("watch_framed_share", {"spans": lines_only}) == 0.0
    rest = [_s("scheduler.confirm", parent=None, cat="ingest", kind="Pod", events=n)
            for n in (400, 200)]
    full = run.read_layer_metric("watch_framed_share",
                                 {"spans": FACTS["spans"] + rest})
    assert full == 100.0       # a backlog's only pod frames are its binds'


def _cut(inf):
    """The stream ends: what was not read is dropped, a new watch resumes
    from the informer's bookmark (as ``RemoteWatch`` does after 5 s)."""
    old = inf._watch
    inf._watch = inf._watch_from(inf.last_revision)
    old.stop()


@pytest.mark.parametrize("nodes,pods,seed", [(40, 1_200, 31), (24, 900, 2**31 + 7)])
def test_a_wave_whose_watch_is_cut_between_segments_binds_as_the_reference(
        monkeypatch, nodes, pods, seed):
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.ops import TPUBatchBackend
    from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.store import frames as frames_mod

    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", 64)
    config = copy.deepcopy(cluster.load_config("density-5k"))
    config["nodes"]["count"], config["pods"]["count"] = nodes, pods
    world = cluster.World(config, seed, {"preload": pods, "window_pods": 0})
    store = Store()
    for kind, objs in (("Node", world.nodes), ("Service", world.services)):
        for obj in objs:
            store.create(kind, copy.deepcopy(obj))
    store.create_many("Pod", copy.deepcopy(world.preload))
    cs = Clientset(store)
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo, max_segment_pods=256)
    sched = Scheduler(cs, algorithm=algo, backend=backend, emit_events=False)
    sched.start()
    inf = sched.informers.informer("Pod")
    drains, drain = [], sched.queue.drain

    def recording_drain(max_n=None):
        got = drain(max_n)
        drains.append([p.meta.key for p in got])
        return got

    sched.queue.drain = recording_drain
    bind_many, binds = cs.pods.bind_many, []

    def bind_then_cut(bindings):
        out = bind_many(bindings)
        binds.append(len(bindings))
        _cut(inf)        # this segment's confirm frames were never read
        return out

    cs.pods.bind_many = bind_then_cut
    lines = sched.metrics.watch_line_events.value
    frames0 = sched.metrics.watch_frame_events.value
    bound, failed = sched.schedule_pending_batch()
    sched.pump()
    assert len(binds) == backend.stats["segments"] >= 3
    assert bound == sum(binds) and bound + failed == pods and failed > 0
    # every confirmation came from a replayed frame, by the revision fence
    assert sched.metrics.watch_frame_events.value - frames0 == bound
    assert sched.metrics.watch_line_events.value == lines
    assert sched.metrics.confirm_fallbacks.value == 0
    bindings = {cluster.pod_key(p): p["spec"].get("nodeName") or None
                for p in store.list("Pod")[0]}
    numbers = check.compare(world, drains, bindings,
                            {k for k, v in bindings.items() if v is None},
                            int(algo._round_robin), 0, seed=seed)
    assert check.verdict(numbers), numbers
    assert numbers["bound"] == bound and numbers["scored"] == pods
