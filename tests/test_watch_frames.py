"""Batched watch frames (ISSUE 6): column-packed event delivery and
one-lock wave application, store → informer → confirm.

The contract under test, layer by layer:

- **store**: a correlated batch txn (``create_many``/``bind_many``) fans
  out as ONE :class:`WatchFrame` to frame-aware watchers, and as the
  IDENTICAL per-event sequence (order, content, revisions) to everyone
  else; the wire form round-trips and broken columns fail loudly;
- **informer**: a frame applies to the cache under one lock hold with
  per-event semantics preserved exactly (handler callbacks, crash
  isolation, revision fencing, deliver/decode faults), safe under
  concurrent readers; a frame lost whole (``informer.apply_batch``)
  marks a gap that the existing relist path heals;
- **scheduler**: a bind-confirm frame confirms the whole wave against
  the frame's columns — identical end state to the per-pod confirm, with
  the revision fence falling back per-pod on any intervening write;
- **broadcaster**: frames inherit the EVENTS-budget accounting — an
  overflowing ``event_batch`` frames exactly the admitted events;
- **compaction**: the opt-in promote-and-drop-raw sweep releases pinned
  wire payloads without changing any observable value.
"""

from __future__ import annotations

import copy
import gc
import json
import threading
import time as _time
import tracemalloc

import pytest

from kubernetes_tpu import faults
from kubernetes_tpu.api import Binding, ObjectMeta
from kubernetes_tpu.api import lazy as lazy_mod
from kubernetes_tpu.api import types as api
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.client.informer import Handler, SharedInformer
from kubernetes_tpu.client.record import EventBroadcaster
from kubernetes_tpu.faults import FaultPlan
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
from kubernetes_tpu.store import Store
from kubernetes_tpu.store import frames as frames_mod
from kubernetes_tpu.store.frames import FRAME, FrameDecodeError, WatchFrame
from kubernetes_tpu.testutil import make_node, make_pod


def _drain(watch, n_items, timeout=2.0):
    out = []
    deadline = _time.monotonic() + timeout
    while len(out) < n_items and _time.monotonic() < deadline:
        ev = watch.get(timeout=0.05)
        if ev is not None:
            out.append(ev)
    return out


def _flatten(items):
    """(type, key, revision, object) rows for mixed event/frame lists."""
    rows = []
    for ev in items:
        if ev.type == FRAME:
            rows.extend((e.type, e.key, e.revision, e.object)
                        for e in ev.events())
        else:
            rows.append((ev.type, ev.key, ev.revision, ev.object))
    return rows


# ---------------------------------------------------------------------------
# store: frame fan-out ≡ per-event fan-out
# ---------------------------------------------------------------------------


def test_frame_roundtrip_equals_per_event_delivery():
    cs = Clientset(Store())
    framed = cs.store.watch("Pod", frames=True)
    plain = cs.store.watch("Pod")
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=f"p{i}",
                               node_name="n1") for i in range(3)])
    cs.pods.create(make_pod("solo", cpu="100m"))  # single: never framed

    framed_items = _drain(framed, 3)
    plain_items = _drain(plain, 8)
    # the frame-aware watcher got 2 frames + 1 event; the per-event one 8
    assert [it.type for it in framed_items] == [FRAME, FRAME, "ADDED"]
    assert [len(it) for it in framed_items[:2]] == [4, 3]
    assert len(plain_items) == 8
    # expansion reproduces the exact per-event sequence: order, content,
    # revisions — nothing framed is lost or reordered
    assert _flatten(framed_items) == _flatten(plain_items)
    framed.stop()
    plain.stop()


def test_bind_frame_carries_prev_revision_and_node_columns():
    cs = Clientset(Store())
    w = cs.store.watch("Pod", frames=True)
    created = cs.pods.create_many(
        [make_pod(f"p{i}", cpu="100m") for i in range(3)])
    pre_revs = [c.meta.resource_version for c in created]
    _drain(w, 1)  # the ADDED frame
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=f"p{i}",
                               node_name=f"n{i}") for i in range(3)])
    frame = _drain(w, 1)[0]
    assert frame.type == FRAME and frame.kind == "Pod"
    assert frame.types == ["MODIFIED"] * 3
    assert frame.node_names == ["n0", "n1", "n2"]
    # the columnar-confirm fence: prev revision == the revision each pod
    # held when the bind CAS ran (here: its creation revision)
    assert frame.prev_revisions == pre_revs
    w.stop()


def test_frame_wire_roundtrip_and_validation():
    cs = Clientset(Store())
    w = cs.store.watch("Pod", frames=True)
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(3)])
    frame = _drain(w, 1)[0]
    wire = json.loads(json.dumps(frame.to_wire()))
    back = WatchFrame.from_wire(wire)
    assert (back.kind, back.types, back.keys, back.revisions) == (
        frame.kind, frame.types, frame.keys, frame.revisions)
    assert back.objects == frame.objects
    assert back.revision == frame.revision
    w.stop()

    # broken columns fail loudly — the consumer turns this into a gap
    bad = dict(wire)
    bad["keys"] = wire["keys"][:-1]
    with pytest.raises(FrameDecodeError):
        WatchFrame.from_wire(bad)
    bad = dict(wire)
    bad["revisions"] = list(reversed(wire["revisions"]))
    with pytest.raises(FrameDecodeError):
        WatchFrame.from_wire(bad)
    with pytest.raises(FrameDecodeError):
        WatchFrame.from_wire({"type": FRAME, "kind": "Pod", "types": [],
                              "keys": [], "revisions": [], "objects": []})
    bad = dict(wire)
    bad["objects"] = ["not-a-dict"] * len(wire["objects"])
    with pytest.raises(FrameDecodeError):
        WatchFrame.from_wire(bad)


def test_frames_seam_off_restores_per_event_everywhere(monkeypatch):
    monkeypatch.setattr(frames_mod, "ENABLED", False)
    cs = Clientset(Store())
    w = cs.store.watch("Pod", frames=True)  # opted in, but the seam is off
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(3)])
    items = _drain(w, 3)
    assert [it.type for it in items] == ["ADDED"] * 3
    w.stop()


# ---------------------------------------------------------------------------
# informer: batch apply ≡ per-event apply
# ---------------------------------------------------------------------------


def _recording_handler(log):
    return Handler(
        on_add=lambda o: log.append(("add", o.meta.key)),
        on_update=lambda old, new: log.append(("update", new.meta.key)),
        on_delete=lambda o: log.append(("del", o.meta.key)),
    )


def _per_event_informer(client):
    """An informer forced onto the per-event watch path (the pre-frame
    consumer shape) — the equivalence oracle."""
    inf = SharedInformer(client)
    inf._watch_from = lambda rev: client.watch(from_revision=rev)
    return inf


def test_informer_batch_apply_matches_per_event():
    cs = Clientset(Store())
    framed_log, plain_log = [], []
    framed = SharedInformer(Clientset(cs.store).pods)
    plain = _per_event_informer(Clientset(cs.store).pods)
    framed.add_handler(_recording_handler(framed_log))
    plain.add_handler(_recording_handler(plain_log))
    framed.start_manual()
    plain.start_manual()
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(6)])
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=f"p{i}",
                               node_name="n1") for i in range(6)])
    cs.pods.delete("p5")
    framed.pump()
    plain.pump()
    assert framed.stats["frames"] == 2 and framed.stats["frame_events"] == 12
    assert plain.stats["frames"] == 0
    # identical handler sequences and identical caches
    assert framed_log == plain_log
    assert framed.keys() == plain.keys()
    assert framed.last_revision == plain.last_revision
    for key in framed.keys():
        assert framed.get(key).to_dict() == plain.get(key).to_dict()


def test_on_batch_handler_receives_frame_and_crashes_isolated():
    cs = Clientset(Store())
    inf = SharedInformer(cs.pods)
    batches, peer = [], []
    inf.add_handler(Handler(on_batch=lambda f, d: (_ for _ in ()).throw(
        RuntimeError("boom in batch handler"))))
    inf.add_handler(Handler(on_batch=lambda f, d: batches.append((f, d))))
    inf.add_handler(_recording_handler(peer))
    inf.start_manual()
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
    inf.pump()
    # the crashing batch handler is isolated; the batch-aware peer got
    # ONE call for the whole frame; the per-event peer got 4 callbacks
    assert inf.stats["handler_errors"] == 1
    assert len(batches) == 1
    frame, deltas = batches[0]
    assert frame.type == FRAME and len(deltas) == 4
    assert [d[0] for d in deltas] == ["ADDED"] * 4
    assert peer == [("add", f"default/p{i}") for i in range(4)]


def test_frame_revision_fence_drops_stale_frames():
    cs = Clientset(Store())
    inf = SharedInformer(cs.pods)
    inf.start_manual()
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(2)])
    inf.pump()
    fence = inf.last_revision
    stale = WatchFrame(
        "Pod", ["MODIFIED"], ["default/p0"], [fence],
        [{"metadata": {"name": "p0", "namespace": "default",
                       "resourceVersion": fence},
          "spec": {"nodeName": "bogus"}}])
    inf._apply_batch(stale)  # a straggler a relist already superseded
    assert inf.get("default/p0").spec.node_name == ""
    assert inf.last_revision == fence
    assert inf.stats["frame_events"] == 2  # only the live frame's events


def test_per_event_faults_keep_their_semantics_inside_frames():
    """informer.deliver drop and informer.decode error hit ONE delta of a
    frame — that delta is lost (counted, gap for decode), the rest of the
    frame applies."""
    cs = Clientset(Store())
    inf = SharedInformer(cs.pods)
    inf.start_manual()
    plan = FaultPlan(seed=1).on("informer.deliver", mode="drop", nth=2)
    with plan.armed():
        cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
        inf.pump()
    assert inf.stats["dropped_events"] == 1
    assert sorted(inf.keys()) == [f"default/p{i}" for i in (0, 2, 3)]
    plan = FaultPlan(seed=1).on("informer.decode", mode="error", nth=2)
    with plan.armed():
        cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(3)])
        inf.pump()
        assert inf.stats["decode_errors"] == 1
        assert inf.get("default/q1") is None  # that delta lost...
        assert inf.get("default/q2") is not None  # ...but not its peers
        inf.pump()  # gap-pending: relists and reconverges (incl. p1)
    assert inf.stats["relists"] >= 1
    assert inf.get("default/q1") is not None
    assert inf.get("default/p1") is not None


def test_apply_batch_fault_loses_frame_marks_gap_and_relist_heals():
    cs = Clientset(Store())
    inf = SharedInformer(cs.pods)
    inf.start_manual()
    plan = FaultPlan(seed=1).on("informer.apply_batch", mode="error", nth=1)
    with plan.armed():
        cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(5)])
        inf.pump()
        assert inf.stats["batch_errors"] == 1
        assert inf.keys() == []  # the whole frame lost as a unit
        inf.pump()  # gap-pending: this pump relists
    assert plan.fired["informer.apply_batch"] == 1
    assert inf.stats["relists"] >= 1
    assert sorted(inf.keys()) == [f"default/p{i}" for i in range(5)]


def test_batch_apply_under_concurrent_readers():
    cs = Clientset(Store())
    inf = SharedInformer(cs.pods)
    inf.start_manual()
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                for o in inf.list():
                    o.meta.key  # promote under concurrent batch applies
                inf.get("default/w0-p0")
                inf.keys()
            except Exception as e:  # noqa: BLE001 - the assertion target
                errors.append(e)
                return

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for w in range(20):
            cs.pods.create_many([make_pod(f"w{w}-p{i}", cpu="100m")
                                 for i in range(25)])
            inf.pump()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert not errors
    assert len(inf.keys()) == 500
    assert inf.stats["frames"] == 20


# ---------------------------------------------------------------------------
# scheduler: columnar confirm ≡ per-pod confirm
# ---------------------------------------------------------------------------


def _world(n_nodes=8, store=None):
    cs = Clientset(store or Store())
    for i in range(n_nodes):
        cs.nodes.create(make_node(f"n{i}", cpu="16", memory="32Gi", pods=110,
                                  labels={"kubernetes.io/hostname": f"n{i}"}))
    algo = GenericScheduler()
    sched = Scheduler(cs, algorithm=algo,
                      backend=TPUBatchBackend(algorithm=algo),
                      emit_events=False)
    sched.start()
    return cs, sched


def _cache_fingerprint(cache):
    """Everything the scheduler's decisions read from the cache."""
    states = {k: (v[1], v[2]) for k, v in cache._pod_states.items()}
    nodes = {}
    for name, info in cache._nodes.items():
        nodes[name] = (
            sorted(p.meta.key for p in info.pods),
            sorted(p.meta.key for p in info.pods_with_affinity),
            tuple(info.requested.units),
            tuple(info.nonzero_requested.units),
            sorted(info.used_ports),
        )
    return states, nodes


def _churn_wave(cs, sched, n_pods, prefix):
    cs.pods.create_many([make_pod(f"{prefix}-{i:04d}", cpu="100m",
                                  memory="128Mi") for i in range(n_pods)])
    sched.pump()
    bound, failed = sched.schedule_pending_batch()
    sched.pump()  # digest the bind-confirm frame (or events)
    return bound, failed


def test_columnar_confirm_equals_per_pod_confirm_on_a_wave(monkeypatch):
    # arm B: frames + columnar confirm
    cs_b, sched_b = _world()
    for w in range(3):
        assert _churn_wave(cs_b, sched_b, 50, f"w{w}") == (50, 0)
    # arm A: the per-event per-pod confirm oracle, same ops
    monkeypatch.setattr(frames_mod, "ENABLED", False)
    cs_a, sched_a = _world()
    for w in range(3):
        assert _churn_wave(cs_a, sched_a, 50, f"w{w}") == (50, 0)
    monkeypatch.undo()

    bind_b = {p.meta.key: p.spec.node_name for p in cs_b.pods.list()[0]}
    bind_a = {p.meta.key: p.spec.node_name for p in cs_a.pods.list()[0]}
    assert bind_b == bind_a and all(bind_b.values())
    states_b, nodes_b = _cache_fingerprint(sched_b.cache)
    states_a, nodes_a = _cache_fingerprint(sched_a.cache)
    assert states_b == states_a  # every wave confirmed to "bound"
    assert nodes_b == nodes_a
    # and the fast path actually ran: frames with zero fallbacks
    assert sched_b.metrics.watch_frames.value > 0
    assert sched_b.metrics.confirm_fallbacks.value == 0
    assert sched_a.metrics.watch_frames.value == 0


def test_confirm_falls_back_per_pod_on_intervening_write():
    cs, sched = _world(n_nodes=2)
    cs.pods.create(make_pod("a", cpu="100m", memory="128Mi"))
    cs.pods.create(make_pod("b", cpu="100m", memory="128Mi"))
    sched.pump()
    pods = {p.meta.name: p for p in sched.informers.informer("Pod").list()}
    sched.cache.assume_many([(pods["a"], "n0"), (pods["b"], "n0")])
    # an intervening label write bumps "a"'s revision AFTER the assume:
    # the frame's prev_revision no longer matches the assumed object
    def _label(d):
        d.setdefault("metadata", {}).setdefault("labels", {})["x"] = "y"
        return d
    cs.store.guaranteed_update("Pod", "default", "a", _label)
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=n,
                               node_name="n0") for n in ("a", "b")])
    sched.pump()
    # both confirmed bound either way — "a" through the per-pod compare
    states, _nodes = _cache_fingerprint(sched.cache)
    assert states == {"default/a": ("n0", "bound"),
                      "default/b": ("n0", "bound")}
    assert sched.metrics.confirm_fallbacks.value == 1
    info = sched.cache._nodes["n0"]
    assert sorted(p.meta.key for p in info.pods) == ["default/a", "default/b"]
    # the cache holds the POST-write API truth for the fallback pod
    cached = {p.meta.key: p for p in info.pods}
    assert cached["default/a"].meta.labels.get("x") == "y"


def test_confirm_wave_with_apply_batch_fault_heals_to_same_state():
    """The confirm frame is lost whole mid-wave: assumed pods stay
    assumed until the gap-driven relist delivers the API truth — then the
    cache matches the no-fault end state."""
    cs, sched = _world()
    cs.pods.create_many([make_pod(f"p{i:03d}", cpu="100m", memory="128Mi")
                         for i in range(30)])
    sched.pump()
    plan = FaultPlan(seed=7).on("informer.apply_batch", mode="error",
                                match={"kind": "Pod"}, nth=1)
    with plan.armed():
        bound, failed = sched.schedule_pending_batch()
        assert (bound, failed) == (30, 0)
        sched.pump()  # the confirm frame dies here...
        assert sched.informers.informer("Pod").stats["batch_errors"] == 1
        sched.pump()  # ...and the gap-driven relist heals
    states, _ = _cache_fingerprint(sched.cache)
    assert all(st == ("bound",) or st[1] == "bound"
               for st in states.values()), states
    bindings = {p.meta.key: p.spec.node_name for p in cs.pods.list()[0]}
    assert all(bindings.values())
    assert {k: v[0] for k, v in states.items()} == bindings


# ---------------------------------------------------------------------------
# remote: frames over the wire
# ---------------------------------------------------------------------------


def _wait(pred, timeout=10.0, interval=0.02):
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        if pred():
            return True
        _time.sleep(interval)
    return False


@pytest.fixture
def api_server():
    from kubernetes_tpu.apiserver import APIServer

    server = APIServer(Store())
    server.start()
    yield server
    server.stop()


def test_remote_frames_end_to_end(api_server):
    from kubernetes_tpu.client.remote import RemoteStore

    rs = RemoteStore(api_server.url, retry_backoff=0.005)
    cs = Clientset(api_server.store)
    inf = SharedInformer(Clientset(rs).pods, metrics=rs.metrics)
    inf.start_manual()
    # wait for the live stream: a batch committed BEFORE the watch
    # connects is replayed from the log per-event (by design)
    assert _wait(lambda: inf._watch._resp is not None)
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(5)])
    assert _wait(lambda: (inf.pump(), len(inf.list()))[-1] == 5)
    # the batch crossed the wire as ONE frame line
    assert inf.stats["frames"] >= 1
    assert inf.stats["frame_events"] >= 5
    # a per-event client against the same server sees plain events
    plain = _per_event_informer(Clientset(RemoteStore(api_server.url)).pods)
    plain.start_manual()
    assert _wait(lambda: plain._watch._resp is not None)
    cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(3)])
    assert _wait(lambda: (plain.pump(), len(plain.list()))[-1] == 8)
    assert plain.stats["frames"] == 0
    assert _wait(lambda: (inf.pump(), len(inf.list()))[-1] == 8)
    assert sorted(plain.keys()) == sorted(inf.keys())
    inf.stop()
    plain.stop()


def test_remote_frame_decode_failure_gaps_and_relist_heals(api_server):
    """The ISSUE 6 satellite: a mid-frame decode failure on
    remote.watch.stream is classified as a GAP (never a lost loop, never
    a partial apply) and the informer's relist reconverges the cache."""
    from kubernetes_tpu.client.remote import RemoteStore

    rs = RemoteStore(api_server.url, retry_backoff=0.005,
                     sleep=lambda s: _time.sleep(min(s, 0.02)))
    cs = Clientset(api_server.store)
    inf = SharedInformer(Clientset(rs).pods, metrics=rs.metrics)
    inf.start_manual()
    assert _wait(lambda: inf._watch._resp is not None)  # live stream up
    plan = FaultPlan(seed=3).on(
        "remote.watch.stream", mode="error", nth=1,
        match={"phase": "frame", "resource": "pods"})
    with plan.armed():
        cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
        # the frame dies in decode → GAP → the pump-driven relist heals
        assert _wait(lambda: (inf.pump(), len(inf.list()))[-1] == 4)
    assert plan.fired["remote.watch.stream"] == 1
    assert rs.metrics.watch_gaps.value >= 1
    assert inf.stats["relists"] >= 1
    assert sorted(inf.keys()) == [f"default/p{i}" for i in range(4)]
    inf.stop()


# ---------------------------------------------------------------------------
# broadcaster: frames meet the EVENTS budget
# ---------------------------------------------------------------------------


def test_event_batch_overflow_frames_exactly_the_admitted_events():
    cs = Clientset(Store())
    pods = [make_pod(f"p{i}", cpu="100m") for i in range(8)]
    b = EventBroadcaster(cs, max_queued=5)
    w = cs.store.watch("Event", frames=True)
    b.recorder("Pod").event_batch(
        [(p, "Normal", "Tick", f"msg-{i}") for i, p in enumerate(pods)])
    # bounds/overflow accounted in EVENTS: the batch truncated to room
    assert len(b) == 5 and b.dropped_overflow == 3
    b.flush()
    frame = w.get(timeout=1.0)
    # one correlated chunk → one create_many txn → ONE frame carrying
    # exactly the admitted events, in emit order
    assert frame.type == FRAME and frame.kind == "Event" and len(frame) == 5
    messages = [(o.get("spec") or o).get("message", "") for o in frame.objects]
    assert messages == [f"msg-{i}" for i in range(5)]
    assert b.correlator.stats["created"] == 5
    w.stop()


# ---------------------------------------------------------------------------
# bounded frames (ISSUE 28): a txn leaves in pieces of <= FRAME_MAX_ROWS
# ---------------------------------------------------------------------------

_BOUND = 4


@pytest.fixture
def small_bound(monkeypatch):
    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", _BOUND)


def _piece_lens(n, bound=_BOUND):
    """Row counts of the pieces an n-row txn leaves in."""
    return [min(bound, n - lo) for lo in range(0, n, bound)]


def _take_now(watch):
    """What the watcher's queue holds NOW — no waiting: a piece put
    after the txn's call returned would be missing."""
    out = []
    while True:
        ev = watch.get(timeout=0)
        if ev is None:
            return out
        out.append(ev)


@pytest.mark.parametrize("op", ["create_many", "bind_many"])
@pytest.mark.parametrize("n", [2, 4, 5, 9, 12])
def test_txn_leaves_in_pieces_of_at_most_the_bound(small_bound, op, n):
    from kubernetes_tpu.utils.metrics import DEFAULT_STORE_METRICS

    cs = Clientset(Store())
    pods = [make_pod(f"p{i:03d}", cpu="100m") for i in range(n)]
    pre = None
    if op == "bind_many":  # the bind txn is under test: watch after these
        pre = [c.meta.resource_version for c in cs.pods.create_many(pods)]
    framed = cs.store.watch("Pod", frames=True)
    second = cs.store.watch("Pod", frames=True)
    plain = cs.store.watch("Pod")
    c0 = DEFAULT_STORE_METRICS.watch_frames.value
    if op == "create_many":
        cs.pods.create_many(pods)
    else:
        assert cs.pods.bind_many([
            Binding(pod_namespace="default", pod_name=p.meta.name,
                    node_name=f"n{i % 2}")
            for i, p in enumerate(pods)]) == [None] * n
    # every piece is on the queue when the txn's call has returned
    got = _take_now(framed)
    assert all(g.type == FRAME for g in got)
    assert [len(g) for g in got] == _piece_lens(n)
    # one shared txn id, fences strictly increasing across the pieces
    assert len({g.txn for g in got}) == 1 and got[0].txn.startswith(op)
    fences = [g.revision for g in got]
    assert fences == sorted(set(fences))
    # concatenated, the pieces are the per-event sequence: order, content
    assert _flatten(got) == _flatten(_take_now(plain))
    # the prev-revision fence column is sliced like the others
    if op == "bind_many":
        assert [r for g in got for r in g.prev_revisions] == pre
        assert all(len(g.prev_revisions) == len(g) for g in got)
    else:
        assert all(g.prev_revisions is None for g in got)
    # the pieces are packed once and shared by every frames watcher, and
    # the counter moves once per piece, not per watcher
    shared = _take_now(second)
    assert len(shared) == len(got) and all(a is b for a, b in zip(got, shared))
    assert DEFAULT_STORE_METRICS.watch_frames.value - c0 == len(got)
    if n <= _BOUND:
        # at or under the bound: one frame, byte for byte the frame the
        # txn has always been
        evs = list(got[0].events())
        whole = WatchFrame(
            "Pod", [e.type for e in evs], [e.key for e in evs],
            [e.revision for e in evs], [e.object for e in evs],
            prev_revisions=pre,
            txn=got[0].txn)
        assert got[0].wire_bytes() == whole.wire_bytes()
    for w in (framed, second, plain):
        w.stop()


def _queue_keys(sched):
    return sorted(p.meta.key for p in sched.queue.snapshot_pending())


@pytest.mark.parametrize("bound", [7, 49])
def test_cut_confirm_wave_equals_uncut_wave(monkeypatch, bound):
    """Informer → ``_on_pod_frame`` → ``confirm_many`` take a piece as
    the frame it is: three 50-pod waves cut at ``bound`` (49: a one-row
    remainder) leave the scheduler's cache and queue as the uncut waves
    do, every entry confirmed by the columnar fence."""
    cs_a, sched_a = _world()  # uncut: 50 <= FRAME_MAX_ROWS
    for w in range(3):
        assert _churn_wave(cs_a, sched_a, 50, f"w{w}") == (50, 0)
    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", bound)
    cs_b, sched_b = _world()
    for w in range(3):
        assert _churn_wave(cs_b, sched_b, 50, f"w{w}") == (50, 0)

    bind_b = {p.meta.key: p.spec.node_name for p in cs_b.pods.list()[0]}
    bind_a = {p.meta.key: p.spec.node_name for p in cs_a.pods.list()[0]}
    assert bind_b == bind_a and all(bind_b.values())
    assert _cache_fingerprint(sched_b.cache) == _cache_fingerprint(sched_a.cache)
    assert _queue_keys(sched_b) == _queue_keys(sched_a) == []
    assert sched_b.metrics.confirm_fallbacks.value == 0
    # a create frame and a confirm frame per piece per wave; every event
    assert sched_a.metrics.watch_frames.value == 3 * 2
    assert (sched_b.metrics.watch_frames.value
            == 3 * 2 * len(_piece_lens(50, bound)))
    assert (sched_b.metrics.watch_frame_events.value
            == sched_a.metrics.watch_frame_events.value == 3 * 2 * 50)


@pytest.mark.parametrize("field_selector", [None, "spec.nodeName=n1"])
def test_remote_pieces_with_and_without_a_field_selector(
        api_server, small_bound, field_selector):
    """Over HTTP a cut txn is ceil(n / bound) frame lines; behind a
    field selector each piece is re-packed (``select``) as a frame was,
    and the stream equals the per-event stream under the same selector."""
    from kubernetes_tpu.client.remote import RemoteStore

    rs = RemoteStore(api_server.url, retry_backoff=0.005)
    cs = Clientset(api_server.store)
    n = 10
    cs.pods.create_many([make_pod(f"p{i:03d}", cpu="100m") for i in range(n)])
    rev = api_server.store.list("Pod")[1]
    wf = rs.watch("Pod", from_revision=rev, frames=True,
                  field_selector=field_selector)
    we = rs.watch("Pod", from_revision=rev, field_selector=field_selector)
    assert _wait(lambda: wf._resp is not None and we._resp is not None)
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=f"p{i:03d}",
                               node_name=f"n{i % 2}") for i in range(n)])
    # all ten rows, or the five on n1: pieces of 4, 4, 2 re-packed 2, 2, 1
    want_rows = n if field_selector is None else n // 2
    want_lens = [4, 4, 2] if field_selector is None else [2, 2, 1]
    got = _drain(wf, len(want_lens), timeout=10.0)
    plain = _drain(we, want_rows, timeout=10.0)
    assert [g.type for g in got] == [FRAME] * len(want_lens)
    assert [len(g) for g in got] == want_lens
    assert len({g.txn for g in got}) == 1
    assert all(len(g.prev_revisions) == len(g) for g in got)
    assert len(plain) == want_rows and _flatten(got) == _flatten(plain)
    if field_selector is not None:
        assert {nn for g in got for nn in g.node_names} == {"n1"}
    assert wf.get(timeout=0.2) is None  # nothing repeated, nothing more
    wf.stop()
    we.stop()


@pytest.mark.parametrize("transport", ["store", "http"])
def test_watch_resumed_between_two_pieces_loses_and_repeats_nothing(
        api_server, small_bound, transport):
    """A watch that ends after piece k resumes from piece k's fence
    (``frame.revision``): the rest of the txn replays from the log, per
    event, with no row lost and none repeated."""
    from kubernetes_tpu.client.remote import RemoteStore

    store = api_server.store
    cs = Clientset(store)
    rs = RemoteStore(api_server.url, retry_backoff=0.005)

    def open_watch(from_rev):
        if transport == "store":
            return store.watch("Pod", from_revision=from_rev, frames=True)
        w = rs.watch("Pod", from_revision=from_rev, frames=True)
        assert _wait(lambda: w._resp is not None)
        return w

    n = 10
    rev0 = store.list("Pod")[1]
    first = open_watch(rev0)
    plain = store.watch("Pod", from_revision=rev0)
    cs.pods.create_many([make_pod(f"p{i:03d}", cpu="100m") for i in range(n)])
    head = _drain(first, 2, timeout=10.0)[:2]  # pieces 1 and 2 of 3 ...
    first.stop()                               # ... and the watch ends
    assert [len(h) for h in head] == [4, 4]
    resumed = open_watch(head[-1].revision)
    tail = _drain(resumed, n - 8, timeout=10.0)
    assert _flatten(head + tail) == _flatten(_drain(plain, n))
    assert resumed.get(timeout=0.2) is None
    resumed.stop()
    plain.stop()


def test_stream_timeout_does_not_fall_between_two_queued_pieces(
        api_server, small_bound, monkeypatch):
    """``timeoutSeconds`` runs out while a txn's pieces are going out: the
    stream ends after the txn's last piece, not between two of them (the
    resume would replay the rest per event), and takes nothing that
    comes after the txn with it."""
    import urllib.request

    encode = WatchFrame.wire_bytes

    def slow_encode(self):
        _time.sleep(0.5)
        return encode(self)

    monkeypatch.setattr(WatchFrame, "wire_bytes", slow_encode)
    cs = Clientset(api_server.store)
    n = 12
    cs.pods.create_many([make_pod(f"p{i:03d}", cpu="100m") for i in range(n)])
    rev = api_server.store.list("Pod")[1]
    watchers = len(api_server.store._watchers)
    resp = urllib.request.urlopen(
        f"{api_server.url}/api/v1/pods?watch=true&frames=1"
        f"&timeoutSeconds=1&resourceVersion={rev}", timeout=10.0)
    assert _wait(lambda: len(api_server.store._watchers) > watchers)
    t0 = _time.monotonic()
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name=f"p{i:03d}",
                               node_name=f"n{i % 2}") for i in range(n)])
    # a second txn, queued behind the first before the deadline: past the
    # deadline the stream takes the first txn's pieces and nothing else
    cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(2)])
    lines = [json.loads(raw) for raw in resp if raw.strip()]  # to a clean end
    assert _time.monotonic() - t0 >= 1.0
    got = [WatchFrame.from_wire(d) for d in lines]
    assert [len(g) for g in got] == _piece_lens(n)
    assert len({g.txn for g in got}) == 1 and got[0].txn.startswith("bind_many")
    assert [k for g in got for k in g.keys] == [
        f"default/p{i:03d}" for i in range(n)]


# ---------------------------------------------------------------------------
# compaction: promote-and-drop-raw
# ---------------------------------------------------------------------------


def _rich_raw(i):
    store = Store()
    pod = make_pod(f"r{i}", cpu="250m", memory="512Mi", host_ports=[8000 + i],
                   labels={"app": "web"}, node_selector={"disk": "ssd"})
    return store.create("Pod", pod.to_dict())


def test_promote_and_drop_raw_preserves_observable_value():
    raw = _rich_raw(0)
    eager = api.Pod.from_dict(copy.deepcopy(raw))
    lz = lazy_mod.wrap(api.Pod, copy.deepcopy(raw))
    assert lazy_mod.promote_and_drop_raw(lz) is True
    assert lz.raw is None
    assert lz == eager and lz.to_dict() == eager.to_dict()
    # every raw fast path now answers through the typed objects
    assert lazy_mod.undecoded_spec(lz) is None
    assert lazy_mod.undecoded_meta(lz) is None
    assert lazy_mod.pod_brief(lz) == lazy_mod.pod_brief(eager)
    assert lazy_mod.resource_version_of(lz) == eager.meta.resource_version
    assert lz.host_ports() == eager.host_ports()
    # idempotent, and a no-op on eager objects
    assert lazy_mod.promote_and_drop_raw(lz) is False
    assert lazy_mod.promote_and_drop_raw(eager) is False
    # generic wrapper kinds drop too
    svc_raw = Store().create("Service", api.Service(
        meta=ObjectMeta(name="s"), selector={"app": "x"}).to_dict())
    lsvc = lazy_mod.wrap(api.Service, svc_raw)
    assert lazy_mod.promote_and_drop_raw(lsvc) is True
    assert lsvc.selector == {"app": "x"} and lsvc.raw is None


def test_informer_compact_cache_sweeps_synced_caches():
    cs = Clientset(Store())
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
    sched_cs = Clientset(cs.store)
    inf = SharedInformer(sched_cs.pods)
    inf.start_manual()
    before = {k: inf.get(k).to_dict() for k in inf.keys()}
    assert inf.compact_cache() == 4
    assert inf.stats["compactions"] == 4
    for key, d in before.items():
        obj = inf.get(key)
        assert obj.raw is None and obj.to_dict() == d
    # the sweep is idempotent and later deltas re-pin fresh payloads
    assert inf.compact_cache() == 0
    cs.pods.bind_many([Binding(pod_namespace="default", pod_name="p0",
                               node_name="n1")])
    inf.pump()
    assert inf.get("default/p0").raw is not None
    assert inf.compact_cache() == 1


def test_compact_on_resync_flag_sweeps_after_relist():
    """ISSUE 7 satellite (ROADMAP carried item): with the flag on, every
    relist/resync tick ends with the compaction sweep — counted in
    ``client_informer_compactions_total`` with the freed bytes on the
    gauge — and the default (flag off) still never compacts."""
    from kubernetes_tpu.utils.metrics import ClientMetrics

    cs = Clientset(Store())
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(4)])
    metrics = ClientMetrics()
    inf = SharedInformer(Clientset(cs.store).pods, metrics=metrics,
                         compact_on_resync=True)
    inf.start_manual()
    assert all(inf.get(k).raw is not None for k in inf.keys())
    inf.relist()  # the resync-timer tick (reference resyncPeriod alias)
    assert all(inf.get(k).raw is None for k in inf.keys())
    assert inf.stats["compactions"] == 4
    assert metrics.informer_compactions.value == 4
    assert metrics.informer_compaction_freed_bytes.value > 0
    # second tick: the relist itself re-pinned fresh LIST payloads, so
    # the sweep drops them again — steady state is one sweep per resync
    inf.relist()
    assert metrics.informer_compactions.value == 8
    assert all(inf.get(k).raw is None for k in inf.keys())

    # flag off (the default): relist never compacts behind your back
    inf2 = SharedInformer(Clientset(cs.store).pods)
    inf2.start_manual()
    inf2.relist()
    assert all(inf2.get(k).raw is not None for k in inf2.keys())


def test_compaction_memory_delta():
    """The sweep must actually FREE the pinned wire payloads: raw dicts
    with unmodeled fields (the realistic wire shape — most of a real
    pod's bytes are fields this framework never types) are released."""
    def fat_raw(i):
        d = make_pod(f"m{i}", cpu="100m", memory="128Mi").to_dict()
        d["metadata"]["managedFields"] = [
            {"manager": "kubelet", "blob": "x" * 2048, "n": j}
            for j in range(4)]
        d["spec"]["containers"][0]["unmodeledEnv"] = [
            {"name": f"E{j}", "value": "v" * 64} for j in range(20)]
        # json round-trip: exclusively-owned, non-interned leaves, like a
        # payload that actually crossed the wire
        return json.loads(json.dumps(d))

    tracemalloc.start()
    try:
        pods = [lazy_mod.wrap(api.Pod, fat_raw(i)) for i in range(300)]
        for p in pods:
            p.meta.key  # the informer's light touch
        gc.collect()
        before, _ = tracemalloc.get_traced_memory()
        for p in pods:
            assert lazy_mod.promote_and_drop_raw(p)
        gc.collect()
        after, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    freed = before - after
    # ~6MB observed; demand a decisive fraction so the assertion is
    # robust to allocator noise while still failing on a broken drop
    assert freed > 2_000_000, f"only {freed} bytes freed"
