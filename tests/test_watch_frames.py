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
from kubernetes_tpu.api import BindingColumns, ObjectMeta
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
    cs.pods.bind_many(BindingColumns([f"default/p{i}" for i in range(3)],
                                     ["n1"] * 3))
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
    cs.pods.bind_many(BindingColumns([f"default/p{i}" for i in range(3)],
                                     [f"n{i}" for i in range(3)]))
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
    cs.pods.bind_many(BindingColumns([f"default/p{i}" for i in range(6)],
                                     ["n1"] * 6))
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
    cs.pods.bind_many(BindingColumns(["default/a", "default/b"], ["n0", "n0"]))
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


def _stop_soon(watch):
    """Stop a watch without waiting: closing a chunked response reads it
    to its end, which an idle stream reaches at its ``timeoutSeconds``."""
    threading.Thread(target=watch.stop, daemon=True).start()


def _cut_watch(inf):
    """The informer's stream ends here, as at ``timeoutSeconds``: what it
    had not yet read is gone, and a new watch resumes from its bookmark."""
    old = inf._watch
    inf._watch = inf._watch_from(inf.last_revision)
    _stop_soon(old)


@pytest.mark.parametrize("batch_arrives", ["live", "replayed"])
def test_remote_frames_end_to_end(api_server, batch_arrives):
    from kubernetes_tpu.client.remote import RemoteStore

    rs = RemoteStore(api_server.url, retry_backoff=0.005)
    cs = Clientset(api_server.store)
    inf = SharedInformer(Clientset(rs).pods, metrics=rs.metrics)
    inf.start_manual()
    assert _wait(lambda: inf._watch._resp is not None)
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(5)])
    if batch_arrives == "replayed":
        # the batch was committed before this watch connected: the log
        # replays it as the frame it left in, not per event
        _cut_watch(inf)
    assert _wait(lambda: (inf.pump(), len(inf.list()))[-1] == 5)
    # the batch crossed the wire as ONE frame line
    assert inf.stats["frames"] == 1
    assert inf.stats["frame_events"] == 5
    # a per-event client against the same server sees plain events
    plain = _per_event_informer(Clientset(RemoteStore(api_server.url)).pods)
    plain.start_manual()
    assert _wait(lambda: plain._watch._resp is not None)
    cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(3)])
    if batch_arrives == "replayed":
        _cut_watch(plain)
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
        assert cs.pods.bind_many(BindingColumns(
            [p.meta.key for p in pods],
            [f"n{i % 2}" for i in range(n)])) == [None] * n
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
    cs.pods.bind_many(BindingColumns([f"default/p{i:03d}" for i in range(n)],
                                     [f"n{i % 2}" for i in range(n)]))
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


# ---------------------------------------------------------------------------
# a resumed frames watch gets frames (ISSUE 31)
# ---------------------------------------------------------------------------


def _open_watch(api_server, transport, from_rev, frames=True):
    if transport == "store":
        return api_server.store.watch("Pod", from_revision=from_rev,
                                      frames=frames)
    from kubernetes_tpu.client.remote import RemoteStore

    w = RemoteStore(api_server.url, retry_backoff=0.005).watch(
        "Pod", from_revision=from_rev, frames=frames)
    assert _wait(lambda: w._resp is not None)
    return w


def _frame_fields(item):
    if item.type != FRAME:
        return (item.type, item.key, item.revision, item.object)
    return (item.kind, item.types, item.keys, item.revisions,
            item.prev_revisions, item.objects, item.txn)


# rows of the 10-row bind the first watch had delivered when it ended:
# none (the resume is before the txn), a piece (between two pieces), a
# piece and a half (inside one), all but one, all (after the txn)
@pytest.mark.parametrize("seen,want", [
    (0, ["MODIFIED", 4, 4, 2, "ADDED", 3]),
    (4, [4, 2, "ADDED", 3]),
    (6, [4, "ADDED", 3]),
    (8, [2, "ADDED", 3]),
    (9, [1, "ADDED", 3]),
    (10, ["ADDED", 3]),
])
@pytest.mark.parametrize("transport", ["store", "http"])
def test_resumed_frames_watch_replays_a_txn_as_its_frames(
        api_server, small_bound, transport, seen, want):
    """A frames watch that resumes from a revision gets the log's batch
    txns as the pieces they left in (the rest of one from the row after
    the revision, cut like the live pieces), with the txn's id and its
    prev-revision column, single writes between them in their order; a
    plain watch's replay is what it was."""
    store = api_server.store
    cs = Clientset(store)
    n = 10
    pre = [c.meta.resource_version for c in cs.pods.create_many(
        [make_pod(f"p{i:03d}", cpu="100m") for i in range(n)])]
    rev0 = store.list("Pod")[1]
    live = store.watch("Pod", from_revision=rev0, frames=True)
    live_plain = store.watch("Pod", from_revision=rev0)

    def _label(d):
        d["metadata"].setdefault("labels", {})["x"] = "y"
        return d
    store.guaranteed_update("Pod", "default", "p000", _label)
    pre[0] = rev0 + 1
    cs.pods.bind_many(BindingColumns([f"default/p{i:03d}" for i in range(n)],
                                     [f"n{i % 2}" for i in range(n)]))
    cs.pods.create(make_pod("solo", cpu="100m"))
    cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(3)])
    live_items = _take_now(live)
    plain_rows = _flatten(_take_now(live_plain))
    assert [len(g) if g.type == FRAME else g.type for g in live_items] == [
        "MODIFIED", 4, 4, 2, "ADDED", 3]

    # rev0 + 1 is the label write, the bind's rows are rev0 + 2 ... + 11
    from_rev = rev0 if seen == 0 else rev0 + 1 + seen
    resumed = _open_watch(api_server, transport, from_rev)
    got = _drain(resumed, len(want), timeout=10.0)
    assert [len(g) if g.type == FRAME else g.type for g in got] == want
    # none lost, none repeated: the rows are the plain stream's
    rows = _flatten(got)
    assert rows == plain_rows[len(plain_rows) - len(rows):]
    assert len(rows) == n + 5 - (0 if seen == 0 else seen + 1)
    assert resumed.get(timeout=0.2) is None
    bind = [g for g in got if g.type == FRAME
            and g.txn.startswith("bind_many")]
    assert [r for g in bind for r in g.prev_revisions] == pre[seen:]
    assert {g.txn for g in bind} <= {live_items[1].txn}
    assert got[-1].txn == live_items[-1].txn and got[-1].prev_revisions is None
    if seen in (0, 4, 8, 10):
        # resumed at a live piece's fence: field for field the live pieces
        tail = live_items[len(live_items) - len(got):]
        assert [_frame_fields(g) for g in got] == [_frame_fields(t)
                                                   for t in tail]
    # a watcher that asked for no frames: event for event what it was
    plain = _open_watch(api_server, transport, from_rev, frames=False)
    replayed = _drain(plain, len(rows), timeout=10.0)
    assert all(ev.type != FRAME for ev in replayed)
    assert _flatten(replayed) == rows
    if transport == "store":  # the log's own shared events, as ever
        assert all(a.object is b[3] for a, b in zip(
            replayed, plain_rows[len(plain_rows) - len(replayed):]))
    for w in (live, live_plain, resumed, plain):
        _stop_soon(w)


def test_a_coalescing_window_replays_as_its_flush_frames(small_bound):
    """The rows one coalescing window delivered replay folded and packed
    as its flush did: each key's latest, by kind, the window's id."""
    store = Store(coalesce_window_s=30.0)
    cs = Clientset(store)
    live = store.watch(frames=True)   # every kind
    rev0 = store.revision
    for i in range(5):
        cs.pods.create(make_pod(f"p{i}", cpu="100m"))
    cs.nodes.create(make_node("n0", cpu="4", memory="8Gi"))
    for i in (1, 3):
        store.guaranteed_update("Pod", "default", f"p{i}", lambda d: d)
    store.flush_coalesced()
    live_items = _take_now(live)
    assert [len(g) if g.type == FRAME else g.kind for g in live_items] == [
        4, 1, "Node"]
    replayed = _take_now(store.watch(from_revision=rev0, frames=True))
    assert [_frame_fields(g) for g in replayed] == [
        _frame_fields(g) for g in live_items]
    # from inside the window: the rows after it, folded alike
    part = _take_now(store.watch("Pod", from_revision=rev0 + 3, frames=True))
    assert [g.keys for g in part] == [
        ["default/p4", "default/p1", "default/p3"]]
    plain = _take_now(store.watch("Pod", from_revision=rev0))
    assert [ev.type for ev in plain] == ["ADDED"] * 5 + ["MODIFIED"] * 2
    store.close()


def test_the_txn_index_is_trimmed_with_the_log(small_bound):
    store = Store(event_log_window=8)
    cs = Clientset(store)
    pods = [make_pod(f"p{i:03d}", cpu="100m") for i in range(10)]
    pre = [c.meta.resource_version for c in cs.pods.create_many(pods)]
    assert [(t.first, t.last) for t in store._log_txns] == [(1, 10)]
    cs.pods.bind_many(BindingColumns([p.meta.key for p in pods],
                                     ["n0"] * len(pods)))
    # the creates have left the window and their txn the index with them;
    # the bind's first two rows have left too, and its column still lines
    # up with the rows that stay
    assert [(t.first, t.last) for t in store._log_txns] == [(11, 20)]
    assert store._log[0].revision == 13
    got = _take_now(store.watch("Pod", from_revision=13, frames=True))
    assert [g.revisions for g in got] == [[14, 15, 16, 17], [18, 19, 20]]
    assert [r for g in got for r in g.prev_revisions] == pre[3:]
    with pytest.raises(Exception, match="too old"):
        store.watch("Pod", from_revision=11, frames=True)
    # single writes push the last rows out: nothing is left to remember
    for i in range(8):
        cs.pods.create(make_pod(f"solo{i}", cpu="100m"))
    assert not store._log_txns
    assert all(ev.type != FRAME for ev in _take_now(
        store.watch("Pod", from_revision=21, frames=True)))


@pytest.mark.parametrize("transport", ["store", "http"])
@pytest.mark.parametrize("pieces_read", [0, 1, 3, 8])
def test_resumed_confirm_frames_confirm_a_wave_without_fallbacks(
        api_server, monkeypatch, transport, pieces_read):
    """The scheduler's pod watch ends with some of a wave's confirm
    pieces unread (all, some, none): the resumed watch brings the rest as
    frames, ``confirm_many`` takes them by the prev-revision fence, and
    cache and queue end as after a wave read live."""
    from kubernetes_tpu.client.remote import RemoteStore

    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", 7)
    worlds = []
    for cut in (False, True):
        store = api_server.store if cut else Store()
        cs, sched = _world(store=RemoteStore(api_server.url)
                           if cut and transport == "http" else store)
        inf = sched.informers.informer("Pod")
        Clientset(store).pods.create_many(
            [make_pod(f"w-{i:04d}", cpu="100m", memory="128Mi")
             for i in range(50)])
        assert _wait(lambda: (sched.pump(), len(sched.queue))[-1] == 50)
        lines = sched.metrics.watch_line_events.value
        assert sched.schedule_pending_batch() == (50, 0)
        if cut:
            if transport == "http":   # the 8 pieces are in: read some
                assert _wait(lambda: inf._watch._queue.qsize() == 8)
            for _ in range(pieces_read):
                inf.pump(max_events=1)
            _cut_watch(inf)
        assert _wait(lambda: (sched.pump(), inf.last_revision)[-1]
                     == store.revision)
        assert sched.metrics.watch_line_events.value == lines
        worlds.append((cs, sched))
    (cs_a, sched_a), (cs_b, sched_b) = worlds
    assert (_cache_fingerprint(sched_b.cache)
            == _cache_fingerprint(sched_a.cache))
    assert _queue_keys(sched_b) == _queue_keys(sched_a) == []
    assert sched_b.metrics.confirm_fallbacks.value == 0
    assert (sched_b.metrics.watch_frames.value
            == sched_a.metrics.watch_frames.value == 2 * 8)
    assert (sched_b.metrics.watch_frame_events.value
            == sched_a.metrics.watch_frame_events.value == 2 * 50)
    for _cs, sched in worlds:
        threading.Thread(target=sched.informers.stop_all, daemon=True).start()


def test_stream_past_its_deadline_writes_what_was_queued_then_ends(
        api_server, small_bound, monkeypatch):
    """``timeoutSeconds`` runs out while frames are going out: the stream
    still writes every item that was on its queue when the deadline
    passed, whichever txn it is of, and ends; what is committed after
    that is left for the resume, which gets it as a frame."""
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
    held0 = api_server.watch_held_frames.value
    resp = urllib.request.urlopen(
        f"{api_server.url}/api/v1/pods?watch=true&frames=1"
        f"&timeoutSeconds=1&resourceVersion={rev}", timeout=10.0)
    assert _wait(lambda: len(api_server.store._watchers) > watchers)
    t0 = _time.monotonic()
    cs.pods.bind_many(BindingColumns([f"default/p{i:03d}" for i in range(n)],
                                     [f"n{i % 2}" for i in range(n)]))
    # a second txn, queued behind the first before the deadline: at the
    # deadline (two pieces are out) the third piece and this frame wait
    cs.pods.create_many([make_pod(f"q{i}", cpu="100m") for i in range(2)])
    # a third, committed past the deadline while those two go out
    _time.sleep(max(0.0, t0 + 1.3 - _time.monotonic()))
    cs.pods.create_many([make_pod(f"late{i}", cpu="100m") for i in range(2)])
    lines = [json.loads(raw) for raw in resp if raw.strip()]  # to a clean end
    assert 2.0 <= _time.monotonic() - t0 < 4.0
    got = [WatchFrame.from_wire(d) for d in lines]
    assert [len(g) for g in got] == _piece_lens(n) + [2]
    assert [g.txn.split("-")[0] for g in got] == ["bind_many"] * 3 + [
        "create_many"]
    assert [k for g in got for k in g.keys] == [
        f"default/p{i:03d}" for i in range(n)] + ["default/q0", "default/q1"]
    assert api_server.watch_held_frames.value - held0 == 2
    monkeypatch.undo()
    resumed = _open_watch(api_server, "http", got[-1].revision)
    late = _drain(resumed, 1, timeout=10.0)
    assert [g.keys for g in late] == [["default/late0", "default/late1"]]
    assert late[0].type == FRAME
    _stop_soon(resumed)


def test_a_resumed_watch_is_one_replay_span_counted_by_rows_and_frames(
        small_bound):
    """``store.watch.replay``: once per resumed watch, never per row, with
    the rows and the frames it put; the two counters move alike."""
    from kubernetes_tpu.utils import tracing
    from kubernetes_tpu.utils.metrics import DEFAULT_STORE_METRICS as m

    store = Store()
    cs = Clientset(store)
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(9)])
    cs.pods.create(make_pod("solo", cpu="100m"))
    cs.nodes.create(make_node("n0", cpu="4", memory="8Gi"))
    tr = tracing.enable()
    try:
        ev0, fr0 = m.watch_replay_events.value, m.watch_replay_frames.value
        store.watch("Pod", frames=True)              # from now: no replay
        store.watch("Pod", from_revision=2, frames=True)
        store.watch("Pod", from_revision=2)
        spans = [sp for sp in tr.background if sp.name == "store.watch.replay"]
    finally:
        tracing.disable()
    assert [sp.attrs for sp in spans] == [
        {"kind": "Pod", "from_revision": 2, "events": 8, "frames": 2,
         "txns": 1},
        {"kind": "Pod", "from_revision": 2, "events": 8, "frames": 0,
         "txns": 0}]
    assert m.watch_replay_events.value - ev0 == 16
    assert m.watch_replay_frames.value - fr0 == 2


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
    cs.pods.bind_many(BindingColumns(["default/p0"], ["n1"]))
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
