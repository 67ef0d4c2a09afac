"""A ``bind_many`` txn is committed by columns and a bound pod's watch
payload is derived when somebody reads it (``store.BoundPodEvent``).

Every case holds the change against a reference arm that this file
builds eagerly itself (``_reference``: the parent's sequential loop over
deep copies taken before the txn): events and frames equal by ``==`` on
every payload and byte for byte on the wire, through every kind of
reader and after every later write that could reach a payload built
late.  ``test_no_write_verb_reaches_a_committed_bind_event`` is the
invariant ``Store``'s docstring states.
"""

import copy
import json

import pytest

from kubernetes_tpu import faults
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client.remote import RemoteStore
from kubernetes_tpu.store import Store, WatchEvent, frames as frames_mod
from kubernetes_tpu.store.replication import FollowerReplica, ReplicatedStore
from kubernetes_tpu.store.store import (
    MODIFIED,
    BoundPodEvent,
    ExpiredRevisionError,
)
from kubernetes_tpu.utils.metrics import DEFAULT_STORE_METRICS

PIECE = 4  # FRAME_MAX_ROWS in this file: a 9-row txn leaves in 3 pieces


@pytest.fixture(autouse=True)
def _small_frames(monkeypatch):
    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", PIECE)


def _pod(name: str, node: str = "", finalizers=()) -> dict:
    meta = {"name": name, "namespace": "default",
            "labels": {"app": name, "tier": "t"}}
    if finalizers:
        meta["finalizers"] = list(finalizers)
    return {"kind": "Pod", "metadata": meta,
            "spec": {"containers": [{"name": "c", "resources": {
                "requests": {"cpu": "100m", "memory": "64Mi"}}}],
                "nodeName": node, "priority": 0},
            "status": {"phase": "Pending"}}


def _world(store: Store) -> None:
    """p0..p8 pending (p7 with a finalizer, p8 without a ``spec``), p9
    bound to n9, p10 bound to n-old."""
    pods = [_pod(f"p{i}") for i in range(7)]
    pods.append(_pod("p7", finalizers=("example.com/hold",)))
    bare = _pod("p8")
    del bare["spec"]
    pods += [bare, _pod("p9", node="n9"), _pod("p10", node="n-old")]
    store.create_many("Pod", pods)


# what a txn is made of, as (pod key, node name) rows; each is also run
# alone
ITEMS = {
    "plain": [(f"default/p{i}", f"n{i % 3}") for i in range(9)],
    "rebound": [("default/p9", "n9")],
    "conflicting": [("default/p10", "n-new")],
    "missing": [("default/ghost", "n1")],
    "injected": [("default/p3", "n0")],  # (the plan below drops p3)
    "unqualified": [("p4", "n1")],  # a key without a namespace: no pod
}
ITEMS["mixed"] = (ITEMS["plain"][:3] + ITEMS["missing"] + ITEMS["rebound"]
                  + ITEMS["plain"][3:6] + ITEMS["conflicting"]
                  + ITEMS["plain"][6:])


def _snapshot(store: Store) -> tuple:
    """(key -> a deep copy of what is stored, the store's revision)."""
    objs, rev = store.list("Pod")
    return {f'{o["metadata"]["namespace"]}/{o["metadata"]["name"]}': o
            for o in objs}, rev


def _reference(objs: dict, rev: int, items, injected=()):
    """The eager arm: the CAS loop as the parent ran it, on private deep
    copies.  Returns (results, [(type, kind, key, revision, payload)],
    prev_revisions)."""
    results, events, prevs = [], [], []
    for key, node in items:
        if key in injected:
            results.append("injected: bind fault")
            continue
        obj = objs.get(key)
        if obj is None:
            results.append("not found")
            continue
        spec = obj.setdefault("spec", {})
        cur = spec.get("nodeName", "")
        if cur and cur != node:
            results.append(f"conflict: already bound to {cur}")
            continue
        prevs.append(obj["metadata"]["resourceVersion"])
        rev += 1
        spec["nodeName"] = node
        obj["metadata"]["resourceVersion"] = rev
        events.append((MODIFIED, "Pod", key, rev, copy.deepcopy(obj)))
        results.append(None)
    return results, events, prevs


def _bind(store, items):
    """``bind_many`` of ``items`` as the verb takes them: two columns."""
    return store.bind_many([key for key, _ in items],
                           [node for _, node in items])


def _reference_frames(events, prevs, txn, lo=0):
    """The rows from index ``lo`` on as eagerly built frames."""
    out = []
    for at in range(lo, len(events), PIECE):
        rows = events[at:at + PIECE]
        out.append(frames_mod.WatchFrame(
            "Pod", [r[0] for r in rows], [r[2] for r in rows],
            [r[3] for r in rows], [r[4] for r in rows],
            prev_revisions=prevs[at:at + PIECE], txn=txn))
    return out


def _event_line(row) -> bytes:
    return json.dumps({"type": row[0], "kind": row[1], "key": row[2],
                       "revision": row[3], "object": row[4]}).encode() + b"\n"


def _row(ev) -> tuple:
    return (ev.type, ev.kind, ev.key, ev.revision, ev.object)


def _drain(watch) -> list:
    out = []
    while True:
        item = watch.get(timeout=0)
        if item is None:
            return out
        out.append(item)


def _assert_frames(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.type == frames_mod.FRAME
        # the columns first: reading them builds no payload
        assert (g.kind, g.types, g.keys, g.revisions, g.prev_revisions,
                g.txn, g.revision, len(g)) == (
            w.kind, w.types, w.keys, w.revisions, w.prev_revisions,
            w.txn, w.revision, len(w))
        assert g.node_names == w.node_names
        assert g.objects == w.objects
        assert g.wire_bytes() == w.wire_bytes()
        assert [_row(e) for e in g.events()] == [_row(e) for e in w.events()]
        assert frames_mod.WatchFrame.from_wire(
            json.loads(g.wire_bytes())).objects == w.objects


def _assert_events(got, want_rows):
    assert [_row(e) for e in got] == want_rows
    for ev, row in zip(got, want_rows):
        assert ev == WatchEvent(*row) and WatchEvent(*row) == ev
        assert frames_mod.event_wire_bytes(ev) == _event_line(row)
        # (the shared-encode cache hangs on the event)
        assert frames_mod.event_wire_bytes(ev) is frames_mod.event_wire_bytes(ev)


def _txn_of(items):
    """The txn id of the first frame among ``items`` (None: no frame)."""
    return next((it.txn for it in items if it.type == frames_mod.FRAME), None)


# -- what a txn is made of -------------------------------------------------

@pytest.mark.parametrize("via", ["store", "wire"])
@pytest.mark.parametrize("case", list(ITEMS))
def test_a_txns_events_and_frames_equal_the_eager_arm(case, via):
    """``via="wire"``: the same two columns through ``RemoteStore`` and the
    apiserver's ``bindings:batch`` handler commit the same txn."""
    store = Store()
    _world(store)
    server = None
    binder = store
    if via == "wire":
        server = APIServer(store)
        server.start()
        binder = RemoteStore(server.url)
    items = ITEMS[case]
    injected = {"default/p3"} if case in ("injected", "mixed") else set()
    objs, rev = _snapshot(store)
    want_results, want_rows, want_prevs = _reference(
        objs, rev, items, injected)
    framed = store.watch("Pod", frames=True)
    plain = store.watch("Pod")
    try:
        if injected:
            plan = faults.FaultPlan(seed=1).on(
                "scheduler.bind", mode="drop",
                match={"via": "bind_many", "pod": "default/p3"})
            with plan.armed():
                results = _bind(binder, items)
        else:
            results = _bind(binder, items)
    finally:
        if server is not None:
            server.stop()
    assert results == want_results
    assert store.revision == rev + len(want_rows)
    got = _drain(framed)
    if len(want_rows) > 1:
        _assert_frames(got, _reference_frames(want_rows, want_prevs,
                                              _txn_of(got)))
        assert _txn_of(got).startswith("bind_many")
    else:  # a txn of one row (or none) goes out as the event
        _assert_events(got, want_rows)
    _assert_events(_drain(plain), want_rows)
    # what is stored is what the last event of each pod says
    now, _ = _snapshot(store)
    for row in want_rows:
        assert now[row[2]] == objs[row[2]]
    store.close()


# -- later writes x readers ------------------------------------------------

def _later_none(store):
    pass


def _later_update(store):
    for name in ("p0", "p5"):
        obj = store.get("Pod", "default", name)
        obj["spec"]["priority"] = 7
        obj["metadata"]["labels"]["app"] = "changed"
        obj["status"] = {"phase": "Running"}
        store.update("Pod", obj)


def _later_rebind(store):
    assert _bind(store, ITEMS["plain"][:6]) == [None] * 6


def _later_delete(store):
    store.delete("Pod", "default", "p1")
    store.delete("Pod", "default", "p6")


def _later_delete_finalizers(store):
    marked = store.delete("Pod", "default", "p7")
    assert marked["metadata"]["deletionRevision"]
    assert store.get("Pod", "default", "p7")["metadata"]["deletionRevision"]


def _later_trim(store):
    # push the whole txn out of the log window (32 rows here)
    store.create_many("Pod", [_pod(f"late{i}") for i in range(40)])


LATER = {"nothing": _later_none, "update": _later_update,
         "rebind": _later_rebind, "delete": _later_delete,
         "delete_with_finalizers": _later_delete_finalizers,
         "log_trim": _later_trim}
READERS = ["live_frames", "live_events", "resumed_frames",
           "resumed_events", "frames_disabled"]


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("later", list(LATER))
def test_payloads_read_after_a_later_write_equal_the_eager_arm(
        later, reader, monkeypatch):
    if reader == "frames_disabled":
        monkeypatch.setattr(frames_mod, "ENABLED", False)
    store = Store(event_log_window=32)
    _world(store)
    objs, rev = _snapshot(store)
    items = ITEMS["mixed"]
    want_results, want_rows, want_prevs = _reference(objs, rev, items)
    live = store.watch("Pod", frames=reader != "live_events")
    assert _bind(store, items) == want_results
    LATER[later](store)  # nobody has read a payload of the txn yet
    resumed = reader.startswith("resumed")
    skip = 5 if resumed else 0  # resume inside the txn's second piece
    if resumed:
        try:
            got = _drain(store.watch("Pod", from_revision=rev + skip,
                                     frames=reader == "resumed_frames"))
        except ExpiredRevisionError:
            assert later == "log_trim"
            got = None
    else:
        got = _drain(live)
    last = want_rows[-1][3]
    if got is not None:
        got = [g for g in got if g.revision <= last]  # the txn's own
    if got is None:
        pass  # (the resume point left the window with the txn)
    elif reader in ("live_frames", "resumed_frames"):
        txn = _txn_of(got)
        assert txn.startswith("bind_many")
        _assert_frames(got, _reference_frames(want_rows, want_prevs, txn,
                                              lo=skip))
    else:
        assert all(g.type != frames_mod.FRAME for g in got)
        _assert_events(got, want_rows[skip:])
    store.close()


# -- the readers that need the payload at commit ---------------------------

def test_a_wal_takes_every_payload_at_commit_and_replays_it(tmp_path):
    m = DEFAULT_STORE_METRICS
    store = Store(data_dir=str(tmp_path))
    _world(store)
    objs, rev = _snapshot(store)
    want_results, want_rows, _ = _reference(objs, rev, ITEMS["mixed"])
    d0, b0 = m.bind_rows_deferred.value, m.event_payloads_built.value
    assert _bind(store, ITEMS["mixed"]) == want_results
    # durability before visibility: every record was written under the
    # lock, so every payload was built there and none deferred
    assert m.bind_rows_deferred.value - d0 == 0
    assert m.event_payloads_built.value - b0 == len(want_rows)
    _later_update(store)
    _later_delete_finalizers(store)
    want_state = _snapshot(store)
    store.close()
    fresh = Store(data_dir=str(tmp_path))
    assert _snapshot(fresh) == want_state
    for row in want_rows:
        if row[2] not in ("default/p0", "default/p5", "default/p7"):
            assert want_state[0][row[2]] == objs[row[2]]
    fresh.close()


def test_a_follower_gets_every_bind_event_as_the_eager_arm_builds_it():
    leader = ReplicatedStore()
    follower = FollowerReplica("f1")
    leader.add_follower(follower)
    _world(leader)
    objs, rev = _snapshot(leader)
    want_results, want_rows, want_prevs = _reference(
        objs, rev, ITEMS["mixed"])
    theirs = follower.store.watch("Pod")
    ours = leader.watch("Pod", frames=True)
    assert _bind(leader, ITEMS["mixed"]) == want_results
    _later_rebind(leader)
    _later_delete_finalizers(leader)
    _assert_events(_drain(theirs)[:len(want_rows)], want_rows)
    got = [g for g in _drain(ours) if g.type == frames_mod.FRAME]
    _assert_frames(got[:3], _reference_frames(want_rows, want_prevs,
                                              got[0].txn))
    assert _snapshot(follower.store) == _snapshot(leader)
    assert follower.applied_revision == leader.revision
    leader.close()


def test_no_quorum_refuses_the_txn_before_anything_is_written():
    from kubernetes_tpu.store.replication import NoQuorumError

    leader = ReplicatedStore()
    followers = [FollowerReplica("f1"), FollowerReplica("f2")]
    for f in followers:
        leader.add_follower(f)
    _world(leader)
    before = _snapshot(leader)
    for f in followers:
        f.fail()
    with pytest.raises(NoQuorumError):
        _bind(leader, ITEMS["plain"])
    assert _snapshot(leader) == before
    leader.close()


# -- the invariant ---------------------------------------------------------

def _verb_update(store, key):
    obj = store.get("Pod", "default", key)
    obj["spec"]["nodeName"] = ""
    obj["spec"]["priority"] = 9
    obj["metadata"]["labels"] = {"rewritten": "yes"}
    store.update("Pod", obj)


def _verb_guaranteed_update(store, key):
    def mutate(obj):
        obj["metadata"]["annotations"] = {"a": "b"}
        obj["spec"]["containers"] = []
        return obj
    store.guaranteed_update("Pod", "default", key, mutate)


def _verb_rebind(store, key):
    node = store.get("Pod", "default", key)["spec"]["nodeName"]
    assert store.bind_many([f"default/{key}"] * 2, [node] * 2) == [None, None]


def _verb_delete(store, key):
    store.delete("Pod", "default", key)


def _verb_delete_then_finalize(store, key):
    marked = store.delete("Pod", "default", key)
    marked["metadata"]["finalizers"] = []
    store.update("Pod", marked)
    assert all(o["metadata"]["name"] != key for o in store.list("Pod")[0])


def _verb_create(store, key):
    store.create("Pod", _pod("another"))
    store.create_many("Pod", [_pod("more0"), _pod(key)])  # (key: exists)


def _verb_apply_replicated(store, key):
    obj = store.get("Pod", "default", key)
    rev = store.revision + 1
    obj["metadata"]["resourceVersion"] = rev
    obj["spec"]["nodeName"] = "elsewhere"
    store.apply_replicated(
        WatchEvent(MODIFIED, "Pod", f"default/{key}", rev, obj))


def _verb_install_snapshot(store, key):
    obj = store.get("Pod", "default", key)
    obj["spec"] = {"nodeName": "snap"}
    obj["metadata"]["resourceVersion"] = 500
    store.install_snapshot(500, {"Pod": {f"default/{key}": obj}})


VERBS = {"update": _verb_update,
         "guaranteed_update": _verb_guaranteed_update,
         "bind_many_again": _verb_rebind,
         "delete": _verb_delete,
         "delete_with_finalizers": _verb_delete,
         "delete_then_last_finalizer_cleared": _verb_delete_then_finalize,
         "create_and_create_many": _verb_create,
         "apply_replicated": _verb_apply_replicated,
         "install_snapshot": _verb_install_snapshot}


@pytest.mark.parametrize("verb", list(VERBS))
def test_no_write_verb_reaches_a_committed_bind_event(verb):
    """A dict the store holds is never changed in place except by
    ``bind_many``'s own two values, which the event overrides: so a
    payload built after any later write is the payload of the commit."""
    key = "p7" if "finalizer" in verb else "p2"
    store = Store()
    _world(store)
    watch = store.watch("Pod")
    assert store.bind_many(["default/p2", "default/p7", "default/p8"],
                           ["n2", "n7", "n8"]) == [None] * 3
    events = _drain(watch)
    assert [type(e) for e in events] == [BoundPodEvent] * 3
    assert all(e._payload is None for e in events)  # nothing built yet
    want = {e.key: store.get("Pod", "default", e.key.split("/")[1])
            for e in events}
    revs = {e.key: e.revision for e in events}
    VERBS[verb](store, key)
    for e in events:
        assert e.object == want[e.key]
        assert json.dumps(e.object) == json.dumps(want[e.key])  # key order
        assert e.object["metadata"]["resourceVersion"] == revs[e.key]
        assert e.object is e.object  # built once, then kept
    store.close()


def test_a_later_write_does_not_reach_a_payload_already_built():
    store = Store()
    _world(store)
    watch = store.watch("Pod")
    store.bind_many(["default/p7"], ["n7"])
    (ev,) = _drain(watch)
    built = copy.deepcopy(ev.object)
    store.bind_many(["default/p7"], ["n7"])
    store.delete("Pod", "default", "p7")
    assert ev.object == built
    store.close()


# -- the counters and the span ---------------------------------------------

def test_a_reader_of_columns_alone_builds_no_payload():
    from kubernetes_tpu.utils import tracing

    m = DEFAULT_STORE_METRICS
    store = Store()
    _world(store)
    framed = store.watch("Pod", frames=True)
    tr = tracing.enable()
    try:
        d0, b0 = m.bind_rows_deferred.value, m.event_payloads_built.value
        _bind(store, ITEMS["plain"])
        spans = [sp for sp in tr.background if sp.name == "store.txn"
                 and sp.attrs.get("op") == "bind_many"]
    finally:
        tracing.disable()
    assert len(spans) == 1
    assert spans[0].attrs["deferred"] == spans[0].attrs["committed"] == 9
    assert spans[0].attrs["errors"] == 0
    assert m.bind_rows_deferred.value - d0 == 9
    got = _drain(framed)
    assert [len(f) for f in got] == [4, 4, 1]
    assert [n for f in got for n in f.node_names] == [
        node for _, node in ITEMS["plain"]]
    assert [k for f in got for k in f.keys] == [
        key for key, _ in ITEMS["plain"]]
    assert all(p >= 0 for f in got for p in f.prev_revisions)
    assert got[-1].revision == store.revision
    assert m.event_payloads_built.value - b0 == 0  # columns alone
    got[0].wire_bytes()
    assert m.event_payloads_built.value - b0 == 4  # one piece's encode
    got[0].objects, got[0].wire_bytes(), list(got[0].events())
    assert m.event_payloads_built.value - b0 == 4  # built once
    assert got[2].objects[0]["spec"]["nodeName"] == "n2"
    assert m.event_payloads_built.value - b0 == 5
    store.close()
