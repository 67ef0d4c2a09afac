"""Wave tracing + flight recorder (ISSUE 7).

Four tiers:

1. span-layer unit tests — tree nesting, per-thread stacks, leaked-span
   unwinding, the ring/dump bounds, the disabled path;
2. the **end-to-end correlation** test: one scheduled batch, and the
   ``bind_many`` txn id minted by the store appears in the store span,
   the informer's frame-apply span, AND the scheduler's confirm span of
   ONE exported Chrome trace;
3. the **dump-on-fault matrix**: every registered fault point (the same
   registry the fault matrix gates) and every kernel-breaker transition
   produces a flight-recorder dump that contains the firing wave's
   trace;
4. ``utils/trace.py`` fold — ``Trace.log_if_long`` threshold/step
   deltas under a fake clock, and the shared ``format_slow`` path.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from kubernetes_tpu import faults
from kubernetes_tpu.client import Clientset
from kubernetes_tpu.faults import FaultInjected, FaultPlan, FaultSpec
from kubernetes_tpu.ops import TPUBatchBackend
from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
from kubernetes_tpu.store import Store
from kubernetes_tpu.testutil import make_node, make_pod
from kubernetes_tpu.utils import tracing
from kubernetes_tpu.utils.trace import Trace

from tests.test_faults import MATRIX, FakeClock, World


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """The tracer is process-global state: a leaked enable() would
    silently instrument every later test in the session."""
    yield
    tracing.disable()


# =====================================================================
# 1. span-layer unit tests
# =====================================================================


def test_disabled_path_is_inert():
    assert tracing.current() is None
    # the notify hooks are the instrumented sites' whole disabled cost:
    # one global load + None check, no exceptions, no state
    tracing.notify_fault("store.commit", {"op": "x"}, "error")
    tracing.notify_breaker("degrade", ("k",), "pallas", "interpret")
    tracing.notify_requeue("default/p")
    # txn ids are minted whether or not tracing is on (they ride the
    # watch frame; a consumer enabling tracing mid-stream still
    # correlates)
    a, b = tracing.next_txn("bind_many"), tracing.next_txn("create_many")
    assert a != b and a.startswith("bind_many-")


def test_span_tree_nesting_and_ring():
    clk = FakeClock()
    tr = tracing.enable(clock=clk, ring_waves=2)
    with tr.wave(pods=3) as w:
        clk.advance(1.0)
        with tr.span("tensorize", cat="phase"):
            clk.advance(0.5)
        with tr.span("dispatch", cat="phase", rung="interpret"):
            clk.advance(0.25)
            with tr.span("frontier.chunk", cat="frontier"):
                clk.advance(0.1)
    assert [c.name for c in w.children] == ["tensorize", "dispatch"]
    assert w.children[1].children[0].name == "frontier.chunk"
    assert w.t1 is not None and w.duration == pytest.approx(1.85)
    # phase totals are wall durations of the cat="phase" spans (the
    # frontier chunk is INSIDE dispatch, so dispatch includes it)
    assert w.phase_totals() == {"tensorize_s": pytest.approx(0.5),
                                "dispatch_s": pytest.approx(0.35)}
    # ring is bounded to the last K waves
    with tr.wave():
        pass
    with tr.wave():
        pass
    assert [s.attrs["wave"] for s in tr.ring] == [2, 3]
    # non-wave roots land in the background ring, not the wave ring
    with tr.span("store.txn", cat="store"):
        pass
    assert tr.background[-1].name == "store.txn"


def test_leaked_open_child_is_unwound():
    clk = FakeClock()
    tr = tracing.enable(clock=clk)
    cm_outer = tr.span("outer")
    outer = cm_outer.__enter__()
    cm_child = tr.span("child")
    child = cm_child.__enter__()
    clk.advance(1.0)
    # the child's __exit__ is skipped (an exception path) — closing the
    # outer span must close the leaked child and not corrupt parentage
    cm_outer.__exit__(None, None, None)
    assert child.t1 == outer.t1 == 1.0
    with tr.span("after") as sp:
        pass
    assert sp in tr.background  # a fresh root, not a child of the leak


def test_spans_on_other_threads_are_separate_roots():
    tr = tracing.enable()
    with tr.wave() as w:
        def off_thread():
            with tr.span("informer.frame.apply", cat="ingest"):
                pass
        t = threading.Thread(target=off_thread)
        t.start()
        t.join()
    assert w.children == []  # the other thread's span did not nest here
    assert tr.background[-1].name == "informer.frame.apply"
    assert tr.background[-1].tid != w.tid


def test_the_request_account_is_per_thread_and_record_adopts_nothing():
    assert tracing.account() is None
    acct = tracing.open_account()
    assert tracing.account() is acct
    other = []
    t = threading.Thread(target=lambda: other.append(tracing.account()))
    t.start()
    t.join()
    assert other == [None], "another thread's store call opens nothing"
    acct.add("server.body", 1.0, 1.5)
    assert acct.parts == [("server.body", 1.0, 0.5)]
    tracing.close_account()
    assert tracing.account() is None
    # parts of another process are filed as they are: two of one start
    # stay siblings (complete() would nest the second's elders in it)
    clk = FakeClock()
    tr = tracing.enable(clock=clk)
    with tr.span("remote.request", cat="client") as sp:
        tr.record(sp, "server.store_lock", 0.0, 0.0, cat="server")
        tr.record(sp, "server.store", 0.0, 0.0, cat="server")
    assert [c.name for c in sp.children] == ["server.store_lock",
                                             "server.store"]
    assert all(c.children == [] and c.tid == sp.tid for c in sp.children)


def test_flight_recorder_bounds_and_dump_dir(tmp_path):
    clk = FakeClock()
    tr = tracing.enable(clock=clk, ring_waves=2, max_dumps=2,
                        dump_dir=str(tmp_path))
    with tr.wave():
        clk.advance(1.0)
    tr.instant("frontier.alive", frac=0.5)
    for i in range(3):
        tr.dump(f"reason-{i}")
    assert len(tr.dumps) == 2 and tr.dropped_dumps == 1
    assert [d["reason"] for d in tr.dumps] == ["reason-1", "reason-2"]
    # every dump snapshots the wave ring + instants at dump time
    assert all(len(d["waves"]) == 1 for d in tr.dumps)
    assert tr.dumps[-1]["instants"][-1]["name"] == "frontier.alive"
    # dump_dir gets one JSON file per dump, valid JSON
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["flight_0001.json", "flight_0002.json",
                     "flight_0003.json"]
    with open(tmp_path / "flight_0003.json") as f:
        assert json.load(f)["reason"] == "reason-2"
    # reading the recorder must not fill it
    snap = tr.flight_snapshot()
    assert len(tr.dumps) == 2
    assert snap["dropped_dumps"] == 1 and len(snap["current"]["waves"]) == 1


def test_notify_hooks_never_crash_the_call_site():
    """The notify hooks sit on production paths (fault sites, the
    breaker, bind handling): a recorder failure must be swallowed and
    logged, never propagated into the behavior being observed."""
    tr = tracing.enable()

    def boom(*a, **k):
        raise RuntimeError("recorder bug")

    tr.dump = boom  # instance-level: only this tracer is broken
    tracing.notify_fault("store.commit", {"op": "x"}, "error")
    tracing.notify_breaker("degrade", ("k",), "pallas", "interpret")
    tracing.notify_requeue("default/p")
    assert len(tr.dumps) == 0  # nothing recorded, nothing raised


def test_requeue_dumps_coalesce_per_window():
    """A transient bind_many failure requeues every pod in the segment;
    each requeue records an instant, but only the first in the window
    pays for a full recorder dump — the recorder must not amplify the
    stall it is recording."""
    clk = FakeClock()
    tr = tracing.enable(clock=clk)
    for i in range(50):
        tracing.notify_requeue(f"default/p-{i}")
    assert len([d for d in tr.dumps if d["reason"] == "bind.requeue"]) == 1
    assert tr.coalesced_dumps == 49
    assert len([e for e in tr.instants
                if e["name"] == "bind.requeue"]) == 50  # per-pod timeline
    # a requeue in a LATER window dumps again
    clk.advance(tracing.REQUEUE_DUMP_COALESCE_S + 0.1)
    tracing.notify_requeue("default/p-late")
    assert len([d for d in tr.dumps if d["reason"] == "bind.requeue"]) == 2
    assert tr.flight_snapshot()["coalesced_dumps"] == 49
    # coalescing is per-reason: fault dumps are not throttled by it
    tracing.notify_fault("scheduler.bind", {}, "error")
    tracing.notify_fault("scheduler.bind", {}, "error")
    assert len([d for d in tr.dumps
                if d["reason"] == "fault:scheduler.bind"]) == 2


def test_notify_hooks_dump_with_reasons():
    tr = tracing.enable()
    tracing.notify_fault("scheduler.bind", {"via": "bind_many"}, "drop")
    tracing.notify_breaker("degrade", ("shape",), "interpret", "oracle")
    tracing.notify_requeue("default/p-0")
    assert [d["reason"] for d in tr.dumps] == [
        "fault:scheduler.bind", "breaker:degrade", "bind.requeue"]
    assert tr.dumps[0]["attrs"]["mode"] == "drop"
    assert tr.dumps[1]["attrs"]["frm"] == "interpret"
    # the instants ring carries the same triggers for the timeline view
    assert [e["name"] for e in tr.instants] == [
        "fault:scheduler.bind".replace(":", "."), "breaker.degrade",
        "bind.requeue"]


# =====================================================================
# 2. end-to-end correlation + Chrome export
# =====================================================================


def _mini_world(n_nodes=4, clock=None, **backend_kw):
    cs = Clientset(Store())
    for i in range(n_nodes):
        cs.nodes.create(make_node(f"n{i}", cpu="8", memory="16Gi"))
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo, **backend_kw)
    kw = {"clock": clock} if clock is not None else {}
    sched = Scheduler(cs, algorithm=algo, backend=backend, **kw)
    sched.start()
    return cs, sched, backend


def _txn_spans(doc):
    """txn id -> set of span names carrying it, from a Chrome export."""
    out: dict[str, set] = {}
    for ev in doc["traceEvents"]:
        txn = (ev.get("args") or {}).get("txn")
        if txn:
            out.setdefault(txn, set()).add(ev["name"])
    return out


@pytest.mark.timeout(120)
def test_end_to_end_txn_correlation():
    """The acceptance path: ONE exported trace in which a ``bind_many``
    txn id appears on the store's txn span, the informer's watch-frame
    apply span, and the scheduler's confirm span — the full
    store → informer → confirm propagation of one wave's binds."""
    tr = tracing.enable()
    cs, sched, _ = _mini_world()
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(12)])
    sched.pump()
    bound, failed = sched.schedule_pending_batch()
    assert bound == 12 and failed == 0
    sched.pump()  # digest the bind-confirm frame

    doc = tr.chrome_trace()
    txns = _txn_spans(doc)
    bind_txns = [t for t in txns if t.startswith("bind_many-")]
    assert bind_txns, f"no bind_many txn in the export: {sorted(txns)}"
    for txn in bind_txns:
        assert {"store.txn", "informer.frame.apply",
                "scheduler.confirm"} <= txns[txn], (txn, txns[txn])
    # the create txn correlates too (ADDED frame has no confirm hop
    # required — but the store and apply spans must share the id)
    create_txns = [t for t in txns if t.startswith("create_many-")]
    assert any({"store.txn", "informer.frame.apply"} <= txns[t]
               for t in create_txns)


@pytest.mark.parametrize("op,n,frames", [
    ("create_many", 9, 3), ("bind_many", 9, 3), ("coalesce_flush", 9, 3),
    ("bind_many", 4, 1), ("bind_many", 1, 0)])
def test_store_txn_span_counts_its_frames(monkeypatch, op, n, frames):
    """``store.txn`` of a batch txn or a coalescing flush carries
    ``frames``: the pieces of at most ``FRAME_MAX_ROWS`` rows it packed
    (0 where nothing was framed: a one-event txn goes out as the event),
    and ``store_watch_frames_total`` moves by the same number."""
    from kubernetes_tpu.api import BindingColumns
    from kubernetes_tpu.store import frames as frames_mod
    from kubernetes_tpu.utils.metrics import DEFAULT_STORE_METRICS

    monkeypatch.setattr(frames_mod, "FRAME_MAX_ROWS", 4)
    store = Store(coalesce_window_s=10.0 if op == "coalesce_flush" else 0.0)
    cs = Clientset(store)
    watch = store.watch("Pod", frames=True)
    pods = [make_pod(f"p{i}", cpu="100m") for i in range(n)]
    if op == "bind_many":
        cs.pods.create_many(pods)
    tr = tracing.enable()
    c0 = DEFAULT_STORE_METRICS.watch_frames.value
    d0 = DEFAULT_STORE_METRICS.bind_rows_deferred.value
    b0 = DEFAULT_STORE_METRICS.event_payloads_built.value
    if op == "create_many":
        cs.pods.create_many(pods)
    elif op == "bind_many":
        cs.pods.bind_many(BindingColumns([p.meta.key for p in pods],
                                         ["n0"] * len(pods)))
    else:
        for p in pods:
            cs.pods.create(p)
        store.flush_coalesced()
    spans = [sp for sp in tr.background if sp.name == "store.txn"
             and sp.attrs.get("op") == op]
    assert len(spans) == 1 and spans[0].attrs["frames"] == frames
    assert DEFAULT_STORE_METRICS.watch_frames.value - c0 == frames
    # a bind txn builds no watch payload at commit: its span says how
    # many rows it deferred, and the frames' columns are read without one
    assert spans[0].attrs.get("deferred") == (n if op == "bind_many" else None)
    assert DEFAULT_STORE_METRICS.bind_rows_deferred.value - d0 == (
        n if op == "bind_many" else 0)
    got = []
    while (item := watch.get(timeout=0)) is not None:
        got.append(item)
    if op == "bind_many" and frames:
        assert sum(len(f.keys) + len(f.node_names) + len(f.revisions)
                   for f in got[-frames:]) == 3 * n
        assert DEFAULT_STORE_METRICS.event_payloads_built.value == b0
        assert sum(len(f.objects) for f in got[-frames:]) == n
        assert DEFAULT_STORE_METRICS.event_payloads_built.value - b0 == n
    watch.stop()
    store.close()


@pytest.mark.timeout(120)
def test_chrome_export_validates_and_phases_derive_from_trace():
    tr = tracing.enable()
    cs, sched, _ = _mini_world()
    cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(8)])
    sched.pump()
    sched.schedule_pending_batch()

    # the per-wave phase dict is DERIVED from the wave's span tree: the
    # two can never disagree because they are the same clock reads
    wave = tr.ring[-1]
    totals = wave.phase_totals()
    for key in ("tensorize_s", "dispatch_s", "device_wait_s", "commit_s"):
        assert key in totals
        assert sched.last_batch_phases[key] == totals[key]
    assert wave.attrs["pods"] == 8 and wave.attrs["bound"] == 8

    # Chrome trace-event format: every event is a complete X duration
    # event or an i instant, microsecond timestamps, sorted, and the
    # whole document survives a JSON round-trip
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events
    for ev in events:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
        assert ev["pid"] == 1 and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        else:
            assert ev["s"] in ("t", "g")
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    round_trip = json.loads(json.dumps(doc))
    assert len(round_trip["traceEvents"]) == len(events)
    names = {e["name"] for e in events}
    assert {"store.txn", "tensorize", "dispatch", "commit"} <= names
    assert any(n.startswith("wave-") for n in names)


WAVE_CHILDREN = ["queue.drain", "snapshot", "priority_context", "host_state",
                 "segment_plan", "tensorize", "dispatch", "device_wait",
                 "place", "commit"]


@pytest.mark.timeout(120)
def test_the_wave_covers_the_loop_iteration_in_named_children():
    """The wave root starts at the drain and its children name every
    stretch of the iteration, in order and without overlap; the commit's
    and the tensorize's parts are children IN THE TREE (adopted by the
    after-the-fact ``complete``), and the new phase names leave the
    pump's ``apply_s`` alone."""
    from benchmark import trace_reduce
    from benchmark.layer_metrics import _gaps

    tr = tracing.enable()
    cs, sched, _ = _mini_world()

    def wave(tag):
        cs.pods.create_many([make_pod(f"{tag}{i}", cpu="100m")
                             for i in range(12)])
        sched.pump()
        assert sched.schedule_pending_batch() == (12, 0)
        return tr.ring[-1]

    first = wave("a")
    # an empty drain records no root
    assert sched.schedule_pending_batch() == (0, 0) and len(tr.ring) == 1
    second = wave("b")
    for root in (first, second):
        # the overlapped ingest ("prep") rides in the device's shadow
        kids = [c for c in root.children if c.name != "prep"]
        assert [c.name for c in kids] == WAVE_CHILDREN
        assert all(c.cat == "phase" for c in kids)
        assert root.t0 == kids[0].t0, "the root starts at the drain"
        for a, b in zip(root.children, root.children[1:]):
            assert a.t1 <= b.t0, (a.name, b.name)
        assert root.children[-1].t1 <= root.t1
        by = {c.name: c for c in kids}
        assert [c.name for c in by["commit"].children] == [
            "commit.assume", "commit.bind", "commit.finish"]
        assert [c.name for c in by["tensorize"].children] == [
            "tensorize.build_static", "tensorize.initial_state"]
        for parent in (by["commit"], by["tensorize"]):
            assert parent.t0 <= parent.children[0].t0
            assert parent.children[-1].t1 <= parent.t1
        assert all(c.attrs["pods"] == 12 for c in by["commit"].children)
        # the assume went by node: the 4 groups place made, all 12 pods
        assert by["commit"].children[0].attrs == {
            "pods": 12, "nodes": 4, "batched": 12}
        assert by["queue.drain"].attrs == {"pods": 12}
        assert by["snapshot"].attrs == {"nodes": 4}
        assert by["segment_plan"].attrs == {"pods": 12, "segments": 1,
                                            "disk_pods": 0}
        # the one segment's columns came from the wave's plan
        assert by["tensorize"].attrs["pods"] == 12
        assert by["tensorize"].attrs["planned"] == 12
        assert by["place"].attrs["pods"] == 12
        assert by["device_wait"].t1 == by["place"].t0
    by_first = {c.name: c for c in first.children}
    by_second = {c.name: c for c in second.children}
    assert by_first["host_state"].attrs == {"nodes": 4, "mode": "rebuild",
                                            "dirty_nodes": 4}
    assert by_second["host_state"].attrs["mode"] == "reconcile"
    # the results went on by node: each of the 4 touched nodes was cloned
    # once and took one batched call; 12 pods of one signature
    assert by_first["place"].attrs == {"pods": 12, "cloned_nodes": 4,
                                       "nodes": 4, "groups": 1}
    assert by_second["place"].attrs == by_first["place"].attrs
    assert sched.backend.stats["place_batched_pods"] == 24
    assert sched.metrics.assume_batched_pods.value == 24

    # the phase dict gains the new names and keeps the pump's apply_s
    totals = second.phase_totals()
    assert "apply_s" not in totals
    assert sched.last_batch_phases["apply_s"] == pytest.approx(
        second.attrs["apply_s"], abs=1e-6)
    for key in ("queue.drain_s", "host_state_s", "place_s", "commit.bind_s"):
        assert sched.last_batch_phases[key] == totals[key]

    # what no child names, of a warm wave: the set-up between the spans
    spans: list = []
    trace_reduce.flatten(second, second.attrs["wave"], spans, None)
    assert {s["parent"] for s in spans if s["name"].startswith("commit.")} \
        == {"commit"}
    booked = _gaps.book(spans)
    assert booked["waves"] == 1
    assert booked["idle_ns"] == pytest.approx(second.duration * 1e9, abs=2)
    assert booked["unnamed_ns"] / booked["idle_ns"] < 0.25


@pytest.mark.timeout(120)
def test_the_signature_rows_and_spread_counts_are_named_under_tensorize():
    """``tensorize.signature_rows`` lies in ``tensorize.build_static`` and
    ``tensorize.spread_counts`` in ``tensorize.initial_state``, each with
    the segment's real signatures; the services the selectors read are
    the priority context's; untraced, no span is kept."""
    from kubernetes_tpu.api import ObjectMeta, Service

    cs, sched, _ = _mini_world()
    # one service selects a signature of the wave; one selects in another
    # namespace, one selects nothing that exists
    for name, ns, app in (("web", "default", "web"), ("api", "other", "api"),
                          ("idle", "default", "nothing")):
        cs.services.create(Service(meta=ObjectMeta(name=name, namespace=ns),
                                   selector={"app": app}))
    tr = tracing.enable()

    def wave(tag):
        cs.pods.create_many([make_pod(f"{tag}{i}", cpu="100m",
                                      labels={"app": ("web", "api", "batch")[i % 3]})
                             for i in range(12)])
        sched.pump()
        assert sched.schedule_pending_batch() == (12, 0)

    for waves, tag in enumerate("ab", start=1):
        wave(tag)
        root = tr.ring[-1]
        tensorize = next(c for c in root.children if c.name == "tensorize")
        static, init = tensorize.children
        (rows,) = static.children
        (counts,) = init.children
        assert (rows.name, counts.name) == ("tensorize.signature_rows",
                                            "tensorize.spread_counts")
        for parent, child in ((static, rows), (init, counts)):
            assert parent.t0 <= child.t0 <= child.t1 <= parent.t1
            assert child.cat == "phase"
        # 3 real signatures on a padded axis of 32; 3 services, 1 selecting
        assert tensorize.attrs["groups"] == 32
        assert rows.attrs == {"groups": 3, "services": 3, "selectors": 1}
        # the placed pods' distinct labelmaps: none before the first wave
        assert counts.attrs == {"groups": 3, "labelmaps": 3 * (waves - 1)}

    tracing.disable()
    wave("c")
    assert len(tr.ring) == 2


@pytest.mark.timeout(120)
def test_remote_request_span_carries_the_servers_own_time():
    """Every JSON response of the apiserver carries ``Server-Timing``,
    tracing on or off; with tracing on a ``bind_many`` leaves ONE
    ``remote.request`` span holding the server's and the store's own
    time inside the round trip; tracing off leaves no span and the same
    bindings."""
    import urllib.error
    import urllib.request

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.remote import RemoteStore
    from benchmark import run, trace_reduce

    server = APIServer(Store())
    server.start()
    try:
        remote = RemoteStore(server.url)
        cs = Clientset(remote)
        cs.nodes.create(make_node("n0", cpu="8", memory="16Gi"))
        cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(6)])

        def timing(path):
            try:
                with urllib.request.urlopen(f"{server.url}{path}") as r:
                    return r.headers["Server-Timing"]
            except urllib.error.HTTPError as e:
                return e.headers["Server-Timing"]

        # /healthz makes no store call; a LIST and a failed GET do
        assert timing("/healthz").startswith("handle;dur=")
        assert "store" not in timing("/healthz")
        for path in ("/api/v1/pods", "/api/v1/namespaces/default/pods/none"):
            handle, store = timing(path).split(", ")
            assert handle.startswith("handle;dur=") and store.startswith("store;dur=")
            assert 0 < float(store[10:]) <= float(handle[11:])

        assert tracing.current() is None
        assert remote.bind_many([f"default/p{i}" for i in range(3)],
                                ["n0"] * 3) == [None] * 3
        tr = tracing.enable()
        assert remote.bind_many([f"default/p{i}" for i in range(3, 6)],
                                ["n0"] * 3) == [None] * 3
        pods, _ = cs.pods.list()
        assert {p.meta.name: p.spec.node_name for p in pods} == {
            f"p{i}": "n0" for i in range(6)}

        binds = [sp for sp in tr.background if sp.name == "remote.request"
                 and sp.attrs["path"] == "/api/v1/bindings:batch"]
        assert len(binds) == 1, "one span per request, none with tracing off"
        sp = binds[0]
        a = sp.attrs
        assert (a["method"], a["items"], a["status"], a["attempts"]) == (
            "POST", 3, 200, 1)
        assert a["bytes_out"] > 0 and a["bytes_in"] > 0
        assert 0 < a["store_s"] <= a["server_s"] <= sp.duration
        assert "encode_s" not in a and "decode_s" not in a
        enc, dec = (c for c in sp.children if c.cat == "client")
        assert (enc.name, dec.name) == ("client.encode", "client.decode")
        assert sp.t0 <= enc.t0 <= enc.t1 <= dec.t0 <= dec.t1 <= sp.t1
        assert 0 <= enc.duration + dec.duration <= sp.duration

        # a refused request: the span says so, and still has the header
        with pytest.raises(Exception):
            cs.pods.get("none")
        missed = [s for s in tr.background if s.name == "remote.request"
                  and s.attrs.get("status") == 404]
        assert len(missed) == 1 and "NotFound" in missed[0].attrs["error"]
        assert missed[0].attrs["server_s"] > 0

        # the readers of the bind round trip, on the program's own span
        # under the commit's bind (as the wave tree holds it)
        spans: list = []
        trace_reduce.flatten(sp, None, spans, "commit.bind")
        facts = {"spans": spans}
        rtt = run.read_layer_metric("bind_rtt_us_per_pod", facts)
        srv = run.read_layer_metric("bind_server_us_per_pod", facts)
        assert rtt == pytest.approx(sp.duration * 1e6 / 3)
        assert 0 < srv <= rtt
    finally:
        server.stop()


@pytest.mark.timeout(60)
def test_remote_request_span_carries_the_collectors_pause():
    """Where the daemon's collector policy is installed (as
    ``apiserver/__main__.py`` installs it) every answer's ``Server-Timing``
    has a third field, and it lands on the ``remote.request`` span as
    ``gc_s``: present, not negative, inside the server's own time.  So a
    stall of the apiserver's collector shows in the scheduler's wave tree."""
    import gc

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.apiserver.collector import Collector
    from kubernetes_tpu.client.remote import RemoteStore

    class StoreWithAPass(Store):
        def bind_many(self, keys, node_names):
            gc.collect()  # a full pass begun inside the request
            return super().bind_many(keys, node_names)

    store = StoreWithAPass()
    server = APIServer(store)
    server.collector = Collector(lambda: store.revision, server.registry)
    server.collector.install()
    server.start()
    try:
        remote = RemoteStore(server.url)
        cs = Clientset(remote)
        cs.nodes.create(make_node("n0", cpu="8", memory="16Gi"))
        cs.pods.create_many([make_pod(f"p{i}", cpu="100m") for i in range(6)])
        tr = tracing.enable()
        assert remote.bind_many([f"default/p{i}" for i in range(6)],
                                ["n0"] * 6) == [None] * 6
        sp, = (s for s in tr.background if s.name == "remote.request"
               and s.attrs["path"] == "/api/v1/bindings:batch")
        a = sp.attrs
        assert 0 < a["gc_s"] <= a["store_s"] <= a["server_s"] <= sp.duration
        cs.pods.list()
        lists = [s for s in tr.background if s.name == "remote.request"
                 and s.attrs["path"].startswith("/api/v1/pods")]
        assert lists and all(s.attrs["gc_s"] == 0 for s in lists), (
            "no pass fell inside a LIST")
        assert server.registry.get("apiserver_gc_freezes_total").value >= 1
    finally:
        server.stop()
        server.collector.uninstall()
    assert gc.get_freeze_count() == 0


# -- the apiserver's parts of a request (PR 37) ------------------------------

PARTS = ("server.body", "server.parse", "server.store_lock", "server.store",
         "server.answer")


def _parts(sp) -> list:
    return [c for c in sp.children if c.cat == "server"]


def _assert_parts_account_for_the_request(sp, within_span: bool) -> None:
    """The server's parts are in order, do not overlap, sum to at most its
    ``server_s``; the lock's wait and the hold are ``store_s``.  Where the
    server is another process, each lies inside the client's round trip:
    one clock across processes."""
    a = sp.attrs
    parts = _parts(sp)
    assert parts and {p.name for p in parts} <= set(PARTS)
    for prev, nxt in zip(parts, parts[1:]):
        assert prev.t0 <= prev.t1 <= nxt.t0 + 1e-9, (prev.name, nxt.name)
    assert sum(p.duration for p in parts) <= a["server_s"] + 1e-6
    store = sum(p.duration for p in parts if p.name.startswith("server.store"))
    assert store == pytest.approx(a["store_s"], abs=1e-6)
    assert [p.name for p in parts][-1] == "server.answer"
    assert 0 < a["server_cpu_s"] and a["watch_s"] >= 0
    assert a["watch_encode_s"] >= 0
    if within_span:
        for p in parts:
            assert sp.t0 <= p.t0 and p.t1 <= sp.t1, p.name
        enc, dec = (c for c in sp.children if c.cat == "client")
        assert enc.t1 <= parts[0].t0 and parts[-1].t1 <= dec.t0


def _timing(url: str, path: str, data=None, headers=None) -> str:
    import urllib.error
    import urllib.request

    req = urllib.request.Request(f"{url}{path}", data=data,
                                 headers=headers or {},
                                 method="POST" if data is not None else "GET")
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.headers["Server-Timing"]
    except urllib.error.HTTPError as e:
        return e.headers["Server-Timing"]


@pytest.mark.timeout(60)
@pytest.mark.parametrize("path,body,fields", [
    ("/healthz", None, ["handle"]),
    ("/api/v1/pods", None, ["handle", "store"]),
    ("/api/v1/namespaces/default/pods/none", None, ["handle", "store"]),
    ("/api/v1/bindings:batch",
     {"keys": ["default/p0"], "nodeNames": ["n0"]}, ["handle", "store"]),
    ("/api/v1/pods:batch", {"items": []}, ["handle", "store"]),
])
def test_a_request_that_does_not_ask_gets_the_plain_server_timing(
        path, body, fields):
    """No ``tracing.PARTS_HEADER``: the header is the parent's, field for
    field and digit for digit, with tracing on in the process or off."""
    import re

    from kubernetes_tpu.apiserver import APIServer

    store = Store()
    store.create("Pod", make_pod("p0", cpu="100m").to_dict())
    server = APIServer(store)
    server.start()
    try:
        data = json.dumps(body).encode() if body is not None else None
        plain = re.compile(r"^handle;dur=\d+\.\d{3}(, store;dur=\d+\.\d{3})?$")
        for on in (False, True):
            if on:
                tracing.enable()
            got = _timing(server.url, path, data,
                          {"Content-Type": "application/json"})
            assert plain.match(got), got
            assert [f.partition(";")[0] for f in got.split(", ")] == fields
        asked = _timing(server.url, path, data,
                        {"Content-Type": "application/json",
                         tracing.PARTS_HEADER: "1"})
        names = [f.partition(";")[0] for f in asked.split(", ")]
        assert names[:len(fields)] == fields
        assert names[-4:] == ["server.answer", "cpu", "watch",
                              "watch_encode"]
    finally:
        server.stop()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("verb", ["bind_many", "create_many", "list"])
def test_a_traced_request_carries_the_servers_parts_in_process(verb):
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.remote import RemoteStore

    server = APIServer(Store())
    server.start()
    try:
        remote = RemoteStore(server.url)
        remote.create_many("Pod", [make_pod(f"p{i}", cpu="100m").to_dict()
                                   for i in range(200)])
        tr = tracing.enable()
        if verb == "bind_many":
            assert remote.bind_many([f"default/p{i}" for i in range(200)],
                                    ["n0"] * 200) == [None] * 200
        elif verb == "create_many":
            assert None not in remote.create_many(
                "Pod", [make_pod(f"q{i}").to_dict() for i in range(200)])
        else:
            assert len(remote.list("Pod")[0]) == 200
        sp, = (s for s in tr.background if s.name == "remote.request")
        _assert_parts_account_for_the_request(sp, within_span=True)
        names = [p.name for p in _parts(sp)]
        body = ["server.body", "server.parse"] if verb != "list" else []
        rows = ["server.parse"] if verb != "list" else []
        assert names == body + rows + ["server.store_lock", "server.store",
                                       "server.answer"]
    finally:
        server.stop()


@pytest.mark.timeout(60)
def test_a_request_without_tracing_sends_no_ask_and_records_nothing():
    """Tracing off in the client: no header goes out, so the server opens no
    account, and nothing is recorded on either side."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.remote import RemoteStore

    seen = []

    class Spy(Store):
        def bind_many(self, keys, node_names):
            seen.append(tracing.account())
            return super().bind_many(keys, node_names)

    store = Spy()
    store.create("Pod", make_pod("p0").to_dict())
    server = APIServer(store)
    server.start()
    try:
        assert RemoteStore(server.url).bind_many(["default/p0"], ["n0"]) == [None]
        tr = tracing.enable()
        assert RemoteStore(server.url).bind_many(["default/p0"], ["n0"]) == [None]
        assert seen[0] is None and seen[1] is not None
        assert tracing.account() is None, "the test's thread never had one"
        sp, = (s for s in tr.background if s.name == "remote.request")
        assert [p.name for p in _parts(sp)][-3:] == [
            "server.store_lock", "server.store", "server.answer"]
    finally:
        server.stop()


@pytest.mark.timeout(60)
def test_a_planted_holder_of_the_stores_lock_shows_as_store_lock():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.remote import RemoteStore

    store = Store()
    store.create_many("Pod", [make_pod(f"p{i}").to_dict() for i in range(50)])
    server = APIServer(store)
    server.start()
    held = threading.Event()

    def hold():
        with store._mu:
            held.set()
            time.sleep(0.4)

    holder = threading.Thread(target=hold)
    try:
        remote = RemoteStore(server.url)
        tr = tracing.enable()
        holder.start()
        held.wait(5)
        assert remote.bind_many([f"default/p{i}" for i in range(50)],
                                ["n0"] * 50) == [None] * 50
        holder.join()
        sp, = (s for s in tr.background if s.name == "remote.request")
        _assert_parts_account_for_the_request(sp, within_span=True)
        by = {p.name: p.duration for p in _parts(sp)}
        assert by["server.store_lock"] >= 0.2
        assert by["server.store"] < by["server.store_lock"]
        # waiting for a lock is not running
        assert sp.attrs["server_cpu_s"] < sp.attrs["server_s"] - 0.2
    finally:
        server.stop()


@pytest.mark.timeout(180)
def test_a_frames_watcher_encoding_a_bind_shows_as_watch_s_and_less_cpu():
    """A frames watcher encodes a 20,000-row bind's frames while a second
    request runs: its ``watch_s`` is that encode and write, and its thread
    ran a smaller share of its time than the same request alone."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client.remote import RemoteStore

    n = 20_000
    store = Store()
    store.create_many("Pod", [make_pod(f"p{i}", cpu="100m").to_dict()
                              for i in range(n)])
    server = APIServer(store)
    server.start()
    remote = RemoteStore(server.url, timeout=60)
    watch = remote.watch("Pod", from_revision=store.revision, frames=True)
    try:
        tr = tracing.enable()
        alone = []
        for _ in range(2):
            remote.list("Pod")
            sp = [s for s in tr.background if s.name == "remote.request"][-1]
            assert sp.attrs["watch_s"] == 0
            alone.append(sp.attrs["server_cpu_s"] / sp.attrs["server_s"])
        served = server.registry.get("apiserver_watch_serve_seconds_total")
        before = served.value
        store.bind_many([f"default/p{i}" for i in range(n)], ["n0"] * n)
        # requests back to back while the frames are encoded and written:
        # a frame is counted by the request in which its write ended
        beside = []
        for _ in range(12):
            remote.list("Pod")
            sp = [s for s in tr.background if s.name == "remote.request"][-1]
            _assert_parts_account_for_the_request(sp, within_span=True)
            beside.append(sp)
            if sp.attrs["watch_s"] > 0:
                break
        got = 0
        while got < n:
            frame = watch.get(timeout=30)
            assert frame is not None
            got += len(frame.keys)
        sp = max(beside, key=lambda s: s.attrs["watch_s"])
        assert sp.attrs["watch_s"] > 0
        assert sp.attrs["server_cpu_s"] / sp.attrs["server_s"] < max(alone)
        assert sum(s.attrs["watch_s"] for s in beside) <= (
            served.value - before + 1e-6)
        encoded = server.registry.get("apiserver_watch_encode_seconds_total")
        assert sum(s.attrs["watch_encode_s"] for s in beside) <= (
            encoded.value + 1e-6)
        assert 0 < encoded.value <= served.value
    finally:
        watch.stop()
        server.stop()


@pytest.mark.timeout(90)
def test_the_parts_of_a_child_apiserver_lie_inside_the_clients_round_trip():
    """The apiserver as its own process (the daemon, as the benchmark starts
    it): each part's ``[t, t + dur]``, read on the server's clock, lies
    inside the client's ``remote.request``: the host has one clock."""
    import socket
    import subprocess
    import sys
    import urllib.request

    from kubernetes_tpu.client.remote import RemoteStore

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{os.environ.get('PYTHONPATH', '')}")
    child = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.apiserver", "--host",
         "127.0.0.1", "--port", str(port)], env=env, cwd=root,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 30
        while True:
            try:
                urllib.request.urlopen(f"{url}/healthz", timeout=1).read()
                break
            except OSError:
                assert child.poll() is None and time.monotonic() < deadline
                time.sleep(0.05)
        plain = _timing(url, "/api/v1/pods")
        assert [f.partition(";")[0] for f in plain.split(", ")] == [
            "handle", "store", "gc"]
        remote = RemoteStore(url)
        tr = tracing.enable()
        assert None not in remote.create_many(
            "Pod", [make_pod(f"p{i}", cpu="100m").to_dict() for i in range(500)])
        assert remote.bind_many([f"default/p{i}" for i in range(500)],
                                ["n0"] * 500) == [None] * 500
        assert len(remote.list("Pod")[0]) == 500
        spans = [s for s in tr.background if s.name == "remote.request"]
        assert len(spans) == 3
        for sp in spans:
            _assert_parts_account_for_the_request(sp, within_span=True)
            assert 0 <= sp.attrs["gc_s"] <= sp.attrs["server_s"]
    finally:
        child.terminate()
        child.wait(timeout=10)


@pytest.mark.timeout(60)
def test_debug_endpoints_serve_traces_and_flightrecorder():
    """The daemon health server's ``/debug/traces`` (Chrome export) and
    ``/debug/flightrecorder`` endpoints — and their honest
    ``{"enabled": false}`` answer when tracing is off, so probing them
    never perturbs a production daemon."""
    import urllib.request

    from kubernetes_tpu.daemon import serve_health

    server = serve_health(0)
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.local_port}{path}",
                    timeout=5) as resp:
                return json.loads(resp.read())

        assert get("/debug/traces") == {"enabled": False}
        assert get("/debug/flightrecorder") == {"enabled": False}

        tr = tracing.enable()
        with tr.wave(pods=1):
            with tr.span("tensorize", cat="phase"):
                pass
        tr.dump("fault:store.commit", mode="error")
        doc = get("/debug/traces")
        names = {e["name"] for e in doc["traceEvents"]}
        assert "wave-1" in names and "tensorize" in names
        snap = get("/debug/flightrecorder")
        assert snap["enabled"] is True
        assert [d["reason"] for d in snap["dumps"]] == ["fault:store.commit"]
        assert len(snap["current"]["waves"]) == 1
    finally:
        server.stop()


# =====================================================================
# 3. dump-on-fault: the matrix points and the breaker ladder
# =====================================================================

# points whose fire site runs INSIDE an open scheduling wave (the wave
# span must be LIVE in the dump); everything else fires on watch/pump/
# arrival paths where the dump carries the completed-wave ring instead
_IN_WAVE = {"scheduler.bind", "backend.pallas.segment", "backend.compact",
            "scheduler.pipeline.prep", "store.commit"}


def _has_wave(span_dicts, require_open=False):
    for d in span_dicts:
        if d.get("cat") == "wave" and (not require_open or d["t1"] is None):
            return True
    return False


def _warm_then_fire(point, scenario, tmp_path):
    """Run the matrix scenario's world with tracing on: a fault-free
    warm phase completes ≥1 wave into the recorder ring, then the plan
    arms and fresh workload drives the point's natural trigger path."""
    tr = tracing.current()
    server = None
    if scenario["world"] == "remote":
        from kubernetes_tpu.apiserver import APIServer

        server = APIServer(Store())
        server.start()
    w = None
    try:
        w = World(server=server)
        realtime = scenario["world"] == "remote"
        for i in range(8):
            w.cs.pods.create(make_pod(f"warm-{i:03d}", cpu="200m",
                                      memory="256Mi"))
        w.drive(rounds=4, relist_every=0, realtime=realtime)
        assert len(tr.ring) >= 1, "warm phase completed no wave"
        plan = FaultPlan(seed=42).on(point, FaultSpec(**scenario["spec"]))
        with plan.armed():
            for i in range(16):
                w.cs.pods.create(make_pod(f"work-{i:03d}", cpu="200m",
                                          memory="256Mi"))
            w.drive(rounds=8, relist_every=4, realtime=realtime)
        assert plan.fired.get(point, 0) > 0, f"{point}: fault never fired"
    finally:
        if server is not None:
            # watchers first: an orphaned watcher retrying a dead port
            # emits reconnect instants into later tests' tracing
            if w is not None:
                w.sched.informers.stop_all()
            server.stop()


def _wal_fire(point, tmp_path):
    w = World(data_dir=str(tmp_path / "state"))
    for i in range(8):
        w.cs.pods.create(make_pod(f"warm-{i:03d}", cpu="200m",
                                  memory="256Mi"))
    w.drive(rounds=4, relist_every=0)
    assert len(tracing.current().ring) >= 1
    plan = FaultPlan(seed=3).on(point, mode="torn", value=0.5)
    with plan.armed():
        with pytest.raises(FaultInjected):
            w.cs.pods.create(make_pod("marker", cpu="100m"))
    assert plan.fired[point] == 1
    w.store.close()


def _telemetry_fire(point):
    """telemetry.ship fires inside the shipper's drain, off every wave
    path: warm waves fill the ring first, then a scrape batch is
    offered and drained synchronously with the plan armed."""
    from kubernetes_tpu.utils import telemetry, timeseries

    w = World()
    for i in range(8):
        w.cs.pods.create(make_pod(f"warm-{i:03d}", cpu="200m",
                                  memory="256Mi"))
    w.drive(rounds=4, relist_every=0)
    assert len(tracing.current().ring) >= 1, "warm phase completed no wave"
    plan = FaultPlan(seed=3).on(point, mode="error")
    try:
        store = timeseries.enable(w.sched.metrics.registry, interval_s=1.0,
                                  clock=w.clock, start_thread=False)
        shp = telemetry.enable(telemetry.FileSink(os.devnull),
                               registry=w.sched.metrics.registry,
                               start_thread=False, retries=1,
                               backoff_s=0.0, sleep=lambda s: None)
        store.add_observer(telemetry.timeseries_observer(shp))
        with plan.armed():
            store.sample_once()  # scrape -> observer -> offer
            shp.drain_all()  # every ship attempt hits the armed point
        assert plan.fired[point] > 0, f"{point}: fault never fired"
    finally:
        telemetry.disable()
        timeseries.disable()


def _admit_fire(point):
    """apiserver.admit fires in the HTTP handler's admission gate, off
    every wave path: warm waves fill the recorder ring first, then a
    remote create hits the armed gate (dropped to 429 + Retry-After;
    the client's retry lands it)."""
    from kubernetes_tpu.apiserver import APIServer

    server = APIServer(Store())
    server.start()
    w = None
    try:
        w = World(server=server)
        for i in range(8):
            w.cs.pods.create(make_pod(f"warm-{i:03d}", cpu="200m",
                                      memory="256Mi"))
        w.drive(rounds=4, relist_every=0, realtime=True)
        assert len(tracing.current().ring) >= 1, "warm phase completed no wave"
        plan = FaultPlan(seed=3).on(point, mode="drop", value=0.05,
                                    first_n=1)
        rcs = Clientset(w.remote)  # the gate only sees HTTP create paths
        with plan.armed():
            rcs.pods.create(make_pod("admit-marker", cpu="100m"))
        assert plan.fired.get(point, 0) == 1, f"{point}: fault never fired"
    finally:
        if w is not None:
            w.sched.informers.stop_all()
        server.stop()


def _coalesce_fire(point):
    """store.coalesce fires in the store's window flush, off every wave
    path: warm waves on a coalescing store fill the recorder ring, then
    creates open a window and an armed ``flush_coalesced()`` closes it."""
    w = World(store=Store(coalesce_window_s=30.0))
    try:
        for i in range(8):
            w.cs.pods.create(make_pod(f"warm-{i:03d}", cpu="200m",
                                      memory="256Mi"))
        w.store.flush_coalesced()
        w.drive(rounds=4, relist_every=0)
        assert len(tracing.current().ring) >= 1, "warm phase completed no wave"
        w.store.flush_coalesced()     # the binds' own window, unarmed
        plan = FaultPlan(seed=5).on(point, mode="error", nth=1)
        with plan.armed():
            for i in range(4):
                w.cs.pods.create(make_pod(f"work-{i:03d}", cpu="200m",
                                          memory="256Mi"))
            w.store.flush_coalesced()
        assert plan.fired[point] == 1, f"{point}: fault never fired"
    finally:
        w.store.close()


@pytest.mark.timeout(180)
@pytest.mark.parametrize("point", sorted(MATRIX))
def test_every_fault_point_dumps_the_firing_waves_trace(point, tmp_path):
    """The acceptance bar: EVERY fault-matrix point, when it fires with
    tracing on, produces a flight-recorder dump that contains the firing
    wave's trace — live (still-open root) for faults that fire inside
    the wave, the completed-wave ring for watch/pump/arrival faults.

    Convergence under each fault is ``test_faults``' job; this matrix
    proves the OBSERVABILITY contract on the same scenarios."""
    scenario = MATRIX[point]
    tr = tracing.enable()
    if scenario["world"] == "wal":
        _wal_fire(point, tmp_path)
    elif scenario["world"] == "telemetry":
        _telemetry_fire(point)
    elif scenario["world"] == "admit":
        _admit_fire(point)
    elif scenario["world"] == "coalesce":
        _coalesce_fire(point)
    else:
        _warm_then_fire(point, scenario, tmp_path)

    dumps = [d for d in tr.dumps if d["reason"] == f"fault:{point}"]
    assert dumps, (f"{point}: no flight-recorder dump "
                   f"(saw {[d['reason'] for d in tr.dumps]})")
    d = dumps[0]  # the FIRST firing's dump (later ones may differ)
    assert _has_wave(d["waves"]) or _has_wave(d["live"]), (
        f"{point}: dump carries no wave trace")
    if point in _IN_WAVE:
        assert _has_wave(d["live"], require_open=True), (
            f"{point}: fault fired inside a wave but the dump has no "
            f"live wave span")
    if point == "scheduler.bind":
        # the dropped bind also requeues: that is its own trigger
        assert any(x["reason"] == "bind.requeue" for x in tr.dumps)
    if point == "store.coalesce":
        # the seam fires inside the flush's own span: live in the dump
        # (the store's thread has no wave open), folded count and all
        flush = [x for x in d["live"] if x["name"] == "store.txn"
                 and x["attrs"].get("op") == "coalesce_flush"]
        assert flush and flush[0]["t1"] is None
        assert flush[0]["attrs"]["events"] == 4
        assert flush[0]["attrs"]["folded"] == 0


@pytest.mark.timeout(180)
def test_every_breaker_transition_dumps_the_firing_waves_trace():
    """Degrade (interpret → oracle) and the cool-down re-probe restore
    each produce a dump whose live section holds the open wave — one
    dump per transition, matching the backend's transition counter."""
    clock = FakeClock()
    tr = tracing.enable()
    # built explicitly (not via _mini_world): the backend needs the fake
    # clock so the cool-down window is test-controlled
    cs = Clientset(Store())
    for i in range(4):
        cs.nodes.create(make_node(f"n{i}", cpu="64", memory="128Gi"))
    algo = GenericScheduler()
    backend = TPUBatchBackend(algorithm=algo, kernel_impl="xla",
                              pallas_max_failures=1, breaker_cooldown=30.0,
                              clock=clock)
    sched = Scheduler(cs, algorithm=algo, backend=backend, clock=clock)
    sched.start()

    def wave(tag, n=6):
        cs.pods.create_many([make_pod(f"{tag}-{i}", cpu="100m")
                             for i in range(n)])
        sched.pump()
        sched.schedule_pending_batch()
        sched.pump()

    # wave 1: injected interpret failure → one strike trips the shape
    # to the oracle rung (degrade transition, dump taken mid-wave)
    plan = FaultPlan().on("backend.pallas.segment", mode="error",
                          match={"impl": "interpret"}, first_n=1)
    with plan.armed():
        wave("a")
    assert backend.stats["interpret_fallbacks"] >= 1
    assert backend.stats["breaker_transitions"] == 1

    # wave 2: inside the cool-down the shape stays on oracle (no
    # transition, no new breaker dump)
    wave("b")
    assert backend.stats["breaker_transitions"] == 1

    # wave 3: cool-down elapsed → half-open probe succeeds → restore
    clock.advance(31.0)
    wave("c")
    assert backend.stats["breaker_transitions"] == 2

    breaker_dumps = [d for d in tr.dumps
                     if d["reason"].startswith("breaker:")]
    assert len(breaker_dumps) == backend.stats["breaker_transitions"]
    kinds = [d["reason"] for d in breaker_dumps]
    assert kinds[0] == "breaker:degrade" and kinds[1] == "breaker:restore"
    for d in breaker_dumps:
        assert _has_wave(d["live"], require_open=True), (
            f"{d['reason']}: no live wave span in the dump")
        assert d["attrs"]["frm"] in ("pallas", "interpret", "oracle")
        assert d["attrs"]["to"] in ("pallas", "interpret", "oracle")


# =====================================================================
# 4. utils/trace.py fold — log_if_long on the shared span layer
# =====================================================================


def test_log_if_long_over_threshold_logs_step_deltas(caplog):
    clk = FakeClock()
    t = Trace("schedule_one", clock=clk)
    clk.advance(0.120)
    t.step("predicates done")
    clk.advance(0.030)
    t.step("priorities done")
    clk.advance(0.010)
    with caplog.at_level("INFO", logger="kubernetes_tpu.trace"):
        t.log_if_long(0.100)
    assert len(caplog.records) == 1
    msg = caplog.records[0].message
    assert 'Trace "schedule_one" (total 160.0ms):' in msg
    assert "+120.0ms predicates done" in msg
    assert "+30.0ms priorities done" in msg  # DELTA from the prior step


def test_log_if_long_under_threshold_is_silent(caplog):
    clk = FakeClock()
    t = Trace("schedule_one", clock=clk)
    t.step("fast")
    clk.advance(0.010)
    with caplog.at_level("INFO", logger="kubernetes_tpu.trace"):
        t.log_if_long(0.100)
    assert caplog.records == []


def test_trace_lands_in_active_tracer_with_steps():
    clk = FakeClock()
    tr = tracing.enable(clock=clk)
    t = Trace("schedule_one", clock=clk)
    clk.advance(0.5)
    t.step("scored")
    t.log_if_long(10.0)  # under threshold: silent, but still recorded
    recorded = [s for s in tr.background if s.name == "schedule_one"]
    assert len(recorded) == 1
    assert recorded[0].cat == "trace"
    assert recorded[0].steps == [(0.5, "scored")]
    assert recorded[0].duration == pytest.approx(0.5)
    # a second log_if_long call must not double-record
    t.log_if_long(10.0)
    assert len([s for s in tr.background if s.name == "schedule_one"]) == 1


def test_format_slow_renders_total_and_step_deltas():
    out = tracing.format_slow("op", 1.0, [(1.2, "a"), (1.5, "b")], 1.6)
    assert out.splitlines() == [
        'Trace "op" (total 600.0ms):',
        "  +200.0ms a",
        "  +300.0ms b",
    ]
