"""Shared informer: LIST+WATCH → local indexed cache → handler fan-out.

The reference's list-watch-cache stack
(``client-go/tools/cache``: ``reflector.go:239 ListAndWatch``,
``shared_informer.go:182 Run`` + ``processorListener :537``) collapsed into
one component: list to seed the cache at a revision, watch from that
revision, apply deltas to an indexed local store, and fan events out to any
number of handlers (SURVEY.md P4).

Two drive modes:

- ``start()`` — background thread, production-shaped;
- ``pump()`` — synchronously drain pending watch events on the caller's
  thread.  Deterministic tests and single-threaded control loops use this;
  it is the informer analogue of running the event loop manually.

Objects handed to handlers are shared and MUST NOT be mutated.  With
``mutation_detector=True`` the informer snapshots each object and panics on
divergence — the reference's ``KUBE_CACHE_MUTATION_DETECTOR``
(``tools/cache/mutation_detector.go``).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

from .. import faults
from ..api import lazy as lazy_mod
from ..api import types as api
from ..store.frames import FRAME, WatchFrame
from ..store.store import (
    ADDED,
    DELETED,
    MODIFIED,
    WATCH_GAP,
    ExpiredRevisionError,
    WatchEvent,
)
from ..utils import tracing
from ..utils.metrics import DEFAULT_CLIENT_METRICS, ClientMetrics
from .clientset import TypedClient

logger = logging.getLogger("kubernetes_tpu.client.informer")


class Handler:
    def __init__(
        self,
        on_add: Optional[Callable] = None,
        on_update: Optional[Callable] = None,
        on_delete: Optional[Callable] = None,
        on_batch: Optional[Callable] = None,
    ):
        self.on_add = on_add or (lambda obj: None)
        self.on_update = on_update or (lambda old, new: None)
        self.on_delete = on_delete or (lambda obj: None)
        # batch-aware handlers receive a whole watch frame in ONE call:
        # ``on_batch(frame, deltas)`` with deltas = [(type, old, new, i)]
        # (i indexes the frame's columns — dropped/fenced events are
        # absent).  Handlers without it get the per-event callbacks for
        # every framed event, so frames never change handler semantics.
        self.on_batch = on_batch


class SharedInformer:
    def __init__(self, client: TypedClient, mutation_detector: bool = False,
                 metrics: Optional[ClientMetrics] = None,
                 compact_on_resync: bool = False):
        self._client = client
        self.kind = client.kind
        self._handlers: list[Handler] = []
        self._cache: dict[str, object] = {}  # key -> typed object
        self._mu = threading.RLock()
        self._synced = threading.Event()
        self._watch = None
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()
        self._mutation_detector = mutation_detector
        self._snapshots: dict[str, dict] = {}
        self.last_revision = 0
        self.metrics = metrics or DEFAULT_CLIENT_METRICS
        # per-instance recovery audit trail (the fault matrix reads this)
        # + ingest-decode observability (the scheduler deltas decode_s
        # per wave; decode_errors is the informer.decode recovery signal)
        self.stats = {"relists": 0, "dropped_events": 0, "handler_errors": 0,
                      "relist_failures": 0, "decode_errors": 0,
                      "decoded_events": 0, "decode_s": 0.0,
                      # batched watch frames (ISSUE 6): frames applied,
                      # events they carried, frames lost whole (→ gap),
                      # cumulative apply time (cache + handler fan-out) —
                      # the scheduler's per-wave pump_apply delta source —
                      # and promote-and-drop-raw sweeps
                      "frames": 0, "frame_events": 0, "batch_errors": 0,
                      "apply_s": 0.0, "compactions": 0}
        # ROADMAP carried item (ISSUE 7 satellite): with the flag on,
        # every successful relist/resync ends with a promote-and-drop-raw
        # sweep, so a long-lived deployment's cache stops pinning wire
        # payloads without anyone calling compact_cache() by hand
        self.compact_on_resync = compact_on_resync
        # serializes relist(): a resync timer tick racing a GAP
        # escalation must not build two watches and leak the loser
        self._relist_mu = threading.Lock()
        # set when a relist attempt failed (apiserver briefly down):
        # pump()/_run_loop retry on their next turn instead of leaving
        # the informer wedged on a dead watch serving a frozen cache
        self._gap_pending = False

    # -- registration ------------------------------------------------------
    def add_handler(self, handler: Handler) -> None:
        # snapshot under the lock, replay OUTSIDE it (the same contract
        # _deliver's callers follow): handler code under _mu could call
        # back into get()/list() and deadlock, or stall every other
        # informer client behind a slow on_add.  A delta applied between
        # the release and the replay may reach the handler before its
        # replayed add — the same at-least-once ordering client-go's
        # shared informers give a late-registered handler.
        with self._mu:
            self._handlers.append(handler)
            replay = list(self._cache.values()) if self._synced.is_set() else []
        for obj in replay:
            self._deliver(handler.on_add, obj)

    # -- cache reads (the Lister/Indexer surface) --------------------------
    def get(self, key: str):
        with self._mu:
            return self._cache.get(key)

    def list(self) -> list:
        with self._mu:
            return list(self._cache.values())

    def keys(self) -> list[str]:
        with self._mu:
            return list(self._cache.keys())

    def has_synced(self) -> bool:
        return self._synced.is_set()

    # -- lifecycle ---------------------------------------------------------
    def _list(self):
        """LIST through the cheapest available path: the store's packed
        column batch (zero-copy views + precomputed identity columns)
        when the transport offers one, else lazy decode-on-access views,
        else the eager typed decode (the compatibility oracle, and the
        ``--ab-pump`` A arm).  Returns (objs, revision, keys-or-None) —
        keys ride along from the column batch so seeding skips even the
        per-object meta decode."""
        if lazy_mod.ENABLED:
            lc = getattr(self._client, "list_columns", None)
            batch = lc() if lc is not None else None
            if batch is not None:
                # kind-agnostic: Pod and Node batches both expose
                # objects()/keys (store/columns.py COLUMN_BATCH_KINDS)
                return batch.objects(), batch.revision, batch.keys
            ll = getattr(self._client, "list_lazy", None)
            if ll is not None:
                objs, rev = ll()
                return objs, rev, None
        objs, rev = self._client.list()
        return objs, rev, None

    def _watch_from(self, rev: int):
        """Build the watch, opting into column-packed frame delivery when
        the client speaks it (the informer is frame-aware; clients that
        predate the parameter degrade to per-event)."""
        try:
            return self._client.watch(from_revision=rev, frames=True)
        except TypeError:
            return self._client.watch(from_revision=rev)

    def _seed(self) -> None:
        objs, rev, keys = self._list()
        with self._mu:
            self._cache = (dict(zip(keys, objs)) if keys is not None
                           else {o.meta.key: o for o in objs})
            if self._mutation_detector:
                self._snapshots = {o.meta.key: o.to_dict() for o in objs}
            self.last_revision = rev
            self._watch = self._watch_from(rev)
            handlers = list(self._handlers)
            objs_now = list(self._cache.values())
        for h in handlers:
            for o in objs_now:
                # isolated like every later delivery: a handler that
                # panics on the seed fan-out (e.g. promoting a payload it
                # chokes on) must not wedge its peers or the seed
                self._deliver(h.on_add, o)
        self._synced.set()

    def start(self) -> None:
        """Seed synchronously, then consume the watch on a daemon thread."""
        self._seed()
        self._thread = threading.Thread(target=self._run_loop, daemon=True)
        self._thread.start()

    def start_manual(self) -> None:
        """Seed synchronously; caller drives with pump()."""
        self._seed()

    def stop(self) -> None:
        self._stopped.set()
        if self._watch is not None:
            self._watch.stop()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run_loop(self) -> None:
        while not self._stopped.is_set():
            if self._gap_pending:
                self._try_relist()  # the 0.2s get below paces retries
            ev = self._watch.get(timeout=0.2)
            if ev is None:
                continue
            try:
                self._apply(ev)
            except CacheMutationError:
                raise  # the detector's whole point is to panic
            except Exception:
                if self._stopped.is_set():
                    return
                # the watch loop is the informer's heartbeat: one bad
                # delta (or injected delivery failure) must not end it
                logger.exception("informer %s: failed to apply %s %s",
                                 self.kind, ev.type, ev.key)

    def pump(self, max_events: Optional[int] = None) -> int:
        """Synchronously apply all (or up to max_events) pending events.
        A no-op when the watch thread owns the stream (mixed drivers —
        e.g. a clock tick inside a threaded daemon — must not compete
        for events)."""
        if self._thread is not None:
            return 0
        if self._watch is None:
            self._seed()
        if self._gap_pending:
            self._try_relist()  # one retry per pump: bounded, caller-paced
        n = 0
        while max_events is None or n < max_events:
            ev = self._watch.get(timeout=0)
            if ev is None:
                break
            self._apply(ev)
            # a frame counts for the events it carried (max_events stays
            # a soft bound: frames are never split mid-apply)
            n += len(ev) if ev.type == FRAME else 1
        return n

    # -- relist (reflector 410 fallback + resync) --------------------------
    def relist(self) -> None:
        """Full LIST → cache diff → watch restart (``reflector.go``'s
        "too old resource version" fallback, doubling as the resync
        period).  Handlers see the diff as ordinary add/update/delete
        callbacks — exactly what they'd have seen had the lost deltas
        been delivered — so a cache gap of any size reconverges in one
        call.  Safe to call periodically: an in-sync informer diffs to
        nothing and only pays the LIST.

        Crash-safe ordering: the new LIST + watch are built BEFORE the
        old watch is touched, so a failure here (apiserver briefly down)
        leaves the informer exactly as it was — and ``_gap_pending``
        makes pump()/the watch loop retry, never wedging on a dead
        stream.  ``_relist_mu`` serializes concurrent callers (resync
        timer vs GAP escalation): the loser waits and then relists
        against the fresh state instead of leaking a live watch."""
        tr = tracing.current()
        with (tr.span("informer.relist", cat="ingest", kind=self.kind)
              if tr is not None else tracing.NULL_SPAN):
            self._relist_inner()
        if self.compact_on_resync:
            self.compact_cache()

    def _relist_inner(self) -> None:
        with self._relist_mu:
            attempts = 0
            while True:
                objs, rev, keys = self._list()
                try:
                    new_watch = self._watch_from(rev)
                    break
                except ExpiredRevisionError:
                    # the window slid past rev between LIST and WATCH —
                    # possible only under extreme write pressure; relist
                    attempts += 1
                    if attempts >= 5:
                        raise
            new_cache = (dict(zip(keys, objs)) if keys is not None
                         else {o.meta.key: o for o in objs})
            with self._mu:
                old_watch = self._watch
                old_cache = self._cache
                self._cache = new_cache
                if self._mutation_detector:
                    self._snapshots = {o.meta.key: o.to_dict() for o in objs}
                self.last_revision = max(self.last_revision, rev)
                self._watch = new_watch
                handlers = list(self._handlers)
                self.stats["relists"] += 1
                self._gap_pending = False
            if old_watch is not None:
                # events the old stream delivered after our LIST are at
                # revisions the new watch replays too — dropping its
                # queue loses nothing
                old_watch.stop()
        self.metrics.informer_relists.inc()
        for key, obj in new_cache.items():
            old = old_cache.get(key)
            if old is None:
                for h in handlers:
                    self._deliver(h.on_add, obj)
            elif lazy_mod.resource_version_of(old) != lazy_mod.resource_version_of(obj):
                # the raw-aware read keeps the steady-state resync diff
                # (5k nodes + 150k pods) from decoding every object's meta
                for h in handlers:
                    self._deliver(h.on_update, old, obj)
        for key, old in old_cache.items():
            if key not in new_cache:
                for h in handlers:
                    self._deliver(h.on_delete, old)

    # alias: the reference's resyncPeriod is this same relist, on a timer
    resync = relist

    def _try_relist(self) -> bool:
        """Relist, absorbing failure into ``_gap_pending`` so the next
        pump()/loop turn retries — a relist that fails because the
        apiserver is briefly unreachable must degrade to 'stale until it
        returns', never to 'wedged forever'."""
        try:
            self.relist()
            return True
        except Exception:
            with self._mu:
                self._gap_pending = True
                self.stats["relist_failures"] += 1
            logger.exception(
                "informer %s: relist failed — will retry", self.kind)
            return False

    def _deliver(self, fn, *args) -> None:
        """One handler callback, isolated: a panicking handler is counted
        and logged, never allowed to wedge delivery to its peers or kill
        the watch loop (processorListener's crash isolation)."""
        try:
            fn(*args)
        except Exception:
            with self._mu:
                self.stats["handler_errors"] += 1
            self.metrics.informer_handler_errors.inc()
            logger.exception("informer %s: handler error (isolated)", self.kind)

    # -- delta application -------------------------------------------------
    def _apply(self, ev) -> None:
        if ev.type == FRAME:
            # a column-packed batch: one lock hold for the whole frame
            return self._apply_batch(ev)
        if ev.type == WATCH_GAP:
            # the transport admitted it lost continuity (410 on resume):
            # no payload to apply; rebuild from a fresh LIST
            self._try_relist()
            return
        t_apply = time.perf_counter()
        try:
            self._apply_event(ev)
        except Exception as e:
            # the per-event path opens no span (it would be one per pod);
            # a failed apply leaves a point event, whichever thread drives
            tr = tracing.current()
            if tr is not None:
                tr.instant("informer.event.error", kind=self.kind, key=ev.key,
                           type=ev.type, error=f"{type(e).__name__}: {e}")
            raise
        finally:
            # the scheduler deltas this per wave (pump APPLICATION time)
            dt = time.perf_counter() - t_apply
            with self._mu:
                self.stats["apply_s"] += dt

    def _apply_event(self, ev: WatchEvent) -> None:
        if ev.revision <= self.last_revision:
            # revision fence: a straggler from a watch that a relist
            # already superseded (the LIST at last_revision subsumes it)
            # must not overwrite the fresher cache
            return
        fault = faults.hit("informer.deliver", kind=self.kind, key=ev.key,
                           type=ev.type)
        if fault is not None and fault.mode == "drop":
            # lossy delivery: the delta silently never happens — the
            # cache diverges until the next relist/resync reconverges it
            with self._mu:
                self.stats["dropped_events"] += 1
            self.metrics.informer_dropped_events.inc()
            return
        t_decode = time.perf_counter()
        try:
            faults.hit("informer.decode", kind=self.kind, key=ev.key,
                       type=ev.type)
            if lazy_mod.ENABLED:
                # zero-copy: the event payload becomes the object's wire
                # backing; typed fields materialize on first touch
                obj = lazy_mod.wrap(self._client._cls, ev.object)
            else:
                obj = self._client._cls.from_dict(ev.object)
        except Exception:
            # a payload this informer cannot decode (or an injected
            # decode fault) loses the delta, never the watch loop: mark
            # the gap so the next pump/loop turn relists — the informer
            # degrades to 'stale until relist', not 'wedged'
            with self._mu:
                self.stats["decode_errors"] += 1
                self._gap_pending = True
            self.metrics.informer_decode_errors.inc()
            logger.exception("informer %s: failed to decode %s %s — "
                             "relist scheduled", self.kind, ev.type, ev.key)
            return
        dt = time.perf_counter() - t_decode
        with self._mu:
            self.stats["decoded_events"] += 1
            self.stats["decode_s"] += dt
            old = self._cache.get(ev.key)
            if self._mutation_detector and old is not None:
                snap = self._snapshots.get(ev.key)
                if snap is not None and old.to_dict() != snap:
                    raise CacheMutationError(
                        f"{self.kind} {ev.key} was mutated in the informer cache"
                    )
            if ev.type == DELETED:
                self._cache.pop(ev.key, None)
                self._snapshots.pop(ev.key, None)
            else:
                self._cache[ev.key] = obj
                if self._mutation_detector:
                    self._snapshots[ev.key] = obj.to_dict()
            self.last_revision = max(self.last_revision, ev.revision)
            handlers = list(self._handlers)
        for h in handlers:
            if ev.type == ADDED:
                self._deliver(h.on_add, obj)
            elif ev.type == MODIFIED:
                self._deliver(h.on_update, old, obj)
            elif ev.type == DELETED:
                self._deliver(h.on_delete, old if old is not None else obj)

    # -- batch (frame) application -----------------------------------------
    def _decode_frame(self, frame: WatchFrame, fence: int) -> tuple:
        """Decode a frame's payloads OUTSIDE the cache lock.  Returns
        (decoded, dropped, decode_errors, decode_s) where decoded is
        [(i, type, key, revision, obj-or-None)] — per-event faults keep
        their per-event semantics: a dropped delivery or an undecodable
        payload loses THAT delta (gap marked for decode), never the
        frame."""
        decoded = []
        dropped = 0
        decode_errors = 0
        t_decode = time.perf_counter()
        cls = self._client._cls
        for i in range(len(frame)):
            etype, key, rev = frame.types[i], frame.keys[i], frame.revisions[i]
            if rev <= fence:
                continue  # straggler events inside a superseded frame
            fault = faults.hit("informer.deliver", kind=self.kind, key=key,
                               type=etype)
            if fault is not None and fault.mode == "drop":
                dropped += 1
                continue
            try:
                faults.hit("informer.decode", kind=self.kind, key=key,
                           type=etype)
                raw = frame.objects[i]
                obj = (lazy_mod.wrap(cls, raw) if lazy_mod.ENABLED
                       else cls.from_dict(raw))
            except Exception:
                decode_errors += 1
                logger.exception("informer %s: failed to decode %s %s in a "
                                 "frame — relist scheduled", self.kind,
                                 etype, key)
                continue
            decoded.append((i, etype, key, rev, obj))
        return decoded, dropped, decode_errors, time.perf_counter() - t_decode

    def _apply_batch(self, frame: WatchFrame) -> None:
        """Apply one column-packed frame: decode outside the lock, then
        the WHOLE batch lands in the cache under ONE lock hold, and each
        handler receives it in one isolated call (``on_batch``) or as the
        usual per-event callbacks.  A failure before any event applied
        (the ``informer.apply_batch`` fault, broken columns) loses the
        frame as a unit and marks a gap — the existing relist path heals
        it, exactly like a decode failure or a 410.

        The frame-apply span carries the emitting txn's correlation id
        (ISSUE 7): the store's txn span, this span, and the scheduler's
        confirm span (which runs inside this one's handler fan-out) all
        share it, so one trace shows the store→informer→confirm path."""
        tr = tracing.current()
        with (tr.span("informer.frame.apply", cat="ingest", kind=self.kind,
                      txn=frame.txn, events=len(frame))
              if tr is not None else tracing.NULL_SPAN) as sp:
            self._apply_batch_inner(frame, sp)

    def _apply_batch_inner(self, frame: WatchFrame, sp) -> None:
        t_apply = time.perf_counter()
        try:
            faults.hit("informer.apply_batch", kind=self.kind, n=len(frame))
            decoded, dropped, decode_errors, decode_s = self._decode_frame(
                frame, self.last_revision)
        except Exception:
            with self._mu:
                self.stats["batch_errors"] += 1
                self._gap_pending = True
            self.metrics.informer_frame_errors.inc()
            logger.exception(
                "informer %s: failed to apply a %d-event frame — relist "
                "scheduled", self.kind, len(frame))
            return
        if dropped:
            self.metrics.informer_dropped_events.inc(dropped)
        if decode_errors:
            self.metrics.informer_decode_errors.inc(decode_errors)
        applied: list = []
        with self._mu:
            self.stats["frames"] += 1
            self.stats["dropped_events"] += dropped
            self.stats["decode_errors"] += decode_errors
            if decode_errors:
                self._gap_pending = True
            self.stats["decoded_events"] += len(decoded)
            self.stats["decode_s"] += decode_s
            for i, etype, key, rev, obj in decoded:
                if rev <= self.last_revision:
                    continue  # a concurrent relist superseded this event
                old = self._cache.get(key)
                if self._mutation_detector and old is not None:
                    snap = self._snapshots.get(key)
                    if snap is not None and old.to_dict() != snap:
                        raise CacheMutationError(
                            f"{self.kind} {key} was mutated in the informer cache"
                        )
                if etype == DELETED:
                    self._cache.pop(key, None)
                    self._snapshots.pop(key, None)
                else:
                    self._cache[key] = obj
                    if self._mutation_detector:
                        self._snapshots[key] = obj.to_dict()
                self.last_revision = max(self.last_revision, rev)
                applied.append((etype, old, obj, i))
            self.stats["frame_events"] += len(applied)
            handlers = list(self._handlers)
        for h in handlers:
            if h.on_batch is not None:
                # one isolated call per handler: a batch-aware handler
                # (the scheduler's columnar confirm) sees the whole wave
                self._deliver(h.on_batch, frame, applied)
                continue
            for etype, old, obj, _i in applied:
                if etype == ADDED:
                    self._deliver(h.on_add, obj)
                elif etype == MODIFIED:
                    self._deliver(h.on_update, old, obj)
                elif etype == DELETED:
                    self._deliver(h.on_delete, old if old is not None else obj)
        dt = time.perf_counter() - t_apply
        with self._mu:
            self.stats["apply_s"] += dt
        sp.set(applied=len(applied), dropped=dropped,
               decode_errors=decode_errors, decode_s=round(decode_s, 6))

    # -- cache compaction (promote-and-drop-raw) ---------------------------
    def compact_cache(self) -> int:
        """Opt-in sweep over a synced cache: promote every lazy view to
        its typed form and release the pinned wire dict (carried-forward
        ROADMAP item — a cached lazy object otherwise keeps its raw
        payload alive for its lifetime).  Promotion is exactly what any
        reader would have triggered, so concurrent readers are safe; the
        objects' observable value is unchanged (promotion ≡ from_dict).
        Returns the number of objects whose raw payload was dropped.

        Observability (ISSUE 7 satellite): each sweep counts the objects
        it compacted (``client_informer_compactions_total``) and records
        the approximate wire bytes it released
        (``client_informer_compaction_freed_bytes``)."""
        with self._mu:
            objs = list(self._cache.values())
        n = 0
        freed = 0
        for obj in objs:
            try:
                size = lazy_mod.raw_payload_size(obj)
                if lazy_mod.promote_and_drop_raw(obj):
                    n += 1
                    freed += size
            except Exception:  # noqa: BLE001 - sweep is best-effort
                logger.exception("informer %s: compaction failed for one "
                                 "object (kept as-is)", self.kind)
        with self._mu:
            self.stats["compactions"] += n
        if n:
            self.metrics.informer_compactions.inc(n)
        self.metrics.informer_compaction_freed_bytes.set(freed)
        return n


class CacheMutationError(RuntimeError):
    pass


class InformerFactory:
    """SharedInformerFactory analogue: one informer per kind per factory."""

    def __init__(self, clientset, mutation_detector: bool = False,
                 compact_on_resync: bool = False):
        self._clientset = clientset
        self._informers: dict[str, SharedInformer] = {}
        self._mutation_detector = mutation_detector
        self._compact_on_resync = compact_on_resync
        # informer() is reachable from controller sync workers (the GC
        # wiring a just-established CRD kind mid-sync): without the lock
        # two workers can build two informers for one kind and the
        # loser's handlers are silently dropped
        self._mk_mu = threading.Lock()

    def informer(self, kind: str) -> SharedInformer:
        inf = self._informers.get(kind)  # hit path: lock-free
        if inf is None:
            with self._mk_mu:
                inf = self._informers.get(kind)
                if inf is None:
                    inf = SharedInformer(
                        self._clientset.client_for(kind),
                        mutation_detector=self._mutation_detector,
                        compact_on_resync=self._compact_on_resync,
                    )
                    self._informers[kind] = inf
        return inf

    def start_all(self) -> None:
        for inf in self._informers.values():
            if not inf.has_synced():
                inf.start()

    def start_all_manual(self) -> None:
        for inf in self._informers.values():
            if not inf.has_synced():
                inf.start_manual()

    def pump_all(self) -> int:
        # snapshot: a handler may register a NEW informer mid-pump (the
        # GC wiring a just-established CRD kind); the newcomer gets its
        # events on the caller's next pump round
        return sum(inf.pump() for inf in list(self._informers.values()))

    def relist_all(self) -> None:
        """Resync every synced informer (the factory-level resyncPeriod
        tick): each one re-LISTs, diffs, and restarts its watch."""
        for inf in list(self._informers.values()):
            if inf.has_synced():
                inf.relist()

    def compact_all(self) -> int:
        """Promote-and-drop-raw sweep over every synced cache (opt-in:
        trades decode-now for releasing the pinned wire payloads)."""
        return sum(inf.compact_cache()
                   for inf in list(self._informers.values())
                   if inf.has_synced())

    def stop_all(self) -> None:
        for inf in self._informers.values():
            inf.stop()


class PodNodeIndex:
    """By-node pod index over a shared informer (fieldSelector analogue).

    Mutated on the informer's run-loop thread, read from controller worker
    threads (``pods_on``) — both sides hold ``_mu`` (ktpu-analyze RL303)."""

    def __init__(self, informer: "SharedInformer"):
        self._mu = threading.Lock()
        self._by_node: dict[str, dict[str, "api.Pod"]] = {}
        informer.add_handler(
            Handler(on_add=self._upsert, on_update=lambda old, new: self._move(old, new),
                    on_delete=self._drop)
        )

    def _upsert(self, pod: "api.Pod") -> None:
        if pod.spec.node_name:
            with self._mu:
                self._by_node.setdefault(pod.spec.node_name, {})[pod.meta.key] = pod

    def _move(self, old: Optional["api.Pod"], new: "api.Pod") -> None:
        # pop + insert under ONE lock hold: releasing between them leaves a
        # window where the pod is indexed on no node and a concurrent
        # pods_on() reader misses it entirely
        with self._mu:
            if old is not None and old.spec.node_name and old.spec.node_name != new.spec.node_name:
                self._by_node.get(old.spec.node_name, {}).pop(old.meta.key, None)
                self._shed(old.spec.node_name)
            if new.spec.node_name:
                self._by_node.setdefault(new.spec.node_name, {})[new.meta.key] = new

    def _drop(self, pod: "api.Pod") -> None:
        if pod.spec.node_name:
            with self._mu:
                self._by_node.get(pod.spec.node_name, {}).pop(pod.meta.key, None)
                self._shed(pod.spec.node_name)

    def _shed(self, node_name: str) -> None:
        # caller holds _mu: drop the per-node dict once its last pod is
        # gone, or node churn (scale-down, spot reclaim) pins an empty
        # dict per node name the cluster has ever seen
        if not self._by_node.get(node_name):
            self._by_node.pop(node_name, None)

    def pods_on(self, node_name: str) -> list:
        with self._mu:
            return list(self._by_node.get(node_name, {}).values())


class PodOwnerIndex:
    """Pods indexed by controller-owner UID, plus orphans by namespace — the
    index that makes ReplicaSet reconciliation O(pods-of-this-RS) instead of
    O(cluster-pods) (client-go keeps the same index inside its Indexer)."""

    def __init__(self, informer: "SharedInformer"):
        # informer-thread writers vs worker-thread readers (RL303)
        self._mu = threading.Lock()
        self._by_owner: dict[str, dict[str, object]] = {}
        self._orphans: dict[str, dict[str, object]] = {}  # namespace -> key -> pod
        informer.add_handler(
            Handler(
                on_add=self._upsert,
                on_update=lambda old, new: self._move(old, new),
                on_delete=self._drop,
            )
        )

    def _slot(self, pod):
        # caller holds _mu
        ref = pod.meta.controller_ref()
        if ref is not None:
            return self._by_owner.setdefault(ref.uid, {})
        return self._orphans.setdefault(pod.meta.namespace, {})

    def _upsert(self, pod) -> None:
        with self._mu:
            self._slot(pod)[pod.meta.key] = pod

    def _move(self, old, new) -> None:
        with self._mu:
            if old is not None:
                self._slot(old).pop(old.meta.key, None)
                self._shed(old)
            self._slot(new)[new.meta.key] = new

    def _drop(self, pod) -> None:
        with self._mu:
            self._slot(pod).pop(pod.meta.key, None)
            self._shed(pod)

    def _shed(self, pod) -> None:
        # caller holds _mu: drop the slot itself once its last pod is
        # gone, or dead owner UIDs and emptied namespaces pin an empty
        # dict forever (every RS the cluster has ever run)
        ref = pod.meta.controller_ref()
        if ref is not None:
            if not self._by_owner.get(ref.uid):
                self._by_owner.pop(ref.uid, None)
        elif not self._orphans.get(pod.meta.namespace):
            self._orphans.pop(pod.meta.namespace, None)

    def owned_by(self, uid: str) -> list:
        with self._mu:
            return list(self._by_owner.get(uid, {}).values())

    def orphans_in(self, namespace: str) -> list:
        with self._mu:
            return list(self._orphans.get(namespace, {}).values())
