"""Revisioned object store with CAS updates and watch streams.

The capability of the reference's L0+L2 (etcd3 +
``apiserver/pkg/storage/etcd3/store.go`` + the watch cache
``storage/cacher.go``) collapsed into one in-process component:

- a single monotonically increasing **revision** counter (etcd
  ``mod_revision`` analogue) stamped onto every write;
- **GuaranteedUpdate**: optimistic-concurrency read-modify-write that
  retries the caller's mutation function on revision conflict
  (``storage/etcd3/store.go:257``);
- **watch streams from a revision**: every watcher gets the exact ordered
  event sequence after its start revision, served from an in-memory event
  log (the watch-cache sliding window, ``storage/watch_cache.go``) — one
  writer fans out to any number of watchers (SURVEY.md P4).

Deliberate design point: the store holds **serialized dicts**, never live
objects, and deep-copies on every get/list/event — informer objects are
immutable by construction, which is what the reference enforces with its
cache mutation detector (``client-go/tools/cache/mutation_detector.go``).

The scheduler treats everything device-resident as a disposable cache of
this store, rebuildable from snapshot + watch replay (SURVEY.md §5.3).
"""

from __future__ import annotations

# (copy module no longer needed: JSON-shaped fast deepcopy below)
import collections
import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterator, NamedTuple, Optional

from .. import faults
from ..api.meta import new_uid
from ..utils import tracing
from ..utils.metrics import DEFAULT_STORE_METRICS
from . import frames as frames_mod


def _py_fast_deepcopy(obj):
    """Deep copy for JSON-shaped data (dict/list/scalars only) — the store's
    wire form by construction.  ~3x faster than copy.deepcopy, which burns
    time on memo bookkeeping and type dispatch the shape can't need."""
    t = type(obj)
    if t is dict:
        return {k: _py_fast_deepcopy(v) for k, v in obj.items()}
    if t is list:
        return [_py_fast_deepcopy(v) for v in obj]
    return obj  # str/int/float/bool/None are immutable


def _fast_deepcopy(obj):
    """First call resolves the copier — the native C walk
    (csrc/fastcopy.c, another ~3x) when it builds, else the Python walk —
    and rebinds this name, so importing the store never triggers a
    compile and later calls pay zero dispatch overhead."""
    global _fast_deepcopy
    try:
        from ..native import get_fastcopy

        _fast_deepcopy = get_fastcopy() or _py_fast_deepcopy
    except Exception:  # noqa: BLE001 - the store must never lose its copier
        _fast_deepcopy = _py_fast_deepcopy
    return _fast_deepcopy(obj)


def object_key(namespace: str, name: str) -> str:
    """Canonical store/informer key — MUST match ``ObjectMeta.key``:
    cluster-scoped objects (empty namespace) use the bare name."""
    return f"{namespace}/{name}" if namespace else name


ADDED = "ADDED"
MODIFIED = "MODIFIED"
DELETED = "DELETED"
# Not a state transition: a watch transport's admission that continuity
# was lost (410 Gone on resume — the event-log window slid past the
# consumer's bookmark).  An informer receiving this must relist; there is
# no object payload to apply.
WATCH_GAP = "GAP"


class ConflictError(Exception):
    """CAS failure: the object's resourceVersion changed under the writer."""


class NotFoundError(KeyError):
    pass


class AlreadyExistsError(Exception):
    pass


class WatchEvent:
    """One committed transition.  Shared-immutable by contract: one event
    object goes to the log and to every watcher, and nobody changes it
    (``frames.event_wire_bytes`` hangs the encoded line on ``_wire_b``)."""

    __slots__ = ("type", "kind", "key", "revision", "object", "_wire_b")

    def __init__(self, type: str, kind: str, key: str, revision: int,
                 object: dict):
        self.type = type  # ADDED | MODIFIED | DELETED
        self.kind = kind
        self.key = key  # namespace/name
        self.revision = revision
        self.object = object  # serialized object (deep-copied per consumer)

    def __eq__(self, other):
        if not isinstance(other, WatchEvent):
            return NotImplemented
        return ((self.type, self.kind, self.key, self.revision, self.object)
                == (other.type, other.kind, other.key, other.revision,
                    other.object))

    def __repr__(self) -> str:
        return (f"WatchEvent(type={self.type!r}, kind={self.kind!r}, "
                f"key={self.key!r}, revision={self.revision!r}, "
                f"object={self.object!r})")


class BoundPodEvent(WatchEvent):
    """The MODIFIED event of one ``bind_many`` row.  It carries no copy:
    it holds the stored pod's dict, the node and the revision, and
    ``object`` derives the payload when somebody reads it — a WAL or a
    replica at commit, a watcher's thread when it encodes, nobody for a
    row no one asks for.  By the store's invariant (see :class:`Store`)
    the payload is, entry for entry, what a copy taken at commit held,
    whenever it is built: the only values written in place after commit
    are the two this event overrides with its own."""

    __slots__ = ("node_name", "_data", "_payload")

    def __init__(self, key: str, revision: int, node_name: str, data: dict):
        self.type = MODIFIED
        self.kind = "Pod"
        self.key = key
        self.revision = revision
        self.node_name = node_name
        self._data = data
        self._payload = None

    def _build(self) -> dict:
        # {**data, "spec": {**spec, "nodeName": node}, "metadata": {**meta,
        # "resourceVersion": revision}}, by dict.copy (a table copy)
        got = self._data.copy()
        spec = got["spec"] = got["spec"].copy()
        spec["nodeName"] = self.node_name
        meta = got["metadata"] = got["metadata"].copy()
        meta["resourceVersion"] = self.revision
        self._payload = got
        return got

    @property
    def object(self) -> dict:
        got = self._payload
        if got is None:
            got = self._build()
            DEFAULT_STORE_METRICS.event_payloads_built.inc()
        return got


def event_payloads(events: list) -> list:
    """``[ev.object for ev in events]``, the derived ones counted once."""
    out = []
    built = 0
    for ev in events:
        if type(ev) is BoundPodEvent:
            got = ev._payload
            if got is None:
                got = ev._build()
                built += 1
            out.append(got)
        else:
            out.append(ev.object)
    if built:
        DEFAULT_STORE_METRICS.event_payloads_built.inc(built)
    return out


@dataclass
class _Item:
    data: dict
    revision: int


class Watch:
    """One watch stream.  Iterate, or ``stop()`` to end.  Events are
    delivered in revision order with no gaps from ``start_revision``."""

    def __init__(self, store: "Store", q: "queue.Queue[Optional[WatchEvent]]"):
        self._store = store
        self._queue = q
        self._stopped = threading.Event()

    def stop(self) -> None:
        if not self._stopped.is_set():
            self._stopped.set()
            self._store._remove_watch(self._queue)
            self._queue.put(None)

    def __iter__(self) -> Iterator[WatchEvent]:
        while True:
            ev = self._queue.get()
            if ev is None:
                return
            yield ev

    def get(self, timeout: Optional[float] = None) -> Optional[WatchEvent]:
        try:
            return self._queue.get(timeout=timeout)
        except queue.Empty:
            return None

    def qsize(self) -> int:
        """Items delivered and not yet taken."""
        return self._queue.qsize()


class _PendingBatch:
    """One open coalescing window at the broadcaster seam: per-key
    latest-wins fold of single-event churn awaiting one framed flush.

    ``latest`` maps (kind, key) → the newest buffered event for that
    object; a fold deletes-and-reinserts so dict order tracks each
    key's LATEST commit — the flush frame's revision column is strictly
    increasing by construction (the ``from_wire`` invariant).  WAL, the
    event log, and replication all stay per-event at commit time; ONLY
    live watcher delivery waits for the window."""

    __slots__ = ("latest", "deadline", "txn", "folded", "first_rev")

    def __init__(self, deadline: float, txn: str, first_rev: int):
        self.latest: "collections.OrderedDict[tuple, WatchEvent]" = (
            collections.OrderedDict())
        self.deadline = deadline
        self.txn = txn
        self.folded = 0  # deliveries superseded inside this window
        # the first revision buffered: with the last one, the stretch of
        # the log this window's flush frames stand for (_LogTxn)
        self.first_rev = first_rev


class _LogTxn(NamedTuple):
    """Where one batch txn lies in the event log: rows ``first``..``last``
    (consecutive revisions, one lock hold), the id its frames carry, and
    for ``bind_many`` the prev-revision column (row ``r`` at index
    ``r - first``).  ``fold`` marks a coalescing window: its frames hold
    each key's latest row only, per kind.  A frames watcher that resumes
    inside the log window gets these rows packed as the live watchers
    did (``Store.watch``)."""

    first: int
    last: int
    txn: Optional[str]
    prev: Optional[list]
    fold: bool


def _fold_by_kind(events) -> dict:
    """kind -> its events, each key's latest only, a key sorted by its
    latest commit: what one coalescing window delivers."""
    latest: dict = {}
    for ev in events:
        k = (ev.kind, ev.key)
        latest.pop(k, None)
        latest[k] = ev
    by_kind: dict[str, list[WatchEvent]] = {}
    for ev in latest.values():
        by_kind.setdefault(ev.kind, []).append(ev)
    return by_kind


def _replayed_pieces(t: _LogTxn, rows: list) -> list:
    """The rows of txn ``t`` that a resumed frames watcher has not seen
    (a suffix of it, in log order) as the items the live watchers got:
    frames of at most ``frames.FRAME_MAX_ROWS`` rows with the txn's id
    and its prev-revision column sliced alike."""
    if t.fold:
        out: list = []
        for kind, evs in _fold_by_kind(rows).items():
            out.extend(frames_mod.pack_frames(kind, evs, txn=t.txn)
                       if len(evs) > 1
                       else evs)
        return out
    if t.prev is None:
        return frames_mod.pack_frames(rows[0].kind, rows, txn=t.txn)
    # a bind txn: its rows are BoundPodEvents, whose columns are read
    # without building a payload
    lo = rows[0].revision - t.first
    return frames_mod.pack_frames(
        rows[0].kind, rows, prev_revisions=t.prev[lo:lo + len(rows)],
        txn=t.txn,
        bound=([ev.key for ev in rows], [ev.revision for ev in rows],
               [ev.node_name for ev in rows]))


class Store:
    """In-process strongly-ordered object store (etcd3 + watch-cache analogue).

    **Invariant: a dict the store holds is never changed in place, except
    by** :meth:`bind_many`, **which sets** ``spec.nodeName`` **and**
    ``metadata.resourceVersion`` (and creates a missing ``spec``).  Every
    other write verb replaces the ``_Item`` and its dicts wholesale
    (``update``, ``delete`` of an object with finalizers,
    ``apply_replicated``, ``install_snapshot``).  That is what lets a
    :class:`BoundPodEvent` hold the stored dict instead of a copy: it
    overrides those two values with its own, so neither a later re-bind
    nor any other write can reach an event already committed
    (``tests/test_bind_columns.py`` walks every verb)."""

    def __init__(self, event_log_window: int = 100_000,
                 data_dir: Optional[str] = None, fsync: bool = False,
                 compact_every: int = 100_000, transformer=None,
                 coalesce_window_s: float = 0.0):
        self._mu = threading.RLock()
        self._rev = 0
        # kind -> {key -> _Item}
        self._objects: dict[str, dict[str, _Item]] = {}
        # ordered event log (the watch-cache window).  A deque: the window
        # trim must be O(1) — a front-slice del on a list memmoves the
        # whole window on EVERY write once it fills, which at a 300k
        # window costs more than the write itself.
        self._log: collections.deque[WatchEvent] = collections.deque(maxlen=event_log_window)
        self._log_window = event_log_window
        # where each batch txn lies in _log, oldest first, for as long as
        # one of its rows is in the window (_trim_log_txns): what lets a
        # resumed frames watcher's replay leave as frames
        self._log_txns: collections.deque[_LogTxn] = collections.deque()
        # (kind filter, queue, wants_frames): frame-aware watchers opted
        # in via watch(frames=True) receive a correlated batch txn as
        # WatchFrames (one when it fits frames.FRAME_MAX_ROWS); everyone
        # else gets the per-event expansion
        self._watchers: list[tuple[Optional[str], "queue.Queue[Optional[WatchEvent]]", bool]] = []
        # time-window update coalescing (the serving-tier broadcaster
        # seam): 0.0 (default) = off, every event fans out at commit;
        # > 0 = single-event update/delete churn is folded per key
        # (latest wins) and flushed as synthetic WatchFrames per kind
        # (one while the kind's keys fit frames.FRAME_MAX_ROWS)
        # when the window closes.  Batch txns (_emit_many), new watcher
        # registration, and snapshot installs are ordering barriers that
        # flush the open window first.
        self._coalesce_window = float(coalesce_window_s or 0.0)
        self._coalesce_max_keys = 10_000
        self._pending: Optional[_PendingBatch] = None
        self._coalesce_closed = False
        self._coalesce_wake: Optional[threading.Event] = None
        self._coalesce_thread: Optional[threading.Thread] = None
        if self._coalesce_window > 0.0:
            self._coalesce_wake = threading.Event()
            self._coalesce_thread = threading.Thread(
                target=self._coalesce_loop, name="store-coalesce",
                daemon=True)
            self._coalesce_thread.start()
        # durability (the etcd WAL+snapshot analogue, store/wal.py):
        # with a data_dir every committed event is logged before the call
        # returns, and a fresh Store over the same dir recovers the state
        self._wal = None
        if data_dir is not None:
            from .wal import WriteAheadLog

            self._wal = WriteAheadLog(data_dir, compact_every=compact_every,
                                      fsync=fsync, transformer=transformer)
            rev, objects, _ = self._wal.recover()
            self._rev = rev
            for kind, bucket in objects.items():
                for key, data in bucket.items():
                    self._objects.setdefault(kind, {})[key] = _Item(
                        data=data,
                        revision=int(data.get("metadata", {}).get("resourceVersion", rev)),
                    )
            self._wal.open()
        # a batch txn's rows reach the log in one extend where nothing is
        # asked of each: no WAL record, no _replicate override
        self._log_in_bulk = (self._wal is None and
                             type(self)._replicate is Store._replicate)

    def compact(self) -> None:
        """Write a snapshot and truncate the WAL (etcd compaction).  No
        copy needed: write_snapshot serializes synchronously while we
        hold the store lock, so the live dicts cannot mutate mid-encode."""
        if self._wal is None:
            return
        with self._mu:
            objects = {
                kind: {key: item.data for key, item in bucket.items()}
                for kind, bucket in self._objects.items()
            }
            self._wal.write_snapshot(self._rev, objects)

    def close(self) -> None:
        if self._coalesce_thread is not None:
            self._coalesce_closed = True
            self._coalesce_wake.set()
            self._coalesce_thread.join(timeout=5.0)
            self.flush_coalesced()  # nothing buffered outlives the store
        if self._wal is not None:
            self._wal.close()

    # -- revision ----------------------------------------------------------
    @property
    def revision(self) -> int:
        with self._mu:
            return self._rev

    def _next_rev(self) -> int:
        self._rev += 1
        return self._rev

    # -- writes ------------------------------------------------------------
    def create(self, kind: str, obj: dict, _trusted: bool = False) -> dict:
        """``_trusted`` marks ``obj`` as privately owned (the typed
        client's freshly built ``to_dict`` wire form), skipping the
        defensive deep copy — one of two per create on the hot arrival
        path (the other is the shared event/return copy below)."""
        # fault seam BEFORE the lock and any mutation: an injected commit
        # failure models apiserver/etcd overload — the write never starts
        faults.hit("store.commit", op="create", kind=kind)
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="create", kind=kind)
              if tr is not None else tracing.NULL_SPAN), self._mu:
            meta = obj.setdefault("metadata", {})
            key = object_key(meta.get("namespace", "default"), meta.get("name", ""))
            bucket = self._objects.setdefault(kind, {})
            if key in bucket:
                raise AlreadyExistsError(f"{kind} {key} already exists")
            rev = self._next_rev()
            data = obj if _trusted else _fast_deepcopy(obj)
            m = data["metadata"]
            m.setdefault("namespace", "default")
            if not m.get("uid"):
                m["uid"] = new_uid()
            m["resourceVersion"] = rev
            m["creationRevision"] = rev
            bucket[key] = _Item(data=data, revision=rev)
            ev_copy = _fast_deepcopy(data)
            self._emit(WatchEvent(ADDED, kind, key, rev, ev_copy))
            # like update(): the event copy doubles as the caller's return
            # value — both are read-only by contract, and the stored dict
            # never escapes.  One deepcopy per create, not two (the create
            # flood is the arrival path).
            return ev_copy

    def create_many(self, kind: str, objs: list[dict],
                    _trusted: bool = False) -> list[Optional[dict]]:
        """Batch create: every object commits under ONE lock acquisition
        (one revision run, one WAL stretch, one watch-fanout pass) — the
        txn shape for a churn wave's worth of arrivals or a whole bind
        wave's Events, where per-create lock round-trips and sink wake-ups
        are pure overhead.  Semantics per item are exactly :meth:`create`
        (same defaulting, same ADDED event, events in list order); a
        failed item (already exists / malformed) yields None in its slot
        and the REST of the batch still commits — the best-effort contract
        batch writers (the event sink) want, and loud enough for callers
        that care to check."""
        faults.hit("store.commit", op="create_many", kind=kind)
        # correlation id (ISSUE 7): minted per batch txn whether or not
        # tracing is on — it rides the watch frame to every consumer
        txn = tracing.next_txn("create_many")
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="create_many", kind=kind,
                      txn=txn, n=len(objs))
              if tr is not None else tracing.NULL_SPAN) as sp:
            results: list[Optional[dict]] = []
            with self._mu:
                tracing.lock_held()
                bucket = self._objects.setdefault(kind, {})
                events: list[WatchEvent] = []
                for obj in objs:
                    try:
                        meta = obj.setdefault("metadata", {})
                        key = object_key(meta.get("namespace", "default"),
                                         meta.get("name", ""))
                        if key in bucket:
                            results.append(None)
                            continue
                        rev = self._next_rev()
                        data = obj if _trusted else _fast_deepcopy(obj)
                        m = data["metadata"]
                        m.setdefault("namespace", "default")
                        if not m.get("uid"):
                            m["uid"] = new_uid()
                        m["resourceVersion"] = rev
                        m["creationRevision"] = rev
                        bucket[key] = _Item(data=data, revision=rev)
                        ev_copy = _fast_deepcopy(data)
                        events.append(WatchEvent(ADDED, kind, key, rev, ev_copy))
                        results.append(ev_copy)
                    except Exception:  # noqa: BLE001 - one bad item, not the batch
                        results.append(None)
                # the whole txn fans out column-packed to every
                # frame-aware watcher (per-event to everyone else)
                n_frames = self._emit_many(events, txn=txn)
            sp.set(committed=len(events), frames=n_frames)
            return results

    def update(
        self, kind: str, obj: dict, expect_rev: Optional[int] = None, _trusted: bool = False
    ) -> dict:
        """CAS write.  ``expect_rev`` defaults to obj.metadata.resourceVersion;
        pass 0/None there to force-write (last-write-wins).  ``_trusted``
        marks ``obj`` as privately owned (guaranteed_update's copy), skipping
        one defensive deep copy on the hot write path."""
        faults.hit("store.commit", op="update", kind=kind)
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="update", kind=kind)
              if tr is not None else tracing.NULL_SPAN), self._mu:
            meta = obj.get("metadata") or {}
            key = object_key(meta.get("namespace", "default"), meta.get("name", ""))
            bucket = self._objects.setdefault(kind, {})
            item = bucket.get(key)
            if item is None:
                raise NotFoundError(f"{kind} {key}")
            if expect_rev is None:
                expect_rev = int(meta.get("resourceVersion", 0)) or None
            if expect_rev is not None and item.revision != expect_rev:
                raise ConflictError(
                    f"{kind} {key}: expected rev {expect_rev}, have {item.revision}"
                )
            rev = self._next_rev()
            data = obj if _trusted else _fast_deepcopy(obj)
            m = data["metadata"]
            m["uid"] = item.data["metadata"]["uid"]
            m["resourceVersion"] = rev
            m["creationRevision"] = item.data["metadata"].get("creationRevision", 0)
            # deletion tombstone is immutable once set (graceful deletion)
            prior_del = item.data["metadata"].get("deletionRevision")
            if prior_del is not None:
                m["deletionRevision"] = prior_del
                if not m.get("finalizers"):
                    # last finalizer cleared on a deleting object → finish the
                    # delete (store.go:977: deleteForEmptyFinalizers)
                    del bucket[key]
                    final = _fast_deepcopy(data)
                    self._emit(WatchEvent(DELETED, kind, key, rev, final))
                    return final
            bucket[key] = _Item(data=data, revision=rev)
            ev_copy = _fast_deepcopy(data)
            self._emit(WatchEvent(MODIFIED, kind, key, rev, ev_copy))
            # the event copy doubles as the caller's return value: both are
            # read-only by contract, and the stored dict never escapes
            return ev_copy

    def bind_many(self, keys: list[str],
                  node_names: list[str]) -> list[Optional[str]]:
        """Batch placement commit: for each pod key (``namespace/name``, the
        store's own key) and the node name beside it, CAS-set
        ``spec.nodeName`` under ONE lock acquisition — the etcd-txn
        analogue of issuing one BindingREST call per pod, shaped for the TPU
        batch path where hundreds of thousands of bindings land at once.
        The two columns are the verb's input end to end (the wire body of
        ``POST /api/v1/bindings:batch`` holds the same two lists): no side
        reshapes them per row.

        Returns one entry per row: None on success, else an error string
        ("not found" / "conflict: <node>").  The txn is committed by
        columns: the loop does the CAS and appends to the txn's columns,
        and a row's watch event (:class:`BoundPodEvent`) holds the stored
        dict, not a copy — its payload is derived when somebody reads it,
        which a watcher's frame does a piece at a time, after the answer."""
        if len(keys) != len(node_names):
            raise ValueError(f"bind_many: {len(keys)} keys, "
                             f"{len(node_names)} node names")
        faults.hit("store.commit", op="bind_many", kind="Pod")
        txn = tracing.next_txn("bind_many")
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="bind_many", kind="Pod",
                      txn=txn, n=len(keys))
              if tr is not None else tracing.NULL_SPAN) as sp:
            return self._bind_many_locked(keys, node_names, txn, sp)

    def _bind_many_locked(self, keys_in, nodes_in, txn,
                          sp) -> list[Optional[str]]:
        results: list[Optional[str]] = [None] * len(keys_in)
        # the txn's columns, one entry per committed row (the input's,
        # less the rows that failed)
        keys: list[str] = []
        node_names: list[str] = []
        prev_revs: list[int] = []
        stored: list[dict] = []
        errors = 0  # (an item's slot in results: len(keys) + errors)
        # the per-item seam below is asked only of an armed plan
        plan = faults.active_plan()
        with self._mu:
            tracing.lock_held()
            get = self._objects.setdefault("Pod", {}).get
            rev = 0  # the txn's last revision; 0 = none allocated yet
            try:
                for key, node_name in zip(keys_in, nodes_in):
                    # per-item seam: ONE pod's CAS fails while the rest of
                    # the batch commits (the real-world partial-bind
                    # shape) — surfaced as this item's error string,
                    # never an exception
                    if plan is not None and faults.hit(
                            "scheduler.bind", pod=key, node=node_name,
                            via="bind_many") is not None:
                        results[len(keys) + errors] = "injected: bind fault"
                        errors += 1
                        continue
                    item = get(key)
                    if item is None:
                        results[len(keys) + errors] = "not found"
                        errors += 1
                        continue
                    data = item.data
                    spec = data.get("spec")
                    if spec is None:
                        spec = data["spec"] = {}
                    cur = spec.get("nodeName", "")
                    if cur and cur != node_name:
                        results[len(keys) + errors] = (
                            f"conflict: already bound to {cur}")
                        errors += 1
                        continue
                    # the columnar-confirm fence: the revision this pod
                    # held BEFORE the bind CAS — a consumer that assumed
                    # the pod at exactly this revision knows nothing else
                    # changed
                    prev_revs.append(item.revision)
                    if rev:
                        rev += 1
                    else:
                        # the txn's first row asks (a replicated store
                        # checks its quorum there); the rest count on
                        rev = self._next_rev()
                    spec["nodeName"] = node_name
                    data["metadata"]["resourceVersion"] = rev
                    item.revision = rev
                    keys.append(key)
                    node_names.append(node_name)
                    stored.append(data)
            finally:
                if rev:
                    self._rev = rev
            n = len(keys)
            revisions = list(range(rev - n + 1, rev + 1))
            # the log's rows, made from the columns in one pass
            events = list(map(BoundPodEvent, keys, revisions, node_names,
                              stored))
            n_frames = self._emit_many(
                events, prev_revisions=prev_revs, txn=txn,
                bound=(keys, revisions, node_names))
            deferred = (n if self._log_in_bulk else
                        sum(1 for ev in events if ev._payload is None))
        if deferred:
            DEFAULT_STORE_METRICS.bind_rows_deferred.inc(deferred)
        sp.set(committed=n, frames=n_frames, errors=errors,
               deferred=deferred)
        return results

    def guaranteed_update(
        self, kind: str, namespace: str, name: str, mutate: Callable[[dict], dict]
    ) -> dict:
        """Read-modify-write retry loop (``etcd3/store.go:257``).  ``mutate``
        receives a deep copy and returns the new object (or raises)."""
        while True:
            cur = self.get(kind, namespace, name)  # private deep copy already
            rev = int(cur["metadata"]["resourceVersion"])
            new = mutate(cur)
            try:
                return self.update(kind, new, expect_rev=rev, _trusted=True)
            except ConflictError:
                continue

    def delete(self, kind: str, namespace: str, name: str, expect_rev: Optional[int] = None) -> dict:
        """Delete, honoring finalizers (reference
        ``registry/generic/registry/store.go:977`` graceful deletion): while
        ``metadata.finalizers`` is non-empty the object is only *marked*
        deleting (``deletionRevision`` tombstone, MODIFIED event); the actual
        removal happens when an update clears the last finalizer."""
        faults.hit("store.commit", op="delete", kind=kind)
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="delete", kind=kind)
              if tr is not None else tracing.NULL_SPAN), self._mu:
            key = object_key(namespace, name)
            bucket = self._objects.setdefault(kind, {})
            item = bucket.get(key)
            if item is None:
                raise NotFoundError(f"{kind} {key}")
            if expect_rev is not None and item.revision != expect_rev:
                raise ConflictError(f"{kind} {key}")
            rev = self._next_rev()
            meta = item.data["metadata"]
            if meta.get("finalizers"):
                # a new item, not a mark in place (the class's invariant):
                # a bind event may still hold the dicts this one replaces
                data = {**item.data,
                        "metadata": {**meta, "deletionRevision": rev,
                                     "resourceVersion": rev}}
                bucket[key] = _Item(data=data, revision=rev)
                marked = _fast_deepcopy(data)
                self._emit(WatchEvent(MODIFIED, kind, key, rev, marked))
                return marked
            del bucket[key]
            final = _fast_deepcopy(item.data)
            final["metadata"]["deletionRevision"] = rev
            self._emit(WatchEvent(DELETED, kind, key, rev, final))
            return final

    # -- reads -------------------------------------------------------------
    def get(self, kind: str, namespace: str, name: str) -> dict:
        with self._mu:
            item = self._objects.get(kind, {}).get(object_key(namespace, name))
            if item is None:
                raise NotFoundError(f"{kind} {namespace}/{name}")
            return _fast_deepcopy(item.data)

    # -- replication apply (store/replication.py follower side) ------------
    def apply_replicated(self, ev: WatchEvent) -> None:
        """Apply a committed event from a leader verbatim: the state
        transition is taken as-is (no CAS re-check — it already won on the
        leader), the revision sequence follows the leader's, and local
        watchers/WAL observe it exactly like a local commit.  Idempotent:
        an event at or below the applied revision is a no-op (duplicate
        shipping during catch-up races)."""
        with self._mu:
            if ev.revision <= self._rev:
                return
            bucket = self._objects.setdefault(ev.kind, {})
            if ev.type == DELETED:
                bucket.pop(ev.key, None)
            else:
                bucket[ev.key] = _Item(data=_fast_deepcopy(ev.object),
                                       revision=ev.revision)
            self._rev = ev.revision
            self._emit(WatchEvent(ev.type, ev.kind, ev.key, ev.revision,
                                  _fast_deepcopy(ev.object)))

    def install_snapshot(self, rev: int, objects: dict) -> None:
        """Replace state wholesale (raft InstallSnapshot analogue): used
        when a rejoining replica is older than the leader's log window."""
        with self._mu:
            # pending events precede the snapshot: deliver them before
            # the state jump (watchers older than the snapshot relist)
            self._flush_pending_locked()
            self._objects = {
                kind: {key: _Item(data=_fast_deepcopy(data),
                                  revision=data["metadata"].get("resourceVersion", rev))
                       for key, data in bucket.items()}
                for kind, bucket in objects.items()
            }
            self._rev = rev
            self._log.clear()  # watchers older than the snapshot must relist
            self._log_txns.clear()
            if self._wal is not None:
                # durability must follow the state jump: the old WAL holds
                # pre-snapshot events that no longer compose with the new
                # revision line — snapshot it now or recovery diverges
                self.compact()

    def list(self, kind: str, namespace: Optional[str] = None) -> tuple[list[dict], int]:
        """Returns (objects, list_revision) — the revision to start a watch
        from, exactly the reflector's LIST-then-WATCH contract
        (``tools/cache/reflector.go:239``)."""
        with self._mu:
            tracing.lock_held()
            out = []
            for key, item in self._objects.get(kind, {}).items():
                ns = item.data["metadata"].get("namespace", "")
                if namespace is None or ns == namespace:
                    out.append(_fast_deepcopy(item.data))
            out.sort(key=lambda d: (d["metadata"]["namespace"], d["metadata"]["name"]))
            return out, self._rev

    def list_columns(self, kind: str = "Pod", namespace: Optional[str] = None):
        """Columnar LIST fast path (Pod and Node): one packed batch of
        raw object views + parallel identity (and for pods request/
        signature) columns — see ``store/columns.py``.  The views share
        deep subtrees with the stored dicts (zero-copy): only the two
        levels the store ever mutates in place are copied, under the
        lock, so consumers get a consistent snapshot at the returned
        revision.  Consumers MUST treat the payloads as read-only (the
        informer contract).  Returns None for kinds without a columnar
        emitter — callers fall back to :meth:`list`."""
        from .columns import COLUMN_BATCH_KINDS, batch_from_views, shallow_object_view

        if kind not in COLUMN_BATCH_KINDS:
            return None
        with self._mu:
            rev = self._rev
            views = []
            for item in self._objects.get(kind, {}).values():
                if namespace is not None:
                    ns = item.data.get("metadata", {}).get("namespace", "")
                    if ns != namespace:
                        continue
                views.append(shallow_object_view(item.data))
        return batch_from_views(views, rev, kind=kind)

    # -- watch -------------------------------------------------------------
    def watch(self, kind: Optional[str] = None, from_revision: Optional[int] = None,
              frames: bool = False) -> Watch:
        """Watch events for ``kind`` (None = all kinds) strictly after
        ``from_revision`` (None = now).  Raises if the revision has fallen
        out of the event-log window ("too old resource version" — the
        reflector then relists).

        ``frames=True`` opts this watcher into column-packed delivery:
        a correlated batch txn (``create_many``/``bind_many``) arrives as
        :class:`~.frames.WatchFrame` pieces of at most
        ``frames.FRAME_MAX_ROWS`` rows — one frame when it fits — instead
        of N events.  The log replay below frames alike: the rows a batch
        txn committed leave as the pieces the live watchers got (from the
        row after ``from_revision`` where that falls inside a txn), rows
        committed one at a time as events, all in revision order."""
        with self._mu:
            # ordering barrier: flush the open coalescing window before
            # the log replay below — otherwise the replay (which reads
            # the per-event log, where buffered events already live)
            # would be followed by a flush frame re-delivering them
            self._flush_pending_locked()
            q: "queue.Queue[Optional[WatchEvent]]" = queue.Queue()
            if from_revision is not None and from_revision < self._rev:
                oldest = self._log[0].revision if self._log else self._rev + 1
                if from_revision + 1 < oldest:
                    raise ExpiredRevisionError(
                        f"revision {from_revision} too old (oldest {oldest})"
                    )
                self._replay(q, kind, from_revision,
                             frames and frames_mod.ENABLED)
            self._watchers.append((kind, q, frames))
            return Watch(self, q)

    def _replay(self, q, kind: Optional[str], from_revision: int,
                framed: bool) -> None:
        """Put the log's rows after ``from_revision`` on a new watcher's
        queue (shared-immutable, see _emit): one by one, or for a
        ``framed`` watcher each batch txn's rows as frames.  One span and
        two counter bumps per resumed watch, nothing per row."""
        tr = tracing.current()
        n_events = n_frames = 0
        txns = set()
        with (tr.span("store.watch.replay", cat="store", kind=kind,
                      from_revision=from_revision)
              if tr is not None else tracing.NULL_SPAN) as sp:
            for item in self._replay_items(kind, from_revision, framed):
                q.put(item)
                if item.type == frames_mod.FRAME:
                    n_frames += 1
                    n_events += len(item)
                    txns.add(item.txn)
                else:
                    n_events += 1
            sp.set(events=n_events, frames=n_frames, txns=len(txns))
        DEFAULT_STORE_METRICS.watch_replay_events.inc(n_events)
        DEFAULT_STORE_METRICS.watch_replay_frames.inc(n_frames)

    def _replay_items(self, kind: Optional[str], from_revision: int,
                      framed: bool) -> Iterator:
        txns = iter(self._log_txns if framed else ())
        cur = next(txns, None)
        rows: list = []  # cur's rows so far
        for ev in self._log:
            rev = ev.revision
            if rev <= from_revision or (kind is not None and ev.kind != kind):
                continue
            while cur is not None and cur.last < rev:
                if rows:
                    yield from _replayed_pieces(cur, rows)
                    rows = []
                cur = next(txns, None)
            if cur is not None and cur.first <= rev:
                rows.append(ev)
            else:
                yield ev
        if rows:
            yield from _replayed_pieces(cur, rows)

    def _remove_watch(self, q) -> None:
        with self._mu:
            self._watchers = [(k, w, f) for (k, w, f) in self._watchers
                              if w is not q]

    def _append_log(self, ev: WatchEvent) -> None:
        """Durability + watch-cache window for one event (no fan-out)."""
        if self._wal is not None:
            # durability BEFORE visibility: the record is on disk before
            # any watcher (or the caller) observes the commit
            self._wal.append(ev.type, ev.kind, ev.key, ev.revision, ev.object)
            if self._wal.needs_compaction():
                self.compact()  # RLock: safe to re-enter from the write path
        self._log.append(ev)  # deque maxlen trims the window in C

    def _trim_log_txns(self) -> None:
        """Forget the batch txns whose last row has left the log window."""
        txns = self._log_txns
        oldest = self._log[0].revision if self._log else self._rev + 1
        while txns and txns[0].last < oldest:
            txns.popleft()

    def _replicate(self, ev: WatchEvent) -> None:
        """Per-event shipping hook (no-op here): ``ReplicatedStore``
        overrides it to ship to followers.  Called on BOTH the per-event
        and the batch emit path, after local durability."""

    def _emit(self, ev: WatchEvent) -> None:
        # WatchEvent.object is SHARED-IMMUTABLE: one private copy is made at
        # emit time and handed to the log and every watcher.  Consumers must
        # not mutate it (the informer parses it into fresh typed objects;
        # the mutation detector catches violations in tests).
        self._append_log(ev)
        if self._log_txns:
            self._trim_log_txns()
        self._replicate(ev)
        if self._coalesce_window > 0.0:
            # durability and the replay window are already per-event
            # (above); only LIVE delivery waits for the window.  Without
            # coalescing an event committed before watch() registration
            # is not delivered live either, so skipping the buffer when
            # nobody watches changes nothing (watch() replays the log).
            if self._watchers:
                self._buffer_event(ev)
            return
        for kind, q, _frames in self._watchers:
            if kind is None or kind == ev.kind:
                q.put(ev)

    # -- time-window coalescing (the serving-tier broadcaster seam) --------
    def _buffer_event(self, ev: WatchEvent) -> None:
        """Fold one committed event into the open window (opening one if
        needed).  Caller holds the store lock."""
        p = self._pending
        if p is None:
            p = self._pending = _PendingBatch(
                time.monotonic() + self._coalesce_window,
                tracing.next_txn("coalesce"), ev.revision)
            self._coalesce_wake.set()
        k = (ev.kind, ev.key)
        if k in p.latest:
            # latest wins: the superseded delivery is dropped, and the
            # key moves to the tail so the flush frame's revision column
            # stays strictly increasing (each key sorted by its LATEST
            # commit, which is also this window's arrival order)
            del p.latest[k]
            p.folded += 1
        p.latest[k] = ev
        # bounded: hard per-window key cap — the window flushes inline
        # before the pending dict can outgrow it
        if len(p.latest) >= self._coalesce_max_keys:
            self._flush_pending_locked()

    def flush_coalesced(self) -> None:
        """Deliver the open coalescing window NOW — the flusher thread's
        deadline path, an ordering barrier, or an explicit test/shutdown
        flush."""
        with self._mu:
            self._flush_pending_locked()

    def _flush_pending_locked(self) -> None:
        p = self._pending
        if p is None:
            return
        self._pending = None
        events = list(p.latest.values())
        if not events:
            return
        m = DEFAULT_STORE_METRICS
        m.coalesce_flushes.inc()
        if p.folded:
            m.coalesced_events.inc(p.folded)
        by_kind = _fold_by_kind(events)  # (folded as they were buffered)
        # synthetic frames carry NO prev_revisions (fold hides the
        # intermediate transitions, so the pre-transition revision is
        # honestly unknown — consumers take the per-object fallback
        # compare, exactly the plain-update CAS semantics); the fence
        # (frame.revision = last entry) is exact as ever
        frames_by_kind: dict[str, list] = {}
        tr = tracing.current()
        with (tr.span("store.txn", cat="store", op="coalesce_flush",
                      txn=p.txn, events=len(events), folded=p.folded)
              if tr is not None else tracing.NULL_SPAN) as sp:
            try:
                faults.hit("store.coalesce", n=len(events), folded=p.folded)
                if frames_mod.ENABLED:
                    for kind, evs in by_kind.items():
                        if len(evs) > 1:
                            frames_by_kind[kind] = frames_mod.pack_frames(
                                kind, evs, txn=p.txn)
            except Exception:  # noqa: BLE001 - degrade, never drop state
                # flush-path failure (injected or real): this window falls
                # back to per-event delivery of the SAME folded events — the
                # state every consumer converges to is identical, only the
                # packing is lost
                frames_by_kind = {}
                m.coalesce_fallbacks.inc()
                sp.set(fallback=True)
            else:
                self._log_txns.append(_LogTxn(
                    p.first_rev, events[-1].revision, p.txn, None, True))
                self._trim_log_txns()
            n_frames = sum(len(fs) for fs in frames_by_kind.values())
            m.watch_frames.inc(n_frames)
            sp.set(frames=n_frames)
        for wkind, q, wants_frames in self._watchers:
            for kind, evs in by_kind.items():
                if wkind is not None and wkind != kind:
                    continue
                # a kind's window leaves as frames of at most
                # FRAME_MAX_ROWS rows, in order (one frame when it fits)
                pieces = frames_by_kind.get(kind) if wants_frames else None
                for item in pieces or evs:
                    q.put(item)

    def _coalesce_loop(self) -> None:
        """Daemon flusher: parked until a window opens, then sleeps out
        the deadline and flushes.  Never holds the store lock while
        sleeping."""
        while True:
            self._coalesce_wake.wait()  # blocking-ok — daemon flusher parked until a window opens
            self._coalesce_wake.clear()
            if self._coalesce_closed:
                return
            while not self._coalesce_closed:
                with self._mu:
                    p = self._pending
                    delay = 0.0 if p is None else p.deadline - time.monotonic()
                if p is None:
                    break
                if delay > 0:
                    time.sleep(delay)  # blocking-ok — outside the lock, bounded by coalesce_window_s
                    continue
                self.flush_coalesced()

    def _emit_many(self, events: list[WatchEvent],
                   prev_revisions: Optional[list[int]] = None,
                   txn: Optional[str] = None,
                   bound: Optional[tuple] = None) -> int:
        """Fan one correlated batch out: WAL + log stay per-event (the
        replay window and durability framing are unchanged; where there is
        neither a WAL nor a replica the log takes the rows in one call),
        but every frame-aware watcher receives the txn column-packed — as
        :class:`~.frames.WatchFrame` pieces of at most
        ``frames.FRAME_MAX_ROWS`` rows, in revision order, all enqueued
        here under the caller's store-lock hold (one frame, one queue
        put, one informer lock hold when the txn fits the bound).
        Per-event watchers (kubectl -w, controllers, pre-frame clients)
        see the identical event sequence they always did.  Returns the
        number of frames packed (0 when nobody wanted one).  ``bound``
        is a ``bind_many`` txn's own (keys, revisions, node names): its
        frames slice them (``frames.pack_frames``)."""
        if not events:
            return 0
        # ordering barrier: a batch txn fans out at commit, so anything
        # buffered in an open coalescing window must reach the queues
        # first — watchers see revisions in order, no fence violations
        self._flush_pending_locked()
        if self._log_in_bulk:
            self._log.extend(events)  # deque maxlen trims the window in C
        else:
            for ev in events:
                self._append_log(ev)
                self._replicate(ev)
        if len(events) > 1:  # (a txn of one row goes out as the event)
            self._log_txns.append(_LogTxn(
                events[0].revision, events[-1].revision, txn, prev_revisions,
                False))
        if self._log_txns:
            self._trim_log_txns()
        pieces: list = []
        want_frame = len(events) > 1 and frames_mod.ENABLED
        kind = events[0].kind  # batch txns are single-kind by construction
        for wkind, q, wants_frames in self._watchers:
            if wkind is not None and wkind != kind:
                continue
            if wants_frames and want_frame:
                if not pieces:  # packed once, shared-immutable
                    pieces = frames_mod.pack_frames(
                        kind, events, prev_revisions=prev_revisions, txn=txn,
                        bound=bound)
                    DEFAULT_STORE_METRICS.watch_frames.inc(len(pieces))
                for frame in pieces:
                    q.put(frame)
            else:
                for ev in events:
                    q.put(ev)
        return len(pieces)


class ExpiredRevisionError(Exception):
    """Watch window compacted past the requested revision; caller must relist."""
