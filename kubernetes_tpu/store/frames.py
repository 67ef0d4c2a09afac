"""Column-packed watch frames: N correlated events as ONE delivery unit.

The last leg of the zero-copy contract (ROADMAP "batched watch frames"):
LIST went columnar in PR 4 (``store/columns.py``), but every watch event
still crossed the store→informer boundary — and the wire — one at a
time: one queue put, one JSON line, one informer lock acquisition, one
cache dict probe per event.  At churn scale a single ``bind_many`` wave
commits thousands of MODIFIED events back to back, and that per-event
pump APPLICATION (cache apply + bind confirm) was the largest remaining
host cost in the profile (~0.3-0.8s spikes per wave).

A :class:`WatchFrame` packs one correlated store batch — everything a
``create_many``/``bind_many`` txn committed under one store lock hold
or, past :data:`FRAME_MAX_ROWS` rows, one piece of it in revision order
(:func:`pack_frames`) — into parallel columns:

- **op/kind/identity columns**: ``types`` (ADDED/MODIFIED/DELETED),
  ``keys``, ``revisions`` as flat lists (one ``kind`` per frame — a
  store batch is single-kind by construction);
- **prev_revisions**: the revision each object held *before* this
  transition (-1 = unknown).  This is the columnar confirm fence: a
  scheduler that assumed a pod at revision r and sees a bind event with
  ``prev_revision == r`` knows, by CAS semantics, that NOTHING else
  changed in between — the whole containers/affinity equality check
  collapses to one integer compare per column entry;
- **shared raw-view payloads**: ``objects`` are the same shallow views /
  event copies the per-event path would have carried, shared-immutable
  (the informer contract: consumers never mutate wire payloads).  A
  packed frame holds its events and builds this column on first touch
  (encode, ``select``, an informer's decode): a ``bind_many`` row's
  payload is derived only then (``store.BoundPodEvent``), and a consumer
  of ``keys`` / ``revisions`` / ``prev_revisions`` / ``node_names``
  alone never builds one.

Consumers that predate frames are never broken: frames are **opt-in per
watcher** (``Store.watch(..., frames=True)``), the apiserver serves them
only to ``?frames=1`` clients (per-event JSON lines otherwise), and
``events()`` expands a frame back into the exact per-event sequence.

``ENABLED = False`` is the reference arm of ``tests/test_watch_frames.py``:
per-event delivery, which framed delivery must leave every consumer's
state equal to.
"""

from __future__ import annotations

from typing import Iterator, Optional

# False restores per-event delivery everywhere (frame-aware consumers
# stay dormant — they only ever see plain WatchEvents); a test's
# reference arm
ENABLED = True

# True serializes each frame/event wire payload ONCE and shares the
# encoded bytes across every HTTP watcher streaming it; False, every
# client pays its own json.dumps per delivery (arm A of
# tests/watch_fleet_harness.py)
SHARED_ENCODE = True

# WatchFrame.type value: a transport framing marker, not a state
# transition (like WATCH_GAP).  Consumers that dispatch on event type
# must expand the frame (``events()``) or apply it as a batch.
FRAME = "FRAME"

# The most rows one frame carries.  A frame is encoded (and decoded) by
# one C-level json call that holds its process's interpreter lock from
# start to end: at ~845 wire bytes per bound pod a 60,000-row frame is a
# 50 MB, 0.45 s critical section on a watcher's thread of the apiserver,
# ahead of the handler thread that has the answer to the ``bind_many``
# that emitted it.  Nobody needs the txn to be ONE delivery — the fence
# is per frame, the txn id rides every piece — so a batch leaves in
# pieces and the answer waits behind at most one piece's encode.
FRAME_MAX_ROWS = 2048


class FrameDecodeError(Exception):
    """A frame's columns are structurally broken (length mismatch,
    non-monotone revisions, malformed payloads).  A consumer cannot know
    WHICH events it lost — the only honest recovery is a gap + relist,
    never a silent partial apply."""


class WatchFrame:
    """One correlated batch of watch events, column-packed.

    Shared-immutable like :class:`~.store.WatchEvent`: one frame object
    is handed to the log consumers and every watcher; nobody mutates it.
    """

    __slots__ = ("kind", "types", "keys", "revisions", "prev_revisions",
                 "txn", "_objects", "_events", "_node_names", "_wire_b")

    # duck-typed dispatch marker (``ev.type == FRAME``) for consumers
    # that pull mixed WatchEvent/WatchFrame items off one watch queue
    type = FRAME

    def __init__(self, kind: str, types: list, keys: list, revisions: list,
                 objects: Optional[list], prev_revisions: Optional[list] = None,
                 txn: Optional[str] = None, events: Optional[list] = None,
                 node_names: Optional[list] = None):
        self.kind = kind
        self.types = types
        self.keys = keys
        self.revisions = revisions
        # -1 = unknown (creates, deletes, plain updates); >= 0 only where
        # the emitting txn knew the pre-transition revision (bind_many)
        self.prev_revisions = prev_revisions
        # the payload column, or None until first touch where the frame
        # was packed from ``events`` (pack_frames)
        self._objects = objects
        self._events = events
        # correlation id minted by the emitting store txn (ISSUE 7):
        # the same id appears on the store's txn span, this frame, the
        # informer's frame-apply span, and the scheduler's confirm span,
        # so one trace shows the store→informer→confirm propagation
        self.txn = txn
        self._node_names = node_names  # a bind txn knows them at commit
        self._wire_b: Optional[bytes] = None

    def __len__(self) -> int:
        return len(self.keys)

    @property
    def revision(self) -> int:
        """The frame's resourceVersion fence: a consumer that applied
        this frame has seen everything up to its LAST event."""
        return self.revisions[-1] if self.revisions else 0

    @property
    def objects(self) -> list:
        got = self._objects
        if got is None:
            from .store import event_payloads

            # benign race, like wire_bytes: equal columns, last one wins
            got = self._objects = event_payloads(self._events)
        return got

    @property
    def node_names(self) -> list:
        """Per-event ``spec.nodeName`` column, computed on first touch —
        what the scheduler's columnar bind confirm compares against its
        assumed placements (one raw dict get per entry, no decode)."""
        got = self._node_names
        if got is None:
            got = self._node_names = [
                (o.get("spec") or {}).get("nodeName", "") if o else ""
                for o in self.objects]
        return got

    def select(self, indices: list) -> Optional["WatchFrame"]:
        """Column-level sub-frame: keep only the entries at ``indices``
        (ascending, as produced by a selector filter walk), sharing the
        payload dicts with this frame (shared-immutable, like every
        other consumer).  Revision order — and therefore the per-frame
        resourceVersion fence — is preserved by construction.  Returns
        None for an empty selection: an all-filtered frame must not
        reach the wire (``from_wire`` rejects empty frames; the client's
        fence advances on its next matching delivery instead)."""
        if not indices:
            return None
        if len(indices) == len(self.keys):
            return self  # every entry matched: share the packed frame
        prev = self.prev_revisions
        return WatchFrame(
            self.kind,
            [self.types[i] for i in indices],
            [self.keys[i] for i in indices],
            [self.revisions[i] for i in indices],
            [self.objects[i] for i in indices],
            prev_revisions=None if prev is None else [prev[i] for i in indices],
            txn=self.txn,
        )

    def wire_bytes(self) -> bytes:
        """The frame's encoded watch line (wire form + newline), computed
        once and shared across every streaming client (the single-encode
        fan-out leg) while :data:`SHARED_ENCODE` is on.  Benign race by
        design: two handler threads may both encode the same frame; the
        bytes are identical and the last assignment wins."""
        import json

        if not SHARED_ENCODE:
            return json.dumps(self.to_wire()).encode() + b"\n"
        got = self._wire_b
        if got is None:
            got = self._wire_b = json.dumps(self.to_wire()).encode() + b"\n"
        return got

    def events(self) -> Iterator:
        """Expand back into the exact per-event sequence (order, content,
        revisions) — the compatibility path for per-event consumers."""
        if self._events is not None:
            yield from self._events  # the rows this frame was packed from
            return
        from .store import WatchEvent

        for i in range(len(self.keys)):
            yield WatchEvent(self.types[i], self.kind, self.keys[i],
                             self.revisions[i], self.objects[i])

    # -- wire form (the apiserver's ?frames=1 watch line) -------------------
    def to_wire(self) -> dict:
        out = {
            "type": FRAME,
            "kind": self.kind,
            "types": self.types,
            "keys": self.keys,
            "revisions": self.revisions,
            "objects": self.objects,
        }
        if self.prev_revisions is not None:
            out["prevRevisions"] = self.prev_revisions
        if self.txn is not None:
            out["txn"] = self.txn
        return out

    @classmethod
    def from_wire(cls, d: dict) -> "WatchFrame":
        """Decode + validate.  A structurally broken frame must fail HERE
        with :class:`FrameDecodeError` — the consumer turns it into a
        watch gap (relist), never a partial apply."""
        try:
            kind = d["kind"]
            types = d["types"]
            keys = d["keys"]
            revisions = [int(r) for r in d["revisions"]]
            objects = d["objects"]
            prev = d.get("prevRevisions")
            if prev is not None:
                prev = [int(r) for r in prev]
        except (KeyError, TypeError, ValueError) as e:
            raise FrameDecodeError(f"malformed frame: {e!r}") from None
        n = len(keys)
        if not (len(types) == len(revisions) == len(objects) == n) or (
                prev is not None and len(prev) != n):
            raise FrameDecodeError(
                f"frame column lengths diverge: keys={n} types={len(types)} "
                f"revisions={len(revisions)} objects={len(objects)}")
        if n == 0:
            raise FrameDecodeError("empty frame")
        if any(revisions[i] >= revisions[i + 1] for i in range(n - 1)):
            # one store txn commits strictly increasing revisions; a frame
            # violating that was corrupted in flight
            raise FrameDecodeError("frame revisions not strictly increasing")
        if any(o is not None and not isinstance(o, dict) for o in objects):
            raise FrameDecodeError("frame payloads must be dicts")
        txn = d.get("txn")
        if txn is not None and not isinstance(txn, str):
            raise FrameDecodeError("frame txn id must be a string")
        return cls(kind, list(types), list(keys), revisions, list(objects),
                   prev_revisions=prev, txn=txn)


def pack_frames(kind: str, events: list,
                prev_revisions: Optional[list] = None,
                txn: Optional[str] = None,
                bound: Optional[tuple] = None) -> list:
    """One correlated batch (revision order, single kind) as a list of
    :class:`WatchFrame` pieces of at most :data:`FRAME_MAX_ROWS` rows
    each, in order, ``prev_revisions`` sliced alike, every piece carrying
    the txn's id.  A batch at or under the bound is one frame; a one-row
    remainder is still a frame (its prev-revision fence column is kept).
    The pieces' fences (``frame.revision``) increase strictly, so a
    consumer that applied some of them resumes after its last one.

    ``bound`` is a ``bind_many`` txn's own columns, (keys, revisions,
    node names), one entry per event: the pieces slice them instead of
    reading the events back, and know their ``node_names`` from the
    start.  Every piece holds its events and builds ``objects`` on first
    touch."""
    out = []
    for lo in range(0, len(events), FRAME_MAX_ROWS):
        hi = lo + FRAME_MAX_ROWS
        evs = events[lo:hi]
        if bound is None:
            types = [e.type for e in evs]
            keys = [e.key for e in evs]
            revisions = [e.revision for e in evs]
            node_names = None
        else:
            types = [evs[0].type] * len(evs)
            keys, revisions, node_names = (col[lo:hi] for col in bound)
        out.append(WatchFrame(
            kind, types, keys, revisions, None,
            prev_revisions=(None if prev_revisions is None
                            else prev_revisions[lo:hi]),
            txn=txn, events=evs, node_names=node_names,
        ))
    return out


def event_wire_bytes(ev) -> bytes:
    """Encoded watch line for one plain :class:`~.store.WatchEvent`
    (wire form + newline), computed once per event and shared across
    every streaming client while :data:`SHARED_ENCODE` is on.  The cache
    rides the event object itself (its ``_wire_b`` slot): events are
    shared-immutable across all watcher queues, so the first client to
    encode pays and the rest reuse.
    Benign race: concurrent encoders produce identical bytes."""
    import json

    if SHARED_ENCODE:
        got = getattr(ev, "_wire_b", None)
        if got is not None:
            return got
    line = json.dumps({
        "type": ev.type,
        "kind": ev.kind,
        "key": ev.key,
        "revision": ev.revision,
        "object": ev.object,
    }).encode() + b"\n"
    if SHARED_ENCODE:
        ev._wire_b = line
    return line
