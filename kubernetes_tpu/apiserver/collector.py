"""The apiserver daemon's garbage-collector policy.

What the store holds is JSON-decoded data: objects, their event copies and
the log's rows, acyclic by construction, alive until they are deleted or
trimmed, and then freed by reference count.  CPython's oldest-generation
pass walks all of it all the same, whenever a quarter of the heap's last
count has been promoted since: at 150,000 pods that is 1.5 s with every
thread stopped, and it begins inside whichever ``bind_many`` tipped the
count.

So the daemon owns its collector.  The allocator never starts an
oldest-generation pass in this process (the third threshold is out of
reach; young passes stay as they are, and stay cheap because everything
older is frozen or waiting).  The daemon starts it: after an answer's
bytes are written, once the store has committed ``PASS_ROWS`` rows since
the last pass, or from the daemon's tick when the store stands still.  A
pass is ``gc.collect()`` over what is not yet frozen, then
``gc.freeze()``: its cost follows what was committed since the last one,
not the heap.  Frozen objects still die by reference count; only a cycle
among them is never reclaimed, and the collect before each freeze takes
the cycles that exist then (``apiserver_gc_full_collected_objects_total``
says how much that is).

This is the policy of a *process*.  ``main`` installs it; ``Store`` and
``APIServer`` call nothing of ``gc``, because tests, ``chip_smoke.py`` and
the benchmark's scheduler embed them beside JAX and the scheduler, whose
cyclic garbage a library they merely use must not freeze."""

from __future__ import annotations

import gc
import threading
import time
from typing import Callable

from ..utils.metrics import Counter, Gauge, Registry

# rows the store commits between two passes of the daemon (one module
# constant, read on the chip: PERF.md, PR 32)
PASS_ROWS = 16_384

# gc.set_threshold takes C ints: the oldest generation's count is of
# middle-generation passes, and never gets here
_NEVER = (1 << 31) - 1


class Collector:
    """``revision``: the store's revision, one per committed row."""

    def __init__(self, revision: Callable[[], int], registry: Registry):
        self._revision = revision
        self._mu = threading.Lock()
        self._pass_rev = 0
        self._tick_rev = 0
        self._began = None
        self._thresholds = None
        self.full_passes = registry.register(Counter(
            "apiserver_gc_full_passes_total",
            "oldest-generation passes of this process's collector"))
        self.full_pause = registry.register(Counter(
            "apiserver_gc_full_pause_seconds_total",
            "seconds those passes held every thread"))
        self.full_collected = registry.register(Counter(
            "apiserver_gc_full_collected_objects_total",
            "unreachable objects those passes found: the cyclic garbage "
            "a freeze without its collect would have kept"))
        self.freezes = registry.register(Counter(
            "apiserver_gc_freezes_total",
            "passes of the daemon: a collect over what was not frozen, "
            "then a freeze of what it left"))
        self.frozen = registry.register(Gauge(
            "apiserver_gc_frozen_objects",
            "objects in the permanent generation after the last freeze"))

    def install(self) -> None:
        self._thresholds = gc.get_threshold()
        gc.callbacks.append(self._on_gc)
        self._tick_rev = self._revision()
        self.run_pass()
        gc.set_threshold(*self._thresholds[:2], _NEVER)

    def uninstall(self) -> None:
        """For a process that goes on without the policy (tests): the
        daemon exits frozen, which spares it the interpreter's last pass."""
        gc.callbacks.remove(self._on_gc)
        gc.set_threshold(*self._thresholds)
        gc.unfreeze()

    @property
    def pause_s(self) -> float:
        """Seconds of full passes so far; a request reads it at its
        headers and at its answer (``Server-Timing``'s ``gc;dur=``)."""
        return self.full_pause.value

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.full_pause.inc(time.perf_counter() - self._began)
            self._began = None
            self.full_passes.inc()
            self.full_collected.inc(info["collected"] + info["uncollectable"])

    def after_request(self) -> None:
        """Called by a handler once its answer is written."""
        if self._revision() - self._pass_rev >= PASS_ROWS:
            self.run_pass()

    def tick(self) -> None:
        """The daemon's idle tick: a store that committed nothing since the
        last tick gives the pass its boundary, if anything waits for one
        (rows under ``PASS_ROWS``, or survivors of young passes that no
        write will ever bring a pass to)."""
        rev = self._revision()
        moved = rev != self._tick_rev
        self._tick_rev = rev
        if not moved and (rev != self._pass_rev or gc.get_count()[2]):
            self.run_pass()

    def run_pass(self) -> None:
        if not self._mu.acquire(blocking=False):
            return  # another thread's pass covers this one's rows
        try:
            rev = self._revision()
            passes = self.full_passes.value
            gc.collect()
            if self.full_passes.value == passes:
                # a young pass of another thread was under way (its
                # callbacks let this thread in) and gc.collect() stood
                # back: nothing was collected, so nothing is frozen, and
                # the next boundary tries again
                return
            gc.freeze()
            self._pass_rev = rev
            self.freezes.inc()
            self.frozen.set(gc.get_freeze_count())
        finally:
            self._mu.release()
