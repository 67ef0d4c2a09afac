"""The scheduler daemon: watch wiring, the scheduleOne loop, and the batch seam.

Capability of ``plugin/pkg/scheduler/scheduler.go`` +
``factory/factory.go:120 NewConfigFactory``:

- informers feed the scheduler cache (bound/assumed pods, nodes) and the
  pending queue (unscheduled pods) — factory.go:140,188-199,391-520;
- ``schedule_one`` (scheduler.go:253): pop → snapshot → schedule → assume →
  bind, with failure → backoff re-enqueue (MakeDefaultErrorFunc,
  factory.go:718) and assumed-pod TTL expiry self-healing;
- Scheduled / FailedScheduling events (scheduler.go:174,248) and the three
  latency SLIs (metrics/metrics.go).

The TPU path: ``schedule_pending_batch`` drains the whole queue and hands
the batch to a pluggable ``backend`` (``kubernetes_tpu/ops/backend.py``),
generalizing the reference's 1-deep assume/bind pipeline (SURVEY.md P9) to
batch depth.  The oracle path stays available both as the correctness
reference and as the fallback when a batch member's bind CAS fails.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

from .. import faults
from ..api import lazy
from ..api import types as api
from ..client.clientset import BindConflictError, Clientset
from ..client.informer import Handler, InformerFactory
from ..client.record import EventBroadcaster
from ..store.store import ADDED, MODIFIED, NotFoundError
from ..utils import tracing
from ..utils.metrics import SchedulerMetrics
from ..utils.trace import Trace
from .generic_scheduler import FitError, GenericScheduler
from .nodeinfo import NodeInfo, PlacedSegment, SchedulerCache
from .priorities import PriorityContext
from .queue import PodBackoff, SchedulingQueue

logger = logging.getLogger("kubernetes_tpu.scheduler")

DEFAULT_SCHEDULER_NAME = "default-scheduler"

# memoized accelerator platform ("tpu" / "gpu" / "cpu"):
# _pipeline_idle's full-window polling gate reads it once per process
_ACCEL_PLATFORM: Optional[str] = None


def _accel_platform() -> str:
    global _ACCEL_PLATFORM
    if _ACCEL_PLATFORM is None:
        import jax

        # a device that cannot be read raises (see ops.backend._device_platform)
        _ACCEL_PLATFORM = jax.devices()[0].platform
    return _ACCEL_PLATFORM


def _poll_full_device_window() -> bool:
    """Should overlapped prep keep polling the device for the whole scan
    window?  A real accelerator (TPU/GPU) executes off the host CPU, so
    polling always hides in its shadow — poll unconditionally (ROADMAP
    open item: the old ``cpu_count > 1`` gate wrongly throttled 1-CPU
    TPU hosts).  On the XLA *CPU* "device" the computation shares the
    host cores, and on a 1-core box every poll cycle stretches the scan
    1:1 (measured 2x) — keep the spare-core requirement there."""
    import os

    if _accel_platform() != "cpu":
        return True
    return (os.cpu_count() or 1) > 1


def _is_scheduler_pod(pod: api.Pod, name: str) -> bool:
    _, sched_name, phase = lazy.pod_brief(pod)
    return sched_name == name and phase in (api.PENDING, api.RUNNING)


class Scheduler:
    def __init__(
        self,
        clientset: Clientset,
        algorithm: Optional[GenericScheduler] = None,
        backend=None,
        scheduler_name: str = DEFAULT_SCHEDULER_NAME,
        assume_ttl: float = 30.0,
        clock: Callable[[], float] = time.monotonic,
        emit_events: bool = True,
        enable_preemption: bool = True,
    ):
        self.clientset = clientset
        self.algorithm = algorithm or GenericScheduler()
        self.backend = backend  # TPU batch backend (ops/backend.py) or None
        self.scheduler_name = scheduler_name
        self.cache = SchedulerCache(ttl=assume_ttl, clock=clock)
        self.queue = SchedulingQueue(clock=clock)
        self.backoff = PodBackoff(clock=clock)
        self.metrics = SchedulerMetrics()
        if backend is not None and hasattr(backend, "fallback_counter"):
            # kernel fallbacks surface in this scheduler's metrics registry
            backend.fallback_counter = self.metrics.pallas_fallback_total
        if backend is not None and hasattr(backend, "breaker_counter"):
            backend.breaker_counter = self.metrics.kernel_breaker_transitions
        if backend is not None and hasattr(backend, "frontier_counter"):
            backend.frontier_counter = self.metrics.frontier_compactions
        if backend is not None and hasattr(backend, "shed_counter"):
            backend.shed_counter = self.metrics.score_plane_sheds
        # overload control (ISSUE 17): a DegradationLadder wired via
        # attach_overload; None = full fidelity always
        self.overload = None
        self.emit_events = emit_events
        self.enable_preemption = enable_preemption
        self._clock = clock
        self._snapshot: dict[str, NodeInfo] = {}
        # steady-state pipeline: overlap the next wave's ingest (pump +
        # signature warming) with the current wave's device execution —
        # the cross-wave extension of the per-segment commit overlap.
        # False restores the lock-step behavior (the reference arm of
        # tests/test_pipeline.py).
        self.overlap_ingest = True
        self._last_prep_s = 0.0
        # per-wave phase split of the last schedule_pending_batch call.
        # With tracing enabled the tensorize/dispatch/device_wait/commit/
        # prep keys are DERIVED from the wave's span tree (same clock
        # reads — the two cannot disagree); disabled, they come from the
        # backend's stats deltas.
        self.last_batch_phases: dict = {}
        # attrs the batch loop stamps onto the NEXT wave's root span
        # (queue wait / accumulation window measured before the drain)
        self._wave_attrs_pending: dict = {}
        # async event pipeline (client-go tools/record): the hot path only
        # enqueues; correlation + store writes happen on the sink thread
        self.broadcaster = EventBroadcaster(
            clientset, source=scheduler_name, clock=clock
        )
        self._recorder = self.broadcaster.recorder("Pod")

        self.informers = InformerFactory(clientset)
        self._wire_informers()

    # -- informer wiring (factory.go:140-520) ------------------------------
    def _wire_informers(self) -> None:
        pods = self.informers.informer("Pod")
        line = self.metrics.watch_line_events.inc

        def counted(route):
            # an event the informer hands over one by one came as a line
            # of its own, not in a frame (on_batch takes those)
            def on_line(*objs):
                line()
                route(*objs)
            return on_line

        pods.add_handler(
            Handler(
                on_add=counted(self._on_pod_add),
                on_update=counted(self._on_pod_update),
                on_delete=counted(self._on_pod_delete),
                on_batch=self._on_pod_frame,
            )
        )
        nodes = self.informers.informer("Node")
        nodes.add_handler(
            Handler(
                on_add=lambda n: self.cache.add_node(n),
                on_update=lambda old, new: self.cache.update_node(new),
                on_delete=lambda n: self.cache.remove_node(n.meta.name),
            )
        )
        # services/replicasets: cache-only informers for spreading priorities;
        # PVs/PVCs: volume predicates (the reference wires 8 informers,
        # factory.go:120 — pods, nodes, PVs, PVCs, RCs, RSs, statefulsets,
        # services)
        self.informers.informer("Service")
        self.informers.informer("ReplicaSet")
        self.informers.informer("PersistentVolume")
        self.informers.informer("PersistentVolumeClaim")

    def _on_pod_add(self, pod: api.Pod) -> None:
        # pod_brief reads the routing fields (nodeName/schedulerName/
        # phase) straight off the wire dict for lazy events — the handler
        # fan-out never builds spec/status views for pods it only routes
        node_name, sched_name, phase = lazy.pod_brief(pod)
        if node_name:
            self.cache.add_pod(pod)
        elif sched_name == self.scheduler_name and phase in (api.PENDING,
                                                            api.RUNNING):
            self.queue.add(pod)

    def _on_pod_update(self, old: api.Pod, new: api.Pod) -> None:
        if lazy.pod_brief(new)[0]:
            if old is not None and lazy.pod_brief(old)[0]:
                self.cache.update_pod(old, new)
            else:
                self.queue.remove(new.meta.key)
                self.cache.add_pod(new)
        else:
            if _is_scheduler_pod(new, self.scheduler_name):
                self.queue.update(new)
            else:
                # pod became terminal (Failed/Succeeded) or changed scheduler
                # while pending: drop it from the queue
                self.queue.remove(new.meta.key)

    def _on_pod_delete(self, pod: api.Pod) -> None:
        if lazy.pod_brief(pod)[0]:
            self.cache.remove_pod(pod)
        else:
            self.queue.remove(pod.meta.key)

    def _on_pod_frame(self, frame, deltas) -> None:
        """Batch-aware pod routing (``Handler.on_batch``, ISSUE 6): one
        column-packed watch frame carries a whole correlated store txn.
        A bind-confirm frame (``bind_many``: all-MODIFIED, prev-revision
        column present) confirms the ENTIRE wave against the frame's
        identity/node/prev-revision columns in one cache lock hold —
        per-pod dict probes and containers compares collapse to integer
        compares (``SchedulerCache.confirm_many``).  Whatever the
        columnar fence rejects — and every non-confirm delta — takes the
        existing per-pod routing, so semantics are identical to per-event
        delivery by construction.

        The confirm span carries the emitting txn's correlation id
        (ISSUE 7) — the third hop of the store→informer→confirm trace."""
        tr = tracing.current()
        if tr is None:
            return self._route_pod_frame(frame, deltas)
        with tr.span("scheduler.confirm", cat="ingest", kind=frame.kind,
                     txn=frame.txn, events=len(deltas)) as sp:
            fb0 = self.metrics.confirm_fallbacks.value
            self._route_pod_frame(frame, deltas)
            sp.set(fallbacks=int(self.metrics.confirm_fallbacks.value - fb0))

    def _route_pod_frame(self, frame, deltas) -> None:
        self.metrics.watch_frames.inc()
        self.metrics.watch_frame_events.inc(len(deltas))
        rest = deltas
        prev = frame.prev_revisions
        if prev is not None:
            node_names = frame.node_names
            keys = frame.keys
            confirmable: list = []
            rest = []
            for d in deltas:
                etype, old, new, i = d
                if etype == MODIFIED and node_names[i]:
                    confirmable.append((keys[i], node_names[i], prev[i],
                                        new, old))
                else:
                    rest.append(d)
            if confirmable:
                # one queue lock + one cache lock for the whole wave
                self.queue.remove_many([c[0] for c in confirmable])
                for key, _node, _prev, new, old in self.cache.confirm_many(
                        confirmable):
                    # revision fence rejected it (no assumption, different
                    # node, or an intervening write): the per-pod compare
                    # path decides, exactly as per-event delivery would
                    self.metrics.confirm_fallbacks.inc()
                    self._on_pod_update(old, new)
        for etype, old, new, _i in rest:
            if etype == ADDED:
                self._on_pod_add(new)
            elif etype == MODIFIED:
                self._on_pod_update(old, new)
            else:
                self._on_pod_delete(old if old is not None else new)

    def start(self, manual: bool = True) -> None:
        """Seed informers.  manual=True (tests, benchmark) → caller pumps and
        events drain via ``broadcaster.flush()``; manual=False → informer
        threads run the watch loops and the event sink thread runs."""
        if manual:
            self.informers.start_all_manual()
        else:
            self.informers.start_all()
            if self.emit_events:
                self.broadcaster.start()

    def pump(self) -> int:
        tr = tracing.current()
        with (tr.span("ingest.pump", cat="ingest")
              if tr is not None else tracing.NULL_SPAN) as sp:
            n = self.informers.pump_all()
            if not self.broadcaster.running:
                # manual drive: no sink thread, so drain events synchronously
                self.broadcaster.flush()
            sp.set(events=n)
        return n

    def _ingest_decode_stats(self) -> tuple[float, int]:
        """(cumulative informer decode seconds, cumulative lazy
        promotions) across this scheduler's informers — per-wave deltas
        feed ``scheduler_ingest_decode_seconds``."""
        from ..api import lazy as lazy_mod

        decode_s = sum(
            inf.stats.get("decode_s", 0.0)
            for inf in self.informers._informers.values())
        st = lazy_mod.STATS
        return decode_s, st["promotions"] + st["sections"]

    def _pump_apply_stats(self) -> tuple[float, int, int]:
        """(cumulative pump-application seconds, frames, frame events)
        across this scheduler's informers — per-wave deltas feed
        ``scheduler_pump_apply_seconds`` (ISSUE 6)."""
        apply_s = frames = frame_events = 0
        for inf in self.informers._informers.values():
            st = inf.stats
            apply_s += st.get("apply_s", 0.0)
            frames += st.get("frames", 0)
            frame_events += st.get("frame_events", 0)
        return apply_s, frames, frame_events

    def _observe_mesh_wave(self, lf, pre_shard, ncache, wave_span) -> None:
        """Per-shard SLO attribution of the sharded wave loop (the PR-12
        caveat lands here: an AGGREGATE upload fraction hides one cold
        shard behind N-1 warm ones).  The worst shard's upload fraction
        and the alive-fraction skew land on literal-named gauges —
        ``utils.slo.mesh_slos`` windows them — and the per-shard lists
        ride the existing wave span as one ``mesh`` attr, not a second
        trace format."""
        mesh_segs = [s for s in (lf or []) if s.get("mode") == "mesh"]
        if not mesh_segs:
            return
        n_shards = max(int(s.get("n_shards", 0)) for s in mesh_segs)
        self.metrics.mesh_shards.set(n_shards)
        attrs: dict = {"n_shards": n_shards}
        skews = [max(fr) - min(fr) for s in mesh_segs
                 for fr in (s.get("shard_alive_frac") or []) if fr]
        if skews:
            skew = round(max(skews), 4)
            self.metrics.mesh_shard_alive_skew.set(skew)
            attrs["shard_alive_skew"] = skew
        if pre_shard is not None and ncache is not None:
            dirty = ncache.stats.get("shard_dirty_cols", ())
            cols = ncache.stats.get("shard_cols_total", ())
            # first mesh wave: set_mesh() sized the per-shard counters
            # AFTER the pre-wave capture — an empty pre-list means zero
            pre_d = pre_shard[0] or (0,) * len(dirty)
            pre_c = pre_shard[1] or (0,) * len(cols)
            fracs = []
            if len(dirty) == len(pre_d) and len(cols) == len(pre_c):
                for d0, d1, c0, c1 in zip(pre_d, dirty, pre_c, cols):
                    if c1 - c0 > 0:
                        fracs.append((d1 - d0) / (c1 - c0))
            if fracs:
                worst = round(max(fracs), 4)
                self.metrics.mesh_worst_shard_upload_fraction.set(worst)
                attrs["shard_upload_fractions"] = [round(f, 4) for f in fracs]
                attrs["worst_shard_upload_fraction"] = worst
        self.last_batch_phases["mesh"] = attrs
        if wave_span is not None:
            wave_span.set(mesh=attrs)

    # -- snapshot ----------------------------------------------------------
    def snapshot(self) -> dict[str, NodeInfo]:
        """Generation-checked CoW refresh (cache.go:79)."""
        self.cache.snapshot_into(self._snapshot)
        return self._snapshot

    def _volume_listers(self) -> tuple[dict, dict]:
        """(pvs by name, pvcs by namespaced key) — shared by scheduling and
        preemption so both resolve claims identically."""
        pvs = {pv.meta.name: pv for pv in self.informers.informer("PersistentVolume").list()}
        pvcs = {pvc.meta.key: pvc for pvc in self.informers.informer("PersistentVolumeClaim").list()}
        return pvs, pvcs

    def priority_context(self, snapshot: dict[str, NodeInfo]) -> PriorityContext:
        services = self.informers.informer("Service").list()
        replicasets = self.informers.informer("ReplicaSet").list()
        pvs, pvcs = self._volume_listers()
        return PriorityContext(
            snapshot, services=services, replicasets=replicasets, pvcs=pvcs, pvs=pvs
        )

    # -- events / SLIs -----------------------------------------------------
    def _event(self, pod: api.Pod, etype: str, reason: str, message: str) -> None:
        if not self.emit_events:
            return
        self._recorder.event(pod, etype, reason, message)

    # -- bind + failure handling ------------------------------------------
    def _requeue_after_bind_failure(self, pod: api.Pod) -> None:
        """Transient bind failures re-enqueue the pod with backoff.

        Without this a pod whose bind hit a transport/store error was
        stranded: popped from the queue, never bound, and no watch event
        would ever re-add it.  Re-enqueues the LATEST informer version
        (like handle_schedule_failure) and only while the pod is still
        ours to place — a pod that meanwhile got bound or turned terminal
        belongs to whoever did that."""
        latest = self.informers.informer("Pod").get(pod.meta.key)
        if latest is None:
            return  # deleted while the bind was in flight
        if latest.spec.node_name or not _is_scheduler_pod(latest, self.scheduler_name):
            return  # bound by someone else, or became terminal
        self.metrics.bind_requeues.inc()
        # a decided placement that did not land: flight-recorder trigger
        tracing.notify_requeue(pod.meta.key)
        self.queue.add_after(latest, self.backoff.get_backoff(pod.meta.key))

    def _bind(self, pod: api.Pod, node_name: str) -> bool:
        tr = tracing.current()
        with (tr.span("scheduler.bind", cat="bind", pod=pod.meta.key,
                      node=node_name)
              if tr is not None else tracing.NULL_SPAN):
            return self._bind_attempt(pod, node_name)

    def _bind_attempt(self, pod: api.Pod, node_name: str) -> bool:
        start = self._clock()
        try:
            faults.hit("scheduler.bind", pod=pod.meta.key, node=node_name,
                       via="bind")
            self.clientset.pods.bind(
                api.Binding(
                    pod_namespace=pod.meta.namespace, pod_name=pod.meta.name, node_name=node_name
                )
            )
        except (BindConflictError, NotFoundError) as e:
            # permanent for THIS placement: the pod's fate is owned
            # elsewhere (already bound / deleted) — the informer stream
            # delivers the truth, nothing to retry
            logger.warning("bind failed for %s: %s", pod.meta.key, e)
            self.metrics.bind_failures.inc()
            self.cache.forget_pod(pod)
            self._event(pod, "Warning", "FailedBinding", str(e))
            return False
        except Exception as e:
            # transient (transport error, apiserver overload, injected
            # fault): the placement decision may still be right — drop
            # the assumption and retry the pod with backoff
            logger.warning("transient bind failure for %s: %s: %s",
                           pod.meta.key, type(e).__name__, e)
            self.metrics.bind_failures.inc()
            self.cache.forget_pod(pod)
            self._event(pod, "Warning", "FailedBinding", str(e))
            self._requeue_after_bind_failure(pod)
            return False
        self.metrics.binding_latency.observe((self._clock() - start) * 1e6)
        self.cache.finish_binding(pod.meta.key)
        self._event(pod, "Normal", "Scheduled", f"Successfully assigned {pod.meta.key} to {node_name}")
        return True

    def handle_schedule_failure(self, pod: api.Pod, err: Exception,
                                ev_batch: Optional[list] = None,
                                preempt_cohort: Optional[list] = None) -> None:
        """MakeDefaultErrorFunc (factory.go:718): re-enqueue with backoff.

        Re-enqueues the *latest* version from the informer cache, not the
        popped object — a spec patch that landed while the pod was in
        flight (e.g. adding the missing toleration) must not be lost.

        For priority pods, tries preemption first (the PostFilter phase):
        evicting a minimal set of lower-priority victims and requeueing the
        preemptor without backoff into the freed space.

        ``ev_batch``: batch callers pass a list to collect the
        FailedScheduling event instead of enqueueing (and waking the sink)
        per pod mid-batch.  ``preempt_cohort``: batch callers pass a list
        to DEFER priority pods' preemption to one cohort pass after the
        drain (``_preempt_cohort``) — the prefilter kernel then amortizes
        over the whole cohort instead of sweeping every node per pod."""
        self.metrics.schedule_failures.inc()
        if ev_batch is not None and self.emit_events:
            ev_batch.append((pod, "Warning", "FailedScheduling", str(err)))
        else:
            self._event(pod, "Warning", "FailedScheduling", str(err))
        latest = self.informers.informer("Pod").get(pod.meta.key)
        if latest is None:
            return  # deleted while we were scheduling it
        if latest.spec.node_name or not _is_scheduler_pod(latest, self.scheduler_name):
            return  # bound by someone else, or became terminal
        if self.enable_preemption and latest.spec.priority > 0:
            # overload ladder (ISSUE 17): at rung >= 2 the batched
            # PostFilter pass is reserved for the critical tier — lower
            # tiers take the plain backoff requeue below, so top-tier
            # preemption work is never diluted by standard-tier churn
            ov = self.overload
            if (ov is not None
                    and ov.classifier.tier_of(latest) < ov.preempt_tier_floor):
                self.metrics.preemption_sheds.inc()
            elif preempt_cohort is not None:
                preempt_cohort.append(latest)  # requeue decided at cohort time
                return
            elif self._try_preempt(latest):
                self.queue.add(latest)  # victims evicted; retry immediately
                return
        delay = self.backoff.get_backoff(pod.meta.key)
        self.queue.add_after(latest, delay)

    def _evict_victims(self, pod: api.Pod, target, ev_batch: Optional[list] = None) -> None:
        for victim in target.victims:
            try:
                self.clientset.pods.delete(victim.meta.name, victim.meta.namespace)
                self.metrics.preemption_victims.inc()
                msg = (f"Preempted by {pod.meta.key} (priority "
                       f"{pod.spec.priority}) on {target.node_name}")
                if ev_batch is not None and self.emit_events:
                    ev_batch.append((victim, "Normal", "Preempted", msg))
                else:
                    self._event(victim, "Normal", "Preempted", msg)
            except NotFoundError:
                continue

    def _try_preempt(self, pod: api.Pod) -> bool:
        from .preemption import find_preemption_target

        start = self._clock()
        self.metrics.preemption_attempts.inc()
        pvs, pvcs = self._volume_listers()
        target = find_preemption_target(
            pod, self.snapshot(), self.algorithm.predicates, pvcs=pvcs, pvs=pvs
        )
        if target is None:
            self.metrics.preemption_latency.observe((self._clock() - start) * 1e6)
            return False
        self._evict_victims(pod, target)
        self.pump()  # observe the deletions so the next attempt sees freed space
        self.metrics.preemption_latency.observe((self._clock() - start) * 1e6)
        return True

    def _preempt_cohort(self, cohort: list, ev_batch: Optional[list] = None) -> int:
        """Batch-path PostFilter (SURVEY §7.4.7): one prefilter-kernel call
        bounds every (preemptor, node) pair's victim cost; the exact
        reprieve evaluation then runs only on nodes whose bound can win
        (``find_preemption_target_fast`` — decisions identical to the
        per-pod oracle on the same state by construction).  Preemptors are
        processed in batch order; each eviction updates the state columns
        of the touched node so later preemptors see the new truth.
        Returns the number of successful preemptions; every cohort pod is
        requeued (immediately on success, with backoff otherwise)."""
        from ..ops.preemption_kernel import PreemptionState
        from .preemption import _fast_eligible, find_preemption_target_fast
        from .units import pod_request_vec

        if not cohort:
            return 0
        from ..models.snapshot import pod_signature_key

        snapshot = self.snapshot()
        pvs, pvcs = self._volume_listers()
        state = PreemptionState(snapshot)
        touched: set[str] = set()
        # node-static predicate gate memo per preemptor SIGNATURE (the
        # gate is victim-independent and generation-checked inside
        # find_preemption_target_fast, so same-template preemptors pay
        # it once per node across the whole cohort)
        static_caches: dict = {}
        preempted = 0
        # fits-now recheck state: shadow clones of earlier-eviction
        # targets (the ONLY nodes that can have become feasible since the
        # batch proved these pods unschedulable).  ``claims`` carries
        # every cohort member already promised capacity on a node —
        # evictors and fits-now grantees alike — and shadows are rebuilt
        # as fresh-state-plus-claims, so a SECOND eviction on the same
        # node never drops earlier claimants.  Capped: a huge touched
        # set degrades the recheck to best-effort-off.
        recheck_shadow: dict[str, NodeInfo] = {}
        claims: dict[str, list] = {}
        recheck_cap = 64
        for pod in cohort:
            start = self._clock()
            self.metrics.preemption_attempts.inc()
            latest = self.informers.informer("Pod").get(pod.meta.key)
            if latest is None:
                continue  # deleted while deferred
            if latest.spec.node_name or not _is_scheduler_pod(latest, self.scheduler_name):
                continue
            cands: list = []
            if not _fast_eligible(latest, self.algorithm.predicates):
                # odd preemptors (ports/volumes/own required affinity /
                # custom predicate set) take the branch-and-bound path,
                # which needs the prefilter bounds; the fast vectorized
                # path derives everything from `state` directly
                cands = state.candidates_for(
                    pod_request_vec(latest).units, latest.spec.priority)
            target = find_preemption_target_fast(
                latest, snapshot, cands, self.algorithm.predicates,
                pvcs=pvcs, pvs=pvs,
                static_cache=static_caches.setdefault(
                    pod_signature_key(latest), {}),
                state=state,
                recheck_nodes=sorted(recheck_shadow.items())
                if 0 < len(recheck_shadow) <= recheck_cap else None)
            if target is None:
                self.metrics.preemption_latency.observe(
                    (self._clock() - start) * 1e6)
                delay = self.backoff.get_backoff(pod.meta.key)
                self.queue.add_after(latest, delay)
                continue
            if not target.victims:
                # an earlier cohort eviction already freed space this pod
                # provably fits into — no eviction, retry immediately;
                # record the claim so later cohort members see it taken
                claims.setdefault(target.node_name, []).append(latest)
                shadow = recheck_shadow.get(target.node_name)
                if shadow is not None:
                    shadow.add_pod(latest)
                self.queue.add(latest)
                self.metrics.preemption_latency.observe(
                    (self._clock() - start) * 1e6)
                continue
            self._evict_victims(latest, target, ev_batch)
            self.pump()  # observe deletions: cache + informers advance
            snapshot = self.snapshot()
            fresh = snapshot.get(target.node_name)
            state.update_node(target.node_name, fresh)
            claims.setdefault(target.node_name, []).append(latest)
            if fresh is not None:
                # shadow = post-eviction state PLUS every outstanding
                # claim on this node (earlier grantees/evictors retry
                # into this space next batch) — later cohort members
                # must not be granted already-promised capacity
                shadow = fresh.clone()
                for claimant in claims[target.node_name]:
                    shadow.add_pod(claimant)
                recheck_shadow[target.node_name] = shadow
            touched.add(target.node_name)
            preempted += 1
            self.queue.add(latest)  # retry immediately into the freed space
            self.metrics.preemption_latency.observe((self._clock() - start) * 1e6)
        return preempted

    # -- the per-pod oracle loop (scheduler.go:253) ------------------------
    def schedule_one(self, timeout: Optional[float] = 0.0, async_bind: bool = False) -> bool:
        pod = self.queue.pop(timeout=timeout)
        if pod is None:
            return False
        start = self._clock()
        trace = Trace(f"Scheduling {pod.meta.key}", clock=self._clock)
        self.metrics.schedule_attempts.inc()
        snapshot = self.snapshot()
        trace.step("snapshot")
        try:
            algo_start = self._clock()
            result = self.algorithm.schedule(pod, snapshot, self.priority_context(snapshot))
            self.metrics.scheduling_algorithm_latency.observe((self._clock() - algo_start) * 1e6)
        except FitError as e:
            self.handle_schedule_failure(pod, e)
            return True
        trace.step("schedule")
        self.cache.assume_pod(pod, result.node_name)
        self.backoff.forget(pod.meta.key)
        if async_bind:
            threading.Thread(target=self._bind, args=(pod, result.node_name), daemon=True).start()
        else:
            self._bind(pod, result.node_name)
        trace.step("bind")
        self.metrics.e2e_scheduling_latency.observe((self._clock() - start) * 1e6)
        trace.log_if_long(0.1)
        return True

    def run_pending(self, max_pods: Optional[int] = None, pump_every: int = 100) -> int:
        """Drive schedule_one until the queue drains (test/bench harness)."""
        n = 0
        while (max_pods is None or n < max_pods) and len(self.queue) > 0:
            if not self.schedule_one(timeout=0.0):
                break
            n += 1
            if n % pump_every == 0:
                self.pump()
        self.pump()
        return n

    # -- the steady-state pipeline -----------------------------------------
    def _pipeline_idle(self, device_busy: Optional[Callable[[], bool]] = None) -> None:
        """Cross-wave overlapped prep, run by the backend in the shadow of
        the final segment's device execution: pump the informers (so the
        next wave's arrivals, node updates, and our own earlier bind
        confirmations are already digested when the drain happens) and
        warm the per-pod signature/content memos of everything queued.
        With a ``device_busy`` probe, prep keeps pumping until the device
        finishes — the whole scan window becomes ingest time instead of a
        blocked finalize.

        Touches only informers, cache, and queue — never the snapshot the
        in-flight batch was tensorized from, so the current wave's
        decisions are already fixed and parity is unaffected.  A failure
        here (including the injected ``scheduler.pipeline.prep`` fault)
        is contained: the work re-runs synchronously at the next wave's
        start, which is exactly the unpipelined behavior."""
        import time as _time

        t0 = _time.perf_counter()
        # Full-window polling is gated by PLATFORM (ROADMAP open item): a
        # real accelerator executes off the host CPU, so prep always hides
        # in its shadow; only the XLA CPU "device" — which shares the host
        # cores — still requires a spare core (on a 1-core box every poll
        # cycle stretched the scan 1:1, measured 2x).
        poll = device_busy is not None and _poll_full_device_window()
        try:
            faults.hit("scheduler.pipeline.prep")
            from ..models.snapshot import _pod_content_key, pod_signature_key

            while True:
                self.pump()
                for pod in self.queue.snapshot_pending():
                    # the wave's decode work, spread into the idle shadow:
                    # on the lazy path these are raw-dict reads (columns),
                    # never full object decodes — the drain then finds
                    # every per-pod memo warm
                    pod_signature_key(pod)
                    _pod_content_key(pod)
                if not poll or not device_busy():
                    break
                _time.sleep(0.002)
        except Exception as e:
            self.metrics.pipeline_prep_failures.inc()
            logger.warning("overlapped prep failed (work deferred to the "
                           "next wave): %s: %s", type(e).__name__, e)
        finally:
            t_end = _time.perf_counter()
            self._last_prep_s = t_end - t0
            self.metrics.pipeline_prep_latency.observe(self._last_prep_s * 1e6)
            tr = tracing.current()
            if tr is not None:
                # the overlapped host prep, attributed inside the wave's
                # device shadow (same clock reads as _last_prep_s)
                tr.complete("prep", t0, t_end, cat="phase", polled=poll)

    # -- overload control (ISSUE 17) ---------------------------------------
    def attach_overload(self, ladder) -> None:
        """Wire a ``utils.overload.DegradationLadder``: its rung lands in
        this scheduler's gauge/counter, and the batch loop consults it
        every iteration for effective accumulation knobs, score-plane
        shedding, and the preemption tier floor."""
        self.overload = ladder
        ladder.gauge = self.metrics.degradation_rung
        ladder.transition_counter = self.metrics.degradation_transitions

    def _apply_overload_knobs(self) -> None:
        """Push the ladder's rung-1/2 knobs onto the backend before a
        wave: score-plane shedding and sticky-bucket coarsening.  Cheap
        and idempotent — called once per wave."""
        ov = self.overload
        if ov is None or self.backend is None:
            return
        if hasattr(self.backend, "shed_score_planes"):
            self.backend.shed_score_planes = ov.shed_score_planes
        tz = getattr(self.backend, "tensorizer", None)
        if tz is not None and hasattr(tz, "bucket_scale"):
            tz.bucket_scale = ov.bucket_scale

    def _top_tier_ready(self) -> bool:
        """True when a critical-tier pod is waiting in the queue — under
        overload the accumulation window breaks early for it (the top
        tier never waits the widened window).  O(pending) scan; callers
        rate-limit it."""
        ov = self.overload
        if ov is None:
            return False
        cls = ov.classifier
        for pod in self.queue.snapshot_pending():
            if cls.tier_of(pod) >= cls.CRITICAL:
                return True
        return False

    def run_batch_loop(
        self,
        min_batch: int = 1,
        max_wait: float = 0.05,
        idle_timeout: Optional[float] = None,
        max_waves: Optional[int] = None,
        poll_interval: float = 0.005,
        max_batch: Optional[int] = None,
        stop: Optional[threading.Event] = None,
    ) -> int:
        """Continuous service mode: drain-and-schedule as pods arrive,
        under a min-batch/max-wait accumulation policy, until the queue
        is closed (or ``stop`` is set, or ``idle_timeout``/``max_waves``
        ends the loop).

        Each iteration pumps the informers (a no-op when watch threads
        own the streams), waits until at least ``min_batch`` pods are
        ready or ``max_wait`` has elapsed since the first ready pod (the
        queue-wait SLI records the window), and runs one pipelined wave.
        ``queue.close()`` unblocks the accumulation wait and ends the
        loop.  Returns total pods bound."""
        bound_total = 0
        waves = 0

        def stopped() -> bool:
            return self.queue.closed or (stop is not None and stop.is_set())

        idle_deadline = (self._clock() + idle_timeout
                         if idle_timeout is not None else None)
        while not stopped() and (max_waves is None or waves < max_waves):
            self.pump()
            ready = len(self.queue)
            self.metrics.pending_pods.set(float(ready))
            if ready == 0:
                if idle_deadline is not None and self._clock() >= idle_deadline:
                    break
                self.queue.wait_ready(timeout=poll_interval)
                continue
            # overload ladder (ISSUE 17): knobs are re-read every
            # iteration, so a rung change takes effect on the NEXT wave
            # without restarting the loop
            ov = self.overload
            eff_min_batch, eff_max_wait = min_batch, max_wait
            if ov is not None:
                ov.poll()
                eff_min_batch, eff_max_wait = ov.batch_knobs(min_batch, max_wait)
            t_first = self._clock()
            tier_check_at = t_first  # rate-limits the O(pending) tier scan
            while (ready < eff_min_batch and not stopped()
                   and self._clock() - t_first < eff_max_wait):
                # plain sleep, NOT wait_ready: something is already ready
                # (that's how we got here), so wait_ready would return
                # immediately and turn the accumulation window into a
                # 100% busy-spin of pump()+len()
                time.sleep(poll_interval)
                self.pump()
                ready = len(self.queue)
                if ov is not None and ov.rung >= 1:
                    now = self._clock()
                    if now >= tier_check_at:
                        tier_check_at = now + 0.025
                        if self._top_tier_ready():
                            break  # critical pods never wait the widened window
            queue_wait = self._clock() - t_first
            self.metrics.batch_queue_wait.observe(queue_wait * 1e6)
            self.metrics.pending_pods.set(float(ready))
            # the accumulation window rides onto the next wave's root
            # span (ISSUE 7): queue wait + how many pods the window
            # gathered vs the min-batch target
            self._wave_attrs_pending = {
                "queue_wait_s": round(queue_wait, 6),
                "accumulated": ready, "min_batch": eff_min_batch}
            if ov is not None:
                self._wave_attrs_pending["overload_rung"] = ov.rung
            bound, _ = self.schedule_pending_batch(max_batch)
            bound_total += bound
            waves += 1
            idle_deadline = (self._clock() + idle_timeout
                             if idle_timeout is not None else None)
        return bound_total

    # -- the batch TPU path ------------------------------------------------
    def schedule_pending_batch(self, max_batch: Optional[int] = None) -> tuple[int, int]:
        """Drain the queue, schedule the whole batch on the backend, then
        assume+bind each result in pod order.  Returns (bound, failed)."""
        if self.backend is None:
            raise RuntimeError("no batch backend configured")
        tr = tracing.current()
        # the wave begins at its drain (an empty drain records nothing)
        t_drain = tr.clock() if tr is not None else 0.0
        pods = self.queue.drain(max_batch)
        if not pods:
            return (0, 0)
        t_drained = tr.clock() if tr is not None else 0.0
        self._apply_overload_knobs()
        self.metrics.batch_size.observe(len(pods))
        # Cyclic GC is paused for the whole batch (tensorize + kernel +
        # commit): at 150k pods a collection pass walks millions of live
        # objects and costs more than everything it frees (the Go
        # reference has a concurrent GC; Python's stop-the-world pass
        # must not land inside the hot loop).  The apiserver daemon's
        # counterpart is apiserver/collector.py.
        import gc as _gc

        gc_was_enabled = _gc.isenabled()
        _gc.disable()
        totals = {"bound": 0, "failed": 0, "committed": 0,
                  "attempted_binds": 0, "commit_s": 0.0}
        # ONE event enqueue for the whole batch, after the last commit:
        # enqueueing per segment would wake the sink thread mid-batch and
        # its correlation/store writes would steal the GIL from the host
        # phases that are NOT in the device's shadow (tensorize/apply)
        ev_batch: list = []
        # priority pods whose scheduling failed: preemption is deferred to
        # ONE cohort pass after the drain (see _preempt_cohort)
        preempt_cohort: list = [] if self.enable_preemption else None

        def commit_segment(entries: list) -> None:
            """Assume + bind + record one segment's results (the batch
            generalization of the reference's async-bind pipeline,
            SURVEY.md P9, now streamed per segment: the backend invokes
            this while the device executes the NEXT segment, so the
            commit cost hides in the scan's shadow)."""
            t_commit = time.perf_counter()
            unplaced = [e[0] for e in entries if e[1] is None]
            for pod in unplaced:
                self.handle_schedule_failure(pod, FitError(pod, {}), ev_batch,
                                             preempt_cohort=preempt_cohort)
            totals["failed"] += len(unplaced)
            # the entries that have a node keep what the backend sent with
            # them: per-signature request vectors, and from a kernel
            # segment the grouping by node, which spare the cache assume a
            # per-pod quantity re-parse and a per-pod NodeInfo write
            placed = PlacedSegment.placed_of(entries) if unplaced else entries
            # what every step below needs of a pod: its key, once (the
            # store's own), and for the bind the node column beside it
            keys = [e[0].meta.key for e in placed]
            node_names = [e[1] for e in placed]
            self.backoff.forget_many(keys)
            with (tr.span("commit.assume", cat="phase", pods=len(placed))
                  if tr is not None else tracing.NULL_SPAN) as sp:
                nodes, batched = self.cache.assume_many(placed, keys)
                sp.set(nodes=nodes, batched=batched)
            self.metrics.assume_batched_pods.inc(batched)
            bind_start = self._clock()
            with (tr.span("commit.bind", cat="phase", pods=len(keys))
                  if tr is not None else tracing.NULL_SPAN):
                try:
                    errors = self.clientset.pods.bind_many(
                        api.BindingColumns(keys, node_names))
                except Exception as e:
                    # the whole segment's commit failed before any CAS
                    # applied (store overload / transport outage / injected
                    # fault): nothing bound — every entry takes the per-item
                    # failure path below, which forgets the assumption and
                    # requeues
                    logger.warning("bind_many failed for %d pods: %s: %s",
                                   len(keys), type(e).__name__, e)
                    errors = [f"transient: {e}"] * len(keys)
            self.metrics.binding_latency.observe((self._clock() - bind_start) * 1e6)
            if self.emit_events:
                ev_batch.extend([
                    (e[0], "Normal", "Scheduled",
                     ("Successfully assigned %s to %s", key, e[1]))
                    if err is None else (e[0], "Warning", "FailedBinding", err)
                    for e, key, err in zip(placed, keys, errors)])
            finished = keys
            if errors.count(None) != len(keys):
                finished = [key for key, err in zip(keys, errors) if err is None]
                for e, key, err in zip(placed, keys, errors):
                    if err is None:
                        continue
                    logger.warning("bind failed for %s: %s", key, err)
                    self.metrics.bind_failures.inc()
                    self.cache.forget_pod(e[0])
                    # requeue-with-backoff when the pod is still ours and
                    # unbound (transient CAS/transport failure) — decided
                    # from the informer's latest truth, so a genuine
                    # conflict (bound elsewhere) is NOT retried
                    self._requeue_after_bind_failure(e[0])
                    totals["failed"] += 1
            totals["bound"] += len(finished)
            with (tr.span("commit.finish", cat="phase", pods=len(finished))
                  if tr is not None else tracing.NULL_SPAN):
                self.cache.finish_binding_many(finished)
            totals["committed"] += len(finished)
            totals["attempted_binds"] += len(keys)
            # per-segment e2e SLI: pods committed in segment s of S were
            # bound NOW, at this point of the drain — not at batch end.
            # One observe_many per segment keeps p50/p99 distinct without
            # per-pod lock rounds (the reference's three SLIs are per-pod
            # for exactly this reason, metrics/metrics.go:26-50)
            self.metrics.e2e_scheduling_latency.observe_many(
                (self._clock() - start) * 1e6, len(keys))
            t_commit_end = time.perf_counter()
            totals["commit_s"] += t_commit_end - t_commit
            if tr is not None:
                # same two clock reads feed the stats timer and the span:
                # the trace-derived commit_s below IS this measurement.
                # It adopts commit.assume / .bind / .finish; the walks
                # over the entries stay as its self time
                tr.complete("commit", t_commit, t_commit_end, cat="phase",
                            pods=len(entries), bound=len(finished))

        # phase accounting: deltas of the backend's cumulative timers
        # bracket this batch's tensorize/device split
        bstats = getattr(self.backend, "stats", None)
        phase_keys = ("tensorize_s", "dispatch_s", "device_wait_s")
        pre_phases = ({k: bstats.get(k, 0.0) for k in phase_keys}
                      if isinstance(bstats, dict) else None)
        # blocking device→host round-trips: same pre/post-delta seam as
        # the phase timers (the device-resident loop drives this to
        # O(compactions + 1) per wave; the chunked host loop is O(chunks))
        pre_syncs = (bstats.get("host_syncs", 0)
                     if isinstance(bstats, dict) else None)
        ncache = getattr(self.backend, "device_node_cache", None)
        pre_cols = ((ncache.stats["dirty_cols"], ncache.stats["cols_total"],
                     ncache.stats["reuses"])
                    if ncache is not None else None)
        # per-shard upload accounting (mesh mode): snapshot the per-shard
        # cumulative counters so the wave delta attributes dirty columns
        # to the shard that received them
        pre_shard = ((tuple(ncache.stats.get("shard_dirty_cols", ())),
                      tuple(ncache.stats.get("shard_cols_total", ())))
                     if ncache is not None else None)
        pre_decode = self._ingest_decode_stats()
        pre_apply = self._pump_apply_stats()
        pre_fallbacks = self.metrics.confirm_fallbacks.value
        self._last_prep_s = 0.0
        extra = {}
        if self.overlap_ingest:
            # checked per call: tests swap schedule_batch for wrappers
            # that predate the on_idle seam
            import inspect

            try:
                if "on_idle" in inspect.signature(
                        self.backend.schedule_batch).parameters:
                    extra["on_idle"] = self._pipeline_idle
            except (TypeError, ValueError):
                pass

        # one span tree per wave (ISSUE 7): everything this thread does
        # for the batch — the drain, snapshot, tensorize, segment
        # dispatch/finalize, frontier chunks, commits, overlapped prep,
        # ingest pumps — nests under this root; closed (and pushed into
        # the flight-recorder ring) in the finally below.  Entered
        # immediately before the try so no exception path can leak an
        # open root on the span stack (a leaked root would adopt every
        # later wave as a child).
        wave_cm = wave_span = None
        if tr is not None:
            wave_cm = tr.wave(t0=t_drain, pods=len(pods),
                              **self._wave_attrs_pending)
            self._wave_attrs_pending = {}
            wave_span = wave_cm.__enter__()
            tr.complete("queue.drain", t_drain, t_drained, cat="phase",
                        pods=len(pods))
        try:
            start = self._clock()
            with (tr.span("snapshot", cat="phase")
                  if tr is not None else tracing.NULL_SPAN) as sp:
                snapshot = self.snapshot()
                sp.set(nodes=len(snapshot))
            with (tr.span("priority_context", cat="phase")
                  if tr is not None else tracing.NULL_SPAN):
                pctx = self.priority_context(snapshot)
            algo_start = self._clock()
            self.backend.schedule_batch(pods, snapshot, pctx,
                                        on_segment=commit_segment, **extra)
            # wall time of the whole batch dispatch: on the kernel path the
            # per-segment commits run concurrently with the device scan and
            # hide in its shadow (subtracting them would under-report device
            # time); on the oracle fallback and for the final segment the
            # commit is serial and IS part of the batch wall time.
            # binding_latency isolates the commit cost either way
            self.metrics.batch_device_latency.observe(
                (self._clock() - algo_start) * 1e6)
            self.metrics.schedule_attempts.inc(len(pods))
            if preempt_cohort:
                # PostFilter: one prefilter-kernel pass over the failed
                # priority pods, exact victim selection on the survivors
                self._preempt_cohort(preempt_cohort, ev_batch)
            bound, failed = totals["bound"], totals["failed"]
            if pre_phases is not None:
                self.last_batch_phases = {
                    k: bstats.get(k, 0.0) - pre_phases[k] for k in phase_keys
                }
                self.last_batch_phases["commit_s"] = totals["commit_s"]
                self.last_batch_phases["prep_s"] = self._last_prep_s
                self.metrics.pipeline_device_wait.observe(
                    self.last_batch_phases["device_wait_s"] * 1e6)
            if pre_syncs is not None:
                wave_syncs = int(bstats.get("host_syncs", 0) - pre_syncs)
                self.last_batch_phases["host_syncs"] = wave_syncs
                if wave_syncs > 0:
                    self.metrics.host_syncs.inc(wave_syncs)
                if wave_span is not None:
                    wave_span.set(host_syncs=wave_syncs)
            # ingest-decode split of the wave (ISSUE 4): informer decode
            # seconds + lazy promotions since the last snapshot
            post_decode = self._ingest_decode_stats()
            decode_s = post_decode[0] - pre_decode[0]
            promos = post_decode[1] - pre_decode[1]
            self.last_batch_phases["decode_s"] = decode_s
            self.last_batch_phases["promotions"] = promos
            if wave_span is not None:
                wave_span.set(decode_s=round(decode_s, 6), promotions=promos)
            self.metrics.ingest_decode_seconds.observe(decode_s)
            if promos > 0:
                self.metrics.ingest_promotions.inc(promos)
            # pump APPLICATION split of the wave (ISSUE 6): informer
            # cache-apply + handler fan-out (incl. the columnar bind
            # confirm) time, frame volume, and confirm fallbacks
            post_apply = self._pump_apply_stats()
            apply_s = post_apply[0] - pre_apply[0]
            frames = post_apply[1] - pre_apply[1]
            frame_events = post_apply[2] - pre_apply[2]
            self.last_batch_phases["apply_s"] = apply_s
            self.last_batch_phases["frames"] = frames
            self.last_batch_phases["frame_events"] = frame_events
            self.last_batch_phases["confirm_fallbacks"] = int(
                self.metrics.confirm_fallbacks.value - pre_fallbacks)
            self.metrics.pump_apply_seconds.observe(apply_s)
            if wave_span is not None:
                wave_span.set(apply_s=round(apply_s, 6), frames=frames,
                              frame_events=frame_events)
            if pre_cols is not None:
                dirty = ncache.stats["dirty_cols"] - pre_cols[0]
                cols = ncache.stats["cols_total"] - pre_cols[1]
                if cols > 0:
                    self.metrics.tensorize_upload_fraction.observe(dirty / cols)
                    if wave_span is not None:
                        # tensorize attribution: dirty-column diff volume
                        # and the upload fraction of the node axis
                        wave_span.set(dirty_cols=dirty, cols_total=cols,
                                      upload_fraction=round(dirty / cols, 4))
            # frontier trajectory of this wave (per-segment prefilter
            # widths, alive-union fractions, compactions)
            lf = getattr(self.backend, "last_frontier", None)
            if lf:
                self.last_batch_phases["frontier"] = [dict(seg) for seg in lf]
                if wave_span is not None:
                    wave_span.set(frontier=[dict(seg) for seg in lf])
                for seg in lf:
                    fr = seg.get("alive_frac") or []
                    if fr:
                        self.metrics.frontier_alive_fraction.observe(min(fr))
            self._observe_mesh_wave(lf, pre_shard, ncache, wave_span)
        finally:
            if wave_cm is not None:
                wave_span.set(bound=totals["bound"], failed=totals["failed"],
                              committed=totals["committed"])
                wave_cm.__exit__(None, None, None)
                # derive the phase split FROM the wave's span tree: the
                # spans were fed by the very same clock reads as the
                # stats timers, so the dict and the exported trace can
                # never disagree
                self.last_batch_phases.update(wave_span.phase_totals())
            if gc_was_enabled:
                _gc.enable()
            # committed segments' events must survive a mid-batch failure —
            # their pods ARE bound in the cluster
            if ev_batch:
                self._recorder.event_batch(ev_batch)
        if self.emit_events and not self.broadcaster.running:
            # manual drive (no sink thread): drain synchronously so the
            # batch path's events land just like the per-pod path's
            self.broadcaster.flush()
        return (bound, failed)

    # -- housekeeping ------------------------------------------------------
    def cleanup(self) -> list[str]:
        return self.cache.cleanup_expired()
