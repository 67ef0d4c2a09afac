"""Prometheus-style metrics primitives.

Capability of the vendored Prometheus client as the reference uses it:
counters and histograms with labels, a process-global registry, and a text
exposition dump.  The scheduler's three SLIs
(``plugin/pkg/scheduler/metrics/metrics.go:26-50``) are predefined below;
the e2e SLO checks read exactly these (SURVEY.md §5.4).
"""

from __future__ import annotations

import bisect
import threading
from typing import Optional

# reference metrics.go shape: 1ms .. ~1000s exponential (in microseconds),
# at 2^(1/4) steps — 80 buckets instead of the reference's 20, so a
# reported quantile's upper bound is within ~19% of the true value.  At
# sqrt(2) steps the >8s buckets were ~3.4s wide and adjacent segment
# commits of a north drain could land in ONE bucket, collapsing p50 and
# p99 to the same boundary.
_DEFAULT_BUCKETS = [1e3 * (2 ** (i / 4)) for i in range(80)]


class Histogram:
    def __init__(self, name: str, help: str = "", buckets: Optional[list[float]] = None):
        self.name = name
        self.help = help
        self.buckets = sorted(buckets or _DEFAULT_BUCKETS)
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._total = 0
        self._mu = threading.Lock()

    def observe(self, value: float) -> None:
        with self._mu:
            i = bisect.bisect_left(self.buckets, value)
            self._counts[i] += 1
            self._sum += value
            self._total += 1

    def observe_many(self, value: float, n: int) -> None:
        """n observations of the same value under one lock/bisect — the
        batch scheduler records one shared e2e latency for every pod in a
        committed batch; per-pod observe() would cost 150k lock rounds."""
        with self._mu:
            i = bisect.bisect_left(self.buckets, value)
            self._counts[i] += n
            self._sum += value * n
            self._total += n

    @property
    def count(self) -> int:
        return self._total

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> float:
        """Approximate quantile from bucket boundaries (upper bound)."""
        with self._mu:
            if self._total == 0:
                return 0.0
            target = q * self._total
            acc = 0
            for i, c in enumerate(self._counts):
                acc += c
                if acc >= target:
                    return self.buckets[i] if i < len(self.buckets) else float("inf")
            return float("inf")

    def state(self) -> tuple[list[int], int, float]:
        """One consistent ``(bucket_counts, total, sum)`` snapshot under a
        single lock round — the time-series scraper derives several
        quantile tracks per scrape, and three ``quantile()`` calls could
        each see a different population."""
        with self._mu:
            return list(self._counts), self._total, self._sum

    def expose(self) -> str:
        # one consistent snapshot: without the lock a concurrent
        # observe() can land between the bucket walk and the _total
        # read, exposing cumulative bucket counts that exceed (or trail)
        # the reported _count — scrapers and the SLO checks both assume
        # the exposition is internally consistent
        with self._mu:
            counts = list(self._counts)
            total = self._total
            total_sum = self._sum
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        acc = 0
        for b, c in zip(self.buckets, counts):
            acc += c
            lines.append(f'{self.name}_bucket{{le="{b}"}} {acc}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {total}')
        lines.append(f"{self.name}_sum {total_sum}")
        lines.append(f"{self.name}_count {total}")
        return "\n".join(lines)


class Counter:
    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._value = 0.0
        self._mu = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._mu:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value

    def expose(self) -> str:
        return (
            f"# HELP {self.name} {self.help}\n# TYPE {self.name} counter\n"
            f"{self.name} {self._value}"
        )


class Gauge:
    """Last-write-wins gauge.  ``set`` takes a lock like the other
    primitives — gauges are written from resync/compaction threads and
    scraped from the health server's connection threads, so the
    single-writer assumption the pre-lock version leaned on does not
    hold for every instance (ktpu-analyze race-lint hygiene)."""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._value = 0.0
        self._mu = threading.Lock()

    def set(self, v: float) -> None:
        with self._mu:
            self._value = v

    def inc(self, amount: float = 1.0) -> None:
        with self._mu:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value

    def expose(self) -> str:
        return (
            f"# HELP {self.name} {self.help}\n# TYPE {self.name} gauge\n"
            f"{self.name} {self._value}"
        )


class Registry:
    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._mu = threading.Lock()

    def register(self, metric):
        with self._mu:
            self._metrics[metric.name] = metric
        return metric

    def get(self, name: str):
        with self._mu:
            return self._metrics.get(name)

    def snapshot(self) -> list:
        """The registered metrics as a list, captured under the registry
        lock.  Daemons register metrics lazily (first use), so a scrape
        racing a registration must not iterate the mutating dict — both
        ``expose()`` and the time-series scraper walk this snapshot
        instead, outside the lock."""
        with self._mu:
            return list(self._metrics.values())

    def expose(self) -> str:
        # per-metric expose() takes each metric's own lock; holding the
        # registry lock across that walk would nest registry-lock →
        # metric-lock against every observe() in flight — snapshot the
        # dict under the lock, render outside it
        return "\n".join(m.expose() for m in self.snapshot()) + "\n"


class ClientMetrics:
    """Client-transport observability: retry/reconnect/relist counters.

    The fault-injection matrix (tests/test_faults.py) asserts recovery
    through exactly these — a retry that happens but is invisible here
    fails the test.  One instance per RemoteStore (watches inherit it);
    informers default to the process-wide :data:`DEFAULT_CLIENT_METRICS`
    unless handed their own."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry or Registry()
        self.registry = r
        self.remote_retries = r.register(Counter(
            "client_remote_retries_total",
            "request attempts re-issued after a retryable failure"))
        self.remote_fatal = r.register(Counter(
            "client_remote_fatal_total",
            "requests abandoned on a non-retryable (4xx) classification"))
        self.remote_retry_exhausted = r.register(Counter(
            "client_remote_retry_exhausted_total",
            "requests abandoned after the retry budget ran out"))
        self.watch_reconnects = r.register(Counter(
            "client_watch_reconnects_total",
            "watch streams re-established after an error or EOF"))
        self.watch_gaps = r.register(Counter(
            "client_watch_gaps_total",
            "watch resumes refused with 410 Gone — informer must relist"))
        self.watch_errors = r.register(Counter(
            "client_watch_errors_total",
            "classified watch-stream errors (transport + HTTP)"))
        # best-effort cleanup visibility (ktpu-analyze CH702): a close or
        # drain that fails is tolerated by design, but never invisibly
        self.watch_close_errors = r.register(Counter(
            "client_watch_close_errors_total",
            "watch response closes that raised (half-open stream torn "
            "down anyway)"))
        self.remote_drain_errors = r.register(Counter(
            "client_remote_drain_errors_total",
            "keep-alive body drains that raised before a retry (socket "
            "abandoned to the pool's cleanup)"))
        self.informer_relists = r.register(Counter(
            "client_informer_relists_total",
            "full LIST + watch restarts (gap escalation or resync)"))
        self.informer_dropped_events = r.register(Counter(
            "client_informer_dropped_events_total",
            "deltas dropped before application (fault injection)"))
        self.informer_handler_errors = r.register(Counter(
            "client_informer_handler_errors_total",
            "handler callbacks that raised (isolated, loop continues)"))
        # zero-copy ingest observability (ISSUE 4): decode failures heal
        # via relist; bytes counts the wire payload the watch delivered
        # (remote transport only — the in-process store never serializes)
        self.informer_decode_errors = r.register(Counter(
            "client_informer_decode_errors_total",
            "event payloads that failed to decode (delta lost, gap marked "
            "for relist)"))
        self.informer_frame_errors = r.register(Counter(
            "client_informer_frame_errors_total",
            "column-packed watch frames lost whole before application "
            "(apply fault / broken columns) — gap marked for relist"))
        self.ingest_bytes = r.register(Counter(
            "scheduler_ingest_decode_bytes_total",
            "wire bytes of watch payloads delivered to informers"))
        # cache compaction (ISSUE 7 satellite: compact_cache wired to the
        # resync loop): objects whose pinned wire payload was released,
        # and the approximate bytes the LAST sweep freed
        self.informer_compactions = r.register(Counter(
            "client_informer_compactions_total",
            "lazy cache objects promoted-and-raw-dropped by the "
            "resync-time compaction sweep"))
        self.informer_compaction_freed_bytes = r.register(Gauge(
            "client_informer_compaction_freed_bytes",
            "approximate wire-payload bytes released by the most recent "
            "compaction sweep"))
        # overload control (ISSUE 17): retries whose backoff came from a
        # server Retry-After hint (429/503) instead of the client-side
        # exponential schedule
        self.retry_after_honored = r.register(Counter(
            "client_retry_after_honored_total",
            "retry sleeps that honored a server Retry-After header "
            "(clamped to the client's max backoff, jitter preserved)"))
        # serving tier (ISSUE 19): per-CLIENT staleness attribution of
        # the watch-fanout SLO — the WORST client's revision lag behind
        # the store head, sampled every scrape by WatchFanoutTracker
        # (gauge, not counter: it keeps producing data — and can
        # recover — while the fleet idles, the GaugeSLI property)
        self.watch_worst_staleness = r.register(Gauge(
            "client_watch_worst_staleness_revisions",
            "largest per-client revision lag behind the store head at "
            "the last fan-out staleness sample (0 = every watcher "
            "caught up)"))


# informers without an explicit metrics object aggregate here: one place
# to ask "did anything relist / drop / leak handler errors this process"
DEFAULT_CLIENT_METRICS = ClientMetrics()


class StoreMetrics:
    """Broadcaster-side observability (the serving tier): the watch
    frames packed, and the time-window coalescer's flushes, folds, and
    flush-path fallbacks.
    The fault matrix asserts recovery through
    ``store_coalesce_fallbacks_total`` — a degraded window that is
    invisible here fails the test."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry or Registry()
        self.registry = r
        self.coalesce_flushes = r.register(Counter(
            "store_coalesce_flushes_total",
            "coalescing windows flushed to the watcher queues (deadline, "
            "ordering barrier, key cap, or shutdown)"))
        self.coalesced_events = r.register(Counter(
            "store_coalesced_events_total",
            "per-key deliveries superseded inside a coalescing window "
            "(latest-wins folds — fan-out work that never happened)"))
        self.coalesce_fallbacks = r.register(Counter(
            "store_coalesce_fallbacks_total",
            "coalescing windows degraded to per-event delivery after a "
            "flush-path failure (state preserved, packing lost)"))
        self.watch_frames = r.register(Counter(
            "store_watch_frames_total",
            "watch frames packed by batch txns and coalescing flushes "
            "(one per piece of at most frames.FRAME_MAX_ROWS rows, "
            "however many watchers share it)"))
        self.watch_replay_events = r.register(Counter(
            "store_watch_replay_events_total",
            "log rows replayed to watchers that resumed from a revision "
            "(inside frames or one by one)"))
        self.watch_replay_frames = r.register(Counter(
            "store_watch_replay_frames_total",
            "watch frames packed from the log for resumed frames "
            "watchers: a batch txn's rows leave as they did live"))
        self.bind_rows_deferred = r.register(Counter(
            "store_bind_rows_deferred_total",
            "rows bind_many committed without building their watch "
            "payload (no WAL record or replica asked for it at commit)"))
        self.event_payloads_built = r.register(Counter(
            "store_event_payloads_built_total",
            "watch payloads of bind_many rows derived on demand (a "
            "frame's encode, a per-event reader, a WAL record): it "
            "trails store_bind_rows_deferred_total by what nobody read"))


# stores aggregate here (one broadcaster seam per process in practice);
# tests/watch_fleet_harness.py reads this registry alongside the client one
DEFAULT_STORE_METRICS = StoreMetrics()


class SchedulerMetrics:
    """The reference's three scheduling SLIs, in microseconds
    (``metrics/metrics.go:26-50``), plus batch-backend extras."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry or Registry()
        self.registry = r
        self.e2e_scheduling_latency = r.register(
            Histogram("scheduler_e2e_scheduling_latency_microseconds")
        )
        self.scheduling_algorithm_latency = r.register(
            Histogram("scheduler_scheduling_algorithm_latency_microseconds")
        )
        self.binding_latency = r.register(
            Histogram("scheduler_binding_latency_microseconds")
        )
        self.schedule_attempts = r.register(Counter("scheduler_schedule_attempts_total"))
        self.schedule_failures = r.register(Counter("scheduler_schedule_failures_total"))
        # batch-backend extras
        self.batch_size = r.register(Histogram("scheduler_batch_size", buckets=[2**i for i in range(20)]))
        self.batch_device_latency = r.register(
            Histogram("scheduler_batch_device_latency_microseconds")
        )
        self.pallas_fallback_total = r.register(Counter(
            "scheduler_pallas_fallback_total",
            "pallas dispatch/finalize failures that fell back to the XLA scan",
        ))
        self.kernel_breaker_transitions = r.register(Counter(
            "scheduler_kernel_breaker_transitions_total",
            "circuit-breaker level changes (degrade, probe, restore) on "
            "the pallas→interpret→oracle ladder",
        ))
        self.bind_failures = r.register(Counter(
            "scheduler_bind_failures_total",
            "bind attempts that failed (conflict, not-found, transport)",
        ))
        self.bind_requeues = r.register(Counter(
            "scheduler_bind_requeues_total",
            "pods requeued with backoff after a transient bind failure",
        ))
        # steady-state pipeline (run_batch_loop / overlapped ingest)
        self.batch_queue_wait = r.register(Histogram(
            "scheduler_batch_queue_wait_microseconds",
            "time from the first ready pod to the wave's drain (the "
            "min-batch/max-wait accumulation window)",
        ))
        self.pipeline_prep_latency = r.register(Histogram(
            "scheduler_pipeline_prep_microseconds",
            "host prep (pump + signature warming) run inside the device's "
            "shadow between the final dispatch and its finalize",
        ))
        self.pipeline_device_wait = r.register(Histogram(
            "scheduler_pipeline_device_wait_microseconds",
            "device time left after the overlapped prep returned — the "
            "unfilled overlap headroom of the wave",
        ))
        self.pipeline_prep_failures = r.register(Counter(
            "scheduler_pipeline_prep_failures_total",
            "overlapped-prep runs that raised; the work is deferred to the "
            "next wave's synchronous path (no decisions are affected)",
        ))
        # zero-copy ingest (ISSUE 4): per-wave informer decode time in
        # SECONDS (lazy wrap ~0; the eager compatibility path shows the
        # true from_dict cost), plus lazy-promotion volume — how much
        # typed decode the wave's consumers actually pulled
        self.ingest_decode_seconds = r.register(Histogram(
            "scheduler_ingest_decode_seconds",
            "informer event-decode time per scheduling wave (seconds; "
            "near-zero on the lazy path)",
            buckets=[1e-5 * (2 ** (i / 2)) for i in range(44)],
        ))
        self.ingest_promotions = r.register(Counter(
            "scheduler_ingest_promotions_total",
            "lazy-object sections/objects promoted to typed form by "
            "consumers (decode work that was actually needed)",
        ))
        # batched watch frames (ISSUE 6): per-wave pump APPLICATION time
        # in SECONDS (informer cache apply + handler fan-out + the
        # scheduler's bind confirm), plus frame/event volume and how often
        # the columnar confirm had to fall back to the per-pod compare
        self.pump_apply_seconds = r.register(Histogram(
            "scheduler_pump_apply_seconds",
            "informer event/frame application time per scheduling wave "
            "(cache apply + handler fan-out + bind confirm; seconds)",
            buckets=[1e-5 * (2 ** (i / 2)) for i in range(44)],
        ))
        self.watch_frames = r.register(Counter(
            "scheduler_watch_frames_total",
            "column-packed watch frames applied by this scheduler's "
            "informers (a correlated store batch txn arrives as "
            "ceil(events / frames.FRAME_MAX_ROWS) of them)",
        ))
        self.watch_frame_events = r.register(Counter(
            "scheduler_watch_frame_events_total",
            "events delivered inside watch frames (the per-event path "
            "they replaced)",
        ))
        self.watch_line_events = r.register(Counter(
            "scheduler_watch_line_events_total",
            "pods the informer handed to this scheduler one by one, not "
            "as rows of a frame: a watch line of its own (a single "
            "write) or an item of a LIST",
        ))
        self.assume_batched_pods = r.register(Counter(
            "scheduler_assume_batched_pods_total",
            "pods the cache assumed by node: one aggregate NodeInfo write "
            "per touched node of a kernel segment, from the groups the "
            "backend placed it by (the rest are assumed pod by pod)",
        ))
        self.confirm_fallbacks = r.register(Counter(
            "scheduler_confirm_fallbacks_total",
            "frame bind-confirm entries the columnar revision fence "
            "rejected — routed through the per-pod compare instead",
        ))
        self.tensorize_upload_fraction = r.register(Histogram(
            "scheduler_tensorize_upload_fraction",
            "fraction of node-axis columns re-uploaded to device per wave "
            "(0 = fully cache-resident, 1 = full upload)",
            buckets=[i / 20 for i in range(21)],
        ))
        # frontier scan (ISSUE 5): monotone node pruning + mid-segment
        # node-axis compaction on the XLA scan path
        self.frontier_compactions = r.register(Counter(
            "scheduler_frontier_compactions_total",
            "mid-segment device node-axis compactions (the alive-union "
            "fraction fell below the threshold and the scan resumed at a "
            "smaller power-of-two width)",
        ))
        self.frontier_alive_fraction = r.register(Histogram(
            "scheduler_frontier_alive_fraction",
            "lowest alive-union fraction observed per frontier segment "
            "(1.0 = no column ever died; small = heavy pruning)",
            buckets=[i / 20 for i in range(21)],
        ))
        # device-resident wave loop (ISSUE 11): blocking device→host
        # round-trips on the finalize path — O(compactions + 1) per wave
        # with the while_loop form, O(chunks) with the chunked host loop
        self.host_syncs = r.register(Counter(
            "scheduler_host_syncs_total",
            "blocking device→host round-trips performed by batch "
            "finalize (control reads + result copies)",
        ))
        # preemption (the PostFilter phase)
        self.preemption_attempts = r.register(Counter(
            "scheduler_preemption_attempts_total"))
        self.preemption_victims = r.register(Counter(
            "scheduler_preemption_victims_total"))
        self.preemption_latency = r.register(Histogram(
            "scheduler_preemption_latency_microseconds"))
        # overload control (ISSUE 17): the degradation ladder's state and
        # its shed actions.  pending_pods is the ladder's input signal
        # (GaugeSLI windowed mean — sampled every scrape, so the ladder
        # can recover even with zero traffic); the rest are its outputs.
        self.pending_pods = r.register(Gauge(
            "scheduler_pending_pods",
            "ready pods in the scheduling queue at the last batch-loop "
            "iteration (the overload ladder's queue-depth signal)"))
        self.degradation_rung = r.register(Gauge(
            "scheduler_degradation_rung",
            "current overload degradation rung (0=full fidelity, "
            "1=widened batching, 2=score planes shed, 3=admission "
            "throttled)"))
        self.degradation_transitions = r.register(Counter(
            "scheduler_degradation_transitions_total",
            "degradation-ladder rung changes (engage, step, recover)"))
        self.score_plane_sheds = r.register(Counter(
            "scheduler_score_plane_sheds_total",
            "batches scheduled with preferred interpod-affinity score "
            "planes shed (rung >= 2; feasibility untouched)"))
        self.preemption_sheds = r.register(Counter(
            "scheduler_preemption_sheds_total",
            "preemption-eligible pods denied the PostFilter pass because "
            "their tier is below the ladder's floor (rung >= 2)"))
        # sharded wave loop (ISSUE 18): per-shard SLO attribution of the
        # node-axis mesh — aggregate fractions hid one cold shard behind
        # the warm ones (the PR-12 caveat), so the WORST shard is what
        # gets a first-class signal.  Gauges, not histograms: the SLO
        # layer consumes them as windowed means (GaugeSLI).
        self.mesh_shards = r.register(Gauge(
            "scheduler_mesh_shards",
            "shard count of the node-axis mesh the last sharded wave "
            "loop ran on (0 = single-device path)"))
        self.mesh_worst_shard_upload_fraction = r.register(Gauge(
            "scheduler_mesh_worst_shard_upload_fraction",
            "highest per-shard dirty-column upload fraction of the last "
            "wave (1 = some shard re-uploaded its whole node slice)"))
        self.mesh_shard_alive_skew = r.register(Gauge(
            "scheduler_mesh_shard_alive_skew",
            "max spread between per-shard alive fractions at the last "
            "sharded loop exit (large = the frontier died unevenly and "
            "some shards carry dead columns)"))
