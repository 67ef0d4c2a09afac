"""The hollow-watcher fleet harness behind ``test_watch_fleet.py``.

Kubemark applied to the WATCH axis: many in-process hollow watchers, a
small HTTP cohort and a few real informers against ONE broadcaster under
single-event churn, once per arm (B = time-window coalescing + framed
delivery + single-encode fan-out; A = per-event delivery).  What it
returns is for the tests' gates: fan-out liveness, a zero-mismatch
state-equivalence sweep over every client's final cache, and the
per-CLIENT staleness SLO burning and recovering mid-run with the top-K
laggard breach dump."""

from __future__ import annotations

import random
import time


def _rss_mb() -> float:
    """Current resident set (VmRSS) in MiB — current, not peak, so the
    second arm of an A/B is not poisoned by the first arm's high-water
    mark the way ``ru_maxrss`` would be."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except OSError:
        pass
    return 0.0


def _fleet_arm(arm_b: bool, n_watchers: int, seed_pods: int, churn_ops: int,
               http_watchers: int, selector_watchers: int, n_informers: int,
               pump_threads: int, coalesce_window_s: float, seed: int,
               slo_probe: bool, drain_timeout_s: float = 120.0) -> dict:
    """One arm of the hollow-watcher fleet: B = coalescing window +
    framed delivery + shared encode, A = per-event delivery (the
    reference arm), same harness, same seeded churn.

    The fleet is kubemark applied to the WATCH axis: ``n_watchers``
    in-process hollow watchers (no thread each — a pump pool drives
    slices), a small HTTP cohort on real apiserver streams (selector
    watchers among them exercising column-level sub-frame packing), and
    a few real ``SharedInformer``s with ``compact_on_resync`` for the
    RSS point.  Throughput is LOGICAL fan-out: every churn event must
    reach every full watcher (a coalesced fold counts — the client holds
    the newest state that event produced), so events/s =
    churn_ops x full_watchers / drain wall."""
    import dataclasses
    import threading

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Clientset
    from kubernetes_tpu.client.informer import SharedInformer
    from kubernetes_tpu.client.remote import RemoteStore
    from kubernetes_tpu.kubelet.hollow import HollowWatcher, HollowWatcherFleet
    from kubernetes_tpu.store import Store
    from kubernetes_tpu.store import frames as frames_mod
    from kubernetes_tpu.utils import tracing
    from kubernetes_tpu.utils.fanout import WatchFanoutTracker
    from kubernetes_tpu.utils.metrics import (DEFAULT_STORE_METRICS,
                                              ClientMetrics, Registry)
    from kubernetes_tpu.utils.slo import BurnRateEvaluator, serving_slos
    from kubernetes_tpu.utils.timeseries import TimeSeriesStore

    frames_was, shenc_was = frames_mod.ENABLED, frames_mod.SHARED_ENCODE
    frames_mod.ENABLED = arm_b
    frames_mod.SHARED_ENCODE = arm_b
    sm = DEFAULT_STORE_METRICS
    sm0 = (sm.coalesce_flushes.value, sm.coalesced_events.value,
           sm.coalesce_fallbacks.value)
    store = Store(event_log_window=max(200_000, 8 * (seed_pods + churn_ops)),
                  coalesce_window_s=(coalesce_window_s if arm_b else 0.0))
    server = None
    stop = threading.Event()
    stall = threading.Event()
    threads: list[threading.Thread] = []
    tracer = tracing.enable(ring_waves=4) if slo_probe else None
    try:
        rng = random.Random(seed)
        cs = Clientset(store)

        def pod(i):
            return {"metadata": {"name": f"fp-{i:05d}", "namespace": "default",
                                 "labels": {"tier": "hot" if i % 2 == 0
                                            else "cold"}},
                    "spec": {}, "status": {"phase": "Pending"}}

        for i in range(seed_pods):
            store.create("Pod", pod(i))
        seed_head = store.revision

        metrics = ClientMetrics(Registry())
        tracker = WatchFanoutTracker(metrics)
        fleet = HollowWatcherFleet(store, n_watchers, kind="Pod",
                                   frames=arm_b, tracker=tracker,
                                   from_revision=seed_head)
        server = APIServer(store)
        server.start()
        remote = RemoteStore(server.url)
        http_fleet = HollowWatcherFleet(remote, http_watchers, kind="Pod",
                                        frames=arm_b, tracker=tracker,
                                        prefix="http",
                                        from_revision=seed_head)
        sel_watchers = [
            HollowWatcher(
                f"sel-{i:03d}",
                remote.watch("Pod", from_revision=seed_head, frames=arm_b,
                             label_selector="tier=hot"))
            for i in range(selector_watchers)
        ]
        informers = [SharedInformer(cs.pods, compact_on_resync=True)
                     for _ in range(n_informers)]
        for inf in informers:
            inf.start_manual()

        # -- pump pool: slices of the hollow fleet + one aux driver --------
        def pump_slice(ws):
            while not stop.is_set():
                if stall.is_set():
                    time.sleep(0.002)
                    continue
                n = 0
                for w in ws:
                    n += w.pump()
                if n == 0:
                    time.sleep(0.001)

        def pump_aux():
            while not stop.is_set():
                if stall.is_set():
                    time.sleep(0.002)
                    continue
                n = http_fleet.pump_all()
                for w in sel_watchers:
                    n += w.pump()
                for inf in informers:
                    n += inf.pump()
                if n == 0:
                    time.sleep(0.001)

        step = max(1, n_watchers // pump_threads)
        for j in range(0, n_watchers, step):
            t = threading.Thread(target=pump_slice,
                                 args=(fleet.watchers[j:j + step],),
                                 daemon=True, name=f"fleet-pump-{j}")
            threads.append(t)
        threads.append(threading.Thread(target=pump_aux, daemon=True,
                                        name="fleet-pump-aux"))

        # staleness sampler: per-tick p50/p99 revision lag across the
        # hollow fleet (plain int reads — watcher applied_rev is a word)
        lag_p50: list[int] = []
        lag_p99: list[int] = []

        def sampler():
            while not stop.is_set():
                head = store.revision
                tracker.observe_head(head)
                lags = sorted(head - w.applied_rev for w in fleet.watchers)
                lag_p50.append(lags[len(lags) // 2])
                lag_p99.append(lags[(len(lags) * 99) // 100])
                tracker.sample()
                time.sleep(0.02)

        threads.append(threading.Thread(target=sampler, daemon=True,
                                        name="fleet-sampler"))
        for t in threads:
            t.start()

        # -- the measured churn: singles (the coalescer's diet) ------------
        alive = set(range(seed_pods))
        hot = list(range(0, seed_pods, 2))
        touched: set = set()
        t0 = time.perf_counter()
        for op in range(churn_ops):
            i = rng.choice(hot)
            touched.add(i)
            r = rng.random()
            if i in alive and r < 0.12:
                store.delete("Pod", "default", f"fp-{i:05d}")
                alive.discard(i)
            elif i not in alive:
                store.create("Pod", pod(i))
                alive.add(i)
            else:
                obj = store.get("Pod", "default", f"fp-{i:05d}")
                obj["status"] = {"phase": f"Running-{op}"}
                store.update("Pod", obj)
        head = store.revision
        deadline = time.perf_counter() + drain_timeout_s
        while (fleet.converged(head) < n_watchers
               or http_fleet.converged(head) < http_watchers):
            if time.perf_counter() > deadline:
                break
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        # grace for the selector cohort (its applied_rev tops out at the
        # last MATCHING revision, not head) and the informers
        time.sleep(0.25)

        full_clients = n_watchers + http_watchers
        logical = churn_ops * full_clients
        delivered = sum(w.event_units for w in fleet.watchers + http_fleet.watchers)
        deliveries = sum(w.deliveries for w in fleet.watchers + http_fleet.watchers)

        # -- state-equivalence gate (over the keys the watchers SAW:
        # the fleet watches from the seed head, so only churned keys
        # have deliveries to agree on) --------------------------------------
        expected = {}
        for i in touched:
            key = f"default/fp-{i:05d}"
            if i in alive:
                expected[key] = int(
                    store.get("Pod", "default", f"fp-{i:05d}")
                    ["metadata"]["resourceVersion"])
            else:
                expected[key] = None
        mismatches = gapped = 0
        for w in fleet.watchers + http_fleet.watchers:
            if w.gaps:
                gapped += 1
                continue
            for key, rev in expected.items():
                if w.cache.get(key) != rev and not (rev is None
                                                    and key not in w.cache):
                    mismatches += 1
                    break
        sel_bad = sel_mismatch = 0
        for w in sel_watchers:
            if any(not k.split("/", 1)[1].startswith("fp-") or
                   int(k.split("fp-")[1]) % 2 != 0 for k in w.cache):
                sel_bad += 1
            for key, rev in expected.items():
                if rev is not None and w.cache.get(key) != rev:
                    sel_mismatch += 1
                    break
        for inf in informers:
            inf.relist()  # resync -> compact_on_resync sweep (the RSS point)
        inf_lag = [head - inf.last_revision for inf in informers]
        rss = _rss_mb()

        # -- SLO probe: stall the pumps, burn, drain, recover --------------
        slo_block = None
        if slo_probe:
            tracker.attach_breach_context()
            clk = [0.0]
            ts = TimeSeriesStore(metrics.registry, interval_s=0.5,
                                 capacity=600, clock=lambda: clk[0])
            slos = [dataclasses.replace(s, fast_window_s=1.0,
                                        slow_window_s=3.0, recovery_evals=2)
                    for s in serving_slos(worst_lag_revisions=40.0)]
            ev = BurnRateEvaluator(slos=slos, store=ts)
            events: list[dict] = []

            def tick():
                clk[0] += 0.5
                tracker.observe_head(store.revision)
                tracker.sample()
                ts.sample_once()
                events.extend(ev.evaluate())

            stall.set()
            for op in range(120):  # lag builds while nobody pumps
                i = rng.choice(hot)
                if i in alive:
                    obj = store.get("Pod", "default", f"fp-{i:05d}")
                    obj["status"] = {"phase": f"stall-{op}"}
                    store.update("Pod", obj)
            store.flush_coalesced()
            for _ in range(30):
                tick()
                if any(e["type"] == "breach" for e in events):
                    break
                time.sleep(0.02)
            stall.clear()
            shead = store.revision
            sdl = time.perf_counter() + 30.0
            while (fleet.converged(shead) < n_watchers
                   and time.perf_counter() < sdl):
                time.sleep(0.005)
            for _ in range(40):
                tick()
                if any(e["type"] == "recovered" for e in events):
                    break
                time.sleep(0.02)
            dump_ctx = None
            for d in (tracer.dumps if tracer is not None else []):
                if d["reason"].startswith("slo:watch_fanout_worst_client"):
                    dump_ctx = d["attrs"].get("context")
            slo_block = {
                "slo": "watch_fanout_worst_client_staleness",
                "breached": any(e["type"] == "breach" for e in events),
                "recovered": any(e["type"] == "recovered" for e in events),
                "breach_dump_top_laggards": (
                    len(dump_ctx["top_laggards"]) if dump_ctx else 0),
                "events": events,
            }

        return {
            "arm": "B_coalesced_shared" if arm_b else "A_per_event",
            "wall_s": round(wall, 3),
            "fanout_events_per_s": int(logical / wall) if wall else None,
            "logical_events": logical,
            "delivered_units": delivered,
            "deliveries": deliveries,
            "staleness_p50_revisions": (sorted(lag_p50)[len(lag_p50) // 2]
                                        if lag_p50 else 0),
            "staleness_p99_revisions": (sorted(lag_p99)[len(lag_p99) // 2]
                                        if lag_p99 else 0),
            "rss_mb": rss,
            "coalesce": {
                "flushes": int(sm.coalesce_flushes.value - sm0[0]),
                "folded": int(sm.coalesced_events.value - sm0[1]),
                "fallbacks": int(sm.coalesce_fallbacks.value - sm0[2]),
            },
            "equiv": {"clients": full_clients, "mismatches": mismatches,
                      "gapped": gapped},
            "selector": {"clients": selector_watchers,
                         "non_matching_keys": sel_bad,
                         "mismatches": sel_mismatch},
            "informers": {"count": n_informers,
                          "compact_on_resync": True,
                          "lag_after_relist": inf_lag,
                          "compactions": sum(i.stats["compactions"]
                                             for i in informers)},
            "slo": slo_block,
        }
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        try:
            fleet.stop_all()
            http_fleet.stop_all()
            for w in sel_watchers:
                w.stop()
            for inf in informers:
                inf.stop()
        except Exception:
            pass
        if server is not None:
            server.stop()
        store.close()
        if tracer is not None:
            tracing.disable()
        frames_mod.ENABLED = frames_was
        frames_mod.SHARED_ENCODE = shenc_was


def run_watch_fleet(n_watchers: int, seed_pods: int, churn_ops: int,
                    http_watchers: int, selector_watchers: int,
                    n_informers: int, pump_threads: int,
                    coalesce_window_s: float = 0.005, seed: int = 0) -> dict:
    """Both arms over the same seeded churn, and what the two agree on."""
    a = _fleet_arm(False, n_watchers, seed_pods, churn_ops, http_watchers,
                   selector_watchers, n_informers, pump_threads,
                   coalesce_window_s, seed, slo_probe=False)
    b = _fleet_arm(True, n_watchers, seed_pods, churn_ops, http_watchers,
                   selector_watchers, n_informers, pump_threads,
                   coalesce_window_s, seed, slo_probe=True)
    mism = (a["equiv"]["mismatches"] + b["equiv"]["mismatches"]
            + a["selector"]["mismatches"] + b["selector"]["mismatches"]
            + a["selector"]["non_matching_keys"]
            + b["selector"]["non_matching_keys"])
    return {
        "A": a,
        "B": b,
        "verdict": {
            "state_mismatches": mism,
            "dropped_state_clients": a["equiv"]["gapped"] + b["equiv"]["gapped"],
        },
    }
