"""CI smoke for the hollow-watcher fleet (ISSUE 19).

``watch_fleet_harness.run_watch_fleet`` at a couple hundred watchers for
a couple of seconds, gating fan-out liveness on both arms, ZERO
dropped-state clients (the state-equivalence sweep over every client's
final cache), and the per-CLIENT staleness SLO evaluator actually
sampling (burn on the pump stall, recovery after the drain, top-K
laggard attribution on the breach dump)."""

import pytest


@pytest.fixture(scope="module")
def fleet_result():
    from tests.watch_fleet_harness import run_watch_fleet

    return run_watch_fleet(
        n_watchers=200, seed_pods=80, churn_ops=150, http_watchers=4,
        selector_watchers=2, n_informers=1, pump_threads=4)


def test_fleet_fanout_liveness(fleet_result):
    """Both arms actually fanned churn out to every client."""
    for arm in ("A", "B"):
        r = fleet_result[arm]
        assert r["fanout_events_per_s"] > 0
        assert r["delivered_units"] > 0
        assert r["deliveries"] > 0
    # the coalescing arm folded and framed: fewer physical deliveries
    # for the same logical coverage
    assert (fleet_result["B"]["deliveries"]
            < fleet_result["A"]["deliveries"])
    assert fleet_result["B"]["coalesce"]["flushes"] > 0
    assert fleet_result["B"]["coalesce"]["fallbacks"] == 0


def test_fleet_zero_dropped_state_clients(fleet_result):
    """The state-equivalence gate: every client's final cache agrees
    with the store on every churned key, no client gapped, selector
    streams carried nothing outside the selector."""
    v = fleet_result["verdict"]
    assert v["state_mismatches"] == 0
    assert v["dropped_state_clients"] == 0
    for arm in ("A", "B"):
        assert fleet_result[arm]["equiv"]["mismatches"] == 0
        assert fleet_result[arm]["equiv"]["gapped"] == 0
        assert fleet_result[arm]["selector"]["non_matching_keys"] == 0


def test_fleet_slo_evaluator_sampled(fleet_result):
    """The per-CLIENT staleness SLO lived through the run: the stalled
    pumps burned the budget (breach), the drain recovered it, and the
    breach's flight-recorder dump named the laggards."""
    slo = fleet_result["B"]["slo"]
    assert slo is not None
    assert slo["slo"] == "watch_fanout_worst_client_staleness"
    assert slo["breached"] and slo["recovered"]
    assert slo["breach_dump_top_laggards"] > 0
    types = [e["type"] for e in slo["events"]]
    assert types.index("breach") < types.index("recovered")
