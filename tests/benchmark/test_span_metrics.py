"""The readers of the program's own wave spans (PR 24) on hand-made facts:
each gives the exact number, nothing (never 0) with its span missing, and
``_gaps`` books a synthetic wave to the nanosecond."""

import pytest

from benchmark import run
from benchmark.layer_metrics import _gaps

BASE = 12.0            # perf_counter seconds at the synthetic wave's start
OFFSET_NS = 5_000 - BASE * 1e9     # trace ns = OFFSET_NS + t * 1e9


def _span(name, lo_ns, hi_ns, wave=1, parent="wave-1", cat="phase", **attrs):
    t0, t1 = BASE + lo_ns / 1e9, BASE + hi_ns / 1e9
    return {"name": name, "cat": cat, "t0": t0, "t1": t1, "dur": t1 - t0,
            "self_s": t1 - t0, "attrs": attrs, "wave": wave, "parent": parent}


# one wave of 1,000 ns; the second began after the traced slice
WAVE = [
    _span("wave-1", 0, 1000, parent=None, cat="wave", wave=1, pods=1000),
    _span("queue.drain", 0, 100, pods=1000),
    _span("tensorize", 150, 300, pods=1000),
    _span("tensorize.build_static", 160, 280, parent="tensorize"),
    _span("dispatch", 300, 350),
    _span("device_wait", 350, 600, pods=1000),
    _span("place", 600, 700, pods=800),
    _span("commit", 720, 990, pods=1000, bound=800),
    _span("commit.bind", 750, 950, parent="commit", pods=800),
    _span("remote.request", 760, 940, parent="commit.bind", cat="client",
          items=800, server_s=150e-9),
    _span("wave-2", 2000, 2400, parent=None, cat="wave", wave=2, pods=10),
    _span("commit", 2100, 2300, wave=2, parent="wave-2", pods=10, bound=10),
    # an informer's apply on its own thread belongs to no wave
    _span("informer.frame.apply", 100, 900, wave=None, parent=None, cat="ingest"),
]
# trace ns: the scan from 340 to 590 and a copy at 595, inside wave-1; one
# event between the waves
KERNELS = [(5_340, 5_590, 0), (5_595, 5_600, 0), (6_500, 6_600, 0)]


def test_gaps_book_a_synthetic_wave_to_the_nanosecond():
    out = _gaps.book(WAVE, KERNELS, OFFSET_NS)
    assert out["waves"] == 1          # wave-2 holds no kernel event: skipped
    assert out["by_span"] == {
        "queue.drain": 100, _gaps.UNNAMED: 50 + 20 + 10, "tensorize": 10 + 20,
        "tensorize.build_static": 120, "dispatch": 40, "device_wait": 5,
        "place": 100, "commit": 30 + 40, "commit.bind": 10 + 10,
        "remote.request": 180}
    assert out["idle_ns"] == 1000 - 250 - 5 and out["unnamed_ns"] == 80


def test_without_a_busy_interval_gaps_give_the_uncovered_share_of_every_wave():
    out = _gaps.book(WAVE)
    assert out["waves"] == 2
    assert out["idle_ns"] == 1000 + 400
    # wave-1: 100-150, 700-720, 990-1000; wave-2: all but its commit
    assert out["unnamed_ns"] == 80 + 200
    assert out["by_span"]["device_wait"] == 250
    assert _gaps.book([]) == {"by_span": {}, "idle_ns": 0, "unnamed_ns": 0,
                              "waves": 0}


def _s(name, dur, parent="wave-1", cat="phase", **attrs):
    return {"name": name, "cat": cat, "t0": 1.0, "t1": 1.0 + dur, "dur": dur,
            "self_s": dur, "attrs": attrs, "wave": 1, "parent": parent}


FACTS = {
    "spans": [
        _s("wave-1", 2.0, parent=None, cat="wave", pods=1000),
        _s("host_state", 0.003, mode="reconcile", nodes=50, dirty_nodes=4),
        _s("segment_plan", 0.001, pods=1000, segments=1),
        _s("place", 0.002, pods=800, cloned_nodes=40),
        _s("commit.assume", 0.0016, parent="commit", pods=800),
        _s("remote.request", 0.04, parent="commit.bind", cat="client",
           items=800, server_s=0.03, store_s=0.02),
        # an older server sends no Server-Timing: a round trip, no server time
        _s("remote.request", 0.01, parent="commit.bind", cat="client", items=200),
        # the informer's LIST is no bind
        _s("remote.request", 0.5, parent=None, cat="client"),
    ],
    "profile": None,
}


@pytest.mark.parametrize("name,want,needs", [
    ("plan_us_per_pod", 4.0, ("host_state", "segment_plan")),
    ("place_us_per_pod", 2.5, ("place",)),
    ("assume_us_per_pod", 2.0, ("commit.assume",)),
    ("bind_rtt_us_per_pod", 50.0, ("remote.request",)),
    ("bind_server_us_per_pod", 37.5, ("remote.request",)),
])
def test_span_reader_reads_what_it_says_and_nothing_without_its_span(name, want, needs):
    assert run.read_layer_metric(name, FACTS) == pytest.approx(want, rel=1e-9)
    without = dict(FACTS, spans=[s for s in FACTS["spans"] if s["name"] not in needs])
    assert run.read_layer_metric(name, without) is None
    assert run.read_layer_metric(name, dict(FACTS, spans=[])) is None


def test_the_server_time_of_an_older_server_is_absent_not_zero():
    old = dict(FACTS, spans=[s for s in FACTS["spans"]
                             if "server_s" not in s["attrs"]])
    assert run.read_layer_metric("bind_rtt_us_per_pod", old) == pytest.approx(50.0)
    assert run.read_layer_metric("bind_server_us_per_pod", old) is None


def test_idle_unnamed_share_is_the_gaps_share_and_nothing_without_a_trace(capsys):
    facts = {"spans": WAVE,
             "profile": {"offset_ns": OFFSET_NS, "kernels": KERNELS}}
    got = run.read_layer_metric("idle_unnamed_share", facts)
    assert got == pytest.approx(100.0 * 80 / 745, rel=1e-12)
    assert "by span: remote.request 0.000000" in capsys.readouterr().err
    assert run.read_layer_metric("idle_unnamed_share", dict(facts, profile=None)) is None
    assert run.read_layer_metric("idle_unnamed_share", dict(facts, spans=[])) is None
    untied = {"offset_ns": None, "kernels": KERNELS}
    assert run.read_layer_metric("idle_unnamed_share", dict(facts, profile=untied)) is None
