"""Each per-layer reader on hand-made spans, samples and a reduced trace: it
reads what its docstring says, and returns nothing (never 0) when there is
nothing to read."""

import json
import os

import pytest

from benchmark import run, trace_reduce


def _span(name, t0, dur, cat="phase", self_s=None, **attrs):
    return {"name": name, "cat": cat, "t0": t0, "t1": t0 + dur, "dur": dur,
            "self_s": dur if self_s is None else self_s, "attrs": attrs,
            "wave": 1, "parent": None}


FACTS = {
    "spans": [
        _span("wave-1", 0.0, 2.0, cat="wave", pods=1000, queue_wait_s=0.05),
        _span("wave-2", 2.0, 1.0, cat="wave", pods=500, queue_wait_s=0.15),
        _span("tensorize", 0.1, 0.004, pods=1000),
        _span("dispatch", 0.2, 0.002),
        _span("tensorize", 2.1, 0.002, pods=500),
        _span("dispatch", 2.2, 0.001),
        _span("commit", 0.5, 0.06, pods=1000, bound=900),
        _span("commit", 2.5, 0.03, pods=500, bound=450),
        _span("informer.frame.apply", 0.7, 0.02, cat="ingest", self_s=0.009,
              kind="Pod", events=900),
        _span("informer.frame.apply", 0.8, 0.5, cat="ingest", kind="Node", events=5),
    ],
    "samples": {"due": [0.0, 1.0, 2.0, 3.0], "sent": [0.0, 1.001, 2.0, 3.2],
                "acked": [0.01, 1.011, 2.03, 3.24], "stopped_at": 10.0,
                "window_keys": ["a", "b", "c", "d"]},
    "dispatched": [{"t": 0.2, "pods": 1000, "terms": 4, "volume_slots": 1},
                   {"t": 2.2, "pods": 500, "terms": 4, "volume_slots": 1}],
    "seen": [0.5, 1.5, 2.5, None],
    "profile": {"offset_ns": 1e9, "busy_s": 0.3, "window_s": 3.0,
                # two kernels: 6 ms after the first dispatch, 2 ms after the second
                "kernels": [(1e9 + 0.25e9, 1e9 + 0.256e9, 0),
                            (1e9 + 2.25e9, 1e9 + 2.252e9, 0)]},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    "n_nodes": 5000,
}


@pytest.mark.parametrize("name,want", [
    ("create_ack_p95_ms", 40.0),
    ("informer_apply_us_per_pod", 10.0),
    ("queue_wait_ms", 100.0),
    ("pods_per_wave", 750.0),
    ("tensorize_us_per_pod", 4.0),
    ("dispatch_us_per_pod", 2.0),
    ("commit_us_per_pod", 90_000 / 1350),
    ("scan_us_per_pod", 8_000 / 1500),
    ("device_idle_share", 90.0),
    ("bind_p50_ms", 500.0),
    ("bind_p95_ms", 7_000.0),
    ("bind_p99_ms", 7_000.0),
    ("generator_late_p95_ms", 200.0),
])
def test_reader_reads_what_it_says(name, want):
    assert run.read_layer_metric(name, FACTS) == pytest.approx(want, rel=1e-6)


def test_scan_roofline_is_the_counted_work_over_the_kernel_time():
    from benchmark import roofline

    work = [roofline.scan_work(pods, 5000, 4, 1, 1) for pods in (1000, 500)]
    least = sum(roofline.least_seconds(w, "TPU v5 lite")[0] for w in work)
    got = run.read_layer_metric("scan_roofline", FACTS)
    assert got == pytest.approx(100.0 * least / 0.008, rel=1e-6)
    assert 0.0 < got < 100.0


def test_nothing_to_read_is_nothing_and_a_missing_kernel_is_an_error():
    bare = dict(FACTS, spans=[], profile=None,
                samples={"due": [], "sent": [], "acked": [], "stopped_at": 0.0,
                         "window_keys": []},
                dispatched=[], seen=[])
    with open(os.path.join(os.path.dirname(run.HERE), "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    for name in names:
        assert run.read_layer_metric(name, bare) is None, name
    no_kernel = dict(FACTS, profile=dict(FACTS["profile"], kernels=[]))
    for name in ("scan_us_per_pod", "scan_roofline"):
        with pytest.raises(trace_reduce.TraceError):
            run.read_layer_metric(name, no_kernel)
