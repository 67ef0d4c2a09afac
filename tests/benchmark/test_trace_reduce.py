"""``trace_reduce`` on the small trace recorded on the chip (PR 23: one
6,000-pod segment over 512 nodes, ``benchmark/testdata``) and on hand-made
planes."""

import os

import pytest

from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(tr.__file__), "testdata",
                        "scan_512x6000.xplane.pb")


def test_recorded_trace_reduces_to_the_numbers_read_by_hand():
    planes = tr.read_planes(RECORDED)
    assert list(planes["devices"]) == [0] and len(planes["devices"][0]) == 68
    assert [a[2] for a in planes["annotations"]] == ["bench.wave"]
    out = tr.reduce_planes(planes)
    assert out["chips"] == [0]
    assert out["busy_s"] == pytest.approx(0.01470595, rel=1e-6)
    assert out["kernel_s"] == pytest.approx(0.014701331, rel=1e-6)
    assert len(out["kernels"]) == 1
    assert out["device_ops"][0][0] == "tpu_custom_call"
    assert out["window_s"] == pytest.approx(0.273216755, rel=1e-6)
    assert out["idle_gaps"][0][0] == "bench.wave"
    assert out["idle_gaps"][0][1] == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-3)


def test_union_counts_overlap_once():
    total, merged = tr.union_length([(0, 10), (5, 12), (20, 30), (30, 31), (25, 26)])
    assert total == 23 and merged == [[0, 12], [20, 31]]


def test_gaps_go_to_the_innermost_host_span_that_covers_them():
    planes = {"devices": {0: [(100, 200, "%tpu_custom_call.1 = custom-call()"),
                              (600, 700, "%copy.3 = copy()")]},
              "annotations": [(0, 1000, "bench.wave"), (210, 590, "bench.commit.bind_many"),
                              (0, 5, "bench.clock")]}
    out = tr.reduce_planes(planes, window_ns=(0, 1000))
    assert out["busy_s"] == pytest.approx(200e-9)
    gaps = dict(out["idle_gaps"])
    # the gap from 200 to 600: 380 ns inside bind_many, the 20 ns around it
    # and the gaps before 100 and after 700 in the wave alone
    assert gaps["bench.commit.bind_many"] == pytest.approx(380e-9)
    assert gaps["bench.wave"] == pytest.approx(420e-9)
    assert "bench.clock" not in gaps
    assert [k[:2] for k in out["kernels"]] == [(100, 200)]
    assert tr.short_op_name("%fusion.12 = f32[8]{0} fusion(%p)") == "fusion"


def test_a_trace_without_device_work_is_an_error_not_a_zero():
    with pytest.raises(tr.TraceError):
        tr.reduce_planes({"devices": {0: []}, "annotations": []})
    with pytest.raises(tr.TraceError):
        tr.find_xplane(os.path.dirname(__file__))
