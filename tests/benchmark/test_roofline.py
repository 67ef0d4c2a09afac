"""``roofline.py`` counts from shapes; the peaks table refuses a device it
does not know."""

import pytest

from benchmark import roofline


def test_work_grows_with_its_shapes():
    base = roofline.scan_work(pods=1_000, nodes=512, terms=0, volume_slots=0, segments=1)
    assert base["ops"] == 1_000 * 512 * base["ops_per_pair"]
    more_terms = roofline.scan_work(1_000, 512, 4, 0, 1)
    assert more_terms["ops_per_pair"] == base["ops_per_pair"] + 4 * roofline.OPS_PER_TERM
    twice = roofline.scan_work(2_000, 512, 0, 0, 1)
    assert twice["ops"] == 2 * base["ops"]
    assert twice["bytes"] - base["bytes"] == 1_000 * (roofline.POD_ROW_BYTES + roofline.CHOICE_BYTES)
    two_segments = roofline.scan_work(1_000, 512, 0, 0, 2)
    assert two_segments["bytes"] - base["bytes"] == 512 * roofline.NODE_PLANES * 4


def test_least_time_names_its_bound():
    work = roofline.scan_work(65_536, 5_000, 4, 1, 1)
    seconds, bound = roofline.least_seconds(work, "TPU v5 lite")
    assert bound == "ops"
    assert seconds == pytest.approx(work["ops"] / 6.16e12)
    tiny = {"ops": 1, "bytes": 819e9}
    assert roofline.least_seconds(tiny, "TPU v5 lite") == (pytest.approx(1.0), "bytes")


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.load_peaks("source")
