"""Percentile, rate and lateness arithmetic on hand-made samples."""

import pytest

from benchmark import stats


def test_percentile_is_nearest_rank_over_every_sample():
    values = list(range(1, 101))
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 99) == 99
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_stall_in_the_window_moves_the_rate_and_the_tail():
    # 100 pods due every 10 ms; the system binds each 5 ms after it is due
    due = [i * 0.01 for i in range(100)]
    steady = [d + 0.005 for d in due]
    # the same, but nothing is bound between t = 0.5 s and t = 0.8 s
    stalled = [b if b < 0.5 else max(b, 0.8) for b in steady]
    lat_steady, _ = stats.bind_latencies_ms(due, steady, give_up_at=2.0)
    lat_stalled, _ = stats.bind_latencies_ms(due, stalled, give_up_at=2.0)
    assert stats.percentile(lat_steady, 95) == pytest.approx(5.0)
    assert stats.percentile(lat_stalled, 95) >= 250.0
    assert stats.percentile(lat_stalled, 50) == pytest.approx(5.0)
    # the rate is all pods bound in the window over the whole window
    assert stats.rate(stats.bound_in_window(steady, 0.0, 0.75), 0.75) == pytest.approx(100.0, rel=0.02)
    assert stats.rate(stats.bound_in_window(stalled, 0.0, 0.75), 0.75) == pytest.approx(50 / 0.75)


def test_a_pod_never_bound_lies_beyond_the_tail():
    due = [0.0] * 10
    seen = [0.1] * 9 + [None]
    lat, never = stats.bind_latencies_ms(due, seen, give_up_at=30.0)
    assert never == 1
    assert stats.percentile(lat, 95) == pytest.approx(30_000.0)
    assert stats.bound_in_window(seen, 0.0, 1.0) == 9


def test_lateness_is_sent_minus_due_and_never_negative():
    assert stats.lateness_ms([1.0, 2.0, 3.0], [1.0, 2.5, 2.999]) == [
        0.0, pytest.approx(500.0), 0.0]
    with pytest.raises(ValueError):
        stats.rate(5, 0.0)


def test_the_timeline_shows_a_stall_and_where_it_began():
    # four pods a second for 3 s; the create of second 1 takes 1.004 s (a SYN
    # sent again), so nothing is seen bound in second 1 and twice as much in 2
    due = [i / 4 for i in range(12)]
    sent = list(due)
    acked = [s + (1.004 if 1.0 <= s < 2.0 else 0.01) for s in sent]
    seen = [a + 0.1 for a in acked]
    lines = stats.timeline(due, sent, acked, seen, t_open=0.0, window_s=3.0)
    by_name = {line.split(": ")[0]: line.split(": ")[1].split() for line in lines}
    assert by_name["per second, due"] == ["4", "4", "4"]
    assert by_name["per second, seen bound"] == ["4", "0", "8"]
    assert by_name["per second, worst create ms"] == ["10", "1004", "10"]
    assert by_name["per second, worst late ms"] == ["0", "0", "0"]
    assert by_name["per second, worst bind ms"] == ["110", "1104", "110"]
