"""``bind_server_unnamed_share`` (PR 37) on hand-made facts: the exact share
where the apiserver sent its parts, the parent's form where it sent only
``store_s``, nothing only where no bind request is in the window; and the
idle time under a bind booked to the server's parts by ``_gaps``, with no
edit to it."""

import pytest

from benchmark import run
from benchmark.layer_metrics import _gaps

BASE = 40.0


def _span(name, t0, t1, parent="wave-1", cat="phase", wave=1, **attrs):
    return {"name": name, "cat": cat, "t0": BASE + t0, "t1": BASE + t1,
            "dur": t1 - t0, "self_s": t1 - t0, "attrs": attrs, "wave": wave,
            "parent": parent}


def _bind(t0, t1, items, server_s, store_s, **attrs):
    return _span("remote.request", t0, t1, parent="commit.bind", cat="client",
                 items=items, server_s=server_s, store_s=store_s, **attrs)


def _part(name, t0, t1, wave=1):
    return _span(name, t0, t1, parent="remote.request", cat="server", wave=wave)


WAVE = [
    _span("wave-1", 0.0, 4.0, parent=None, cat="wave", pods=3000),
    _span("commit", 1.0, 3.9, pods=3000, bound=3000),
    _span("commit.bind", 1.05, 1.65, parent="commit", pods=1000),
    _bind(1.1, 1.6, 1000, server_s=0.4, store_s=0.25, server_cpu_s=0.3,
          watch_s=0.05, watch_encode_s=0.02),
    _span("client.encode", 1.1, 1.15, parent="remote.request", cat="client"),
    _part("server.body", 1.15, 1.17),
    _part("server.parse", 1.17, 1.25),
    _part("server.parse", 1.25, 1.27),
    _part("server.store_lock", 1.28, 1.29),
    _part("server.store", 1.29, 1.53),
    _part("server.answer", 1.53, 1.54),
    _span("client.decode", 1.58, 1.59, parent="remote.request", cat="client"),
    _span("commit.bind", 1.95, 3.05, parent="commit", pods=2000),
    _bind(2.01, 2.99, 2000, server_s=0.8, store_s=0.6, server_cpu_s=0.4,
          watch_s=0.3, watch_encode_s=0.1),
    _part("server.body", 2.1, 2.12),
    _part("server.parse", 2.12, 2.22),
    _part("server.store_lock", 2.22, 2.32),
    _part("server.store", 2.32, 2.82),
    _part("server.answer", 2.82, 2.84),
    # the informer's LIST is no bind, and its parts are not a bind's
    _span("remote.request", 3.0, 3.5, parent=None, cat="client", wave=None,
          server_s=0.4, store_s=0.1),
    _part("server.store", 3.1, 3.2, wave=None),
]
# of 0.4 s the first bind names 0.02 + 0.08 + 0.02 + 0.01 + 0.24 + 0.01 =
# 0.38; of 0.8 s the second 0.02 + 0.1 + 0.1 + 0.5 + 0.02 = 0.74
NAMED = 0.38 + 0.74


def test_the_share_no_part_of_the_servers_time_names():
    got = run.read_layer_metric("bind_server_unnamed_share", {"spans": WAVE})
    assert got == pytest.approx(100.0 * (1.2 - NAMED) / 1.2, rel=1e-9)


def test_the_split_by_part_and_by_bind_goes_to_stderr(capsys):
    run.read_layer_metric("bind_server_unnamed_share", {"spans": WAVE})
    err = capsys.readouterr().err
    assert ("2 bind(s), server_s 1.200000, server_cpu_s 0.700000, "
            "watch_s 0.350000, watch_encode_s 0.120000") in err
    assert "server.parse 0.200000" in err and "server.store 0.740000" in err
    assert "server.store_lock 0.110000" in err
    first, second = [ln for ln in err.splitlines() if ln.startswith("  bind of")]
    assert first.startswith("  bind of 1000") and "watch_s 0.05" in first
    assert second.startswith("  bind of 2000") and "server.store_lock 0.100000" in second


@pytest.mark.parametrize("keep_parts", [False, True])
def test_the_parents_form_names_only_store_s(keep_parts):
    """A server that sends no parts (the parent's) names its ``store_s``;
    a part that lies outside every bind by time names nothing."""
    spans = [s for s in WAVE if s["cat"] != "server"]
    if keep_parts:
        spans.append(_part("server.store", 3.6, 3.7))
    got = run.read_layer_metric("bind_server_unnamed_share", {"spans": spans})
    assert got == pytest.approx(100.0 * (1.2 - 0.25 - 0.6) / 1.2, rel=1e-9)


@pytest.mark.parametrize("spans", [
    [],
    [s for s in WAVE if s["parent"] != "commit.bind"],
    [_span("wave-1", 0.0, 4.0, parent=None, cat="wave")],
])
def test_nothing_only_where_no_bind_request_is_in_the_window(spans):
    assert run.read_layer_metric("bind_server_unnamed_share",
                                 {"spans": spans}) is None


def test_a_bind_with_no_part_and_no_store_time_reads_all_unnamed():
    spans = [_bind(1.0, 1.5, 10, server_s=0.2, store_s=0.0)]
    assert run.read_layer_metric("bind_server_unnamed_share",
                                 {"spans": spans}) == pytest.approx(100.0)


def test_idle_time_under_a_bind_is_booked_to_the_servers_parts():
    """``_gaps`` books each idle piece to the innermost span: under a bind
    that is now the server's part, not the round trip."""
    booked = _gaps.book(WAVE)
    by = booked["by_span"]
    ns = lambda s: round(s * 1e9)  # noqa: E731
    assert by["server.store"] == pytest.approx(ns(0.24 + 0.5), abs=4)
    assert by["server.parse"] == pytest.approx(ns(0.08 + 0.02 + 0.1), abs=4)
    assert by["client.encode"] == pytest.approx(ns(0.05), abs=2)
    # what is left of the round trip: connect, transfer, the gaps
    assert by["remote.request"] == pytest.approx(
        ns(0.5 + 0.98 - 0.05 - 0.01 - NAMED), abs=8)
