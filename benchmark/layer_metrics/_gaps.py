"""Device-idle time inside the traced waves, booked to the program's own
spans (``kubernetes_tpu/utils/tracing``): what ``trace_reduce.reduce_planes``
does with the harness's ``bench.*`` annotations, done with what an operator
of the daemon has.

The program's spans are on ``time.perf_counter``; the harness ties the
profiler's clock to it (``bench.clock`` -> ``facts["profile"]["offset_ns"]``),
so a span maps to trace nanoseconds as ``offset_ns + t * 1e9``.  The device is
busy during the scan kernel's events (``facts["profile"]["kernels"]``: every
cell runs wholly on the Pallas rung, and the other device operations of a
wave total microseconds).  Every idle stretch of a wave goes, piece by piece,
to the innermost span of that wave that covers the piece; a piece only the
wave root covers is ``UNNAMED``.
"""

UNNAMED = "wave: no child span"


def book(spans: list, kernels=None, offset_ns=None) -> dict:
    """{"by_span": {span name: idle ns}, "idle_ns", "unnamed_ns", "waves"}
    over the waves among ``spans`` (the flattened spans of
    ``trace_reduce.window_spans``).  A wave in which no kernel event lies
    (it ran outside the traced slice) is skipped.  Without any busy interval
    (a CPU rehearsal, a unit test) every wave is idle from end to end, and
    ``unnamed_ns / idle_ns`` is the share of the waves that no child span
    covers."""
    offset = offset_ns or 0.0
    busy = sorted((int(s), int(e)) for s, e, *_ in kernels or ())

    def ns(t: float) -> int:
        return round(offset + t * 1e9)

    by_span: dict = {}
    waves = 0
    for root in spans:
        if root["cat"] != "wave":
            continue
        w0, w1 = ns(root["t0"]), ns(root["t1"])
        mine = [(max(s, w0), min(e, w1)) for s, e in busy if s < w1 and e > w0]
        if busy and not mine:
            continue
        waves += 1
        inside = [(ns(s["t0"]), ns(s["t1"]), s["name"]) for s in spans
                  if s["wave"] == root["wave"] and s is not root]
        cursor = w0
        for s, e in mine + [(w1, w1)]:
            if s > cursor:
                _book_stretch(cursor, s, inside, by_span)
            cursor = max(cursor, e)
    idle = sum(by_span.values())
    return {"by_span": by_span, "idle_ns": idle,
            "unnamed_ns": by_span.get(UNNAMED, 0), "waves": waves}


def _book_stretch(lo: int, hi: int, inside: list, by_span: dict) -> None:
    over = [sp for sp in inside if sp[0] < hi and sp[1] > lo]
    cuts = sorted({lo, hi, *(t for s, e, _ in over for t in (s, e) if lo < t < hi)})
    for a, b in zip(cuts, cuts[1:]):
        cover = [(e - s, name) for s, e, name in over if s <= a and e >= b]
        name = min(cover)[1] if cover else UNNAMED
        by_span[name] = by_span.get(name, 0) + (b - a)
