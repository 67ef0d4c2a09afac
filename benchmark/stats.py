"""Arithmetic of the end-to-end metrics: rate, percentile, lateness.

Every pod of the window is in every number: a rate is all pods bound in the
window over the whole window, a percentile is taken over every pod created
in it, and a pod that was never bound counts with the time it had waited
when the run gave up on it, which is beyond any tail a bound pod can have.
"""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``q`` per
    cent of the samples at or below it).  Refuses an empty list: a metric
    with nothing to read is left out, never reported as 0."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def rate(count: int, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s


def bind_latencies_ms(due: list, bound_seen: list, give_up_at: float) -> tuple:
    """(latency in ms per pod, number never bound).  ``due`` is when the
    pod's create was due, ``bound_seen`` when the client's watch saw its
    ``spec.nodeName`` (None: never)."""
    out, failed = [], 0
    for d, b in zip(due, bound_seen):
        if b is None:
            failed += 1
            b = give_up_at
        out.append((b - d) * 1e3)
    return out, failed


def lateness_ms(due: list, sent: list) -> list:
    """How late the generator sent each create (never negative: a create
    is not sent before it is due)."""
    return [max(0.0, (s - d) * 1e3) for d, s in zip(due, sent)]


def bound_in_window(bound_seen: list, t_open: float, t_close: float) -> int:
    return sum(1 for b in bound_seen if b is not None and t_open <= b <= t_close)


def timeline(due: list, sent: list, acked: list, bound_seen: list,
             t_open: float, window_s: float) -> list:
    """The window second by second, for stderr: pods due, pods seen bound,
    and the worst lateness, create round trip and binding latency (ms) of
    the pods due in that second.  A stall shows as a second with few
    bindings, and which of the three numbers rises says where it began."""
    n = max(1, math.ceil(window_s))
    rows = [[0, 0, 0.0, 0.0, 0.0] for _ in range(n)]
    for d, s, a, b in zip(due, sent, acked, bound_seen):
        row = rows[min(n - 1, max(0, int(d - t_open)))]
        row[0] += 1
        row[2] = max(row[2], (s - d) * 1e3)
        if a is not None:
            row[3] = max(row[3], (a - s) * 1e3)
        if b is not None:
            row[4] = max(row[4], (b - d) * 1e3)
            if 0 <= b - t_open < n:
                rows[int(b - t_open)][1] += 1
    names = ("due", "seen bound", "worst late ms", "worst create ms", "worst bind ms")
    return [f"per second, {name}: " + " ".join(str(round(row[i])) for row in rows)
            for i, name in enumerate(names)]
