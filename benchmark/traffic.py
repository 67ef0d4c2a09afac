"""The one traffic generator: a traffic file's parameters and a seed become
a preload and a list of timed bursts.

``backlog``: every pod of the deployment is created before the window;
there are no bursts.  ``arrivals``: an open loop of bursts.  The number of
pods is rate x seconds, each burst size owns its ``pod_share`` of them (a
share of the pods, as load.go states it, not of the bursts), and the gaps
between bursts are the quantiles of the exponential distribution with
the right mean, scaled to fill the window exactly.  The seed shuffles sizes
and gaps: every seed offers the same multiset of bursts and gaps, in another
order, so a Poisson process's shape without its run-to-run change of work.

A new mix is a new file of these parameters, never new code.
"""

from __future__ import annotations

import math
import random

KINDS = ("backlog", "arrivals")


def plan(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    """{"preload": pods created before the window, "bursts": [(due_s,
    first, last)] slices into the window's pod list, "window_pods": n,
    "warm_waves": the warm-up waves, each {"pods": n, ...}}."""
    kind = traffic["kind"]
    if kind == "backlog":
        return {"preload": config["pods"]["count"], "bursts": [],
                "window_pods": 0, "warm_waves": []}
    if kind != "arrivals":
        raise ValueError(f"traffic kind {kind!r}; known: {KINDS}")
    rng = random.Random(seed ^ 0x5EED)
    total = int(round(traffic["rate_pods_per_s"] * seconds))
    by_size = sorted(traffic["bursts"], key=lambda b: b["size"])
    # the larger sizes by their shares of the pods; the smallest size fills
    # what is left, so the pods add up to the rate exactly and the multiset
    # is the seed's to order, not to change
    small = by_size[0]["size"]
    burst_sizes = [b["size"] for b in by_size[1:]
                   for _ in range(int(round(b["pod_share"] * total / b["size"])))]
    left = total - sum(burst_sizes)
    if left < 0:
        raise ValueError(f"{total} pods are fewer than the large bursts alone")
    burst_sizes += [small] * (left // small)
    if left % small:
        burst_sizes.append(left % small)
    rng.shuffle(burst_sizes)
    n = len(burst_sizes)
    gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    rng.shuffle(gaps)
    bursts, t, first = [], 0.0, 0
    for size, gap in zip(burst_sizes, gaps):
        bursts.append((t, first, first + size))
        t += gap
        first += size
    return {"preload": 0, "bursts": bursts, "window_pods": total,
            "warm_waves": list(traffic.get("warm_waves") or [])}
