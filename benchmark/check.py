"""What decides ``correct``: the served path's bindings against the plain
reference (``reference.py``), at the timed size, on what the window produced.

The reference walks the scheduler's decisions in the order the scheduler
took them (the recorded queue drains; the oracle replay of ``bench.py:337``
``_oracle_replay_waves`` and ``:2414`` ``run_prefix_parity`` works the same
way and is the origin of this copy).  The reference's state follows the
bindings **as the apiserver stored them**, so one wrong decision is counted
once and does not cascade:

- every decision: the bound node must be feasible in the reference's state
  at that point, and a pod the reference can place must not be left unbound
  (nor one it cannot place be bound);
- a seeded sample of the decisions, with the first and the last of the run
  in it: the bound node must be the reference's own choice, scores, tie
  counter and all.  A full sequential scoring of 150,000 pods over 5,000
  nodes takes about a minute, longer than the window, so scoring is
  sampled; feasibility is not;
- the tie counter after the last decision must equal the program's;
- an unbound pod must carry a ``FailedScheduling`` event at the apiserver;
  no pod may have been seen with two different nodes; every pod created
  must have been decided.

Every number is an exact count with the limit 0 (PERF.md section 2 gives the
readings of sound runs and of the control they were set against).
"""

from __future__ import annotations

import random
from typing import Optional

import numpy as np

from .reference import Reference

LIMITS = {
    "choice_mismatches": 0,
    "infeasible_bindings": 0,
    "verdict_mismatches": 0,
    "tie_counter_gap": 0,
    "unbound_unmarked": 0,
    "rebinds": 0,
    "undecided": 0,
    "unknown_nodes": 0,
}


# how many decisions the reference scores: 12,000 keeps it shorter than an
# arrivals window and within seconds of a backlog's
SCORE_BUDGET = 12_000


def sample_positions(n: int, budget: int, seed: int, head: int = 2_000,
                     tail: int = 1_000) -> np.ndarray:
    """Which of ``n`` decisions are scored: all of them when the budget
    allows, else the first ``head``, the last ``tail`` and a seeded draw of
    the rest."""
    take = np.zeros(n, bool)
    if n <= budget:
        take[:] = True
        return take
    take[:head] = True
    take[n - tail:] = True
    rest = budget - head - tail
    if rest > 0:
        rng = random.Random(seed ^ 0xC0FFEE)
        take[rng.sample(range(head, n - tail), min(rest, n - head - tail))] = True
    return take


def compare(world, drains: list, bindings: dict, marked_failed: set,
            program_tie_counter: Optional[int], rebinds: int, seed: int,
            request_bits: Optional[int] = None) -> dict:
    """``drains``: the recorded queue drains, lists of pod keys in decision
    order.  ``bindings``: pod key -> node name read back from the apiserver
    (absent or None: unbound).  Returns the numbers compared, each an int.

    ``request_bits`` is for the control only: it checks with a reference of
    lower precision put in the checker's place, never in a benchmark run."""
    pods = world.all_pods()
    ref = Reference(world.nodes, world.services, request_bits=request_bits)
    order = [k for batch in drains for k in batch]
    take = sample_positions(len(order), SCORE_BUDGET, seed)
    out = dict.fromkeys(LIMITS, 0)
    out["decisions"] = len(order)
    out["scored"] = int(take.sum())
    placed: set = set()
    seen: set = set()
    last_seen = {k: i for i, k in enumerate(order)}
    for i, key in enumerate(order):
        pod = pods.get(key)
        if pod is None:
            raise KeyError(f"the scheduler drained a pod nobody created: {key}")
        seen.add(key)
        if key in placed:
            continue          # a bound pod that was queued again is not re-decided
        node_name = bindings.get(key)
        feas = ref.feasible(pod)
        if not feas.any():
            # cannot be placed now; it is wrong only if it is bound and this
            # was its last chance
            if node_name is not None and last_seen[key] == i:
                out["verdict_mismatches"] += 1
            continue
        if node_name is None:
            if last_seen[key] == i:
                out["verdict_mismatches"] += 1
            continue
        node = ref.index.get(node_name)
        if node is None:
            out["unknown_nodes"] += 1
            continue
        if not feas[node]:
            out["infeasible_bindings"] += 1
        advances = int(np.count_nonzero(feas)) >= 2
        if take[i]:
            want, _ = ref.choose(pod, feas)
            if want != node:
                out["choice_mismatches"] += 1
        ref.place(pod, node, advances)
        placed.add(key)
    out["undecided"] = sum(1 for k in pods if k not in seen)
    out["unbound_unmarked"] = sum(
        1 for k in pods if bindings.get(k) is None and k in seen
        and k not in marked_failed)
    out["rebinds"] = int(rebinds)
    out["tie_counter_gap"] = (abs(ref.round_robin - program_tie_counter)
                              if program_tie_counter is not None else 0)
    out["bound"] = len(placed)
    return out


def verdict(numbers: dict) -> bool:
    return all(numbers[name] <= limit for name, limit in LIMITS.items())


def report(numbers: dict) -> dict:
    """Short plain names, each number beside its limit."""
    return {name: {"value": numbers[name], "limit": limit}
            for name, limit in LIMITS.items()}


def control_bindings(world, drains: list, request_bits: int, limit: int) -> tuple:
    """The control: the reference put in the program's place, gathering
    requests in ``request_bits`` of mantissa, over the first ``limit``
    decisions.  Returns (drains cut to those decisions, its bindings)."""
    pods = world.all_pods()
    ref = Reference(world.nodes, world.services, request_bits=request_bits)
    cut, bindings, n = [], {}, 0
    for batch in drains:
        batch = batch[: max(0, limit - n)]
        if not batch:
            break
        cut.append(batch)
        n += len(batch)
        for key in batch:
            if key not in bindings or bindings[key] is None:
                bindings[key] = ref.schedule(pods[key])
    return cut, bindings, ref.round_robin
