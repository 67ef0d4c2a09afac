"""Operations and bytes the scheduling scan needs, counted from the
algorithm and its shapes, the same whatever implements it.

One decision touches every real node once per active term: the predicates
(pod count and a compare-and-add per requested resource, the static mask,
one lookup per volume slot and per affinity term) and the priorities (least
requested, balanced allocation, selector spread, taint toleration, and one
gather per inter-pod term), then an arg-max with the tie rotation.  The
counts below are the integer operations of ``reference.py``'s formulas per
(pod, node) pair; they are a floor, not a model of any kernel.  They are
32-bit integer vector operations, so they are held against the vector unit's
peak (``peaks.json`` says how it is derived), not the matrix unit's.

Bytes: the node planes go in once per segment, a row per pod goes in, a
choice per pod comes out.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# integer operations per (pod, node) pair
OPS_FIT = 2 + 3 * 2          # pod count; compare+add for cpu, memory, storage
OPS_STATIC = 1               # the static feasibility mask
OPS_LEAST = 2 * 4            # (cap - req) * 10 // cap for cpu and memory, mean
OPS_BALANCED = 9             # two fixed-point fractions, |diff|, scale
OPS_SPREAD = 10              # node count, zone count, two normalisations, blend
OPS_TAINT = 3
OPS_PER_TERM = 4             # inter-pod term: gather, weight, accumulate, compare
OPS_PER_VOLUME = 3
OPS_SELECT = 4               # total, max, tie mask, rotation
NODE_PLANES = 14             # int32 rows of node state a segment reads
POD_ROW_BYTES = 64
CHOICE_BYTES = 4


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or not isinstance(table[device_kind], dict):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       f"add it with its source, do not assume one")
    return table[device_kind]


def scan_work(pods: int, nodes: int, terms: int, volume_slots: int,
              segments: int) -> dict:
    """Operations and bytes of scanning ``pods`` pods over ``nodes`` real
    nodes in ``segments`` segments."""
    per_pair = (OPS_FIT + OPS_STATIC + OPS_LEAST + OPS_BALANCED + OPS_SPREAD
                + OPS_TAINT + OPS_SELECT + OPS_PER_TERM * terms
                + OPS_PER_VOLUME * volume_slots)
    ops = pods * nodes * per_pair
    bytes_ = (segments * nodes * NODE_PLANES * 4
              + pods * (POD_ROW_BYTES + CHOICE_BYTES))
    return {"ops": ops, "bytes": bytes_, "ops_per_pair": per_pair}


def least_seconds(work: dict, device_kind: str) -> tuple:
    """(the least time the chip could take, which bound it is)."""
    peaks = load_peaks(device_kind)
    by_ops = work["ops"] / peaks["vector_int32_ops_per_s"]
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    return (by_ops, "ops") if by_ops >= by_bytes else (by_bytes, "bytes")
