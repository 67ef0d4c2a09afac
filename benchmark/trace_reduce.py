"""From traces to numbers: the profiler's ``.xplane.pb`` and the program's
span trees become busy time, kernel time, the top device operations and the
longest idle gaps by what the host was doing.

Kept with the benchmark so that every PR reads the same number the same way;
checked against the small recorded trace in ``testdata/`` by
``tests/benchmark/test_trace_reduce.py``.

What a v5e trace looks like (taken on the chip, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per executed
operation; the fused scan kernel is the ``tpu_custom_call`` among them.
``jax.profiler.TraceAnnotation`` spans land on the plane ``/host:CPU``, line
``python``.  All times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
KERNEL = re.compile(r"tpu_custom_call|custom-call")
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
CLOCK_MARKER = "bench.clock"


class TraceError(ValueError):
    """The trace lacks what a metric needs; an error, never a zero."""


def union_length(intervals: list) -> tuple:
    """(total length, merged intervals) of possibly overlapping
    (start, end) pairs."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def short_op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def find_xplane(profile_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise TraceError(f"no .xplane.pb under {profile_dir}")
    return files[-1]


def read_planes(path: str) -> dict:
    """{"devices": {chip: [(start_ns, end_ns, name)]}, "annotations":
    [(start_ns, end_ns, name)]} from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    annotations: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    return {"devices": devices, "annotations": annotations}


def reduce_planes(planes: dict, window_ns: Optional[tuple] = None) -> dict:
    """Busy seconds (union of device-op intervals, averaged over the chips
    that ran anything), the kernel's events, the top operations and the idle
    gaps.  ``window_ns`` is the traced window on the trace's clock; without
    it the window is from the first to the last event seen."""
    used = {chip: evs for chip, evs in planes["devices"].items() if evs}
    if not used:
        raise TraceError("no operation ran on a device in the traced window")
    if window_ns is None:
        every = [e for evs in used.values() for e in evs] + planes["annotations"]
        window_ns = (min(e[0] for e in every), max(e[1] for e in every))
    busy_ns = []
    merged_of: dict = {}
    for chip, evs in used.items():
        total, merged = union_length([(s, e) for s, e, _ in evs])
        busy_ns.append(total)
        merged_of[chip] = merged
    by_op: dict = {}
    kernels: list = []
    for chip, evs in used.items():
        for s, e, name in evs:
            op = short_op_name(name)
            by_op[op] = by_op.get(op, 0.0) + (e - s) / 1e9
            if KERNEL.search(name):
                kernels.append((s, e, chip))
    # idle gaps of the fullest-traced chip, by the host annotation that
    # covers most of each gap
    chip0 = min(used)
    gaps: list = []
    cursor = window_ns[0]
    for s, e in merged_of[chip0] + [[window_ns[1], window_ns[1]]]:
        if s > cursor:
            gaps.append((cursor, min(s, window_ns[1])))
        cursor = max(cursor, e)
    notes = [a for a in planes["annotations"] if a[2] != CLOCK_MARKER]
    by_host: dict = {}
    for gs, ge in gaps:
        if ge <= gs:
            continue
        # every stretch of the gap goes to the innermost (shortest) host
        # annotation that covers it, so a gap that outlasts a call is not
        # booked to that call whole
        over = [a for a in notes if a[0] < ge and a[1] > gs]
        cuts = sorted({gs, ge, *(t for s, e, _ in over for t in (s, e) if gs < t < ge)})
        for lo, hi in zip(cuts, cuts[1:]):
            cover = [(e - s, name) for s, e, name in over if s <= lo and e >= hi]
            name = min(cover)[1] if cover else "host: not annotated"
            by_host[name] = by_host.get(name, 0.0) + (hi - lo) / 1e9
    window_s = (window_ns[1] - window_ns[0]) / 1e9
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": window_s,
        "chips": sorted(used),
        "kernels": sorted(kernels),
        "kernel_s": sum(e - s for s, e, _ in kernels) / 1e9,
        "device_ops": [[op, s] for op, s in
                       sorted(by_op.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[name, s] for name, s in
                      sorted(by_host.items(), key=lambda kv: -kv[1])],
    }


def reduce_profile(profile_dir: str, t_marker: Optional[float],
                   t_start: float, t_stop: float) -> dict:
    """The traced slice, with the profiler's clock tied to
    ``time.perf_counter`` by the ``bench.clock`` annotation."""
    planes = read_planes(find_xplane(profile_dir))
    marker = [a for a in planes["annotations"] if a[2] == CLOCK_MARKER]
    window_ns = None
    offset_ns = None
    if marker and t_marker is not None:
        # perf_counter t  <->  trace ns:  ns = offset + t * 1e9
        offset_ns = marker[0][1] - t_marker * 1e9
        window_ns = (offset_ns + t_start * 1e9, offset_ns + t_stop * 1e9)
    out = reduce_planes(planes, window_ns)
    out["offset_ns"] = offset_ns
    out["window_s"] = t_stop - t_start
    return out


# -- the program's span trees -------------------------------------------------


def flatten(span, wave: Optional[int], out: list, parent: Optional[str]) -> None:
    t1 = span.t1 if span.t1 is not None else span.t0
    covered, _ = union_length([(c.t0, c.t1 if c.t1 is not None else c.t0)
                               for c in span.children])
    out.append({"name": span.name, "cat": span.cat, "t0": span.t0, "t1": t1,
                "dur": t1 - span.t0, "self_s": max(0.0, t1 - span.t0 - covered),
                "attrs": dict(span.attrs), "wave": wave, "parent": parent})
    for child in span.children:
        flatten(child, wave, out, span.name)


def window_spans(tracer, t_open: float, t_close: float,
                 t_stopped: float) -> list:
    """The window's spans, flattened, each with its self time (duration
    less what its children cover): the waves that began inside the window,
    and every other span (informer applies, on their own threads) that began
    before the loop stood still, since the last confirmations are applied
    after the last decision."""
    out: list = []
    with tracer._mu:
        roots = list(tracer.ring) + list(tracer.background)
    for root in roots:
        is_wave = root.cat == "wave"
        if not (t_open <= root.t0 <= (t_close if is_wave else t_stopped)):
            continue
        flatten(root, root.attrs.get("wave") if is_wave else None, out, None)
    return out
