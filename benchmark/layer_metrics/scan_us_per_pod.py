"""fused scan kernel: device time of the kernel's events in the profiler
trace over the pods their segments scanned."""
from benchmark.layer_metrics._scan import kernel_segments
from benchmark.trace_reduce import TraceError


def read(facts):
    segs = kernel_segments(facts)
    if segs is None:
        return None
    if not segs:
        raise TraceError("no scan kernel event in the traced slice")
    return sum(s for s, _ in segs) * 1e6 / sum(seg["pods"] for _, seg in segs)
