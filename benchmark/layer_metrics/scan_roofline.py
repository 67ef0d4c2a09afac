"""fused scan kernel: the least time the chip could take for the slice's
scan work (``roofline.py``) over the kernel's device time."""
import sys

from benchmark import roofline
from benchmark.layer_metrics._scan import kernel_segments
from benchmark.trace_reduce import TraceError


def read(facts):
    segs = kernel_segments(facts)
    if segs is None:
        return None
    if not segs:
        raise TraceError("no scan kernel event in the traced slice")
    least = 0.0
    for _, seg in segs:
        work = roofline.scan_work(pods=seg["pods"], nodes=facts["n_nodes"],
                                  terms=seg["terms"],
                                  volume_slots=seg["volume_slots"], segments=1)
        seconds, bound = roofline.least_seconds(work, facts["device"]["kind"])
        least += seconds
    print(f"scan_roofline: bound by {bound}, least {least:.6f}s", file=sys.stderr)
    return 100.0 * least / sum(s for s, _ in segs)
