"""The fused scan kernel's events of the traced slice, each with the sizes
of the segment that dispatched it."""


def kernel_segments(facts):
    """[(kernel seconds, the dispatch's note)] or None when there is no
    device trace.  A kernel event belongs to the last dispatch that began
    before it; the harness notes each dispatch with its time, its pods, its
    affinity terms and its volume slots, read from the segment by name."""
    profile = facts.get("profile")
    if not profile or profile.get("offset_ns") is None:
        return None
    dispatched = sorted(facts["dispatched"], key=lambda d: d["t"])
    out = []
    for start_ns, end_ns, _chip in profile["kernels"]:
        t = (start_ns - profile["offset_ns"]) / 1e9
        owner = [d for d in dispatched if d["t"] <= t]
        if owner:
            out.append(((end_ns - start_ns) / 1e9, owner[-1]))
    return out
