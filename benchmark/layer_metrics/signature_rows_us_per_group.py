"""models/snapshot build_static, its signature rows: microseconds of the
window's ``tensorize.signature_rows`` spans (each signature's spread
selectors, read by walking every service of the priority context, and the
[G, G] ``spread_inc`` loop) per real signature, Σ of their own ``groups``."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = spans_named(facts, "tensorize.signature_rows")
    groups = sum(s["attrs"].get("groups", 0) for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / groups if groups else None
