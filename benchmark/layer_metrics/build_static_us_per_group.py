"""models/snapshot build_static: microseconds of the window's
``tensorize.build_static`` spans per signature row, over Σ ``groups`` of
the ``tensorize`` spans (the padded signature axis,
``len(static.g_request)``)."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    groups = sum(s["attrs"].get("groups", 0) for s in spans_named(facts, "tensorize"))
    spans = spans_named(facts, "tensorize.build_static")
    return sum(s["dur"] for s in spans) * 1e6 / groups if spans and groups else None
