"""models/snapshot tensorize: the tensorize phase spans over the pods they
tensorized."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = spans_named(facts, "tensorize")
    pods = sum(s["attrs"].get("pods", 0) for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / pods if pods else None
