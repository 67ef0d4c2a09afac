"""scheduler commit (assume_many + bind_many): the commit phase spans over
the pods they bound."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = spans_named(facts, "commit")
    bound = sum(s["attrs"].get("bound", 0) for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / bound if bound else None
