"""scheduler cache assume_many: the ``commit.assume`` phase spans over the
pods they assumed."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = spans_named(facts, "commit.assume")
    pods = sum(s["attrs"].get("pods", 0) for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / pods if pods else None
