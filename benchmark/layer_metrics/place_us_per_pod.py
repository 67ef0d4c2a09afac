"""ops/backend place (results onto the working snapshot): the ``place``
phase spans over the pods they placed."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = spans_named(facts, "place")
    pods = sum(s["attrs"].get("pods", 0) for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / pods if pods else None
