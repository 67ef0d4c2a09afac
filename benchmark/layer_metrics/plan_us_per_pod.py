"""ops/backend plan (host state + segmentation): the ``host_state`` and
``segment_plan`` phase spans over the pods of their waves."""
from benchmark.layer_metrics._common import spans_named, waves


def read(facts):
    spans = spans_named(facts, "host_state", "segment_plan")
    pods = sum(w["attrs"].get("pods", 0) for w in waves(facts))
    return sum(s["dur"] for s in spans) * 1e6 / pods if pods and spans else None
