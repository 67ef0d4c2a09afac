"""ops/pallas_kernel pack + upload: the dispatch phase spans over the pods
of their segments (a dispatch follows its segment's tensorize)."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    pods = sum(s["attrs"].get("pods", 0) for s in spans_named(facts, "tensorize")
               if not s["attrs"].get("rejected"))
    spans = spans_named(facts, "dispatch")
    return sum(s["dur"] for s in spans) * 1e6 / pods if pods and spans else None
