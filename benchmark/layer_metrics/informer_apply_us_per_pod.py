"""client/informer: self time of the frame- and event-apply spans over the
pod events they ingested."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    spans = [s for s in spans_named(facts, "informer.frame.apply", "informer.event.apply")
             if s["attrs"].get("kind") == "Pod"]
    events = sum(s["attrs"].get("events", 1) for s in spans)
    if not events:
        return None
    return sum(s["self_s"] for s in spans) * 1e6 / events
