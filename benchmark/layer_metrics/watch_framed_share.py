"""client/informer watch -> scheduler confirm: of the pods the window's
commits bound, the share whose confirmation reached the scheduler as a row
of a watch frame (the ``events`` of the ``scheduler.confirm`` spans of kind
Pod over the ``bound`` of the ``commit`` spans).  In a backlog window the
only pod frames are the binds', so the rest came as a line each, replayed
from the store's log after the stream's deadline, or after the loop stood
still."""
from benchmark.layer_metrics._common import spans_named


def read(facts):
    bound = sum(s["attrs"].get("bound", 0) for s in spans_named(facts, "commit"))
    framed = sum(s["attrs"].get("events", 0)
                 for s in spans_named(facts, "scheduler.confirm")
                 if s["attrs"].get("kind") == "Pod")
    return 100.0 * framed / bound if bound else None
