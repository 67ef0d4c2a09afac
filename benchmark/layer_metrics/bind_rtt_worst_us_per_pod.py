"""client/remote bind_many round trip: the slowest of the window's
``bind_many`` requests, per binding it carried.  Where a wave has several
segments, this is the one that waited behind the apiserver's other work
under its one interpreter lock (a full pass of its collector, a watch
frame's or a replay's encode)."""
from benchmark.layer_metrics.bind_rtt_us_per_pod import bind_requests


def read(facts):
    spans = bind_requests(facts)
    return max(s["dur"] * 1e6 / s["attrs"]["items"] for s in spans) if spans else None
