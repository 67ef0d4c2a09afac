"""apiserver + store bind_many: the apiserver's own time (``server_s``,
from its ``Server-Timing`` header) of the bind round trips over the
bindings they carried."""
from benchmark.layer_metrics.bind_rtt_us_per_pod import bind_requests


def read(facts):
    spans = [s for s in bind_requests(facts) if "server_s" in s["attrs"]]
    items = sum(s["attrs"]["items"] for s in spans)
    return sum(s["attrs"]["server_s"] for s in spans) * 1e6 / items if items else None
