"""served path, client's side: 99th percentile (upstream's SLO percentile) of create due -> binding seen
by the client's watch, over every pod of the window."""
from benchmark.layer_metrics._common import bind_percentile


def read(facts):
    return bind_percentile(facts, 99)
