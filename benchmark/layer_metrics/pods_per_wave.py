"""scheduler queue + run_batch_loop: mean pods per wave of the window."""
from benchmark.layer_metrics._common import waves


def read(facts):
    sizes = [w["attrs"]["pods"] for w in waves(facts) if "pods" in w["attrs"]]
    return sum(sizes) / len(sizes) if sizes else None
