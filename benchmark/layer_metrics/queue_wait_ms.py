"""scheduler queue + run_batch_loop: mean accumulation wait of the waves."""
from benchmark.layer_metrics._common import waves


def read(facts):
    waits = [w["attrs"]["queue_wait_s"] for w in waves(facts)
             if "queue_wait_s" in w["attrs"]]
    return sum(waits) * 1e3 / len(waits) if waits else None
