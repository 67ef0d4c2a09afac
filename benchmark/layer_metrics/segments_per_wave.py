"""ops/backend plan: kernel segments per wave of the window, one
``dispatch`` phase span each."""
from benchmark.layer_metrics._common import spans_named, waves


def read(facts):
    n_waves = len(waves(facts))
    segments = len(spans_named(facts, "dispatch"))
    return segments / n_waves if n_waves and segments else None
