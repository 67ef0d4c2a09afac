"""apiserver + store: the generator's clock round each create call."""
from benchmark import stats


def read(facts):
    s = facts["samples"]
    rounds = [(a - t) * 1e3 for a, t in zip(s["acked"], s["sent"])
              if a is not None and t is not None]
    return stats.percentile(rounds, 95) if rounds else None
