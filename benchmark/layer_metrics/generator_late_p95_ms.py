"""load generator: create sent - create due, 95th percentile."""
from benchmark import stats


def read(facts):
    s = facts["samples"]
    if not s["due"] or None in s["due"]:
        return None
    return stats.percentile(stats.lateness_ms(s["due"], s["sent"]), 95)
