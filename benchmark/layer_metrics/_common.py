"""What the readers share: picking spans of the window by name."""


def spans_named(facts: dict, *names: str) -> list:
    return [s for s in facts.get("spans") or [] if s["name"] in names]


def waves(facts: dict) -> list:
    return [s for s in facts.get("spans") or [] if s["cat"] == "wave"]


def bind_percentile(facts: dict, q: float):
    """Percentile of create due -> binding seen by the client's watch, over
    every pod of the window; None where the cell creates none in it."""
    from benchmark import stats

    s = facts["samples"]
    if not s["window_keys"] or None in s["due"]:
        return None
    lat, _ = stats.bind_latencies_ms(s["due"], facts["seen"], s["stopped_at"])
    return stats.percentile(lat, q)
