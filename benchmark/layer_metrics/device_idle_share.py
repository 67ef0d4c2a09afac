"""device: 1 - union of device-op intervals over the traced slice."""


def read(facts):
    profile = facts.get("profile")
    if not profile:
        return None
    return 100.0 * (1.0 - profile["busy_s"] / profile["window_s"])
