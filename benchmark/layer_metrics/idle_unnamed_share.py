"""device: of the device-idle time inside the traced waves, the share that
no child span of the wave covers (``_gaps``); the split by span goes to
stderr."""
import sys

from benchmark.layer_metrics import _gaps


def read(facts):
    profile = facts.get("profile")
    if not profile or profile.get("offset_ns") is None:
        return None
    booked = _gaps.book(facts.get("spans") or [], profile["kernels"],
                        profile["offset_ns"])
    if not booked["idle_ns"]:
        return None
    split = sorted(booked["by_span"].items(), key=lambda kv: -kv[1])
    print(f"idle_unnamed_share: {booked['waves']} wave(s), idle "
          f"{booked['idle_ns'] / 1e9:.6f}s by span: "
          + ", ".join(f"{name} {v / 1e9:.6f}" for name, v in split),
          file=sys.stderr)
    return 100.0 * booked["unnamed_ns"] / booked["idle_ns"]
