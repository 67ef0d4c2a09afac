"""apiserver bind_many handler: of the server's own time (``server_s``) in
the window's bind round trips, the share that no named part covers.

A request's named parts are its ``cat="server"`` children that lie inside it
by time (the body, its parse, the wait for the store's lock, the store's txn,
the answer: the apiserver's ``Server-Timing`` parts, filed by the client on
the clock both processes read).  A request without them, as a server that
sends none answers, names only its ``store_s``.  The split by part, the
handler thread's CPU time (``server_cpu_s``), the watch streams' time beside
it (``watch_s``, of which ``watch_encode_s`` encoding) and each bind on its
own go to stderr."""
import sys

from benchmark.layer_metrics.bind_rtt_us_per_pod import bind_requests

SLACK_S = 1e-6  # the header's rounding of a part's start and length


def parts_of(spans: list, req: dict) -> list:
    return [s for s in spans
            if s["cat"] == "server" and s["parent"] == "remote.request"
            and s["wave"] == req["wave"]
            and req["t0"] - SLACK_S <= s["t0"] and s["t1"] <= req["t1"] + SLACK_S]


def read(facts):
    spans = facts.get("spans") or []
    reqs = [s for s in bind_requests(facts) if "server_s" in s["attrs"]]
    server_s = sum(r["attrs"]["server_s"] for r in reqs)
    if not server_s:
        return None
    named = 0.0
    total: dict = {}
    lines = []
    for req in sorted(reqs, key=lambda s: s["t0"]):
        a = req["attrs"]
        mine: dict = {}
        for p in parts_of(spans, req):
            mine[p["name"]] = mine.get(p["name"], 0.0) + p["dur"]
        if not mine:
            mine = {"store_s": a.get("store_s", 0.0)}
        named += sum(mine.values())
        for name, v in mine.items():
            total[name] = total.get(name, 0.0) + v
        lines.append(f"  bind of {a['items']} at {req['t0']:.6f}: server_s "
                     f"{a['server_s']:.6f}, server_cpu_s {a.get('server_cpu_s')}, "
                     f"watch_s {a.get('watch_s')}, "
                     f"watch_encode_s {a.get('watch_encode_s')}, "
                     + ", ".join(f"{n} {v:.6f}" for n, v in mine.items()))
    cpu = sum(r["attrs"].get("server_cpu_s", 0.0) for r in reqs)
    watch = sum(r["attrs"].get("watch_s", 0.0) for r in reqs)
    encode = sum(r["attrs"].get("watch_encode_s", 0.0) for r in reqs)
    print(f"bind_server_unnamed_share: {len(reqs)} bind(s), server_s "
          f"{server_s:.6f}, server_cpu_s {cpu:.6f}, watch_s {watch:.6f}, "
          f"watch_encode_s {encode:.6f}, by part: "
          + ", ".join(f"{n} {v:.6f}" for n, v in total.items()), file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    return 100.0 * (server_s - named) / server_s
