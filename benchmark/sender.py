"""The window's sender: a child process that never imports JAX and does
nothing but send the arrivals cell's bursts when they are due.

It is a process of its own so that the creates share an interpreter lock
with nobody: not with the scheduler, and not with the client's watch
(``loadgen.py``), whose decoding of watch frames would otherwise hold the
sender back.  A small pool of threads sends the bursts, each one
``RemoteStore.create_many`` call, in the order they are due; a burst waits
for a free thread only when ``THREADS`` creates are in flight at once, so one
slow answer does not hold the next burst back (an open loop).

Each create opens a connection of its own, because the program's client
(``RemoteStore``) does and its apiserver cannot do otherwise: the handler
caches a request's body on the connection's handler object and never resets
it, so a later request on a kept-alive connection never has its body read
and the unread bytes are answered with HTTP 400 (tried on the CPU, PR 23).  At
150 creates a second those connections overflow the apiserver's listen
backlog of 5 and wait out TCP's 1, 3, 7 and 15 s SYN retransmissions; that is
the program's tail, and PERF.md's Open questions has the readings.

    python -m benchmark.sender --url URL --config NAME --traffic NAME \
        --seed N --seconds S

It builds the same world as the harness and the watch from the same seed,
says ``{"event": "ready"}``, reads one line ``{"t0": t, "out": path}``
(``time.monotonic()``, one clock for every process of a Linux machine),
sends, writes when each pod's create was due, sent and acknowledged to
``out``, says ``{"event": "sent"}`` and exits.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

from . import cluster, traffic

THREADS = 16


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sender")
    ap.add_argument("--url", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse-cpu", default=None)
    ap.add_argument("--traffic-set", action="append", default=[])
    args = ap.parse_args(argv)

    from kubernetes_tpu.client.remote import RemoteStore

    remote = RemoteStore(args.url, timeout=120.0)
    config, mix = cluster.resolve(args.config, args.traffic, args.seconds,
                                  args.rehearse_cpu, args.traffic_set)
    plan = traffic.plan(mix, config, args.seed, args.seconds)
    world = cluster.World(config, args.seed, plan)
    keys = [cluster.pod_key(p) for p in world.window]
    bursts = plan["bursts"]
    n = len(keys)
    due, sent, acked = [None] * n, [None] * n, [None] * n
    print(json.dumps({"event": "ready", "bursts": len(bursts)}), flush=True)
    go = json.loads(sys.stdin.readline())
    t0 = go["t0"]
    taken = iter(range(len(bursts)))
    mu = threading.Lock()
    errors: list = []

    def work() -> None:
        while True:
            with mu:
                i = next(taken, None)
            if i is None:
                return
            at, first, last = bursts[i]
            wait = t0 + at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            t_send = time.monotonic()
            try:
                got = remote.create_many("Pod", world.window[first:last])
            except Exception as e:  # noqa: BLE001 - the pods stay unacknowledged
                errors.append(repr(e))
                got = [None] * (last - first)
            t_ack = time.monotonic()
            for k, item in zip(range(first, last), got):
                due[k] = t0 + at
                sent[k] = t_send
                acked[k] = t_ack if item is not None else None

    threads = [threading.Thread(target=work, daemon=True) for _ in range(THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with open(go["out"], "w") as f:
        json.dump({"window_keys": keys, "due": due, "sent": sent, "acked": acked,
                   "errors": errors[:5]}, f)
    print(json.dumps({"event": "sent", "pods": n,
                      "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
