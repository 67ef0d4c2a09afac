"""The load generator's client: a child process that never imports JAX.

It stands where the system's users stand.  It creates the deployment's
nodes, services, preload and warm-up waves over HTTP through the program's
client library (``RemoteStore``, what ``Clientset(RemoteStore(url))``
wraps) and keeps a pod watch and an event watch of its own: a pod's
binding is timed at the instant this process's watch sees ``spec.nodeName``
set.  The window's creates are sent by ``sender.py``, a process of its own,
so that decoding watch frames never holds a create back; the scheduler's
process shares no interpreter lock with either.

It is told what to do by JSON lines on stdin and answers by JSON lines on
stdout (clocks are ``time.monotonic()``, one clock for every process of a
Linux machine):

    {"cmd": "populate"}          nodes, services and the preload
    {"cmd": "wave", "wave": {..}} one warm-up wave at once; answers when bound
    {"cmd": "go", "t0": t}       the window opens at t: say "all_bound" once
                                 every pod of the window is seen bound
    {"cmd": "stop", "out": path} write the samples, stop the watch, exit
                                 (after its watches have seen "expect_bound"
                                 bindings and "expect_marked" failure
                                 events, or "catch_up_s" at the most)

    python -m benchmark.loadgen --url URL --config NAME --traffic NAME \
        --seed N --seconds S
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

from . import cluster, traffic

CHUNK = 2_000


def _say(**msg) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


class Watcher:
    """First time each pod's nodeName was seen, by this client's watch."""

    def __init__(self, remote, from_revision: int):
        self.bound_at: dict = {}
        self.node_of: dict = {}
        self.rebinds = 0
        self._mu = threading.Lock()
        self._watch = remote.watch("Pod", from_revision=from_revision, frames=True)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        # the FailedScheduling marks, as the apiserver hands them to a client
        self.marked_failed: set = set()
        self._event_watch = remote.watch("Event", from_revision=from_revision,
                                         frames=True)
        self._event_thread = threading.Thread(target=self._run_events, daemon=True)
        self._event_thread.start()

    def _see(self, key: str, node: str, now: float) -> None:
        if not node:
            return
        had = self.node_of.get(key)
        if had is None:
            self.node_of[key] = node
            self.bound_at[key] = now
        elif had != node:
            self.rebinds += 1

    def _run(self) -> None:
        for item in self._watch:
            now = time.monotonic()
            with self._mu:
                if item.type == "FRAME":
                    for key, node in zip(item.keys, item.node_names):
                        self._see(key, node, now)
                elif item.type in ("ADDED", "MODIFIED"):
                    self._see(item.key, (item.object.get("spec") or {})
                              .get("nodeName", ""), now)

    def _run_events(self) -> None:
        for item in self._event_watch:
            objects = item.objects if item.type == "FRAME" else [item.object]
            with self._mu:
                for obj in objects:
                    if obj and obj.get("reason") == "FailedScheduling":
                        self.marked_failed.add(obj.get("involvedKey"))

    def count_bound(self, keys) -> int:
        with self._mu:
            return sum(1 for k in keys if k in self.bound_at)

    def stop(self) -> None:
        self._watch.stop()
        self._event_watch.stop()
        self._thread.join(timeout=10)
        self._event_thread.join(timeout=10)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.loadgen")
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
    watcher = rev = None
    window_keys: list = []
    t0 = None

    def create(kind: str, objs: list) -> None:
        for i in range(0, len(objs), CHUNK):
            got = remote.create_many(kind, objs[i:i + CHUNK])
            if any(item is None for item in got):
                raise RuntimeError(f"the apiserver refused a {kind} create")

    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg["cmd"]
        if cmd == "populate":
            t = time.monotonic()
            create("Node", world.nodes)
            create("Namespace", world.namespaces)
            create("Service", world.services)
            create("Pod", world.preload)
            _, rev = remote.list("Pod", field_selector="spec.nodeName=no-such-node")
            # where the window has creates the watches time them, so they run
            # from now on.  A backlog cell's metrics need no client timing:
            # its watches replay the window from this revision once it has
            # closed ("stop"), and see every binding and event all the same.
            # A second frames watcher inside the window makes the apiserver
            # encode the backlog's one 60,000-pod watch frame either once or
            # twice at once, by a race it calls benign; that race was this
            # harness's, not the deployment's
            if plan["window_pods"] or plan["warm_waves"]:
                watcher = Watcher(remote, rev)
            _say(event="populated", seconds=time.monotonic() - t,
                 nodes=len(world.nodes), pods=len(world.preload))
        elif cmd == "wave":
            pods = world.warm_wave(msg["wave"])
            keys = [cluster.pod_key(p) for p in pods]
            t = time.monotonic()
            # one request, so that the wave reaches the scheduler whole
            if any(item is None for item in remote.create_many("Pod", pods)):
                raise RuntimeError("the apiserver refused a warm-up create")
            deadline = t + msg.get("timeout", 120.0)
            while watcher.count_bound(keys) < len(keys):
                if time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            _say(event="wave", pods=len(keys), bound=watcher.count_bound(keys),
                 seconds=time.monotonic() - t)
        elif cmd == "go" and watcher is not None:
            t0 = msg["t0"]
            window_keys = [cluster.pod_key(p) for p in world.window]
            # every pod created before the window is bound by now (the
            # warm-up waves were waited for), so the window's pods are all
            # bound once this many more are
            want = len(watcher.bound_at) + len(window_keys)

            def tell() -> None:
                # tell the harness as soon as the last pod is bound: the
                # grace period is an upper limit, not a fixed wait
                while len(watcher.bound_at) < want:
                    time.sleep(0.05)
                _say(event="all_bound", seconds=time.monotonic() - t0)

            threading.Thread(target=tell, daemon=True).start()
        elif cmd == "go":
            t0 = msg["t0"]
        elif cmd == "stop":
            if watcher is None:
                watcher = Watcher(remote, rev)
            # the watch may trail the scheduler: read it once it has caught up
            deadline = time.monotonic() + msg.get("catch_up_s", 0.0)
            while ((len(watcher.bound_at) < msg.get("expect_bound", 0)
                    or len(watcher.marked_failed) < msg.get("expect_marked", 0))
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            now = time.monotonic()
            watcher.stop()
            with open(msg["out"], "w") as f:
                json.dump({
                    "t0": t0, "stopped_at": now, "rebinds": watcher.rebinds,
                    "bound_at": watcher.bound_at, "node_of": watcher.node_of,
                    "marked_failed": sorted(watcher.marked_failed),
                }, f)
            _say(event="stopped")
            return 0
        else:
            raise ValueError(f"unknown command {cmd!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
