"""The apiserver daemon's collector policy (``apiserver/collector.py``).

The daemon's process never lets the allocator start an oldest-generation
pass: it runs its own, after an answer is written or when the store stands
still, over what is not yet frozen, and freezes what the pass leaves.  The
policy is the process's, so every in-process case here puts the worker's
collector back as it found it."""

from __future__ import annotations

import ast
import gc
import json
import os
import pathlib
import re
import socket
import subprocess
import sys
import time
import urllib.request
import weakref

import pytest

import kubernetes_tpu
from kubernetes_tpu.apiserver import APIServer, collector as collector_mod
from kubernetes_tpu.apiserver.collector import PASS_ROWS, Collector
from kubernetes_tpu.client.remote import RemoteStore
from kubernetes_tpu.store import Store
from kubernetes_tpu.testutil import make_pod
from kubernetes_tpu.utils import tracing
from kubernetes_tpu.utils.metrics import Registry

ROOT = pathlib.Path(kubernetes_tpu.__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _collector_as_found():
    """No other test of this xdist worker sees a policy, a frozen heap, a
    callback or a tracer that a case here left behind."""
    before = (gc.get_threshold(), gc.isenabled(), list(gc.callbacks))
    yield
    tracing.disable()
    gc.set_threshold(*before[0])
    (gc.enable if before[1] else gc.disable)()
    gc.callbacks[:] = before[2]
    gc.unfreeze()


def install(store) -> Collector:
    """The policy as the daemon's ``main`` installs it."""
    c = Collector(lambda: store.revision, Registry())
    c.install()
    return c


class FullPasses:
    """Oldest-generation passes of this process while the block runs."""

    def __enter__(self):
        self.count = 0
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start" and info["generation"] == 2:
            self.count += 1


def pod_dicts(n: int, start: int = 0) -> list:
    wire = json.dumps(make_pod("p", cpu="100m", memory="100Mi",
                               labels={"app": "web"}).to_dict())
    out = []
    for i in range(start, start + n):
        d = json.loads(wire)  # as the handler decodes a request's body
        d["metadata"]["name"] = f"p{i}"
        out.append(d)
    return out


def tracked_objects(obj) -> int:
    """Containers of a JSON document that the collector tracks."""
    own = 1 if gc.is_tracked(obj) else 0
    if isinstance(obj, dict):
        return own + sum(tracked_objects(v) for v in obj.values())
    if isinstance(obj, list):
        return own + sum(tracked_objects(v) for v in obj)
    return own


def bindings(n: int, start: int = 0) -> tuple:
    """``bind_many``'s two columns: pod keys and the node names beside them."""
    rows = range(start, start + n)
    return [f"default/p{i}" for i in rows], [f"n{i % 50}" for i in rows]


# -- (a) no full pass inside a batch verb ---------------------------------


@pytest.mark.timeout(60)
@pytest.mark.parametrize("policy", ["installed", "cpython_defaults",
                                    "freeze_alone"])
def test_batch_verbs_meet_no_full_pass_under_the_policy(policy):
    """20,000 rows created and bound against a store that holds 20,000:
    under the policy no oldest-generation pass begins between call and
    return.  The controls show that the test can fail: CPython's own
    thresholds start at least one, and so does a bare ``gc.freeze()`` with
    those thresholds (the first pass after it sets the heap's count to
    almost nothing, and the quarter rule then fires again and again over
    the txn's own growing pile)."""
    # the worker's own heap (pytest, jax, what earlier tests left) is taken
    # out of the count first, so that the quarter rule sees the store alone
    gc.collect()
    gc.freeze()
    store = Store(event_log_window=200_000)
    store.create_many("Pod", pod_dicts(20_000))
    more = pod_dicts(20_000, start=20_000)
    if policy == "installed":
        install(store)
    else:
        gc.collect()
        if policy == "freeze_alone":
            gc.freeze()
    with FullPasses() as seen:
        created = store.create_many("Pod", more)
        errors = store.bind_many(*bindings(20_000, start=20_000))
    assert all(c is not None for c in created) and errors == [None] * 20_000
    if policy == "installed":
        assert seen.count == 0
    else:
        assert seen.count >= 1


# -- (b) the pass comes with the row constant, not before -----------------


@pytest.mark.timeout(60)
def test_pass_runs_once_the_row_constant_is_reached():
    store = Store(event_log_window=4 * PASS_ROWS)
    c = install(store)
    assert c.freezes.value == 1, "install collects and freezes once"
    assert gc.get_threshold()[2] == collector_mod._NEVER
    pods = pod_dicts(PASS_ROWS)
    per_row = 2 * tracked_objects(pods[0])  # the stored dict, the event's copy
    frozen = gc.get_freeze_count()
    store.create_many("Pod", pods[:-1])
    with FullPasses() as seen:
        c.after_request()
    assert (seen.count, c.freezes.value) == (0, 1), "one row short: no pass"
    assert gc.get_freeze_count() <= frozen
    store.create_many("Pod", pods[-1:])
    del pods
    with FullPasses() as seen:
        c.after_request()
    assert (seen.count, c.freezes.value, c.full_passes.value) == (1, 2, 2)
    assert abs(c.frozen.value - gc.get_freeze_count()) < 100  # the gauge is the pass's
    grown = gc.get_freeze_count() - frozen
    assert 0.75 * PASS_ROWS * per_row <= grown <= 1.5 * PASS_ROWS * per_row
    # the count starts again from the pass
    c.after_request()
    assert c.freezes.value == 2


@pytest.mark.timeout(60)
def test_idle_tick_takes_what_waits_under_the_constant():
    """A store that stood still for a tick gives the pass its boundary; a
    store that moved since the last tick does not."""
    store = Store()
    c = install(store)
    store.create_many("Pod", pod_dicts(100))
    c.tick()
    assert c.freezes.value == 1, "rows came in since the last tick"
    c.tick()
    assert c.freezes.value == 2, "idle, and 100 rows waited"
    c.tick()
    assert c.freezes.value == 2, "idle, and nothing waits"


@pytest.mark.timeout(60)
def test_handlers_racing_for_the_pass_lose_no_rows(monkeypatch):
    """More handler threads than cores, each committing rows and then
    reporting its answer written: a pass in one thread makes the others
    skip, and the rows they skipped over still count towards the next."""
    import threading

    monkeypatch.setattr(collector_mod, "PASS_ROWS", 200)
    store = Store()
    c = install(store)
    workers, rounds, rows = 16, 10, 50

    def handler(w: int) -> None:
        for k in range(rounds):
            store.create_many("Pod", pod_dicts(rows, start=(w * rounds + k) * rows))
            c.after_request()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=handler, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=50)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    total = workers * rounds * rows
    assert store.revision == total
    c.after_request()
    assert store.revision - c._pass_rev < 200, "what waits is under the constant"
    assert 2 <= c.freezes.value - 1 <= total // 200
    assert c.full_passes.value == c.freezes.value


# -- (c) frozen rows die by reference count -------------------------------


@pytest.mark.timeout(60)
def test_frozen_rows_die_by_reference_count():
    store = Store(event_log_window=1_000)
    c = install(store)
    store.create_many("Pod", pod_dicts(2_000))
    c.run_pass()
    frozen = gc.get_freeze_count()
    # binding past the window pushes every frozen create row out of the log
    assert store.bind_many(*bindings(2_000)) == [None] * 2_000
    assert gc.get_freeze_count() < frozen - 1_000


# -- (d) the collect precedes the freeze ----------------------------------


@pytest.mark.timeout(60)
def test_a_cycle_made_before_a_pass_is_collected_by_it():
    class Knot:
        pass

    c = install(Store())
    gc.disable()  # no young pass may take the cycle first
    knot = Knot()
    knot.me = knot
    alive = weakref.ref(knot)
    del knot
    assert alive() is not None
    found = c.full_collected.value
    c.run_pass()
    assert alive() is None
    assert c.full_collected.value > found


# -- (e) the policy is the daemon's, not the library's --------------------


@pytest.mark.timeout(60)
def test_embedding_store_and_apiserver_leaves_the_collector_alone():
    before = (gc.get_threshold(), gc.isenabled(), gc.get_freeze_count(),
              list(gc.callbacks))
    server = APIServer(Store())
    server.start()
    try:
        assert server.collector is None
        remote = RemoteStore(server.url)
        created = remote.create_many("Pod", pod_dicts(200))
        assert len(created) == 200
        assert remote.bind_many(*bindings(200)) == [None] * 200
        with urllib.request.urlopen(f"{server.url}/api/v1/pods") as r:
            assert "gc;" not in r.headers["Server-Timing"]
        with urllib.request.urlopen(f"{server.url}/metrics") as r:
            assert b"apiserver_gc_" not in r.read()
    finally:
        server.stop()
    assert (gc.get_threshold(), gc.isenabled(), gc.get_freeze_count(),
            list(gc.callbacks)) == before


def test_store_and_server_call_nothing_of_gc():
    """Only ``apiserver/collector.py`` touches ``gc``, and only the
    daemon's ``main`` installs it."""
    pkg = ROOT / "kubernetes_tpu"
    sources = sorted((pkg / "store").glob("*.py")) + [
        pkg / "apiserver" / "server.py", pkg / "apiserver" / "__init__.py"]
    assert len(sources) > 5
    for path in sources:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [a.name for a in node.names]
            assert "gc" not in names and "collector" not in names, (
                f"{path.relative_to(ROOT)}:{node.lineno}")
    installs = [p.relative_to(ROOT).as_posix()
                for p in pkg.rglob("*.py")
                if re.search(r"(?<![A-Za-z_])Collector\(", p.read_text())
                and p.name != "collector.py"]
    assert installs == ["kubernetes_tpu/apiserver/__main__.py"]


# -- (f) the daemon as a child --------------------------------------------


def _gc_metrics(url: str) -> dict:
    with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
        rows = (line.split() for line in r.read().decode().splitlines()
                if line.startswith("apiserver_gc_"))
        return {name: float(value) for name, value in rows}


@pytest.mark.timeout(60)
def test_daemon_serves_its_counters_and_gc_time_reaches_the_span():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{ROOT}{os.pathsep}{os.environ.get('PYTHONPATH', '')}")
    child = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.apiserver", "--host",
         "127.0.0.1", "--port", str(port)], env=env, cwd=ROOT,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 30
        while True:
            try:
                urllib.request.urlopen(f"{url}/healthz", timeout=1).read()
                break
            except OSError:
                assert child.poll() is None and time.monotonic() < deadline
                time.sleep(0.05)
        at_start = _gc_metrics(url)
        assert at_start["apiserver_gc_freezes_total"] == 1
        assert at_start["apiserver_gc_frozen_objects"] > 0

        remote = RemoteStore(url)
        tr = tracing.enable()
        assert len(remote.create_many("Pod", pod_dicts(500))) == 500
        assert remote.bind_many(*bindings(500)) == [None] * 500
        batch = [sp for sp in tr.background if sp.name == "remote.request"
                 and sp.attrs["path"].endswith(":batch")]
        assert [sp.attrs["items"] for sp in batch] == [500, 500]
        for sp in batch:
            assert 0 <= sp.attrs["gc_s"] <= sp.attrs["server_s"]
        req = urllib.request.Request(
            f"{url}/api/v1/bindings:batch", method="POST",
            data=json.dumps({"keys": [], "nodeNames": []}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as r:
            fields = [part.partition(";dur=")
                      for part in r.headers["Server-Timing"].split(", ")]
        assert [f[0] for f in fields] == ["handle", "store", "gc"]
        assert float(fields[2][2]) >= 0

        # 1,000 rows are under the constant: the idle tick takes them
        while _gc_metrics(url)["apiserver_gc_freezes_total"] < 2:
            assert child.poll() is None and time.monotonic() < deadline
            time.sleep(0.1)
        now = _gc_metrics(url)
        assert now["apiserver_gc_frozen_objects"] > (
            at_start["apiserver_gc_frozen_objects"] + 1_000)
        assert now["apiserver_gc_full_passes_total"] == now[
            "apiserver_gc_freezes_total"], "no pass but the daemon's own"
        assert now["apiserver_gc_full_pause_seconds_total"] > 0
    finally:
        child.terminate()
        child.wait(timeout=10)
