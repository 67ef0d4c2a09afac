"""e2e: real daemons over the wire — apiserver HTTP server, a
leader-elected scheduler on a remote clientset with threaded informers,
a threaded controller manager, and a hollow fleet, scheduling 1k pods.

The de-risking test for the daemon process model (reference
``plugin/cmd/kube-scheduler/app/server.go:67,133``,
``cmd/kube-apiserver/app/server.go:112``)."""

import threading
import time

import pytest

from kubernetes_tpu.api import ObjectMeta, ReplicaSet, PodTemplateSpec, PodSpec, Container, Quantity, ResourceRequirements
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Clientset, LeaderElector
from kubernetes_tpu.client.remote import RemoteStore
from kubernetes_tpu.controllers.manager import ControllerManager
from kubernetes_tpu.kubelet.hollow import HollowFleet
from kubernetes_tpu.scheduler import GenericScheduler, Scheduler
from kubernetes_tpu.store import Store


N_PODS = 1000
N_NODES = 20


@pytest.mark.timeout(120)
def test_daemon_stack_schedules_1k_pods_over_the_wire():
    server = APIServer(Store(event_log_window=50_000))
    server.start()
    try:
        _run(server)
    finally:
        server.stop()


def _run(server):
    # -- scheduler daemon: remote clientset, threaded informers, leader lock
    sched_cs = Clientset(RemoteStore(server.url))
    elector = LeaderElector(sched_cs, "kube-scheduler", "sched-a")
    assert elector.try_acquire_or_renew()
    # a standby cannot take the lock while it's held
    standby = LeaderElector(sched_cs, "kube-scheduler", "sched-b")
    assert not standby.try_acquire_or_renew()

    sched = Scheduler(sched_cs, algorithm=GenericScheduler(), emit_events=False)
    sched.start(manual=False)  # threaded informer watch loops
    stop = threading.Event()

    def sched_loop():
        while not stop.is_set():
            if not sched.schedule_one(timeout=0.05, async_bind=False):
                continue

    threads = [threading.Thread(target=sched_loop, daemon=True) for _ in range(1)]
    for t in threads:
        t.start()

    # -- controller manager daemon (replicaset loop drives pod creation)
    cm_cs = Clientset(RemoteStore(server.url))
    mgr = ControllerManager(cm_cs, enabled=["replicaset"])
    mgr.start(manual=False, workers_per_controller=2)

    # -- hollow fleet (shares one process here; talks over the wire too)
    fleet_cs = Clientset(RemoteStore(server.url))
    fleet = HollowFleet(fleet_cs, N_NODES, cpu="64", memory="128Gi", pods=200,
                        pod_start_latency=0.0)
    fleet.register_all()

    # -- workload: one ReplicaSet of 1k pods through the controller plane
    cli = Clientset(RemoteStore(server.url))
    rs = ReplicaSet(
        meta=ObjectMeta(name="web", namespace="default"),
        replicas=N_PODS,
        selector=LabelSelector.from_match_labels({"app": "web"}),
        template=PodTemplateSpec(
            labels={"app": "web"},
            spec=PodSpec(containers=[Container(
                name="c",
                resources=ResourceRequirements(requests={"cpu": Quantity("50m")}),
            )]),
        ),
    )
    cli.replicasets.create(rs)

    deadline = time.time() + 90
    bound = 0
    try:
        while time.time() < deadline:
            fleet.tick_all()
            pods, _ = cli.pods.list()
            bound = sum(1 for p in pods if p.spec.node_name)
            running = sum(1 for p in pods if p.status.phase == "Running")
            if bound >= N_PODS and running >= N_PODS:
                break
            time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        mgr.stop()
        sched.informers.stop_all()

    assert bound >= N_PODS, f"only {bound}/{N_PODS} pods bound before deadline"
    running = sum(1 for p in cli.pods.list()[0] if p.status.phase == "Running")
    assert running >= N_PODS
    elector.release()


def test_scheduler_daemon_serves_healthz_and_metrics():
    """server.go:149: the scheduler daemon mounts /healthz + /metrics."""
    import json
    import subprocess
    import sys
    import time
    import urllib.request

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.store import Store

    import os
    import socket

    server = APIServer(Store())
    server.start()
    proc = None
    # pick a free port up front: no output parsing, no unbounded readline
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.scheduler",
             "--apiserver", server.url, "--backend", "oracle",
             "--healthz-port", str(port)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        deadline = time.time() + 20
        status = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz", timeout=1) as r:
                    status = json.loads(r.read())["status"]
                break
            except Exception:
                time.sleep(0.2)
        assert status == "ok", "daemon healthz never came up"
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                    timeout=5) as r:
            text = r.read().decode()
        assert "scheduler" in text  # the SLI histograms are exposed
    finally:
        if proc is not None:
            proc.terminate()
            proc.wait(timeout=10)
        server.stop()


# -- HA failover (VERDICT r2 ask #5) ----------------------------------------
# Two scheduler daemons against one apiserver: the leader dies mid-flood
# WITHOUT releasing its lease; the standby must observe renewal expiry,
# acquire, and drain the remainder with no double-bindings
# (client-go/tools/leaderelection/leaderelection.go:152,172;
#  plugin/cmd/kube-scheduler/app/server.go:133).

@pytest.mark.timeout(120)
def test_ha_scheduler_failover_mid_flood():
    from kubernetes_tpu.testutil import make_node, make_pod

    server = APIServer(Store(event_log_window=100_000))
    server.start()
    try:
        seed_cs = Clientset(RemoteStore(server.url))
        for i in range(20):
            seed_cs.nodes.create(make_node(
                f"ha-n{i:02d}", cpu="64", memory="128Gi", pods=200,
                labels={"kubernetes.io/hostname": f"ha-n{i:02d}"}))
        for i in range(1000):
            seed_cs.pods.create(make_pod(f"ha-p{i:04d}", cpu="50m",
                                         memory="64Mi", labels={"app": "ha"}))

        fake_now = [time.time()]
        clock = lambda: fake_now[0]  # noqa: E731 — shared lease clock

        binds = {"sched-a": 0, "sched-b": 0}
        conflicts = {"sched-a": 0, "sched-b": 0}

        def make_daemon(ident):
            cs = Clientset(RemoteStore(server.url))
            elector = LeaderElector(cs, "kube-scheduler-ha", ident,
                                    lease_duration=2.0, renew_deadline=1.5,
                                    clock=clock)
            sched = Scheduler(cs, algorithm=GenericScheduler(),
                              emit_events=False)
            orig_bind = sched._bind

            def counting_bind(pod, node_name):
                ok = orig_bind(pod, node_name)
                if ok:
                    binds[ident] += 1
                else:
                    conflicts[ident] += 1
                return ok

            sched._bind = counting_bind
            sched.start(manual=False)  # threaded informers: standby stays warm
            stop = threading.Event()

            def loop():
                # renew on a period (renew_deadline/2, like RunOrDie), not
                # per pod — a lease CAS per schedule_one would triple the
                # HTTP traffic of the hot loop
                is_leader = False
                next_renew = 0.0
                while not stop.is_set():
                    now = time.time()
                    if not is_leader or now >= next_renew:
                        is_leader = elector.try_acquire_or_renew()
                        next_renew = now + 0.5
                    if not is_leader:
                        time.sleep(0.02)
                        continue
                    sched.schedule_one(timeout=0.02)

            t = threading.Thread(target=loop, daemon=True)
            return cs, elector, sched, stop, t

        cs_a, el_a, sched_a, stop_a, t_a = make_daemon("sched-a")
        cs_b, el_b, sched_b, stop_b, t_b = make_daemon("sched-b")
        t_a.start()
        # let A win the race outright before B enters it
        deadline = time.time() + 10
        while time.time() < deadline and not el_a.is_leader:
            time.sleep(0.02)
        assert el_a.is_leader
        t_b.start()

        # phase 1: A makes real progress mid-flood
        deadline = time.time() + 30
        while time.time() < deadline and binds["sched-a"] < 300:
            fake_now[0] = time.time()
            time.sleep(0.05)
        assert binds["sched-a"] >= 300, f"leader stalled at {binds['sched-a']}"
        assert binds["sched-b"] == 0  # standby must not schedule while A holds

        # phase 2: A crashes (no release) -> lease must EXPIRE, not hand over
        stop_a.set()
        t_a.join(timeout=5)
        crash_at = time.time()
        fake_now[0] = crash_at
        assert not el_b.try_acquire_or_renew()  # still within A's lease
        fake_now[0] = crash_at + 3.0  # past leaseDurationSeconds

        # phase 3: B acquires and drains the rest
        deadline = time.time() + 90
        bound = 0
        while time.time() < deadline:
            fake_now[0] += 0.05
            pods, _ = seed_cs.pods.list()
            bound = sum(1 for p in pods if p.spec.node_name)
            if bound >= 1000:
                break
            time.sleep(0.05)
        stop_b.set()
        t_b.join(timeout=5)
        assert bound == 1000, f"only {bound}/1000 bound after failover"
        assert el_b.is_leader
        assert binds["sched-b"] > 0, "standby never scheduled after takeover"
        # no double-bindings: every successful bind is a distinct pod (the
        # store CAS makes a second bind fail, so the sum can only be 1000
        # if no pod was bound twice)
        assert binds["sched-a"] + binds["sched-b"] == 1000
        # handoff is near-clean: B may lose a handful of CAS races on
        # pods A bound right before dying (informer lag), never more
        assert conflicts["sched-b"] <= 5
        sched_a.informers.stop_all()
        sched_b.informers.stop_all()
    finally:
        server.stop()


@pytest.mark.timeout(60)
def test_ha_controller_manager_failover():
    """Standby controller-manager takes over a ReplicaSet mid-scale-out
    after the active one dies holding the lease."""
    from kubernetes_tpu.testutil import make_node

    server = APIServer(Store(event_log_window=50_000))
    server.start()
    try:
        seed = Clientset(RemoteStore(server.url))
        seed.nodes.create(make_node("cm-n0", cpu="64", memory="128Gi", pods=300))
        seed.replicasets.create(ReplicaSet(
            meta=ObjectMeta(name="web", namespace="default"), replicas=40,
            selector=LabelSelector.from_match_labels({"app": "web"}),
            template=PodTemplateSpec(labels={"app": "web"},
                                     spec=PodSpec(containers=[Container(name="c")])),
        ))

        fake_now = [time.time()]
        clock = lambda: fake_now[0]  # noqa: E731

        def make_cm(ident):
            cs = Clientset(RemoteStore(server.url))
            elector = LeaderElector(cs, "kube-controller-manager-ha", ident,
                                    lease_duration=2.0, clock=clock)
            mgr = ControllerManager(cs, enabled=["replicaset"])
            mgr.start()
            return cs, elector, mgr

        cs_a, el_a, mgr_a = make_cm("cm-a")
        cs_b, el_b, mgr_b = make_cm("cm-b")

        assert el_a.try_acquire_or_renew()
        assert not el_b.try_acquire_or_renew()
        # active manager reconciles only PART of the scale-out, then dies
        mgr_a.reconcile_all()
        pods_after_a = len(seed.pods.list()[0])
        assert pods_after_a >= 40  # RS loop created the pods

        # scale up while the dead leader still holds the lease
        def _scale(rs):
            rs.replicas = 70
            return rs
        seed.replicasets.guaranteed_update("web", _scale, "default")
        fake_now[0] += 3.0  # lease expires

        assert el_b.try_acquire_or_renew(), "standby failed to take over"
        for _ in range(5):
            mgr_b.reconcile_all()
        pods = seed.pods.list()[0]
        assert len(pods) == 70, f"standby reconciled to {len(pods)}, want 70"
    finally:
        server.stop()


@pytest.mark.timeout(2)
def test_conftest_timeout_watchdog_enforces(monkeypatch):
    """The timeout mark must be load-bearing (pytest-timeout is absent;
    the conftest SIGALRM watchdog implements it).  A test body that
    sleeps past its deadline fails with TimeoutError instead of hanging."""
    import time as _time

    with pytest.raises(TimeoutError, match="deadline"):
        # the watchdog fires mid-sleep; 10s would otherwise blow the mark
        _time.sleep(10)


@pytest.mark.timeout(120)
def test_deposed_tpu_scheduler_exits_instead_of_keeping_the_device(tmp_path):
    """An accelerator belongs to one process at a time: a --backend tpu
    scheduler that loses its lease must exit (reference server.go:133
    OnStoppedLeading), not re-enter the acquire loop holding the chip
    its successor needs.  Until it leads it must not touch JAX at all."""
    import json
    import os
    import subprocess
    import sys

    from kubernetes_tpu.client.leaderelection import LEASE_ANNOTATION

    server = APIServer(Store())
    server.start()
    proc = None
    log_path = tmp_path / "scheduler.log"
    try:
        cs = Clientset(RemoteStore(server.url))
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, "-m", "kubernetes_tpu.scheduler",
                 "--apiserver", server.url, "--backend", "tpu",
                 "--leader-elect"],
                env=env, stdout=log, stderr=subprocess.STDOUT)
        deadline = time.time() + 60
        while time.time() < deadline:
            if "backend tpu: platform=cpu" in log_path.read_text():
                break
            assert proc.poll() is None, log_path.read_text()
            time.sleep(0.2)
        else:
            raise AssertionError("scheduler never led: " + log_path.read_text())

        def steal(ev):
            ev.meta.annotations[LEASE_ANNOTATION] = json.dumps({
                "holderIdentity": "successor",
                "renewTime": time.time() + 3_600,
                "leaseDurationSeconds": 15.0})
            return ev

        cs.events.guaranteed_update("kube-scheduler", steal, "kube-system")
        assert proc.wait(timeout=40) == 1
        log = log_path.read_text()
        assert "lost the lease while holding the accelerator" in log
        # the device line comes after "became leader": a standby stays off JAX
        assert log.index("became leader") < log.index("backend tpu: platform")
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        server.stop()
