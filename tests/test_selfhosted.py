"""Self-hosted control plane e2e (kubeadm certs/kubeconfig/controlplane
phases): ``cluster init --self-hosted`` boots apiserver / scheduler /
controller-manager as REAL processes under a real-container kubelet's
static-pod source, over TLS with the generated cluster CA.

Behavioral spec: ``cmd/kubeadm/app/phases/certs``, ``phases/kubeconfig``,
``phases/controlplane/manifests.go:45``, and the join-side token
discovery (``kubeadm join`` TLS bootstrap)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_pki_phase(tmp_path):
    """certs phase: CA-chained serving + client certs with the reference
    Subject identities; kubeconfig phase round-trips."""
    from cryptography import x509

    from kubernetes_tpu.pki import create_cluster_pki, load_kubeconfig, write_kubeconfig

    paths = create_cluster_pki(str(tmp_path), node_name="cp")
    with open(paths["ca"], "rb") as f:
        ca = x509.load_pem_x509_certificate(f.read())
    assert ca.subject == ca.issuer  # self-signed root
    with open(paths["kube-scheduler"], "rb") as f:
        sched = x509.load_pem_x509_certificate(f.read())
    assert sched.issuer == ca.subject
    cn = sched.subject.get_attributes_for_oid(
        x509.oid.NameOID.COMMON_NAME)[0].value
    assert cn == "system:kube-scheduler"
    with open(paths["admin"], "rb") as f:
        admin = x509.load_pem_x509_certificate(f.read())
    org = admin.subject.get_attributes_for_oid(
        x509.oid.NameOID.ORGANIZATION_NAME)[0].value
    assert org == "system:masters"
    with open(paths["apiserver"], "rb") as f:
        serving = x509.load_pem_x509_certificate(f.read())
    sans = serving.extensions.get_extension_for_class(
        x509.SubjectAlternativeName).value
    assert "kubernetes.default.svc" in sans.get_values_for_type(x509.DNSName)
    kc = write_kubeconfig(str(tmp_path), "kube-scheduler",
                          "https://127.0.0.1:1", paths["ca"],
                          client_cert=paths["kube-scheduler"],
                          client_key=paths["kube-scheduler_key"])
    doc = load_kubeconfig(kc)
    assert doc["server"] == "https://127.0.0.1:1"
    assert os.path.isabs(doc["client-certificate"])


def test_manifests_inherit_jax_platforms_and_never_default_it(tmp_path, monkeypatch):
    """A --backend tpu scheduler started from these manifests must land on
    whatever platform JAX finds: the variable is inherited when set and
    absent when not (a written default of "cpu" pinned it to the CPU
    without a word)."""
    import yaml

    from kubernetes_tpu.cluster import _write_control_plane_manifests

    paths = {k: f"/pki/{k}" for k in (
        "apiserver", "apiserver_key", "ca", "kubeconfig_kube-scheduler",
        "kubeconfig_kube-controller-manager")}

    def scheduler_env(cluster_dir):
        manifests = _write_control_plane_manifests(
            str(cluster_dir), 6443, paths, "tpu")
        with open(os.path.join(manifests, "kube-scheduler.yaml")) as f:
            return yaml.safe_load(f)["spec"]["containers"][0]["env"]

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert "JAX_PLATFORMS" not in scheduler_env(tmp_path / "unset")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert scheduler_env(tmp_path / "set")["JAX_PLATFORMS"] == "cpu"


@pytest.mark.timeout(240)
def test_selfhosted_control_plane_e2e(tmp_path):
    """THE capstone: init --self-hosted → mirror pods Running over TLS →
    kill -9 the scheduler's container → the kubelet restarts it and
    leader election recovers (a pod still binds) → join verifies
    discovery against the generated CA; a wrong token is rejected."""
    from kubernetes_tpu.api import Container, ObjectMeta, Pod, PodSpec
    from kubernetes_tpu.daemon import remote_clientset

    port = _free_port()
    env = _env()

    def run_cluster(*args, timeout=120):
        return subprocess.run(
            [sys.executable, "-m", "kubernetes_tpu.cluster", *args],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=timeout)

    up = run_cluster("init", "--self-hosted", "--port", str(port),
                     "--backend", "oracle", "--dns-port", "0")
    assert up.returncode == 0, up.stderr + up.stdout
    try:
        state = json.loads(
            (tmp_path / ".kubernetes-tpu-cluster.json").read_text())
        kubeconfig = str(tmp_path / ".kubernetes-tpu" / "admin.kubeconfig")
        cs = remote_clientset(kubeconfig=kubeconfig)

        # all three control-plane components run as mirror-pod-visible
        # static pods (real processes)
        deadline = time.time() + 60
        mirrors = {}
        while time.time() < deadline:
            pods, _ = cs.pods.list("kube-system")
            mirrors = {p.meta.name: p for p in pods}
            if len(mirrors) >= 3 and all(
                    p.status.phase == "Running"
                    and p.status.container_statuses
                    and p.status.container_statuses[0].container_id
                    for p in mirrors.values()):
                break
            time.sleep(1)
        assert sorted(mirrors) == [
            "kube-apiserver-control-plane",
            "kube-controller-manager-control-plane",
            "kube-scheduler-control-plane",
        ], mirrors.keys()
        for p in mirrors.values():
            assert p.meta.annotations.get("kubernetes.io/config.mirror") == "true"
            assert p.status.container_statuses[0].container_id.startswith("pid://")

        # kill -9 the scheduler's REAL process: the kubelet must restart
        # it with a new pid and restart_count+1
        sched = mirrors["kube-scheduler-control-plane"]
        old_pid = int(sched.status.container_statuses[0]
                      .container_id[len("pid://"):])
        os.kill(old_pid, signal.SIGKILL)
        deadline = time.time() + 60
        new_pid = None
        while time.time() < deadline:
            p = cs.pods.get("kube-scheduler-control-plane", "kube-system")
            st = p.status.container_statuses[0]
            if (st.state == "running" and st.container_id
                    and st.container_id != f"pid://{old_pid}"):
                new_pid = int(st.container_id[len("pid://"):])
                assert st.restart_count >= 1
                break
            time.sleep(1)
        assert new_pid, "kubelet never restarted the killed scheduler"

        # join a worker: discovery rides the token-verified CA channel
        join = run_cluster("join", "--apiserver",
                           f"https://127.0.0.1:{port}",
                           "--token", state["token"], "--name", "node-1",
                           timeout=60)
        assert join.returncode == 0, join.stderr + join.stdout
        assert "discovery verified" in join.stdout

        # the RESTARTED scheduler (leader election recovered) binds a pod
        deadline = time.time() + 60
        while time.time() < deadline:
            if any(n.meta.name == "node-1" for n in cs.nodes.list()[0]):
                break
            time.sleep(1)
        cs.pods.create(Pod(
            meta=ObjectMeta(name="web", namespace="default"),
            spec=PodSpec(containers=[Container(name="c", image="i")])))
        bound = None
        deadline = time.time() + 60
        while time.time() < deadline:
            p = cs.pods.get("web")
            if p.spec.node_name:
                bound = p.spec.node_name
                break
            time.sleep(1)
        assert bound == "node-1", \
            "scheduler did not recover after kill -9 (no binding)"

        # a wrong token must fail the discovery handshake
        bad = run_cluster("join", "--apiserver",
                          f"https://127.0.0.1:{port}",
                          "--token", "badbad.0000000000000000",
                          "--name", "evil", timeout=60)
        assert bad.returncode != 0
        assert "FAILED" in (bad.stdout + bad.stderr)

        # anonymous is scoped to join discovery: reading kube-public
        # configmaps works without credentials, but a write is Forbidden
        from kubernetes_tpu.client import Clientset
        from kubernetes_tpu.client.remote import ForbiddenError, RemoteStore

        ca_path = str(tmp_path / ".kubernetes-tpu" / "pki" / "ca.crt")
        anon = Clientset(RemoteStore(f"https://127.0.0.1:{port}",
                                     ca_file=ca_path))
        info = anon.client_for("ConfigMap").get("cluster-info", "kube-public")
        assert "jws-kubeconfig-" in "".join(info.data)
        with pytest.raises(ForbiddenError):
            anon.pods.create(Pod(
                meta=ObjectMeta(name="anon", namespace="default"),
                spec=PodSpec(containers=[Container(name="c")])))
    finally:
        run_cluster("down", timeout=60)
