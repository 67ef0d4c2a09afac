"""Cluster lifecycle: init / join / up / down (the kubeadm +
local-up-cluster capability, ``cmd/kubeadm`` + ``hack/``).

    python -m kubernetes_tpu.cluster up   --nodes 10        # whole cluster
    python -m kubernetes_tpu.cluster init --port 6443       # control plane
    python -m kubernetes_tpu.cluster join --apiserver URL \
        --token <id>.<secret> --name node-7                 # one hollow node
    python -m kubernetes_tpu.cluster down

``init`` mirrors kubeadm's phases at this control plane's depth: start
the apiserver, create the system namespaces, mint a bootstrap token
Secret, publish the signed ``kube-public/cluster-info`` discovery
document, then start the scheduler and controller manager (leader
elected). ``join`` performs the token-verified discovery handshake
(fetch cluster-info WITHOUT credentials, verify its HMAC signature with
the shared token — the reference's JWS check) before starting a kubelet.
Process state lives in ``.kubernetes-tpu-cluster.json`` for ``down``."""

from __future__ import annotations

import argparse
import json
import os
import secrets as pysecrets
import subprocess
import sys
import time
import urllib.request

STATE_FILE = ".kubernetes-tpu-cluster.json"


def _spawn(mod: str, *args: str) -> int:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", mod, *args],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True,
    )
    return proc.pid


def _wait_healthy(url: str, timeout: float = 15.0, ca_file: str = None) -> None:
    import ssl

    ctx = None
    if url.startswith("https://"):
        ctx = ssl.create_default_context(cafile=ca_file)
        ctx.check_hostname = False  # IP-addressed; chain still verified
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(f"{url}/healthz", timeout=1,
                                        context=ctx) as r:
                if r.status == 200:
                    return
        except Exception:
            time.sleep(0.2)
    raise SystemExit(f"apiserver at {url} did not become healthy")


def _clientset(url: str):
    from .client import Clientset
    from .client.remote import RemoteStore

    return Clientset(RemoteStore(url))


def _bootstrap_phase(cs, url: str, token_ttl: float,
                     ca_data: str = "") -> str:
    """kubeadm phase: system namespaces + bootstrap token + the signed
    cluster-info discovery document.  ``ca_data`` (PEM) rides in the
    payload so a TLS join can learn the cluster CA through the
    token-verified channel (the reference embeds the CA in the
    cluster-info kubeconfig the same way)."""
    from .api import Namespace, ObjectMeta
    from .api.cluster import Secret
    from .controllers.ipam import BootstrapSignerController
    from .store.store import AlreadyExistsError

    for ns in ("kube-system", "kube-public"):
        try:
            cs.namespaces.create(Namespace(meta=ObjectMeta(name=ns)))
        except AlreadyExistsError:
            pass
    token_id = pysecrets.token_hex(3)
    token_secret = pysecrets.token_hex(8)
    cs.secrets.create(Secret(
        meta=ObjectMeta(name=f"bootstrap-token-{token_id}", namespace="kube-system"),
        type="bootstrap.kubernetes.io/token",
        data={"token-id": token_id, "token-secret": token_secret,
              "expiration": str(time.time() + token_ttl),
              "usage-bootstrap-authentication": "true"},
    ))
    payload = json.dumps({"server": url,
                          "certificate-authority-data": ca_data})
    signer = BootstrapSignerController(cs, cluster_info_payload=payload)
    signer.informers.start_all_manual()
    signer.informers.pump_all()
    while signer.sync_once():
        pass
    return f"{token_id}.{token_secret}"


def cmd_init(args) -> dict:
    if getattr(args, "self_hosted", False):
        return cmd_init_selfhosted(args)
    pids = {}
    pids["apiserver"] = _spawn(
        "kubernetes_tpu.apiserver", "--host", "127.0.0.1", "--port", str(args.port)
    )
    # persist immediately: if health-wait fails, `down` can still reap it
    _save({"pids": dict(pids)})
    url = f"http://127.0.0.1:{args.port}"
    _wait_healthy(url)
    cs = _clientset(url)

    token = _bootstrap_phase(cs, url, args.token_ttl)

    pids["scheduler"] = _spawn(
        "kubernetes_tpu.scheduler", "--apiserver", url,
        "--backend", args.backend, "--leader-elect",
    )
    pids["controller-manager"] = _spawn(
        "kubernetes_tpu.controllers", "--apiserver", url, "--leader-elect",
    )
    if getattr(args, "dns_port", 0):
        # the kube-dns addon (cluster/addons/dns): part of standard
        # turn-up, serving the cluster zone over UDP
        pids["kube-dns"] = _spawn(
            "kubernetes_tpu.dns", "--apiserver", url,
            "--port", str(args.dns_port),
        )
    print(f"control plane up at {url}")
    print(f"join token: {token}")
    print(f"  python -m kubernetes_tpu.cluster join --apiserver {url} "
          f"--token {token} --name node-1")
    return {"url": url, "pids": pids, "token": token}


CONTROL_PLANE_NODE = "control-plane"


def _write_control_plane_manifests(cluster_dir: str, port: int,
                                   paths: dict, backend: str) -> str:
    """kubeadm ``phases/controlplane/manifests.go:45
    CreateInitStaticPodManifestFiles``: one static-pod manifest per
    control-plane component, consumed by the control-plane kubelet's
    file source and run as REAL processes."""
    import yaml

    manifests = os.path.join(cluster_dir, "manifests")
    os.makedirs(manifests, exist_ok=True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    inherited = os.environ.get("PYTHONPATH", "")
    env = {"PYTHONPATH": (root + os.pathsep + inherited) if inherited else root}
    if "JAX_PLATFORMS" in os.environ:
        # inherited when set, never defaulted: writing "cpu" here pinned a
        # --backend tpu scheduler to the CPU without a word
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]

    def manifest(name: str, argv: list[str]) -> None:
        doc = {
            "kind": "Pod",
            "metadata": {"name": name, "namespace": "kube-system",
                         "labels": {"component": name, "tier": "control-plane"}},
            "spec": {
                "restartPolicy": "Always",
                "containers": [{
                    "name": name,
                    "image": f"ktpu/{name}",
                    "command": [sys.executable, "-m", *argv],
                    "env": env,
                }],
            },
        }
        with open(os.path.join(manifests, f"{name}.yaml"), "w") as f:
            yaml.safe_dump(doc, f)

    manifest("kube-apiserver", [
        "kubernetes_tpu.apiserver", "--host", "127.0.0.1",
        "--port", str(port),
        "--tls-cert-file", paths["apiserver"],
        "--tls-private-key-file", paths["apiserver_key"],
        "--client-ca-file", paths["ca"],
    ])
    manifest("kube-scheduler", [
        "kubernetes_tpu.scheduler",
        "--kubeconfig", paths["kubeconfig_kube-scheduler"],
        "--backend", backend, "--leader-elect",
    ])
    manifest("kube-controller-manager", [
        "kubernetes_tpu.controllers",
        "--kubeconfig", paths["kubeconfig_kube-controller-manager"],
        "--leader-elect",
    ])
    return manifests


def cmd_init_selfhosted(args) -> dict:
    """``init --self-hosted``: certs phase → kubeconfig phase →
    control-plane static-pod manifests → ONE real-container kubelet that
    bootstraps the control plane from its manifest dir (standalone until
    its own apiserver pod answers, then mirrored).  The control plane
    serves TLS with the generated CA; components authenticate with
    client certificates."""
    from .pki import create_cluster_pki, write_kubeconfig

    cluster_dir = os.path.abspath(args.cluster_dir)
    os.makedirs(cluster_dir, exist_ok=True)
    url = f"https://127.0.0.1:{args.port}"
    paths = create_cluster_pki(cluster_dir, node_name=CONTROL_PLANE_NODE)
    for component in ("admin", "kube-scheduler", "kube-controller-manager"):
        paths[f"kubeconfig_{component}"] = write_kubeconfig(
            cluster_dir, component, url, paths["ca"],
            client_cert=paths[component], client_key=paths[f"{component}_key"])
    paths["kubeconfig_kubelet"] = write_kubeconfig(
        cluster_dir, "kubelet", url, paths["ca"],
        client_cert=paths["kubelet"], client_key=paths["kubelet_key"])
    manifests = _write_control_plane_manifests(
        cluster_dir, args.port, paths, args.backend)

    pids = {"control-plane-kubelet": _spawn(
        "kubernetes_tpu.kubelet",
        "--kubeconfig", paths["kubeconfig_kubelet"],
        "--name", CONTROL_PLANE_NODE,
        "--real-containers", "--static-pod-dir", manifests,
    )}
    _save({"pids": dict(pids)})
    _wait_healthy(url, timeout=60.0, ca_file=paths["ca"])

    from .client import Clientset
    from .client.remote import RemoteStore

    with open(paths["ca"]) as f:
        ca_data = f.read()
    cs = Clientset(RemoteStore(url, ca_file=paths["ca"],
                               client_cert=paths["admin"],
                               client_key=paths["admin_key"]))
    token = _bootstrap_phase(cs, url, args.token_ttl, ca_data=ca_data)
    if getattr(args, "dns_port", 0):
        # the kube-dns addon rides the admin kubeconfig (TLS + client cert)
        pids["kube-dns"] = _spawn(
            "kubernetes_tpu.dns",
            "--kubeconfig", paths["kubeconfig_admin"],
            "--port", str(args.dns_port),
        )
        _save({"pids": dict(pids)})
    print(f"self-hosted control plane up at {url}")
    print(f"  pki + kubeconfigs: {cluster_dir}")
    print(f"join token: {token}")
    print(f"  python -m kubernetes_tpu.cluster join --apiserver {url} "
          f"--token {token} --name node-1")
    return {"url": url, "pids": pids, "token": token,
            "cluster_dir": cluster_dir}


def verify_cluster_info(url: str, token: str) -> str:
    """The join-side discovery handshake: fetch cluster-info anonymously,
    verify the signature for OUR token id with OUR token secret.

    Over https the FETCH is deliberately unverified (the joiner does not
    know the cluster CA yet); trust comes from the HMAC signature shared
    through the token — after which the payload's embedded CA becomes
    the pinned trust root (the reference's token-based TLS bootstrap,
    ``kubeadm join`` discovery)."""
    import ssl

    from .controllers.ipam import sign_cluster_info

    ctx = None
    if url.startswith("https://"):
        ctx = ssl._create_unverified_context()  # noqa: S323 — see docstring
    token_id, _, token_secret = token.partition(".")
    with urllib.request.urlopen(
        f"{url}/api/v1/namespaces/kube-public/configmaps/cluster-info",
        timeout=5, context=ctx
    ) as r:
        info = json.loads(r.read())
    data = info.get("data") or {}
    payload = data.get("kubeconfig", "")
    sig = data.get(f"jws-kubeconfig-{token_id}", "")
    want = sign_cluster_info(payload, token_secret)
    if not sig or sig != want:
        raise SystemExit("cluster-info signature verification FAILED "
                         "(wrong token or tampered discovery document)")
    return payload


def cmd_join(args) -> dict:
    payload = verify_cluster_info(args.apiserver, args.token)
    print(f"discovery verified: {payload!r}")
    ca_data = ""
    try:
        ca_data = (json.loads(payload) or {}).get(
            "certificate-authority-data", "")
    except (ValueError, AttributeError):
        pass  # pre-TLS payloads are plain text
    if ca_data:
        # TLS cluster: pin the token-verified CA and join with the
        # bootstrap token as the credential.  Credentials live NEXT TO
        # the cluster state file (not a leaked mkdtemp) so `down` reaps
        # them with everything else
        join_dir = os.path.abspath(f".kubernetes-tpu-join-{args.name}")
        os.makedirs(join_dir, exist_ok=True)
        ca_path = os.path.join(join_dir, "ca.crt")
        with open(ca_path, "w") as f:
            f.write(ca_data)
        from .pki import write_kubeconfig

        kubeconfig = write_kubeconfig(join_dir, f"kubelet-{args.name}",
                                      args.apiserver, ca_path,
                                      token=args.token)
        pid = _spawn(
            "kubernetes_tpu.kubelet", "--kubeconfig", kubeconfig,
            "--name", args.name, "--proxy",
        )
        print(f"node {args.name} joining (pid {pid})")
        return {"pids": {f"kubelet-{args.name}": pid},
                "join_dirs": [join_dir]}
    pid = _spawn(
        "kubernetes_tpu.kubelet", "--apiserver", args.apiserver,
        "--name", args.name, "--proxy",
    )
    print(f"node {args.name} joining (pid {pid})")
    return {"pids": {f"kubelet-{args.name}": pid}}


def _save(state: dict) -> None:
    old = {}
    if os.path.exists(STATE_FILE):
        with open(STATE_FILE) as f:
            old = json.load(f)
    old.setdefault("pids", {}).update(state.get("pids", {}))
    old.setdefault("join_dirs", [])
    old["join_dirs"] = sorted(
        set(old["join_dirs"]) | set(state.get("join_dirs", [])))
    for k, v in state.items():
        if k not in ("pids", "join_dirs"):
            old[k] = v
    with open(STATE_FILE, "w") as f:
        json.dump(old, f, indent=2)


def cmd_down(_args) -> None:
    import signal

    if not os.path.exists(STATE_FILE):
        print("no cluster state found")
        return
    with open(STATE_FILE) as f:
        state = json.load(f)
    for name, pid in state.get("pids", {}).items():
        try:
            os.kill(pid, signal.SIGTERM)
            print(f"stopped {name} (pid {pid})")
        except ProcessLookupError:
            pass
    import shutil

    for d in state.get("join_dirs", []):
        shutil.rmtree(d, ignore_errors=True)  # token-bearing credentials
    os.remove(STATE_FILE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes_tpu.cluster")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("init")
    p.add_argument("--port", type=int, default=6443)
    p.add_argument("--backend", choices=["tpu", "oracle"], default="tpu")
    p.add_argument("--token-ttl", type=float, default=24 * 3600)
    p.add_argument("--dns-port", type=int, default=10053,
                   help="0 disables the kube-dns addon")
    p.add_argument("--self-hosted", action="store_true",
                   help="certs + kubeconfig phases, control plane as "
                   "static pods under a real-container kubelet, TLS "
                   "throughout (the kubeadm shape)")
    p.add_argument("--cluster-dir", default=".kubernetes-tpu",
                   help="where --self-hosted writes pki/, kubeconfigs, "
                   "and manifests/")
    p = sub.add_parser("join")
    p.add_argument("--apiserver", required=True)
    p.add_argument("--token", required=True)
    p.add_argument("--name", required=True)
    p = sub.add_parser("up")
    p.add_argument("--port", type=int, default=6443)
    p.add_argument("--backend", choices=["tpu", "oracle"], default="oracle")
    p.add_argument("--nodes", type=int, default=5)
    p.add_argument("--token-ttl", type=float, default=24 * 3600)
    p.add_argument("--dns-port", type=int, default=10053,
                   help="0 disables the kube-dns addon")
    sub.add_parser("down")
    args = ap.parse_args(argv)

    if args.cmd == "init":
        _save(cmd_init(args))
        return 0
    if args.cmd == "join":
        _save(cmd_join(args))
        return 0
    if args.cmd == "up":
        state = cmd_init(args)
        url, token = state["url"], state["token"]
        for i in range(args.nodes):
            verify_cluster_info(url, token)
            state["pids"][f"kubelet-{i}"] = _spawn(
                "kubernetes_tpu.kubelet", "--apiserver", url,
                "--name", f"node-{i:03d}", "--proxy",
            )
        _save(state)
        print(f"{args.nodes} nodes joining")
        return 0
    if args.cmd == "down":
        cmd_down(args)
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
