"""kube-apiserver daemon (reference ``cmd/kube-apiserver/app/server.go:112``).

    python -m kubernetes_tpu.apiserver --port 6443 \
        [--token-file tokens.csv] [--authorization-mode RBAC] \
        [--audit-log audit.jsonl] [--event-log-window 300000]

``--token-file`` rows are ``token,user[,group1|group2]`` (the reference's
static token file)."""

from __future__ import annotations

import argparse
import logging
import sys

from ..admission import default_chain
from ..daemon import install_signal_stop, wait_forever
from ..store.store import Store
from .collector import Collector
from .server import APIServer


def parse_token_file(path: str) -> dict:
    from ..auth import UserInfo

    tokens: dict[str, object] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(",")
            user = parts[1] if len(parts) > 1 else parts[0]
            groups = parts[2].split("|") if len(parts) > 2 and parts[2] else []
            tokens[parts[0]] = UserInfo(name=user, groups=groups)
    return tokens


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubernetes_tpu.apiserver")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=6443)
    ap.add_argument("--token-file", default=None)
    ap.add_argument("--authorization-mode", default=None,
                    choices=[None, "AlwaysAllow", "RBAC"])
    ap.add_argument("--audit-log", default=None)
    ap.add_argument("--event-log-window", type=int, default=300_000)
    ap.add_argument("--disable-admission", action="store_true")
    ap.add_argument("--data-dir", default=None,
                    help="durable state directory (WAL + snapshots; "
                         "restart recovers the cluster — the etcd analogue)")
    ap.add_argument("--fsync", action="store_true",
                    help="fsync every WAL append (durability over latency)")
    ap.add_argument("--tls-cert-file", default=None)
    ap.add_argument("--tls-private-key-file", default=None)
    ap.add_argument("--client-ca-file", default=None,
                    help="verify client certificates against this CA; a "
                    "verified peer Subject becomes the request identity "
                    "(CN = user, O = groups)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    store_kw = dict(event_log_window=args.event_log_window,
                    data_dir=args.data_dir, fsync=args.fsync)
    if args.disable_admission:
        store = Store(**store_kw)
    else:
        from ..admission import AdmittedStore

        store = AdmittedStore(default_chain(), **store_kw)
    if args.data_dir:
        logging.info("durable store at %s (recovered to revision %d)",
                     args.data_dir, store.revision)

    tokens = parse_token_file(args.token_file) if args.token_file else None
    authorizer = None
    if args.authorization_mode == "RBAC":
        from ..auth import RBACAuthorizer

        authorizer = RBACAuthorizer(store)
    auditor = None
    if args.audit_log:
        from ..auth.audit import Auditor, LogBackend

        auditor = Auditor(backends=[LogBackend(args.audit_log)])

    if bool(args.tls_cert_file) != bool(args.tls_private_key_file):
        ap.error("--tls-cert-file and --tls-private-key-file go together")
    if args.client_ca_file and not args.tls_cert_file:
        ap.error("--client-ca-file requires --tls-cert-file "
                 "(client certificates ride the TLS handshake)")
    tls = None
    authenticator = None
    if args.tls_cert_file:
        from .server import TLSConfig

        tls = TLSConfig(args.tls_cert_file, args.tls_private_key_file,
                        client_ca=args.client_ca_file)
        if args.client_ca_file:
            # cert-authenticated control plane: peer certs carry identity,
            # static tokens (if any) and bootstrap tokens still work, and
            # anonymous stays ON so `join` can fetch the signed
            # cluster-info discovery document without credentials
            # (kubeadm's bootstrap contract) — but anonymous is then
            # AUTHORIZED only for that discovery surface unless an
            # explicit --authorization-mode overrides
            from ..auth import (
                AuthenticatedOrDiscovery,
                BootstrapTokenAuthenticator,
                TokenFileAuthenticator,
                UnionAuthenticator,
            )

            chain = []
            if tokens is not None:
                chain.append(TokenFileAuthenticator(tokens))
            chain.append(BootstrapTokenAuthenticator(store))
            authenticator = UnionAuthenticator(*chain, allow_anonymous=True)
            if authorizer is None and args.authorization_mode is None:
                authorizer = AuthenticatedOrDiscovery()

    server = APIServer(store, host=args.host, port=args.port, tokens=tokens,
                       authenticator=authenticator,
                       authorizer=authorizer, auditor=auditor, tls=tls)
    # this process owns its collector: no oldest-generation pass but the
    # daemon's own, after an answer or on an idle tick (collector.py)
    server.collector = Collector(lambda: store.revision, server.registry)
    server.collector.install()
    server.start()
    print(f"apiserver serving on {server.url}", flush=True)
    stop = install_signal_stop()
    wait_forever(stop, tick=server.collector.tick)
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
