"""HTTP API server: the store served over REST with watch streaming.

Capability of the reference's generic API server + kube-apiserver
(SURVEY.md L3/L4): resource routes installed per kind
(``apiserver/pkg/endpoints/installer.go``), per-verb handlers
(``handlers/rest.go:150 GetResource``, ``:276 ListResource`` incl. the
watch upgrade, ``:388 createHandler``), the Binding subresource
(``pkg/registry/core/pod/storage/storage.go:128``), and a filter chain
(``server/config.go:469``) reduced to its behavioral essentials:
panic recovery → request logging → authentication (optional static bearer
tokens) → dispatch.

Wire form: JSON.  Watches are chunked JSON-lines streams exactly like the
reference's ``?watch=true`` (one ``{"type": ..., "object": ...}`` per
line), resumable via ``resourceVersion``.

Routes:
  GET    /healthz  /metrics  /version
  GET    /api/v1/{resource}[?namespace=&watch=true&resourceVersion=N]
  POST   /api/v1/{resource}
  GET    /api/v1/namespaces/{ns}/{resource}/{name}
  PUT    /api/v1/namespaces/{ns}/{resource}/{name}[?cas=true]
  DELETE /api/v1/namespaces/{ns}/{resource}/{name}
  POST   /api/v1/namespaces/{ns}/pods/{name}/binding
  POST   /api/v1/bindings:batch          (the TPU batch-bind txn; body
         {"keys": ["ns/name", ...], "nodeNames": [...]}, two columns of
         equal length; answer {"errors": [null | "reason", ...]})
  POST   /api/v1/{resource}:batch        (batch create: one store txn)
Cluster-scoped objects use ns "-" in paths.
"""

from __future__ import annotations

import json
import logging
import math
import sys
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from .. import faults
from ..admission.framework import AdmissionDenied
from ..utils import tracing
from ..utils.health import handle_debug_path
from ..store.store import (
    AlreadyExistsError,
    ConflictError,
    ExpiredRevisionError,
    NotFoundError,
    Store,
)
from ..utils.metrics import (DEFAULT_STORE_METRICS, Counter, Histogram,
                             Registry)

logger = logging.getLogger("kubernetes_tpu.apiserver")

# SelfSubjectAccessReview route (reference authorization.k8s.io group,
# served by the generic apiserver; evaluated against the live authorizer)
SSAR_PATH = "/apis/authorization.k8s.io/v1/selfsubjectaccessreviews"

# binary wire negotiation (reference application/vnd.kubernetes.protobuf)
from ..api.wire import CONTENT_TYPE as BINARY_CONTENT_TYPE  # noqa: E402


class TLSConfig:
    """Serving-side TLS for the wire server (reference
    ``--tls-cert-file``/``--tls-private-key-file``/``--client-ca-file``).
    With ``client_ca`` set, the handshake REQUESTS (not requires) a client
    certificate and verifies it against the CA; a verified peer cert
    becomes the request identity via
    ``X509CertificateAuthenticator.from_peercert`` — token-bearing clients
    still authenticate normally without one."""

    def __init__(self, certfile: str, keyfile: str,
                 client_ca: Optional[str] = None):
        self.certfile = certfile
        self.keyfile = keyfile
        self.client_ca = client_ca

    def context(self):
        import ssl

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.certfile, self.keyfile)
        if self.client_ca:
            ctx.load_verify_locations(self.client_ca)
            ctx.verify_mode = ssl.CERT_OPTIONAL
        return ctx

# resource path segment -> kind, derived from the one type registry so
# every registered kind (incl. late-registered CRDs) is wire-addressable.
from ..api.types import CLUSTER_SCOPED_KINDS as CLUSTER_SCOPED  # noqa: E402
from ..api.types import kind_for_plural as _kind_for  # noqa: E402

# link the federation API group into the wire surface (the reference's
# federation-apiserver compiles its types in the same way) — importing
# registers the Cluster kind; federation/__init__ is import-light (lazy
# controller loading) so this does NOT pull in the controller tree
from ..federation import types as _federation_types  # noqa: E402,F401


def _bind_columns_fault(keys, node_names) -> Optional[str]:
    """Why a ``bindings:batch`` body's two columns cannot be committed, or
    None: each must be a list of strings, and the two of equal length."""
    for field, col in (("keys", keys), ("nodeNames", node_names)):
        if type(col) is not list:
            return f"{field}: a list of strings is required"
        # (a C-level pass over the column: no Python step per row)
        if not all(map(str.__instancecheck__, col)):
            return f"{field}: every entry must be a string"
    if len(keys) != len(node_names):
        return (f"{len(keys)} keys and {len(node_names)} nodeNames: "
                f"the columns must be of equal length")
    return None


class APIServer:
    """HTTP front end over the store.

    Filter order mirrors the reference's handler chain
    (``server/config.go:469 DefaultBuildHandlerChain``): panic recovery →
    request-info → max-in-flight → authentication → audit → impersonation
    → authorization → dispatch.
    ``tokens`` is the legacy static-token shorthand; pass ``authenticator``
    / ``authorizer`` / ``auditor`` for the full stack (admission runs in
    the store itself when constructed over an ``AdmittedStore``)."""

    def __init__(
        self,
        store: Store,
        host: str = "127.0.0.1",
        port: int = 0,
        tokens: Optional[dict[str, str]] = None,  # token -> username; None = authn off
        authenticator=None,
        authorizer=None,
        auditor=None,
        tls: Optional["TLSConfig"] = None,
        max_in_flight: int = 0,  # 0 = unlimited (reference default 400)
        tunneler=None,  # master↔node secure channel (tunneler.Tunneler)
    ):
        self.store = store
        self.tls = tls
        self.tunneler = tunneler
        # max-in-flight filter (server/filters/maxinflight.go): a
        # semaphore, never a queue — overload answers 429 immediately
        self._inflight = (threading.Semaphore(max_in_flight)
                          if max_in_flight > 0 else None)
        self.tokens = tokens
        self.authenticator = authenticator
        if authenticator is None and tokens is not None:
            from ..auth import TokenFileAuthenticator, UnionAuthenticator

            self.authenticator = UnionAuthenticator(
                TokenFileAuthenticator(tokens), allow_anonymous=False
            )
        self.authorizer = authorizer
        self.auditor = auditor
        self.registry = Registry()
        self.request_count = self.registry.register(
            Counter("apiserver_request_count", "total requests")
        )
        self.request_latency = self.registry.register(
            Histogram("apiserver_request_latencies_microseconds")
        )
        # /telemetry ingest (ISSUE 13): records shipped by daemons'
        # TelemetryShipper HTTP sinks.  Bounded — a chatty hollow fleet
        # must not grow the apiserver without bound; overflow evicts the
        # oldest and counts, mirroring the shipper's own drop posture.
        self.telemetry_records: deque = deque(maxlen=4096)
        self.telemetry_accepted = self.registry.register(Counter(
            "apiserver_telemetry_accepted_total",
            "telemetry records accepted at /telemetry"))
        # tolerated-failure visibility (ktpu-analyze CH702): best-effort
        # paths may fail, but never invisibly
        self.error_write_failures = self.registry.register(Counter(
            "apiserver_error_write_failures_total",
            "error responses that could not be written (client hung up)"))
        self.watch_held_frames = self.registry.register(Counter(
            "apiserver_watch_stream_held_frames_total",
            "watch frames written past their stream's deadline: they were "
            "queued when it passed, and the stream ended after them"))
        self.watch_serve_s = self.registry.register(Counter(
            "apiserver_watch_serve_seconds_total",
            "seconds the watch streams spent encoding and writing their "
            "frames and lines (a write waits for a slow reader)"))
        self.watch_encode_s = self.registry.register(Counter(
            "apiserver_watch_encode_seconds_total",
            "of those, the seconds spent encoding: this process's "
            "interpreter, which a request's handler waits for"))
        self.apiservice_status_failures = self.registry.register(Counter(
            "apiserver_apiservice_status_failures_total",
            "best-effort APIService availability updates that failed"))
        # the store runs in this process: its counters (frames packed,
        # replays, bind rows deferred, payloads built) leave by /metrics
        for m in DEFAULT_STORE_METRICS.registry.snapshot():
            self.registry.register(m)
        # overload control (ISSUE 17): an AdmissionThrottle (or anything
        # with .admit(resource, bodies) -> Optional[retry_after_s]) gates
        # the create paths at rung 3; None = always admit.  Distinct from
        # the validating admission chain (admission/framework.py): this
        # one answers 429 + Retry-After, not 400.
        self.admission_throttle = None
        self.admission_throttled = self.registry.register(Counter(
            "apiserver_admission_throttled_total",
            "create requests answered 429 + Retry-After by the overload "
            "admission gate (priority tier below the protected floor)"))
        self._telemetry_mu = threading.Lock()
        # the process's collector policy, where the daemon installed one
        # (apiserver/collector.py): told when an answer has been written,
        # and asked what its pauses cost a request.  None wherever this
        # server is embedded, and then nothing is done.
        self.collector = None
        handler = _make_handler(self)
        if tls is not None:
            # The handshake must run in the per-connection worker thread,
            # never the accept loop: a client that connects and trickles
            # (or withholds) its ClientHello would otherwise block accept()
            # and deny service to everyone.
            ctx = tls.context()

            class _TLSServer(ThreadingHTTPServer):
                def get_request(self):
                    sock, addr = self.socket.accept()
                    return ctx.wrap_socket(
                        sock, server_side=True, do_handshake_on_connect=False
                    ), addr

                def finish_request(self, request, client_address):
                    request.settimeout(10.0)
                    request.do_handshake()
                    request.settimeout(None)
                    super().finish_request(request, client_address)

                def handle_error(self, request, client_address):
                    import ssl as _ssl

                    exc = sys.exc_info()[1]
                    if isinstance(exc, (_ssl.SSLError, TimeoutError,
                                        ConnectionError, OSError)):
                        return  # dropped/garbage handshakes are routine
                    super().handle_error(request, client_address)

            self.httpd = _TLSServer((host, port), handler)
        else:
            self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_port
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        scheme = "https" if self.tls is not None else "http"
        return f"{scheme}://{self.httpd.server_address[0]}:{self.port}"

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        if self._thread:
            self._thread.join(timeout=5)

    def ingest_telemetry(self, records: list) -> int:
        """Append shipped records to the bounded ring; returns accepted
        count (deque eviction handles overflow silently — the shipper
        side counts its own drops)."""
        with self._telemetry_mu:
            self.telemetry_records.extend(records)
        self.telemetry_accepted.inc(len(records))
        return len(records)

    def telemetry_snapshot(self) -> list:
        with self._telemetry_mu:
            return list(self.telemetry_records)


def _make_handler(server: APIServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # the open tracing.Account of the request under way, where its
        # client asked for the parts (set per request by _route)
        _acct = None

        # -- plumbing ------------------------------------------------------
        def log_message(self, *args):
            pass

        def _send(self, code: int, obj) -> None:
            self._last_code = code
            acct = self._acct
            t_answer = time.perf_counter() if acct is not None else 0.0
            # content negotiation (reference protobuf negotiation via
            # Accept: application/vnd.kubernetes.protobuf)
            if BINARY_CONTENT_TYPE in self.headers.get("Accept", ""):
                from ..api import wire as binwire

                data = binwire.encode(obj)
                ctype = BINARY_CONTENT_TYPE
            else:
                data = json.dumps(obj).encode()
                ctype = "application/json"
            if acct is not None:
                acct.add("server.answer", t_answer)
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            for k, v in getattr(self, "_extra_headers", ()) or ():
                self.send_header(k, v)
            self._extra_headers = ()
            # this server's own account of the request, for the client's
            # remote.request span: from the moment its headers were in (the
            # body is read inside) to this write, and within that the store
            # call, where the verb made one.  A request that asked for its
            # parts (tracing.PARTS_HEADER: a traced client) also gets each
            # part where it ran, ``name;dur=<ms>;t=<perf_counter s at its
            # start>`` (the clock every process of the host shares), then
            # ``cpu``: this thread's CPU time in the request, ``watch``: the
            # watch streams' encode and write that ended in the same time,
            # and ``watch_encode``: the encode alone.  The rest get the
            # plain header: nothing is read for them.
            timing = (f"handle;dur="
                      f"{(time.perf_counter() - self._t_request) * 1e3:.3f}")
            if self._store_s is not None:
                timing += f", store;dur={self._store_s * 1e3:.3f}"
            if server.collector is not None:
                # collector pauses that fell inside this request
                timing += (f", gc;dur="
                           f"{(server.collector.pause_s - self._gc_s0) * 1e3:.3f}")
            if acct is not None:
                cpu = time.thread_time() - acct.cpu0
                watch = server.watch_serve_s.value - self._watch_s0[0]
                encode = server.watch_encode_s.value - self._watch_s0[1]
                timing += "".join(f", {name};dur={dur * 1e3:.6f};t={t0:.9f}"
                                  for name, t0, dur in acct.parts)
                timing += (f", cpu;dur={cpu * 1e3:.6f}"
                           f", watch;dur={watch * 1e3:.6f}"
                           f", watch_encode;dur={encode * 1e3:.6f}")
            self.send_header("Server-Timing", timing)
            self.end_headers()
            self.wfile.write(data)

        def _store(self, call, *args, **kwargs):
            """One store call of this request, timed for ``Server-Timing``.
            Asked for its parts, it is ``server.store_lock`` up to the
            moment the verb got hold of the store's lock (where the verb
            says: ``bind_many``, ``create_many``, ``list``) and
            ``server.store`` from then: the two sum to ``store_s``."""
            t0 = time.perf_counter()
            acct = self._acct
            if acct is not None:
                acct.held = None
            try:
                return call(*args, **kwargs)
            finally:
                t1 = time.perf_counter()
                self._store_s = (self._store_s or 0.0) + t1 - t0
                if acct is not None:
                    held = acct.held
                    if held is not None:
                        acct.add("server.store_lock", t0, held)
                    acct.add("server.store", t0 if held is None else held, t1)

        def _error(self, code: int, reason: str, message: str,
                   retry_after: Optional[float] = None) -> None:
            if retry_after is not None:
                # RFC 7231 delta-seconds; ceil so a sub-second hint never
                # rounds down to an immediate retry
                self._extra_headers = (
                    ("Retry-After", str(max(1, math.ceil(retry_after)))),)
            self._send(code, {"kind": "Status", "code": code, "reason": reason, "message": message})

        def _body(self) -> dict:
            # cached: the auth filters peek at the body (namespace for
            # authorization) before dispatch consumes it
            if not hasattr(self, "_cached_body"):
                acct = self._acct
                t_body = time.perf_counter() if acct is not None else 0.0
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length) if length else b""
                if acct is not None:
                    t_parse = time.perf_counter()
                    acct.add("server.body", t_body, t_parse)
                if raw and BINARY_CONTENT_TYPE in self.headers.get("Content-Type", ""):
                    from ..api import wire as binwire

                    self._cached_body = binwire.decode(raw)
                else:
                    self._cached_body = json.loads(raw) if raw else {}
                if acct is not None:
                    acct.add("server.parse", t_parse)
            return self._cached_body

        def _admission_gate(self, resource: str, bodies: list) -> bool:
            """Overload admission (ISSUE 17): rung-3 throttling of create
            paths.  Returns False when the request was throttled — the
            429 + Retry-After response is already written (RemoteStore
            classifies it retryable and honors the hint).  The fault
            point ``apiserver.admit`` injects a throttle surge here (drop
            mode; the fault's value is the Retry-After seconds)."""
            retry_after: Optional[float] = None
            fault = faults.hit("apiserver.admit", resource=resource,
                               verb="create", n=len(bodies))
            if fault is not None and fault.mode == "drop":
                retry_after = float(fault.value or 1.0)
            else:
                gate = server.admission_throttle
                if gate is not None:
                    retry_after = gate.admit(resource, bodies)
            if retry_after is None:
                return True
            server.admission_throttled.inc()
            tr = tracing.current()
            if tr is not None:
                tr.instant("apiserver.admit.throttle", resource=resource,
                           n=len(bodies), retry_after=retry_after)
            self._error(429, "TooManyRequests",
                        f"admission throttled under overload "
                        f"({len(bodies)} {resource})",
                        retry_after=retry_after)
            return False

        def _serve_telemetry_ingest(self) -> None:
            # the shipper POSTs ndjson (one JSON record per line); plain
            # JSON documents ({"items": [...]}, a bare list, or a single
            # record) are accepted so curl debugging stays easy
            length = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(length) if length else b""
            self._cached_body = {}  # raw body consumed here, not JSON
            ctype = self.headers.get("Content-Type", "")
            try:
                text = raw.decode()
                if "ndjson" in ctype:
                    records = [json.loads(line)
                               for line in text.splitlines() if line.strip()]
                else:
                    doc = json.loads(text) if text.strip() else []
                    if isinstance(doc, dict):
                        records = doc.get("items", [doc])
                    else:
                        records = list(doc)
            except (UnicodeDecodeError, ValueError) as e:
                return self._error(400, "BadRequest",
                                   f"undecodable telemetry payload: {e}")
            accepted = server.ingest_telemetry(records)
            self._send(200, {"kind": "Status", "code": 200,
                             "accepted": accepted})

        def _request_info(self, method: str):
            """(verb, resource, namespace, name) — the request-info filter
            (reference ``endpoints/filters/requestinfo``)."""
            url = urlparse(self.path)
            q = parse_qs(url.query)
            parts = [p for p in url.path.split("/") if p]
            verb = {"POST": "create", "PUT": "update", "DELETE": "delete",
                    "PATCH": "patch"}.get(method, "get")
            resource, ns, name = "", "", ""
            if parts and parts[0] == "apis" and len(parts) >= 2:
                # aggregated APIs: authorize/audit on "<group>/<resource>"
                # so RBAC rules can scope aggregated access per group
                group = parts[1]
                rest = parts[3:] if len(parts) >= 3 else []  # skip version
                if rest and rest[0] == "namespaces" and len(rest) >= 3:
                    ns = rest[1]
                    resource = f"{group}/{rest[2]}"
                    name = rest[3] if len(rest) >= 4 else ""
                else:
                    resource = f"{group}/{rest[0]}" if rest else group
                    name = rest[1] if len(rest) >= 2 else ""
                if method == "GET":
                    if q.get("watch", ["false"])[0] == "true":
                        verb = "watch"
                    elif not name:
                        verb = "list"
                return verb, resource, ns, name
            if len(parts) >= 3 and parts[0] == "api" and parts[1] == "v1":
                rest = parts[2:]
                if len(rest) == 1:
                    resource = rest[0]
                    if method == "GET":
                        verb = "watch" if q.get("watch", ["false"])[0] == "true" else "list"
                        ns = q.get("namespace", [""])[0] or ""
                    elif method == "POST":
                        # namespace rides in the body on collection creates
                        try:
                            ns = (self._body().get("metadata") or {}).get("namespace", "")
                        except Exception:
                            ns = ""
                elif rest[0] == "namespaces" and len(rest) >= 4:
                    ns = "" if rest[1] == "-" else rest[1]
                    resource = rest[2]
                    name = rest[3]
                    if len(rest) == 5 and rest[4] == "binding":
                        verb = "bind"
                    elif len(rest) == 5 and rest[4] in ("exec", "attach", "cp"):
                        # their own verb: create-pods rights must not imply
                        # command execution / container IO (pods/exec,
                        # pods/attach, pods/cp subresources — the reference
                        # gates attach and cp-over-exec the same way)
                        verb = "exec"
                    elif len(rest) == 5 and rest[4] == "eviction":
                        # distinct verb so create-pods rights do not imply
                        # eviction (reference treats pods/eviction as its
                        # own subresource)
                        verb = "evict"
                elif (rest[0] == "nodes" and len(rest) >= 3
                        and rest[2] == "proxy"):
                    # node proxy: RBAC scopes it as the "nodes/proxy"
                    # subresource (reference node proxy authz) — reading a
                    # node object must not imply reaching its kubelet
                    resource = "nodes/proxy"
                    name = rest[1]
            return verb, resource, ns, name

        def _auth_filters(self, method: str) -> bool:
            """authentication → audit(RequestReceived) → authorization.
            Returns False (response already sent) on 401/403."""
            self._user = None
            self._audit_user = None  # reset per request (keep-alive reuses
            # this handler instance across requests on one connection)
            if server.authenticator is not None:
                user = None
                if server.tls is not None and server.tls.client_ca:
                    # the reference's x509 path: the TLS handshake already
                    # verified the chain; map the peer subject to identity
                    from ..auth.authn import X509CertificateAuthenticator

                    peercert = getattr(self.connection, "getpeercert", lambda: None)()
                    user = X509CertificateAuthenticator.from_peercert(peercert)
                if user is None:
                    user = server.authenticator.authenticate(self.headers)
                if user is None:
                    self._error(401, "Unauthorized", "invalid or missing credentials")
                    return False
                # impersonation filter (endpoints/filters/impersonation.go):
                # Impersonate-User requires the "impersonate" verb on
                # "users" for the REAL identity; on success the request
                # proceeds AS the impersonated identity
                target = self.headers.get("Impersonate-User", "")
                if target:
                    from ..auth import ALLOW, AuthzAttributes, UserInfo

                    if server.authorizer is None:
                        self._error(403, "Forbidden",
                                    "impersonation requires an authorizer")
                        return False
                    # repeated headers (kubectl sends one per --as-group)
                    groups = [g.strip()
                              for raw in (self.headers.get_all("Impersonate-Group")
                                          or [])
                              for g in raw.split(",") if g.strip()]
                    # EVERY impersonated identity part is authorized for
                    # the REAL user: users AND each group — otherwise
                    # impersonate-users rights escalate to arbitrary
                    # group membership (impersonation.go checks each)
                    checks = [("users", target)] + [("groups", g) for g in groups]
                    for resource_name, name in checks:
                        decision, reason = server.authorizer.authorize(
                            AuthzAttributes(user=user, verb="impersonate",
                                            resource=resource_name, name=name))
                        if decision != ALLOW:
                            self._error(
                                403, "Forbidden",
                                f"cannot impersonate {resource_name[:-1]} "
                                f"{name!r}: {reason}")
                            return False
                    # the AUDIT trail must keep the real actor: the
                    # reference annotates impersonated requests with the
                    # original user (filters/impersonation.go + audit)
                    self._audit_user = f"{target} (impersonated-by {user.name})"
                    user = UserInfo(name=target, groups=groups)
                self._user = user
            verb, resource, ns, name = self._request_info(method)
            if server.auditor is not None:
                server.auditor.record(
                    "RequestReceived",
                    getattr(self, "_audit_user", None)
                    or (self._user.name if self._user else ""),
                    verb, resource, ns, name,
                )
            if urlparse(self.path).path in ("/api", "/api/v1", "/apis",
                                            "/openapi/v2", "/swagger.json",
                                            SSAR_PATH):
                # discovery and self-subject access review are granted to
                # every AUTHENTICATED identity (the reference's
                # system:discovery / system:basic-user bindings) — clients
                # must enumerate resources and ask "can I?" before any RBAC
                # rule can name them
                return True
            if server.authorizer is not None:
                from ..auth import ALLOW, ANONYMOUS, AuthzAttributes

                # no authenticator configured -> authorize as anonymous
                # (fail closed, never skip an explicit authorizer)
                user = self._user if self._user is not None else ANONYMOUS
                decision, reason = server.authorizer.authorize(AuthzAttributes(
                    user=user, verb=verb, resource=resource,
                    namespace=ns, name=name, path=urlparse(self.path).path,
                ))
                if decision != ALLOW:
                    self._error(403, "Forbidden", reason)
                    return False
            # per-request identity for admission plugins (thread-local on
            # AdmittedStore, so concurrent handler threads don't race)
            if self._user is not None and hasattr(server.store, "user"):
                server.store.user = self._user.name
            return True

        # -- dispatch ------------------------------------------------------
        def _route(self, method: str) -> None:
            start = self._t_request = time.perf_counter()
            self._store_s = None
            collector = server.collector
            self._gc_s0 = collector.pause_s if collector is not None else 0.0
            self._acct = None
            server.request_count.inc()
            self._last_code = 0
            acquired = False
            # long-running requests (watches) are EXEMPT, as in
            # maxinflight.go's longRunningRequestCheck: N held watch
            # streams must never starve short requests into steady 429.
            # Parse the query PROPERLY — a substring match would let any
            # client opt out via ?foo=watch=true
            is_long_running = parse_qs(urlparse(self.path or "").query).get(
                "watch", ["false"])[0] == "true"
            if server._inflight is not None and not is_long_running:
                acquired = server._inflight.acquire(blocking=False)
                if not acquired:
                    # shed load NOW (maxinflight.go): queueing under
                    # overload just converts overload into latency
                    return self._error(429, "TooManyRequests",
                                       "server overloaded (max in flight)")
            # the request's parts, only where the client asked for them
            acct = None
            if self.headers.get(tracing.PARTS_HEADER):
                acct = self._acct = tracing.open_account()
                self._watch_s0 = (server.watch_serve_s.value,
                                  server.watch_encode_s.value)
            try:
                if not self._auth_filters(method):
                    return
                self._dispatch(method)
            except AdmissionDenied as e:
                self._error(403, "Forbidden", str(e))
            except NotFoundError as e:
                self._error(404, "NotFound", str(e))
            except AlreadyExistsError as e:
                self._error(409, "AlreadyExists", str(e))
            except ConflictError as e:
                self._error(409, "Conflict", str(e))
            except ExpiredRevisionError as e:
                self._error(410, "Expired", str(e))
            except BrokenPipeError:
                pass
            except Exception as e:  # panic recovery filter
                logger.exception("handler panic")
                try:
                    self._error(500, "InternalError", str(e))
                except Exception:  # noqa: BLE001 - client gone mid-error
                    # the 500 is already logged above; the write failing
                    # means the peer hung up — count it, don't re-panic
                    server.error_write_failures.inc()
            finally:
                if acct is not None:
                    tracing.close_account()
                if acquired:
                    server._inflight.release()
                server.request_latency.observe((time.perf_counter() - start) * 1e6)
                if server.auditor is not None:
                    verb, resource, ns, name = self._request_info(method)
                    audit_user = getattr(self, "_audit_user", None) or (
                        self._user.name if getattr(self, "_user", None) else "")
                    server.auditor.record(
                        "ResponseComplete",
                        audit_user,
                        verb, resource, ns, name, code=self._last_code,
                    )
                if collector is not None:
                    # the answer's bytes are out: the txn boundary at which
                    # the daemon's collector may take its pass
                    collector.after_request()

        def do_GET(self):
            self._route("GET")

        def do_POST(self):
            self._route("POST")

        def do_PUT(self):
            self._route("PUT")

        def do_PATCH(self):
            self._route("PATCH")

        def do_DELETE(self):
            self._route("DELETE")

        def _apply_list_selectors(self, items, q):
            """labelSelector / fieldSelector on LIST (reference
            ``ListOptions``; kubelets list pods with
            ``fieldSelector=spec.nodeName=X`` so a 5k-node fleet doesn't
            pull the whole cluster per node).  Returns filtered items, or
            None after writing a 400."""
            label_sel = q.get("labelSelector", [None])[0]
            field_sel = q.get("fieldSelector", [None])[0]
            if label_sel:
                from ..api.selectors import parse_selector_string

                try:
                    sel = parse_selector_string(label_sel)
                except ValueError as e:
                    self._error(400, "BadRequest", f"bad labelSelector: {e}")
                    return None
                items = [i for i in items
                         if sel.matches((i.get("metadata") or {}).get("labels") or {})]
            if field_sel:
                import re as _re

                # the fields the reference's own callers select on
                getters = {
                    "spec.nodeName": lambda i: (i.get("spec") or {}).get("nodeName") or "",
                    "metadata.name": lambda i: (i.get("metadata") or {}).get("name"),
                    "metadata.namespace": lambda i: (i.get("metadata") or {}).get("namespace"),
                    "status.phase": lambda i: (i.get("status") or {}).get("phase") or "",
                }
                for clause in field_sel.split(","):
                    m = _re.fullmatch(r"([^=!]+?)\s*(==|!=|=)\s*(.*)", clause.strip())
                    if m is None:
                        self._error(400, "BadRequest",
                                    f"bad fieldSelector clause {clause!r}")
                        return None
                    key, op, value = m.group(1), m.group(2), m.group(3)
                    get = getters.get(key)
                    if get is None:
                        self._error(400, "BadRequest",
                                    f"unsupported fieldSelector {key!r}")
                        return None
                    if op == "!=":
                        items = [i for i in items if get(i) != value]
                    else:  # '=' and '==' are the same operator
                        items = [i for i in items if get(i) == value]
            return items

        def _compile_selectors(self, q):
            """Parse label/field selectors ONCE into a per-object
            predicate for the watch stream (the LIST path keeps
            :meth:`_apply_list_selectors`, which filters a materialized
            list).  Returns (pred-or-None, error-or-None): pred=None
            with no error means no selectors; an error string means a
            malformed selector the caller must 400."""
            label_sel = q.get("labelSelector", [None])[0]
            field_sel = q.get("fieldSelector", [None])[0]
            tests = []
            if label_sel:
                from ..api.selectors import parse_selector_string

                try:
                    sel = parse_selector_string(label_sel)
                except ValueError as e:
                    return None, f"bad labelSelector: {e}"
                tests.append(lambda i, _s=sel: _s.matches(
                    (i.get("metadata") or {}).get("labels") or {}))
            if field_sel:
                import re as _re

                getters = {
                    "spec.nodeName": lambda i: (i.get("spec") or {}).get("nodeName") or "",
                    "metadata.name": lambda i: (i.get("metadata") or {}).get("name"),
                    "metadata.namespace": lambda i: (i.get("metadata") or {}).get("namespace"),
                    "status.phase": lambda i: (i.get("status") or {}).get("phase") or "",
                }
                for clause in field_sel.split(","):
                    m = _re.fullmatch(r"([^=!]+?)\s*(==|!=|=)\s*(.*)",
                                      clause.strip())
                    if m is None:
                        return None, f"bad fieldSelector clause {clause!r}"
                    key, op, value = m.group(1), m.group(2), m.group(3)
                    get = getters.get(key)
                    if get is None:
                        return None, f"unsupported fieldSelector {key!r}"
                    if op == "!=":
                        tests.append(lambda i, _g=get, _v=value: _g(i) != _v)
                    else:  # '=' and '==' are the same operator
                        tests.append(lambda i, _g=get, _v=value: _g(i) == _v)
            if not tests:
                return None, None
            if len(tests) == 1:
                return tests[0], None
            return (lambda i, _t=tuple(tests): all(t(i) for t in _t)), None

        def _serve_patch(self, kind: str, ns: str, name: str) -> None:
            """The PATCH verb (reference ``handlers/rest.go`` PatchResource):
            patch type negotiated via Content-Type, applied server-side
            under the CAS retry loop so concurrent writers never lose."""
            from ..api.patch import CONTENT_TYPES, apply_patch
            from ..api.scheme import convert_to_internal

            ctype = (self.headers.get("Content-Type") or "").split(";")[0].strip()
            patch_type = CONTENT_TYPES.get(ctype)
            if patch_type is None:
                # a mislabeled body must not be silently merge-patched
                return self._error(415, "UnsupportedMediaType",
                                   f"patch content type {ctype!r}; want one of "
                                   f"{sorted(CONTENT_TYPES)}")
            patch_doc = self._body()

            def _mutate(cur):
                gv = (patch_doc.get("apiVersion", "")
                      if isinstance(patch_doc, dict) else "")
                if gv:
                    # a VERSIONED patch applies in wire space: spoke-encode
                    # the stored hub object, merge, decode back — nested
                    # wire keys land where the conversion puts them, never
                    # as dead keys on the hub form (the reference patches
                    # the versioned object for the same reason)
                    from ..api.scheme import convert_from_internal

                    wire = convert_from_internal(cur, gv)
                    patched = apply_patch(wire, patch_doc, patch_type)
                    return convert_to_internal(patched)
                return apply_patch(cur, patch_doc, patch_type)

            try:
                out = self._store(server.store.guaranteed_update,
                                  kind, ns, name, _mutate)
            except NotFoundError:
                raise
            except (KeyError, IndexError, ValueError, TypeError) as e:
                return self._error(422, "Invalid", f"cannot apply patch: {e}")
            return self._send(200, out)

        def _serve_ssar(self) -> None:
            """SelfSubjectAccessReview: "can the CALLING user do X?"
            evaluated against the live authorizer (reference
            ``pkg/registry/authorization/selfsubjectaccessreview``).  The
            caller's authenticated identity is authoritative — the spec
            carries only the action, never the user."""
            attrs = (self._body().get("spec") or {}).get("resourceAttributes") or {}
            if server.authorizer is None:
                return self._send(201, {"status": {"allowed": True,
                                                   "reason": "no authorizer configured"}})
            from ..auth import ALLOW, ANONYMOUS, AuthzAttributes

            user = self._user if self._user is not None else ANONYMOUS
            decision, reason = server.authorizer.authorize(AuthzAttributes(
                user=user,
                verb=attrs.get("verb", ""),
                resource=attrs.get("resource", ""),
                namespace=attrs.get("namespace", ""),
                name=attrs.get("name", ""),
            ))
            return self._send(201, {"status": {"allowed": decision == ALLOW,
                                               "reason": reason}})

        def _serve_discovery(self, path: str) -> None:
            """Discovery endpoints (reference ``endpoints/discovery``):
            /api lists versions, /api/v1 the live resource list (built
            from the one type registry, so CRD kinds appear the moment
            they establish), /apis the aggregated groups."""
            from ..api.types import CLUSTER_SCOPED_KINDS, KIND_PLURALS

            if path == "/api":
                return self._send(200, {"kind": "APIVersions", "versions": ["v1"]})
            if path == "/api/v1":
                resources = [
                    {"name": plural, "kind": kind,
                     "namespaced": kind not in CLUSTER_SCOPED_KINDS}
                    for kind, plural in sorted(KIND_PLURALS.items())
                ]
                return self._send(200, {"kind": "APIResourceList",
                                        "groupVersion": "v1",
                                        "resources": resources})
            by_group: dict = {}
            for svc in server.store.list("APIService", "")[0]:
                spec = svc.get("spec") or {}
                g = spec.get("group", "")
                if not g:
                    continue
                avail = bool((svc.get("status") or {}).get("available"))
                by_group[g] = by_group.get(g, False) or avail
            groups = [{"name": g, "available": a} for g, a in sorted(by_group.items())]
            return self._send(200, {"kind": "APIGroupList", "groups": groups})

        def _resolve_pod_kubelet(self, ns: str, name: str, q):
            """Shared pod-subresource resolution: pod -> node -> kubelet
            endpoint + validated container, with CONNECT admission
            (reference exec/attach admission — DenyEscalatingExec runs
            here).  Returns (kubelet_url, container, node_name) or None
            after writing the error."""
            try:
                pod = server.store.get("Pod", ns, name)
            except NotFoundError:
                self._error(404, "NotFound", f"pod {ns}/{name}")
                return None
            chain = getattr(server.store, "chain", None)
            if chain is not None:
                from ..admission.framework import Attributes

                try:
                    chain.run(Attributes(operation="CONNECT", kind="Pod",
                                         namespace=ns, name=name,
                                         old_obj=pod,
                                         user=getattr(server.store, "user", "")))
                except AdmissionDenied as e:
                    self._error(403, "Forbidden", str(e))
                    return None
            node_name = (pod.get("spec") or {}).get("nodeName", "")
            if not node_name:
                self._error(400, "BadRequest", "pod is not scheduled yet")
                return None
            try:
                node = server.store.get("Node", "", node_name)
            except NotFoundError:
                self._error(502, "BadGateway", f"node {node_name} not found")
                return None
            kubelet_url = (node.get("status") or {}).get("kubeletURL", "")
            if not kubelet_url:
                self._error(502, "BadGateway",
                            f"node {node_name} exposes no kubelet endpoint")
                return None
            containers = (pod.get("spec") or {}).get("containers") or []
            known = [c.get("name", "") for c in containers]
            container = q.get("container", [None])[0] or (known[0] if known else "")
            if container not in known:
                # also blocks path traversal into other kubelet endpoints
                self._error(400, "BadRequest",
                            f"container {container!r} not in pod {ns}/{name}")
                return None
            return kubelet_url, container, node_name

        def _proxy_pod_log(self, ns: str, name: str, q) -> None:
            """pod/log subresource: resolve the pod's node, proxy to that
            node's kubelet read API (reference ``registry/core/pod/rest``
            LogREST -> kubelet :10250 /containerLogs)."""
            import urllib.request as _rq

            resolved = self._resolve_pod_kubelet(ns, name, q)
            if resolved is None:
                return
            kubelet_url, container, _ = resolved
            target = f"{kubelet_url}/containerLogs/{ns}/{name}/{container}"
            if "tailLines" in q:
                tail = q["tailLines"][0]
                if not tail.isdigit():
                    return self._error(400, "BadRequest", "tailLines must be an integer")
                target += f"?tailLines={tail}"
            try:
                with _rq.urlopen(target, timeout=10) as resp:
                    data = resp.read()
            except Exception as e:
                return self._error(502, "BadGateway", f"kubelet log fetch failed: {e}")
            self._last_code = 200
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _proxy_pod_simple(self, ns: str, name: str, q, endpoint: str,
                              what: str) -> None:
            """GET-style pod subresource proxied verbatim to the owning
            kubelet (attach — reference ``pod/rest`` AttachREST)."""
            import urllib.error
            import urllib.request as _rq

            resolved = self._resolve_pod_kubelet(ns, name, q)
            if resolved is None:
                return
            kubelet_url, container, _ = resolved
            try:
                with _rq.urlopen(f"{kubelet_url}/{endpoint}/{ns}/{name}/{container}",
                                 timeout=10) as resp:
                    data = resp.read()
            except urllib.error.HTTPError as e:
                return self._error(e.code, "KubeletError", e.read().decode()[:200])
            except Exception as e:
                return self._error(502, "BadGateway", f"kubelet {what} failed: {e}")
            self._last_code = 200
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _proxy_pod_cp(self, ns: str, name: str, q, method: str) -> None:
            """pods/cp subresource: file read (GET) / write (PUT) proxied
            to the kubelet's container file API, write-authenticated with
            the cluster exec token (the reference streams tar over exec —
            same capability, same credential class)."""
            import urllib.error
            import urllib.parse as _up
            import urllib.request as _rq

            from ..auth.authn import kubelet_exec_token

            resolved = self._resolve_pod_kubelet(ns, name, q)
            if resolved is None:
                return
            kubelet_url, container, node_name = resolved
            path = q.get("path", [""])[0]
            if not path:
                return self._error(400, "BadRequest", "path required")
            target = (f"{kubelet_url}/cp/{ns}/{name}/{container}"
                      f"?path={_up.quote(path)}")
            # both directions carry the exec credential: cp READ is an
            # exec-class capability (file exfiltration) on the kubelet too
            auth = {"Authorization": f"Bearer {kubelet_exec_token(node_name)}"}
            if method == "GET":
                req = _rq.Request(target, headers=auth)
            elif method == "PUT":
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length) if length else b""
                self._cached_body = {}  # raw body consumed here, not JSON
                req = _rq.Request(target, data=raw, method="PUT", headers=auth)
            else:
                return self._error(405, "MethodNotAllowed", method)
            try:
                with _rq.urlopen(req, timeout=30) as resp:
                    data = resp.read()
            except urllib.error.HTTPError as e:
                return self._error(e.code, "KubeletError", e.read().decode()[:200])
            except Exception as e:
                return self._error(502, "BadGateway", f"kubelet cp failed: {e}")
            self._last_code = 200
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _proxy_pod_exec(self, ns: str, name: str, q) -> None:
            """pods/exec subresource: resolve node, forward the command to
            the kubelet's exec endpoint (the SPDY exec path's capability
            over JSON), authenticated with the cluster-key exec token."""
            import urllib.error
            import urllib.request as _rq

            from ..auth.authn import kubelet_exec_token

            resolved = self._resolve_pod_kubelet(ns, name, q)
            if resolved is None:
                return
            kubelet_url, container, node_name = resolved
            command = self._body().get("command")
            if not isinstance(command, list) or not command:
                return self._error(400, "BadRequest", "command (list) required")
            body = json.dumps({"command": command}).encode()
            req = _rq.Request(
                f"{kubelet_url}/exec/{ns}/{name}/{container}", data=body,
                headers={"Content-Type": "application/json",
                         "Authorization": f"Bearer {kubelet_exec_token(node_name)}"},
                method="POST",
            )
            try:
                with _rq.urlopen(req, timeout=30) as resp:
                    data = resp.read()
            except urllib.error.HTTPError as e:
                # the kubelet's own verdict passes through (e.g. 400/404)
                return self._error(e.code, "KubeletError", e.read().decode()[:200])
            except Exception as e:
                return self._error(502, "BadGateway", f"kubelet exec failed: {e}")
            self._last_code = 200
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _proxy_node(self, name: str, subpath: str, query: str = "") -> None:
            """GET proxied verbatim (path + query) to the node's kubelet
            read API — over the node's tunnel when a tunneler holds one
            (pkg/master/tunneler: nodes may not be directly routable)."""
            import urllib.error
            import urllib.request as _rq

            try:
                node = server.store.get("Node", "", name)
            except NotFoundError:
                return self._error(404, "NotFound", f'node "{name}" not found')
            if query:
                subpath = f"{subpath}?{query}"
            tun = server.tunneler
            if tun is not None and tun.has(name):
                if not tun.healthy(name):
                    return self._error(
                        502, "BadGateway", f'tunnel to node "{name}" is down')
                import http.client as _http_client

                try:
                    status, data, ctype = tun.request(name, "GET", f"/{subpath}")
                except (OSError, _http_client.HTTPException) as e:
                    # a kubelet dying mid-response (IncompleteRead /
                    # BadStatusLine) is a gateway failure, not a handler
                    # crash — same 502 contract as the direct-dial path
                    return self._error(502, "BadGateway",
                                       f"tunnel request failed: {e}")
                if status != 200:
                    return self._error(status, "KubeletError",
                                       data.decode(errors="replace")[:200])
                self._last_code = 200
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                return
            kubelet_url = (node.get("status") or {}).get("kubeletURL") or ""
            if not kubelet_url:
                return self._error(
                    502, "BadGateway", f'node "{name}" has no kubelet endpoint')
            try:
                with _rq.urlopen(f"{kubelet_url}/{subpath}", timeout=10) as resp:
                    data = resp.read()
                    ctype = resp.headers.get("Content-Type", "application/json")
            except urllib.error.HTTPError as e:
                return self._error(e.code, "KubeletError", e.read().decode()[:200])
            except Exception as e:  # noqa: BLE001
                return self._error(502, "BadGateway", f"kubelet proxy failed: {e}")
            self._last_code = 200
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        # -- chunked framing shared by watch serving and the proxy ---------
        def _write_chunk(self, data: bytes) -> None:
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        def _write_watched(self, encode, *args) -> None:
            """One frame or line of a watch stream, its encode and its
            write counted (per frame or line, never per row)."""
            t0 = time.perf_counter()
            data = encode(*args)
            t1 = time.perf_counter()
            server.watch_encode_s.inc(t1 - t0)
            self._write_chunk(data)
            server.watch_serve_s.inc(time.perf_counter() - t0)

        def _end_chunks(self) -> None:
            self.wfile.write(b"0\r\n\r\n")

        def _lookup_apiservice(self, group: str):
            """By convention name==group, else fall back to spec.group (the
            reference names objects '<version>.<group>')."""
            from ..store.store import NotFoundError as _NF

            try:
                return server.store.get("APIService", "", group)
            except _NF:
                pass
            for svc in server.store.list("APIService", "")[0]:
                if (svc.get("spec") or {}).get("group") == group:
                    return svc
            return None

        def _mark_available(self, svc: dict, available: bool) -> None:
            """Best-effort availability condition (the reference's
            aggregator availability controller, folded into the proxy's
            own observations)."""
            name = (svc.get("metadata") or {}).get("name", "")
            if bool((svc.get("status") or {}).get("available")) == available:
                return
            try:
                def _set(d: dict) -> dict:
                    d.setdefault("status", {})["available"] = available
                    return d

                server.store.guaranteed_update("APIService", "", name, _set)
            except Exception as e:  # noqa: BLE001 - status is best-effort
                # availability is advisory (the next proxy attempt
                # re-observes it); a write that keeps failing should
                # still be visible somewhere
                logger.debug("APIService %s availability update failed: %s",
                             name, e)
                server.apiservice_status_failures.inc()

        def _proxy_aggregated(self, method: str, group: str, url) -> None:
            """The kube-aggregator seam (``staging/src/k8s.io/
            kube-aggregator`` proxy handler): ``/apis/<group>/...`` routes
            to the APIService-registered backend.

            Identity crosses as the front-proxy headers X-Remote-User /
            X-Remote-Group — the client's own Authorization credential is
            NEVER forwarded (forwarding it would hand bearer tokens to
            whoever registered the APIService; the reference's aggregator
            re-asserts identity the same way)."""
            import urllib.error
            import urllib.request as _rq

            svc = self._lookup_apiservice(group)
            if svc is None:
                return self._error(404, "NotFound", f"no APIService for group {group!r}")
            base = (svc.get("spec") or {}).get("url", "")
            if not base:
                return self._error(503, "ServiceUnavailable", f"APIService {group} has no backend")
            q = parse_qs(url.query)
            is_watch = q.get("watch", ["false"])[0] == "true"
            target = base.rstrip("/") + url.path + (f"?{url.query}" if url.query else "")
            body = None
            length = int(self.headers.get("Content-Length", 0))
            if length:
                body = self.rfile.read(length)
            req = _rq.Request(target, data=body, method=method)
            for h in ("Content-Type", "Accept"):
                if self.headers.get(h):
                    req.add_header(h, self.headers[h])
            user = getattr(self, "_user", None)
            if user is not None and getattr(user, "name", ""):
                req.add_header("X-Remote-User", user.name)
                if user.groups:
                    req.add_header("X-Remote-Group", ",".join(user.groups))
            try:
                # watches hold the socket open; plain requests fail fast
                resp = _rq.urlopen(req, timeout=300 if is_watch else 30)
            except urllib.error.HTTPError as e:
                data = e.read()
                self._last_code = e.code
                self.send_response(e.code)
                self.send_header("Content-Type", e.headers.get("Content-Type", "application/json"))
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                return
            except Exception as e:
                self._mark_available(svc, False)
                return self._error(502, "BadGateway", f"APIService {group} backend error: {e}")
            self._mark_available(svc, True)
            with resp:
                self._last_code = resp.status
                self.send_response(resp.status)
                chunked = resp.headers.get("Transfer-Encoding", "") == "chunked"
                ctype = resp.headers.get("Content-Type", "application/json")
                self.send_header("Content-Type", ctype)
                # once the response starts, failures may only close the
                # stream — a second status line would corrupt the body
                try:
                    if chunked:
                        self.send_header("Transfer-Encoding", "chunked")
                        self.end_headers()
                        while True:
                            chunk = resp.read1(65536) if hasattr(resp, "read1") else resp.read(65536)
                            if not chunk:
                                break
                            self._write_chunk(chunk)
                        self._end_chunks()
                    else:
                        data = resp.read()
                        self.send_header("Content-Length", str(len(data)))
                        self.end_headers()
                        self.wfile.write(data)
                except (BrokenPipeError, ConnectionResetError, OSError):
                    self.close_connection = True

        def _dispatch(self, method: str) -> None:
            url = urlparse(self.path)
            q = parse_qs(url.query)
            parts = [p for p in url.path.split("/") if p]

            if url.path == "/healthz":
                return self._send(200, {"status": "ok"})
            if url.path == "/telemetry":
                # off-box shipper ingest (ISSUE 13): POST accepts ndjson
                # (one record per line, the shipper's wire shape) or a
                # JSON {"items": [...]} document; GET snapshots the ring
                if method == "POST":
                    return self._serve_telemetry_ingest()
                if method == "GET":
                    records = server.telemetry_snapshot()
                    return self._send(200, {"kind": "TelemetryRecordList",
                                            "count": len(records),
                                            "items": records})
                return self._error(405, "MethodNotAllowed", method)
            # the shared daemon debug surface (utils/health.py): /metrics,
            # /debug/traces, /debug/flightrecorder, /debug/timeseries —
            # identical routes on every component, the apiserver included
            shared = handle_debug_path(url.path, server.registry)
            if shared is not None:
                if method != "GET":
                    return self._error(405, "MethodNotAllowed", method)
                code, payload = shared
                if not isinstance(payload, str):
                    return self._send(code, payload)
                text = payload.encode()
                self._last_code = code
                self.send_response(code)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(text)))
                self.end_headers()
                self.wfile.write(text)
                return
            if url.path in ("/api", "/api/v1", "/apis"):
                if method != "GET":
                    return self._error(405, "MethodNotAllowed", method)
                return self._serve_discovery(url.path)
            if url.path in ("/openapi/v2", "/swagger.json"):
                # the published schema (routes/openapi.go; the era also
                # served /swagger.json) — regenerated per request so CRD
                # kinds appear the moment they establish
                if method != "GET":
                    return self._error(405, "MethodNotAllowed", method)
                from .openapi import build_openapi

                return self._send(200, build_openapi())
            if url.path == "/version":
                from .. import __version__

                return self._send(200, {"version": __version__})
            if url.path == "/api/v1/bindings:batch" and method == "POST":
                body = self._body()
                acct = self._acct
                t_check = time.perf_counter() if acct is not None else 0.0
                keys, node_names = ((body.get("keys"), body.get("nodeNames"))
                                    if type(body) is dict else (None, None))
                bad = _bind_columns_fault(keys, node_names)
                if acct is not None:
                    # the columns checked as the verb takes them: still
                    # parsing
                    acct.add("server.parse", t_check)
                if bad is not None:
                    return self._error(400, "BadRequest", bad)
                errors = self._store(server.store.bind_many, keys, node_names)
                return self._send(200, {"errors": errors})
            # batch create: POST /api/v1/{resource}:batch {"items": [...]}
            # — one store txn (Store.create_many: one lock/WAL/fanout
            # pass); per-item failures come back as null slots, the rest
            # commit (the wire twin of the typed client's create_many)
            if (url.path.startswith("/api/v1/") and url.path.endswith(":batch")
                    and method == "POST"):
                res = url.path[len("/api/v1/"):-len(":batch")]
                kind = _kind_for(res)
                if kind is None:
                    return self._error(404, "NotFound", f"unknown resource {res}")
                if not self._admission_gate(res, self._body().get("items", [])):
                    return
                from ..api.scheme import convert_to_internal

                body = self._body().get("items", [])
                acct = self._acct
                t_items = time.perf_counter() if acct is not None else 0.0
                items = [convert_to_internal(d) for d in body]
                if kind in CLUSTER_SCOPED:
                    for d in items:
                        d.setdefault("metadata", {})["namespace"] = ""
                if acct is not None:
                    acct.add("server.parse", t_items)
                created = self._store(server.store.create_many, kind, items)
                return self._send(201, {"items": created})

            if url.path == SSAR_PATH and method == "POST":
                return self._serve_ssar()
            if parts and parts[0] == "apis" and len(parts) >= 2:
                return self._proxy_aggregated(method, parts[1], url)
            if len(parts) < 3 or parts[0] != "api" or parts[1] != "v1":
                return self._error(404, "NotFound", f"no route for {url.path}")
            parts = parts[2:]

            # node proxy: /api/v1/nodes/{name}/proxy/<kubelet path> — the
            # metrics-scrape path (the reference's apiserver node proxy,
            # which heapster/the HPA metrics client ride to reach
            # kubelet /stats/summary without node-network access)
            if (len(parts) >= 4 and parts[0] == "nodes"
                    and parts[2] == "proxy" and method == "GET"):
                return self._proxy_node(parts[1], "/".join(parts[3:]),
                                        url.query)

            # collection routes: /api/v1/{resource}
            if len(parts) == 1:
                kind = _kind_for(parts[0])
                if kind is None:
                    return self._error(404, "NotFound", f"unknown resource {parts[0]}")
                if method == "GET":
                    if q.get("watch", ["false"])[0] == "true":
                        return self._serve_watch(kind, q)
                    ns = q.get("namespace", [None])[0]
                    # columnar wire fast-path (ISSUE 4): the packed batch
                    # LIST (pods only, no selector filtering — selector
                    # queries take the classic item path below)
                    if (q.get("columnar", ["0"])[0] in ("1", "true")
                            and "labelSelector" not in q
                            and "fieldSelector" not in q):
                        lc = getattr(server.store, "list_columns", None)
                        batch = lc(kind, ns) if lc is not None else None
                        if batch is not None:
                            return self._send(200, batch.to_wire())
                    items, rev = self._store(server.store.list, kind, ns)
                    items = self._apply_list_selectors(items, q)
                    if items is None:
                        return  # error already written
                    return self._send(200, {"items": items, "resourceVersion": rev})
                if method == "POST":
                    if not self._admission_gate(parts[0], [self._body()]):
                        return
                    from ..api.scheme import convert_to_internal

                    body = convert_to_internal(self._body())
                    if kind in CLUSTER_SCOPED:
                        body.setdefault("metadata", {})["namespace"] = ""
                    return self._send(
                        201, self._store(server.store.create, kind, body))
                return self._error(405, "MethodNotAllowed", method)

            # namespaced collection: /api/v1/namespaces/{ns}/{resource}
            # (the canonical path the OpenAPI doc advertises; equivalent
            # to /api/v1/{resource}?namespace={ns})
            if parts[0] == "namespaces" and len(parts) == 3:
                ns = "" if parts[1] == "-" else parts[1]
                kind = _kind_for(parts[2])
                if kind is None:
                    return self._error(404, "NotFound", f"unknown resource {parts[2]}")
                if method == "GET":
                    if q.get("watch", ["false"])[0] == "true":
                        return self._serve_watch(kind, q)
                    items, rev = self._store(server.store.list, kind, ns)
                    items = self._apply_list_selectors(items, q)
                    if items is None:
                        return  # error already written
                    return self._send(200, {"items": items, "resourceVersion": rev})
                if method == "POST":
                    if not self._admission_gate(parts[2], [self._body()]):
                        return
                    from ..api.scheme import convert_to_internal

                    body = convert_to_internal(self._body())
                    meta = body.setdefault("metadata", {})
                    meta["namespace"] = "" if kind in CLUSTER_SCOPED else ns
                    return self._send(
                        201, self._store(server.store.create, kind, body))
                return self._error(405, "MethodNotAllowed", method)

            # object routes: /api/v1/namespaces/{ns}/{resource}/{name}[/binding]
            if parts[0] == "namespaces" and len(parts) in (4, 5):
                ns = "" if parts[1] == "-" else parts[1]
                kind = _kind_for(parts[2])
                name = parts[3]
                if kind is None:
                    return self._error(404, "NotFound", f"unknown resource {parts[2]}")
                if len(parts) == 5:
                    if parts[4] == "binding" and kind == "Pod" and method == "POST":
                        body = self._body()
                        errors = self._store(server.store.bind_many,
                                             [f"{ns}/{name}" if ns else name],
                                             [body["nodeName"]])
                        if errors[0] is not None:
                            return self._error(409, "Conflict", errors[0])
                        return self._send(201, {"status": "bound"})
                    if parts[4] == "log" and kind == "Pod" and method == "GET":
                        return self._proxy_pod_log(ns, name, q)
                    if parts[4] == "exec" and kind == "Pod" and method == "POST":
                        return self._proxy_pod_exec(ns, name, q)
                    if parts[4] == "attach" and kind == "Pod" and method == "GET":
                        return self._proxy_pod_simple(
                            ns, name, q, "attach", "attach stream")
                    if parts[4] == "cp" and kind == "Pod":
                        return self._proxy_pod_cp(ns, name, q, method)
                    if parts[4] == "eviction" and kind == "Pod" and method == "POST":
                        from ..client.clientset import Clientset, EvictionDisallowed

                        try:
                            Clientset(server.store).pods.evict(name, ns)
                        except EvictionDisallowed as e:
                            return self._error(429, "TooManyRequests", str(e))
                        return self._send(201, {"status": "evicted"})
                    return self._error(404, "NotFound", f"unknown subresource {parts[4]}")
                if method == "GET":
                    return self._send(
                        200, self._store(server.store.get, kind, ns, name))
                if method == "PUT":
                    from ..api.scheme import convert_to_internal

                    obj = convert_to_internal(self._body())
                    cas = q.get("cas", ["true"])[0] == "true"
                    expect = None if cas else 0
                    out = self._store(server.store.update, kind, obj,
                                      expect_rev=expect or None)
                    return self._send(200, out)
                if method == "PATCH":
                    return self._serve_patch(kind, ns, name)
                if method == "DELETE":
                    return self._send(
                        200, self._store(server.store.delete, kind, ns, name))
                return self._error(405, "MethodNotAllowed", method)

            return self._error(404, "NotFound", f"no route for {url.path}")

        # -- watch streaming (handlers/rest.go:276 watch upgrade) ----------
        def _serve_watch(self, kind: str, q) -> None:
            from ..store.frames import FRAME, event_wire_bytes

            from_rev = None
            if "resourceVersion" in q:
                from_rev = int(q["resourceVersion"][0])
            timeout = float(q.get("timeoutSeconds", ["30"])[0])
            # selectors compile ONCE per stream into a predicate (the
            # old shape reparsed them per event per client); a malformed
            # selector 400s BEFORE the stream starts
            pred, sel_err = self._compile_selectors(q)
            if sel_err is not None:
                return self._error(400, "BadRequest", sel_err)
            # column-packed frame delivery (?frames=1): a correlated
            # batch txn as JSON lines of at most frames.FRAME_MAX_ROWS
            # events each instead of N lines.  Selector watches
            # get frames too (ISSUE 19): the predicate filters at the
            # COLUMN level and a matching sub-frame is re-packed before
            # encoding — per-event JSON lines only for clients that
            # never opted into frames
            want_frames = q.get("frames", ["0"])[0] in ("1", "true")
            watch = server.store.watch(kind, from_revision=from_rev,
                                       frames=want_frames)
            try:
                self._last_code = 200
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                import time as _t

                deadline = _t.monotonic() + timeout

                def queued():
                    while (left := deadline - _t.monotonic()) > 0:
                        ev = watch.get(timeout=min(0.5, left))
                        if ev is not None:
                            yield ev
                    # past the deadline the stream still writes what was
                    # on the queue when it passed, that many items and no
                    # more (a stream under continuous load ends too): a
                    # dropped frame costs the client a connection, the
                    # store a walk of its log under its lock and a
                    # second encode
                    for _ in range(watch.qsize()):
                        ev = watch.get(timeout=0)
                        if ev is None:
                            return
                        if ev.type == FRAME:
                            server.watch_held_frames.inc()
                        yield ev

                for ev in queued():
                    if ev.type == FRAME:
                        frame = ev
                        if pred is not None:
                            # the LIST-then-WATCH contract at the column
                            # level: keep matching entries, re-pack, and
                            # stream the sub-frame (None = no entry
                            # matched; the client's fence advances on
                            # its next matching delivery)
                            frame = ev.select([
                                i for i, o in enumerate(ev.objects)
                                if o is not None and pred(o)])
                            if frame is None:
                                continue
                        # encoded ONCE per frame per revision and shared
                        # across every streaming client (frames are
                        # shared-immutable across watcher queues)
                        self._write_watched(frame.wire_bytes)
                        continue
                    if pred is not None and not pred(ev.object):
                        # a selector silently ignored on watch would
                        # re-create the full-cluster fan-out the
                        # selector exists to avoid
                        continue
                    self._write_watched(event_wire_bytes, ev)
                self._end_chunks()
            except (BrokenPipeError, ConnectionResetError):
                pass
            finally:
                watch.stop()

    return Handler
