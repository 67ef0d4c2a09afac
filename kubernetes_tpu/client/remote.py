"""Remote store: the Store interface spoken over HTTP to an APIServer.

This is the transport seam promised in ``clientset.py``: a
``Clientset(RemoteStore(url))`` behaves identically to an in-process one —
informers, controllers, schedulers, and kubelets run unchanged against a
network apiserver (reference: ``client-go/rest`` under the generated
clientsets).  Watches consume the chunked JSON-lines stream and reconnect
from the last seen revision (reflector semantics, ``reflector.go:239``).

Failure handling (the part ``client-go/rest`` calls request.go retry +
``reflector.go`` relist):

- every request classifies its failure **honestly**: transport errors,
  5xx, and 429 are retryable (exponential backoff + seeded jitter, budget
  ``max_retries``); 4xx is fatal and maps to the typed store errors;
- a watch stream that breaks reconnects from the last seen revision with
  its own backoff; a resume refused with **410 Gone** cannot be healed by
  the stream itself — the watch emits a :data:`~..store.store.WATCH_GAP`
  sentinel and terminates, and the informer above relists (reflector.go's
  "too old resource version" → full LIST);
- shutdown closes the half-open HTTP response so the reader thread never
  leaks a socket past ``stop()``.

Every failure path is countable (``utils.metrics.ClientMetrics``) and
injectable (fault points ``remote.request`` / ``remote.watch.stream``) —
the fault matrix in tests/test_faults.py drives each one deterministically.
"""

from __future__ import annotations

import http.client
import json
import logging
import queue as queue_mod
import threading
import time
import urllib.error
import urllib.request
from typing import Callable, Optional

from .. import faults
from ..store import frames as frames_mod
from ..store.store import (
    AlreadyExistsError,
    ConflictError,
    ExpiredRevisionError,
    NotFoundError,
    WATCH_GAP,
    WatchEvent,
    object_key,
)
from ..utils import tracing
from ..utils.metrics import ClientMetrics

logger = logging.getLogger("kubernetes_tpu.client.remote")


class RemoteError(Exception):
    pass


class ForbiddenError(RemoteError):
    """HTTP 403 — authorization or admission said no.  A distinct type so
    callers (kubectl) surface 'Error from server (Forbidden)' instead of
    crashing on a generic RemoteError."""


class RetryExhaustedError(RemoteError):
    """A retryable failure outlived the retry budget.  Carries the last
    underlying error so callers can still see WHAT kept failing."""


# HTTP statuses worth re-trying: the server never started (or refused to
# start) the work.  Everything else in 4xx means the request itself is
# wrong — repeating it verbatim cannot succeed and hides real bugs.
RETRYABLE_STATUS = frozenset({429, 500, 502, 503, 504})


def _raise_for_status(body: dict) -> None:
    if body.get("kind") != "Status":
        return
    code, msg = body.get("code"), body.get("message", "")
    if code == 404:
        raise NotFoundError(msg)
    if code == 403:
        raise ForbiddenError(msg)
    if code == 409:
        if body.get("reason") == "AlreadyExists":
            raise AlreadyExistsError(msg)
        raise ConflictError(msg)
    if code == 410:
        raise ExpiredRevisionError(msg)
    raise RemoteError(f"{code}: {msg}")


def _parse_retry_after(headers) -> Optional[float]:
    """Server backoff hint from a 429/503 response (ISSUE 17: the
    apiserver's overload admission gate sends one).  Delta-seconds form
    only (RFC 7231 §7.1.3) — our servers send integers; the HTTP-date
    form is ignored.  None = no usable hint."""
    if headers is None:
        return None
    value = headers.get("Retry-After")
    if not value:
        return None
    try:
        return max(0.0, float(value))
    except (TypeError, ValueError):
        return None


_TIMING_ATTRS = {"handle": "server_s", "store": "store_s", "gc": "gc_s",
                 "cpu": "server_cpu_s", "watch": "watch_s",
                 "watch_encode": "watch_encode_s"}


def _server_timing(value: Optional[str]) -> tuple[dict, list]:
    """``Server-Timing: handle;dur=<ms>, store;dur=<ms>, gc;dur=<ms>`` (the
    apiserver's own account of one request) as the ``remote.request``
    span's ``server_s`` / ``store_s`` / ``gc_s``.  A part the server did
    not send — an older server, a verb that makes no store call, a server
    embedded where no daemon owns the collector — stays absent, never 0.

    A traced client asks for the request's parts (``tracing.PARTS_HEADER``)
    and gets, after those, ``server.<part>;dur=<ms>;t=<s>`` for each part
    where it ran (``t``: ``time.perf_counter`` in the server's process, the
    host's one monotonic clock), and ``cpu`` / ``watch`` /
    ``watch_encode``: the handler thread's CPU time, and the watch streams'
    encode and write, and encode alone, in the same stretch, as
    ``server_cpu_s`` / ``watch_s`` / ``watch_encode_s``.  Returns (attrs,
    [(part, t0, dur_s)])."""
    out: dict = {}
    parts: list = []
    for field in (value or "").split(","):
        name, _, rest = field.strip().partition(";dur=")
        dur, _, t = rest.partition(";t=")
        try:
            if t:
                parts.append((name, float(t), float(dur) / 1e3))
            elif name in _TIMING_ATTRS:
                out[_TIMING_ATTRS[name]] = float(dur) / 1e3
        except ValueError:
            pass
    return out, parts


class RemoteWatch:
    """Chunked-stream consumer with auto-reconnect from the last revision.

    Error classification in the read loop (``_run``):

    - **410 Gone** on resume: the server compacted past our bookmark; no
      reconnect can recover the lost deltas.  Emit ``WATCH_GAP`` and end
      the stream — the informer relists and builds a fresh watch.
    - **mid-frame failure** (a ``?frames=1`` line whose JSON parsed but
      whose columns are broken — length mismatch, corrupt revisions, or
      the injected ``phase=frame`` fault): the frame's events are lost as
      a UNIT and the bookmark cannot be trusted past it — same contract
      as 410: ``WATCH_GAP`` + stream end, the informer relists.  Never a
      silent partial apply, never a dead loop.
    - **stopped**: clean shutdown; the half-open response is closed by
      ``stop()`` so the blocking read unblocks instead of leaking.
    - anything else (connection reset, timeout, truncated JSON line, 5xx
      on reconnect): transient — count it, back off exponentially, and
      reconnect from ``resourceVersion=last_seen`` (reflector.go:239).
      The backoff resets once events flow again.
    """

    def __init__(self, base_url: str, kind: str, from_revision: Optional[int],
                 opener, resource: str, metrics: Optional[ClientMetrics] = None,
                 min_backoff: float = 0.05, max_backoff: float = 2.0,
                 sleep: Callable[[float], None] = time.sleep,
                 frames: bool = False,
                 label_selector: Optional[str] = None,
                 field_selector: Optional[str] = None):
        self._base = base_url
        self._resource = resource
        self._opener = opener
        # request column-packed frame delivery (?frames=1).  A pre-frame
        # server ignores the parameter and streams per-event lines — the
        # read loop handles both shapes, so this is a pure opt-in.
        self._frames = frames
        # server-side stream filtering (the LIST-then-WATCH selector
        # contract); with frames=True the server re-packs matching
        # sub-frames at the column level (ISSUE 19) instead of falling
        # back to per-event lines
        self._label_selector = label_selector
        self._field_selector = field_selector
        self.metrics = metrics or ClientMetrics()
        self._min_backoff = min_backoff
        self._max_backoff = max_backoff
        self._sleep = sleep
        self._queue: "queue_mod.Queue[Optional[WatchEvent]]" = queue_mod.Queue()
        self._stopped = threading.Event()
        self._last_rev = from_revision
        # the in-flight HTTP response: owned by the watch thread, closed
        # by stop() from the caller's thread — both sides under _resp_mu
        self._resp_mu = threading.Lock()
        self._resp = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _open_stream(self):
        url = f"{self._base}/api/v1/{self._resource}?watch=true&timeoutSeconds=5"
        if self._frames:
            url += "&frames=1"
        if self._label_selector:
            from urllib.parse import quote

            url += f"&labelSelector={quote(self._label_selector)}"
        if self._field_selector:
            from urllib.parse import quote

            url += f"&fieldSelector={quote(self._field_selector)}"
        if self._last_rev is not None:
            url += f"&resourceVersion={self._last_rev}"
        tr = tracing.current()
        # the (re)connect is the slow, failure-prone edge of the stream —
        # one span per dial, nothing per event
        with (tr.span("remote.watch.connect", cat="client",
                      resource=self._resource,
                      resume=self._last_rev is not None,
                      from_revision=self._last_rev)
              if tr is not None else tracing.NULL_SPAN):
            faults.hit("remote.watch.stream", phase="connect",
                       resource=self._resource)
            return self._opener(url)

    def _run(self) -> None:
        backoff = self._min_backoff
        while not self._stopped.is_set():
            resp = None
            try:
                resp = self._open_stream()
                with self._resp_mu:
                    if self._stopped.is_set():
                        resp.close()
                        return
                    self._resp = resp
                for raw in resp:
                    if self._stopped.is_set():
                        return
                    line = raw.strip()
                    if not line:
                        continue
                    faults.hit("remote.watch.stream", phase="event",
                               resource=self._resource)
                    self.metrics.ingest_bytes.inc(len(line))
                    d = json.loads(line)
                    if d.get("type") == frames_mod.FRAME:
                        try:
                            faults.hit("remote.watch.stream", phase="frame",
                                       resource=self._resource)
                            frame = frames_mod.WatchFrame.from_wire(d)
                            # resourceVersion fence per frame: a replayed
                            # or reordered frame at-or-below the bookmark
                            # must not rewind it (its events were seen)
                            if (self._last_rev is not None
                                    and frame.revision <= self._last_rev):
                                continue
                        except Exception as e:  # noqa: BLE001 - classified
                            # mid-frame failure: the frame's events are
                            # lost as a unit and the bookmark is no longer
                            # trustworthy — gap + relist, like a 410
                            logger.warning(
                                "watch %s: undecodable frame (%s: %s) — "
                                "emitting gap for relist", self._resource,
                                type(e).__name__, e)
                            self.metrics.watch_errors.inc()
                            self.metrics.watch_gaps.inc()
                            tr = tracing.current()
                            if tr is not None:
                                tr.instant("remote.watch.gap",
                                           resource=self._resource,
                                           cause="bad-frame")
                            self._queue.put(WatchEvent(
                                WATCH_GAP, "", "", self._last_rev or 0, {}))
                            return
                        self._last_rev = frame.revision
                        backoff = self._min_backoff
                        self._queue.put(frame)
                        continue
                    ev = WatchEvent(
                        d["type"], d["kind"], d["key"], d["revision"], d["object"]
                    )
                    self._last_rev = ev.revision
                    backoff = self._min_backoff  # healthy stream: reset
                    self._queue.put(ev)
                # clean server-side timeout (timeoutSeconds elapsed):
                # immediate resume from the bookmark, not an error
            except Exception as e:
                if self._stopped.is_set():
                    return
                self.metrics.watch_errors.inc()
                if isinstance(e, urllib.error.HTTPError) and e.code == 410:
                    # resume refused: the server compacted past our
                    # bookmark.  The stream cannot self-heal — escalate
                    # to a relist through the informer and end.
                    logger.warning(
                        "watch %s: revision %s too old (410) — emitting "
                        "gap for relist", self._resource, self._last_rev)
                    self.metrics.watch_gaps.inc()
                    tr = tracing.current()
                    if tr is not None:
                        tr.instant("remote.watch.gap",
                                   resource=self._resource, cause="410")
                    self._queue.put(WatchEvent(
                        WATCH_GAP, "", "", self._last_rev or 0, {}))
                    return
                # a throttled reconnect (429/503) carries the server's
                # Retry-After hint: honor it — never shorter than our own
                # backoff, clamped to max_backoff (ISSUE 17)
                sleep_s = backoff
                if (isinstance(e, urllib.error.HTTPError)
                        and e.code in (429, 503)):
                    hint = _parse_retry_after(e.headers)
                    if hint is not None:
                        sleep_s = min(max(hint, backoff), self._max_backoff)
                        self.metrics.retry_after_honored.inc()
                # warn once on the transition into the broken state; the
                # retries of an outage that persists log at debug (a dead
                # server would otherwise emit a warning every backoff)
                log = (logger.warning if backoff == self._min_backoff
                       else logger.debug)
                log("watch %s: transient %s: %s — reconnecting from "
                    "revision %s in %.2fs", self._resource,
                    type(e).__name__, e, self._last_rev, sleep_s)
                self._sleep(sleep_s)
                backoff = min(backoff * 2, self._max_backoff)
                self.metrics.watch_reconnects.inc()
            finally:
                if resp is not None:
                    with self._resp_mu:
                        if self._resp is resp:
                            self._resp = None
                    try:
                        resp.close()
                    except Exception:  # noqa: BLE001 - close is best-effort
                        # the stream is being torn down either way; count
                        # it so a systematically failing close is visible
                        self.metrics.watch_close_errors.inc()

    def get(self, timeout: Optional[float] = None) -> Optional[WatchEvent]:
        try:
            return self._queue.get(timeout=timeout)
        except queue_mod.Empty:
            return None

    def __iter__(self):
        while True:
            ev = self._queue.get()
            if ev is None:
                return
            yield ev

    def stop(self) -> None:
        self._stopped.set()
        # unblock the reader: close the half-open response NOW instead of
        # leaking it until the server-side timeout fires
        with self._resp_mu:
            resp, self._resp = self._resp, None
        if resp is not None:
            try:
                resp.close()
            except Exception:  # noqa: BLE001 - close is best-effort
                self.metrics.watch_close_errors.inc()
        self._queue.put(None)


class RemoteStore:
    """Store-interface adapter over the REST API."""

    def __init__(self, base_url: str, token: Optional[str] = None, timeout: float = 10.0,
                 ca_file: Optional[str] = None, client_cert: Optional[str] = None,
                 client_key: Optional[str] = None, binary: bool = False,
                 max_retries: int = 3, retry_backoff: float = 0.05,
                 retry_backoff_max: float = 2.0,
                 retry_seed: Optional[int] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 metrics: Optional[ClientMetrics] = None):
        """``ca_file`` pins the server CA for https:// servers;
        ``client_cert``/``client_key`` present an x509 client identity
        (reference kubeconfig certificate-authority / client-certificate).
        ``binary=True`` negotiates the compact binary wire form for
        resource bodies (reference protobuf content type).

        ``max_retries`` re-issues of a request after a retryable failure
        (5xx/429 for every verb; transport errors only when the request
        provably never ran — see ``_transport_retry_safe``), with
        exponential backoff from ``retry_backoff`` capped at
        ``retry_backoff_max`` and jittered per instance.  ``retry_seed``
        defaults to fresh entropy — a shared fixed seed would march every
        client through the SAME jitter sequence, re-synchronizing the
        thundering herd the jitter exists to break; pass a seed only in
        deterministic tests."""
        import random

        self.base_url = base_url.rstrip("/")
        self.token = token
        self.timeout = timeout
        self.binary = binary
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self.retry_backoff_max = retry_backoff_max
        self._retry_rng = random.Random(retry_seed)
        self._sleep = sleep
        self.metrics = metrics or ClientMetrics()
        self._ssl_ctx = None
        if base_url.startswith("https://"):
            import ipaddress
            import ssl
            from urllib.parse import urlparse as _urlparse

            self._ssl_ctx = ssl.create_default_context(cafile=ca_file)
            if ca_file:
                try:
                    ipaddress.ip_address(_urlparse(base_url).hostname or "")
                    # IP-addressed clusters with a PINNED CA: certs rarely
                    # carry IP SANs; chain verification against the pinned
                    # CA still applies.  Without a pinned CA, hostname
                    # verification stays on — any public cert would
                    # otherwise pass.  DNS-named servers always verify.
                    self._ssl_ctx.check_hostname = False
                except ValueError:
                    pass
            if client_cert:
                self._ssl_ctx.load_cert_chain(client_cert, client_key)

    # -- http --------------------------------------------------------------
    def _open(self, url: str):
        req = urllib.request.Request(url)
        if self.token:
            req.add_header("Authorization", f"Bearer {self.token}")
        return urllib.request.urlopen(req, timeout=self.timeout, context=self._ssl_ctx)

    @staticmethod
    def _transport_retry_safe(method: str, e: BaseException) -> bool:
        """May this transport failure be retried without double-running
        the request?  Idempotent verbs (GET/HEAD): always.  Everything
        else only when the error proves the request never reached the
        server (connection refused) — a reset/timeout mid-POST may have
        committed server-side, and re-sending would turn one create into
        two (surfacing as a spurious AlreadyExists/Conflict to the
        caller).  client-go's retry gate draws the same line."""
        if method in ("GET", "HEAD"):
            return True
        reason = getattr(e, "reason", e)
        return isinstance(reason, ConnectionRefusedError)

    def _retry_delay(self, attempt: int,
                     retry_after: Optional[float] = None) -> float:
        """Exponential backoff with jitter in [0.5x, 1.5x) of the nominal
        step — deterministic per client (seeded RNG).  When the server
        sent a ``Retry-After`` hint (429/503), the hint replaces the
        exponential step — clamped to ``retry_backoff_max`` — with the
        SAME seeded jitter applied, so throttled herds still
        desynchronize instead of re-converging on the hint."""
        if retry_after is not None:
            nominal = min(max(retry_after, 0.0), self.retry_backoff_max)
        else:
            nominal = min(self.retry_backoff * (2 ** attempt), self.retry_backoff_max)
        return nominal * (0.5 + self._retry_rng.random())

    def _request_with_retries(self, send: Callable[[], "object"], method: str,
                              path: str):
        """Run ``send`` (one HTTP attempt) under the retry policy.

        Returns the live response object on success.  Raises the mapped
        typed error on a fatal classification, :class:`RetryExhaustedError`
        when the budget runs out.  ``send`` may raise HTTPError — a
        retryable status re-enters the loop, anything else is handed back
        to the caller for body decoding (the Status body carries the real
        reason: AlreadyExists vs Conflict, etc.)."""
        last_err: Optional[BaseException] = None
        retry_after: Optional[float] = None
        for attempt in range(self.max_retries + 1):
            if attempt > 0:
                tr = tracing.current()
                if tr is not None:
                    # retries are rare and each one is latency the caller
                    # ate — worth a point event; the happy path pays only
                    # the faults seam
                    tr.instant("remote.request.retry", method=method,
                               path=path, attempt=attempt)
                self._sleep(self._retry_delay(attempt - 1, retry_after))
                if retry_after is not None:
                    self.metrics.retry_after_honored.inc()
                retry_after = None
                self.metrics.remote_retries.inc()
            try:
                faults.hit("remote.request", method=method, path=path,
                           attempt=attempt)
                return send()
            except urllib.error.HTTPError as e:
                if e.code in RETRYABLE_STATUS:
                    # the throttle hint must be read BEFORE the drain
                    # below invalidates the response object
                    if e.code in (429, 503):
                        retry_after = _parse_retry_after(e.headers)
                    # drain + close: keep-alive sockets with pending bodies
                    # cannot be reused, and the retry opens a fresh one
                    try:
                        e.read()
                        e.close()
                    except Exception:  # noqa: BLE001 - drain is best-effort
                        # the retry opens a fresh connection regardless;
                        # count the failed drain so a pool that stops
                        # reusing sockets has a visible cause
                        self.metrics.remote_drain_errors.inc()
                    last_err = e
                    logger.warning("%s %s: retryable HTTP %d (attempt %d/%d)",
                                   method, path, e.code, attempt + 1,
                                   self.max_retries + 1)
                    continue
                # fatal 4xx: the caller decodes the Status body into the
                # typed error — retrying a malformed/forbidden/conflicting
                # request verbatim can never succeed
                self.metrics.remote_fatal.inc()
                raise
            except (urllib.error.URLError, TimeoutError, ConnectionError,
                    http.client.HTTPException, OSError) as e:
                if not self._transport_retry_safe(method, e):
                    # a non-idempotent request that MAY have committed:
                    # re-sending could double-run it — surface the
                    # transport error honestly instead
                    self.metrics.remote_fatal.inc()
                    raise
                last_err = e
                logger.warning("%s %s: transport error %s: %s (attempt %d/%d)",
                               method, path, type(e).__name__, e, attempt + 1,
                               self.max_retries + 1)
                continue
        self.metrics.remote_retry_exhausted.inc()
        raise RetryExhaustedError(
            f"{method} {path} failed after {self.max_retries + 1} attempts: "
            f"{type(last_err).__name__}: {last_err}")

    def _call(self, method: str, path: str, body=None,
              content_type: Optional[str] = None,
              items: Optional[int] = None) -> dict:
        """One resource request.  With tracing on it is one
        ``remote.request`` span: what went out and came back, this side's
        encode and decode as the children ``client.encode`` /
        ``client.decode``, and — from the server's ``Server-Timing``
        header — how long the apiserver (``server_s``) and the store
        inside it (``store_s``) took, and what the daemon's collector
        stalled of it (``gc_s``), so a child process's time reaches this
        trace.  It also asks for the server's parts, which become
        ``cat="server"`` children at the times they ran (see
        :func:`_server_timing`).  ``items``: how many objects a batch
        verb carries."""
        tr = tracing.current()
        with (tr.span("remote.request", cat="client", method=method,
                      path=path)
              if tr is not None else tracing.NULL_SPAN) as sp:
            if items is not None:
                sp.set(items=items)
            t_encode = tr.clock() if tr is not None else 0.0
            if content_type is not None:
                # explicit content type (PATCH negotiation) always sends
                # JSON bodies; binary Accept still applies to the response
                data = json.dumps(body).encode() if body is not None else None
                headers = {"Content-Type": content_type}
                if self.binary:
                    from ..api import wire as binwire

                    headers["Accept"] = binwire.CONTENT_TYPE
            elif self.binary:
                from ..api import wire as binwire

                data = binwire.encode(body) if body is not None else None
                headers = {"Content-Type": binwire.CONTENT_TYPE,
                           "Accept": binwire.CONTENT_TYPE}
            else:
                data = json.dumps(body).encode() if body is not None else None
                headers = {"Content-Type": "application/json"}
            if tr is not None:
                tr.record(sp, "client.encode", t_encode, tr.clock(),
                          cat="client")
                sp.set(bytes_out=len(data) if data is not None else 0)
                headers[tracing.PARTS_HEADER] = "1"
            attempts = 0

            def send():
                nonlocal attempts
                attempts += 1
                sp.set(attempts=attempts)
                req = urllib.request.Request(
                    f"{self.base_url}{path}", data=data, method=method,
                    headers=dict(headers),
                )
                if self.token:
                    req.add_header("Authorization", f"Bearer {self.token}")
                return urllib.request.urlopen(req, timeout=self.timeout,
                                              context=self._ssl_ctx)

            try:
                with self._request_with_retries(send, method, path) as resp:
                    out = self._decode(resp, tr, sp)
            except urllib.error.HTTPError as e:
                out = self._decode(e, tr, sp)
            _raise_for_status(out)
            return out

    @staticmethod
    def _decode(resp, tr=None, sp=None) -> dict:
        from ..api import wire as binwire

        raw = resp.read()
        t_decode = tr.clock() if tr is not None else 0.0
        if binwire.CONTENT_TYPE in (resp.headers.get("Content-Type") or ""):
            out = binwire.decode(raw)
        else:
            out = json.loads(raw.decode())
        if tr is not None:
            t_done = tr.clock()
            timing, parts = _server_timing(resp.headers.get("Server-Timing"))
            sp.set(status=resp.status, bytes_in=len(raw), **timing)
            if tr.clock is time.perf_counter:
                # the server read the clock this tracer reads
                for name, t0, dur in parts:
                    tr.record(sp, name, t0, t0 + dur, cat="server")
            tr.record(sp, "client.decode", t_decode, t_done, cat="client")
        return out

    def raw(self, method: str, path: str, body=None,
            timeout: Optional[float] = None) -> bytes:
        """Raw request carrying the store's credential and TLS context —
        the path for non-resource endpoints (discovery, /version,
        /healthz, subresource streams) so callers never hand-roll a
        urlopen that would drop the token or the pinned CA.  ``body`` may
        be a dict (JSON-encoded) or raw bytes (forwarded verbatim, e.g.
        file payloads through kubectl proxy).  Same retry policy as the
        resource verbs."""
        if isinstance(body, (bytes, bytearray)):
            data = bytes(body)
            headers = {}
        else:
            data = json.dumps(body).encode() if body is not None else None
            headers = {"Content-Type": "application/json"} if data else {}

        def send():
            req = urllib.request.Request(
                f"{self.base_url}{path}", data=data, method=method,
                headers=dict(headers))
            if self.token:
                req.add_header("Authorization", f"Bearer {self.token}")
            return urllib.request.urlopen(
                req, timeout=timeout or self.timeout, context=self._ssl_ctx)

        with self._request_with_retries(send, method, path) as resp:
            return resp.read()

    @staticmethod
    def _ns_path(namespace: str) -> str:
        return namespace if namespace else "-"

    @staticmethod
    def _resource(kind: str) -> str:
        from ..api.types import KIND_PLURALS

        plural = KIND_PLURALS.get(kind)
        if plural is None:
            raise RemoteError(f"unknown kind {kind}")
        return plural

    # -- Store interface ---------------------------------------------------
    def create(self, kind: str, obj: dict) -> dict:
        return self._call("POST", f"/api/v1/{self._resource(kind)}", obj)

    def create_many(self, kind: str, objs: list[dict]) -> list:
        """Batch create over the wire (``POST /{resource}:batch``): one
        request, one server-side store txn.  Mirrors Store.create_many's
        per-item best-effort contract (failed slots come back null).
        ONLY a 404 (NotFoundError: a pre-batch server has no such route)
        falls back to per-item creates — every other failure
        (RetryExhausted, Forbidden, 5xx) propagates: re-sending N
        individual requests against a failing or refusing server would
        amplify load and mask the real error."""
        try:
            out = self._call(
                "POST", f"/api/v1/{self._resource(kind)}:batch",
                {"items": objs}, items=len(objs))
            return out.get("items", [])
        except NotFoundError:
            results = []
            for obj in objs:
                try:
                    results.append(self.create(kind, obj))
                except Exception:  # noqa: BLE001 - per-item best effort
                    results.append(None)
            return results

    def get(self, kind: str, namespace: str, name: str) -> dict:
        return self._call(
            "GET",
            f"/api/v1/namespaces/{self._ns_path(namespace)}/{self._resource(kind)}/{name}",
        )

    def list(self, kind: str, namespace: Optional[str] = None,
             label_selector: Optional[str] = None,
             field_selector: Optional[str] = None) -> tuple[list[dict], int]:
        from urllib.parse import quote

        path = f"/api/v1/{self._resource(kind)}"
        params = []
        if namespace is not None:
            params.append(f"namespace={quote(namespace)}")
        if label_selector:
            params.append(f"labelSelector={quote(label_selector)}")
        if field_selector:
            params.append(f"fieldSelector={quote(field_selector)}")
        if params:
            path += "?" + "&".join(params)
        out = self._call("GET", path)
        return out["items"], int(out["resourceVersion"])

    def list_columns(self, kind: str = "Pod",
                     namespace: Optional[str] = None):
        """Columnar LIST over the wire (``?columnar=1``): the server ships
        the packed batch payload (raw views + identity columns) in one
        response; the derived numeric/signature columns are rebuilt
        client-side.  Returns None when the server (or kind) lacks
        columnar support — callers fall back to :meth:`list`."""
        from ..store.columns import COLUMN_BATCH_KINDS

        batch_cls = COLUMN_BATCH_KINDS.get(kind)
        if batch_cls is None:
            return None
        from urllib.parse import quote

        path = f"/api/v1/{self._resource(kind)}?columnar=1"
        if namespace is not None:
            path += f"&namespace={quote(namespace)}"
        try:
            out = self._call("GET", path)
        except RemoteError:
            return None
        if out.get("kind") != f"{kind}ColumnBatch":
            return None  # pre-columnar server answered with plain items
        return batch_cls.from_wire(out)

    def patch(self, kind: str, namespace: str, name: str, patch,
              patch_type: str = "merge") -> dict:
        """Server-side PATCH (the reference's PATCH verb): the server
        applies the patch under its CAS loop — no read-modify-write round
        trips from the client."""
        from ..api.patch import CONTENT_TYPES

        ctype = next((c for c, t in CONTENT_TYPES.items() if t == patch_type),
                     "application/merge-patch+json")
        ns = self._ns_path(namespace)
        return self._call(
            "PATCH",
            f"/api/v1/namespaces/{ns}/{self._resource(kind)}/{name}",
            body=patch, content_type=ctype)

    def update(self, kind: str, obj: dict, expect_rev: Optional[int] = None, _trusted: bool = False) -> dict:
        meta = obj.get("metadata") or {}
        ns = self._ns_path(meta.get("namespace", "default"))
        name = meta.get("name", "")
        if expect_rev is not None:
            obj = dict(obj)
            obj["metadata"] = dict(meta)
            obj["metadata"]["resourceVersion"] = expect_rev
        return self._call(
            "PUT", f"/api/v1/namespaces/{ns}/{self._resource(kind)}/{name}", obj
        )

    def guaranteed_update(self, kind: str, namespace: str, name: str, mutate: Callable[[dict], dict]) -> dict:
        while True:
            cur = self.get(kind, namespace, name)
            rev = int(cur["metadata"]["resourceVersion"])
            new = mutate(cur)
            try:
                return self.update(kind, new, expect_rev=rev)
            except ConflictError:
                continue

    def delete(self, kind: str, namespace: str, name: str, expect_rev: Optional[int] = None) -> dict:
        return self._call(
            "DELETE",
            f"/api/v1/namespaces/{self._ns_path(namespace)}/{self._resource(kind)}/{name}",
        )

    def bind_many(self, keys: list[str],
                  node_names: list[str]) -> list[Optional[str]]:
        """The store's ``bind_many`` over the wire: the two columns go out
        as they are, ``{"keys": [...], "nodeNames": [...]}``."""
        out = self._call("POST", "/api/v1/bindings:batch",
                         {"keys": keys, "nodeNames": node_names},
                         items=len(keys))
        return out["errors"]

    def watch(self, kind: Optional[str] = None, from_revision: Optional[int] = None,
              frames: bool = False,
              label_selector: Optional[str] = None,
              field_selector: Optional[str] = None) -> RemoteWatch:
        if kind is None:
            raise RemoteError("remote watch requires a kind")
        return RemoteWatch(self.base_url, kind, from_revision, self._open,
                           self._resource(kind), metrics=self.metrics,
                           sleep=self._sleep, frames=frames,
                           label_selector=label_selector,
                           field_selector=field_selector)
