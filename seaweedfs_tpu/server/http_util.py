"""Tiny HTTP helpers shared by the daemons (stdlib-only)."""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Optional

import urllib.parse
import urllib.request

from seaweedfs_tpu.util import glog
from seaweedfs_tpu.util.locks import make_lock
from seaweedfs_tpu.util.racecheck import instrument
from seaweedfs_tpu.util.throttler import (
    GOVERNOR,
    INTERNAL_HEADER,
    INTERNAL_TENANT,
    classify_tenant,
)

from ..stats import trace as _trace
from ..util import deadline as _deadline

# Flipped by start_server(): a process that serves cluster traffic marks
# its OUTBOUND pooled-transport requests with X-Sweed-Internal, so
# intra-cluster hops (filer→volume chunk fetches, replication fan-out,
# heartbeats) bypass the tenant governor — throttling replication under a
# misconfigured QoS budget would turn a knob into a durability incident.
# The header is trusted exactly as far as intra-cluster JWT-less auth
# already is (a private network); see docs/OBSERVABILITY.md.
_cluster_process = False


def mark_cluster_process() -> None:
    global _cluster_process
    _cluster_process = True


def _trace_headers(headers: Optional[dict]) -> Optional[dict]:
    """Outbound header injection point for EVERY internal HTTP call: when
    a span is active on this thread, the request carries
    ``X-Sweed-Trace: <trace_id>:<span_id>`` so the receiving daemon's
    server span joins the caller's tree; daemon processes additionally
    stamp ``X-Sweed-Internal`` (tenant-governor bypass). The original
    dict is never mutated; explicit caller-set headers win. The ambient
    deadline rides the same choke point (``X-Sweed-Deadline``), so every
    internal hop a traced request takes also carries its budget."""
    hv = _trace.inject_header()
    dv = _deadline.inject_header()
    if hv is None and dv is None and not _cluster_process:
        return headers
    out = dict(headers or {})
    if hv is not None:
        out.setdefault(_trace.TRACE_HEADER, hv)
    if dv is not None:
        out.setdefault(_deadline.DEADLINE_HEADER, dv)
    if _cluster_process:
        out.setdefault(INTERNAL_HEADER, "1")
    return out


# -- serving-core shared state ------------------------------------------------
def serving_mode() -> str:
    """'aio' or 'threads' — which serving core start_server builds.

    The event-loop reactor is the DEFAULT: idle connections park on the
    loop, hot read routes run native (no worker-thread hop), and the
    bridged worker pool serves everything else byte-identically.
    ``SWEED_SERVING=threads`` is the escape hatch back to classic
    thread-per-connection (see docs/PERF.md migration note)."""
    mode = os.environ.get("SWEED_SERVING", "aio").strip().lower()
    return "threads" if mode == "threads" else "aio"


def serving_watermark() -> int:
    """Inflight-connection admission watermark (0 disables shedding).

    Read per call so tests can raise/lower it around a live server; the
    default is high enough that only genuine connection storms shed."""
    raw = os.environ.get("SWEED_MAX_INFLIGHT", "8192").strip()
    if not (raw.isascii() and raw.isdigit()):
        return 8192
    return int(raw)


def retry_after_seconds() -> int:
    """BASE Retry-After on shed 503s; see dynamic_retry_after for the
    live-pressure scaling that goes on the wire."""
    raw = os.environ.get("SWEED_RETRY_AFTER", "1").strip()
    if not (raw.isascii() and raw.isdigit()):
        return 1
    return max(1, int(raw))


def dynamic_retry_after() -> int:
    """Retry-After derived from live pressure, not a constant: scale the
    base by the inflight/watermark load ratio and the current request
    p99, so a storm's retries spread out proportionally to how far past
    capacity the gateway actually is (a constant value re-synchronizes
    every shed client into the next thundering herd). Clamped to
    [base, 60]; degrades to the base when the watermark is off or no
    latency samples exist yet."""
    base = retry_after_seconds()
    wm = serving_watermark()
    if wm <= 0:
        return base
    load = SERVING.inflight() / wm
    val = base + int(load * (base + 2.0 * SERVING.request_p99()))
    return max(base, min(val, 60))


def sendfile_min_bytes() -> Optional[int]:
    """Data-size floor for the zero-copy GET path, or None when disabled.

    Small needles lose more to the extra metadata reads + fd dup than
    the copy costs; the default floor keeps sendfile for the bodies
    where it pays. ``SWEED_SENDFILE=0`` disables the path outright."""
    if os.environ.get("SWEED_SENDFILE", "1").strip() == "0":
        return None
    raw = os.environ.get("SWEED_SENDFILE_MIN", "65536").strip()
    if not (raw.isascii() and raw.isdigit()):
        return 65536
    return int(raw)


def admission_reject_response() -> bytes:
    """Canned 503 written straight to a just-accepted socket when the
    gateway is past its inflight watermark: the peer learns to back off
    (Retry-After) without the server spending a handler thread / parsed
    request on it."""
    return (
        "HTTP/1.1 503 Service Unavailable\r\n"
        f"Retry-After: {dynamic_retry_after()}\r\n"
        "Content-Length: 0\r\n"
        "Connection: close\r\n\r\n"
    ).encode("ascii")


@instrument
class _ServingState:
    """Cross-server serving-core counters backing the ``sweed_serving_*``
    gauges and the /_status "serving" section. Live servers (threads or
    aio) register themselves; inflight is summed lazily so the counter
    can never drift from the per-server truth."""

    def __init__(self):
        self._lock = make_lock("_ServingState._lock")
        self._servers: "weakref.WeakSet" = weakref.WeakSet()
        self._rejected = 0
        self._keepalive_shed = 0
        self._loop_lag_last_ms = 0.0
        self._loop_lag_max_ms = 0.0
        self._assign_batches = 0
        self._assign_fids = 0
        self._assign_max_batch = 0
        # recent request service times (seconds); feeds dynamic_retry_after
        self._lat_ring: deque = deque(maxlen=256)
        self._reaped = {"idle": 0, "deadline": 0}
        self._native_hits = 0
        self._native_fallbacks = 0
        self._qos = {"ok": 0, "delay": 0, "shed": 0}

    def register_server(self, srv) -> None:
        with self._lock:
            self._servers.add(srv)

    def inflight(self) -> int:
        with self._lock:
            servers = list(self._servers)
        total = 0
        for s in servers:
            try:
                total += s.inflight_count()
            except Exception:  # sweedlint: ok broad-except a dying server mid-teardown must not break the gauge
                pass
        return total

    def handler_count(self) -> int:
        """The most requests one of this process's servers runs handlers
        for at once: the aio core's worker pool. 0 where nothing bounds
        them (the threads core starts a thread a connection) or no server
        is up. What a pool that works for the handlers sizes itself by."""
        with self._lock:
            servers = list(self._servers)
        return max((s.handler_count() for s in servers), default=0)

    def note_rejected(self) -> None:
        with self._lock:
            self._rejected += 1

    def note_keepalive_shed(self) -> None:
        with self._lock:
            self._keepalive_shed += 1

    def note_loop_lag(self, seconds: float) -> None:
        ms = max(0.0, seconds * 1000.0)
        with self._lock:
            self._loop_lag_last_ms = ms
            if ms > self._loop_lag_max_ms:
                self._loop_lag_max_ms = ms

    def note_assign_batch(self, n: int) -> None:
        with self._lock:
            self._assign_batches += 1
            self._assign_fids += n
            if n > self._assign_max_batch:
                self._assign_max_batch = n

    def note_request_seconds(self, seconds: float) -> None:
        with self._lock:
            self._lat_ring.append(seconds)

    def request_p99(self) -> float:
        with self._lock:
            return self._p99_locked()

    def _p99_locked(self) -> float:
        if not self._lat_ring:
            return 0.0
        ring = sorted(self._lat_ring)
        return ring[min(len(ring) - 1, int(len(ring) * 0.99))]

    def note_reaped(self, phase: str) -> None:
        with self._lock:
            self._reaped[phase] = self._reaped.get(phase, 0) + 1

    def note_native(self) -> None:
        with self._lock:
            self._native_hits += 1

    def note_native_fallback(self) -> None:
        with self._lock:
            self._native_fallbacks += 1

    def note_qos(self, outcome: str) -> None:
        with self._lock:
            self._qos[outcome] = self._qos.get(outcome, 0) + 1

    def snapshot(self) -> dict:
        with self._lock:
            batches = self._assign_batches
            return {
                "mode": serving_mode(),
                "watermark": serving_watermark(),
                "inflight": self.inflight_unlocked_sum(),
                "admission_rejected": self._rejected,
                "keepalive_shed": self._keepalive_shed,
                "loop_lag_ms": round(self._loop_lag_last_ms, 3),
                "loop_lag_max_ms": round(self._loop_lag_max_ms, 3),
                "assign_batches": batches,
                "assign_fids": self._assign_fids,
                "assign_max_batch": self._assign_max_batch,
                "assign_avg_batch": round(
                    self._assign_fids / batches, 2
                ) if batches else 0.0,
                "request_p99_ms": round(self._p99_locked() * 1000.0, 3),
                "reaped_idle": self._reaped.get("idle", 0),
                "reaped_deadline": self._reaped.get("deadline", 0),
                "native_hits": self._native_hits,
                "native_fallbacks": self._native_fallbacks,
                "qos_ok": self._qos.get("ok", 0),
                "qos_delayed": self._qos.get("delay", 0),
                "qos_shed": self._qos.get("shed", 0),
            }

    def inflight_unlocked_sum(self) -> int:
        # callers hold self._lock; per-server counts use their own locks
        total = 0
        for s in list(self._servers):
            try:
                total += s.inflight_count()
            except Exception:  # sweedlint: ok broad-except a dying server mid-teardown must not break the gauge
                pass
        return total


SERVING = _ServingState()


def serving_overloaded(handler) -> bool:
    """True when the handler's server is past its admission watermark;
    used to propagate backpressure to keep-alive clients (the reply gets
    Connection: close so the pooled peer re-dials into admission)."""
    srv = getattr(handler, "server", None)
    fn = getattr(srv, "overloaded", None)
    return bool(fn()) if fn is not None else False


def relay_stream(handler, payload, declared_len: Optional[int] = None) -> None:
    """Pipe a file-like body to handler.wfile in bounded pieces, with the
    same error discipline as _reply_stream: peer-gone and upstream failures
    both log, close the payload, and drop the connection (headers are
    already sent — a short body + closed socket is the only honest
    signal). Shared by the S3 and WebDAV gateway relays."""
    sent = 0
    try:
        while True:
            piece = payload.read(1 << 20)
            if not piece:
                break
            handler.wfile.write(piece)
            sent += len(piece)
    except (BrokenPipeError, ConnectionResetError):
        handler.close_connection = True
        return
    except Exception:
        glog.exception("stream relay failed after %d bytes", sent)
        handler.close_connection = True
        return
    finally:
        try:
            payload.close()
        except Exception:  # sweedlint: ok broad-except close of an already-failed upstream body; nothing to report
            pass
    if declared_len is not None and sent != declared_len:
        glog.error("stream relay produced %d of %d bytes", sent, declared_len)
        handler.close_connection = True


class CountedReader:
    """Bounded view of a request body stream; tracks unconsumed bytes so
    handlers know when keep-alive framing was abandoned (shared by the
    WebDAV and S3 gateways' streaming uploads)."""

    def __init__(self, rfile, length: int):
        self._rfile = rfile
        self.left = length

    def read(self, n: int = -1) -> bytes:
        if self.left <= 0:
            return b""
        want = self.left if n is None or n < 0 else min(n, self.left)
        got = self._rfile.read(want)
        self.left -= len(got)
        return got

    def drain(self) -> None:
        while self.left > 0 and self.read(1 << 20):
            pass


def drain_refused_body(handler, reader, cap: int = 32 << 20,
                       timeout: float = 2.0) -> None:
    """After refusing a request whose streamed body is unconsumed: drain a
    bounded amount under a short socket timeout so modest in-flight bodies
    still get their error response delivered on the keep-alive socket —
    but a client that stalls (or never sends the body at all) can't wedge
    the worker. Anything left after the cap/timeout drops the connection."""
    old = handler.connection.gettimeout()
    handler.connection.settimeout(timeout)
    try:
        while reader.left > 0 and cap > 0:
            try:
                got = reader.read(min(1 << 20, cap))
            except OSError:  # includes socket.timeout
                break
            if not got:
                break
            cap -= len(got)
    finally:
        handler.connection.settimeout(old)
    if reader.left > 0:
        handler.close_connection = True


class BadRequest(Exception):
    """Raised by route handlers on a malformed request parameter; the
    JsonHandler dispatcher answers 400 with the message instead of the
    generic 500 a stray ValueError would produce."""


class StreamBody:
    """Handler return value for incrementally-produced response bodies:
    `length` goes in Content-Length, `chunks` (an iterable of bytes) is
    written piece by piece."""

    def __init__(self, length: int, chunks):
        self.length = length
        self.chunks = chunks


class SendfileBody:
    """Handler return value for zero-copy responses: ``count`` bytes at
    ``offset`` of ``file`` (a real OS file, typically a dup of a volume's
    .dat fd) go to the client socket via sendfile(2) — no userspace copy.

    Threads mode relays with ``socket.sendfile`` (which falls back to a
    send loop on TLS sockets); the aio reactor uses ``loop.sendfile``.
    The receiver always closes ``file``."""

    def __init__(self, file, offset: int, count: int):
        self.file = file
        self.offset = offset
        self.count = count

    def close(self) -> None:
        try:
            self.file.close()
        except OSError:
            pass


class AsyncStreamBody:
    """Native-handler return value for incrementally-produced bodies:
    ``length`` goes in Content-Length, ``chunks`` (an ASYNC iterable of
    bytes) is written piece by piece on the event loop — the native
    mirror of StreamBody."""

    def __init__(self, length: int, chunks):
        self.length = length
        self.chunks = chunks


#: Sentinel a native-async route coroutine returns to punt the request to
#: the bridged worker-thread path, which re-runs the untouched handler
#: class — byte-identical legacy behavior by construction. Native handlers
#: implement ONLY the happy hot path; every auth failure, error, or
#: exotic request shape falls back.
NATIVE_FALLBACK = object()


def request_tenant(headers, remote_addr: str) -> str:
    """Tenant key for a request, given any case-insensitive headers
    mapping (http.client message or the native path's view)."""
    return classify_tenant(
        lambda k, d="": (headers.get(k) or d), remote_addr
    )


def observe_tenant_request(tenant: str, seconds: float) -> None:
    """Per-tenant latency evidence for /metrics quantiles. Recorded when
    the tenant is explicit (header / access key) or the governor is on —
    anonymous /24 classes only get labeled samples while QoS is active,
    which bounds label cardinality in the common single-tenant case."""
    if tenant == INTERNAL_TENANT:
        return
    if not (GOVERNOR.enabled() or not tenant.startswith("ip:")):
        return
    try:
        from ..stats import metrics as _metrics

        _metrics.note_qos_request(tenant, seconds)
    except Exception:  # sweedlint: ok broad-except metrics must never break serving
        pass


def count_qos_decision(tenant: str, outcome: str) -> None:
    """Shed/delay/ok counters, per tenant, for /metrics."""
    SERVING.note_qos(outcome)
    try:
        from ..stats import metrics as _metrics

        _metrics.note_qos_decision(tenant, outcome)
    except Exception:  # sweedlint: ok broad-except metrics must never break serving
        pass


def has_dot_segments(path: str) -> bool:
    """True when any "/"-separated segment is literally "." or "..".

    The filer stores segments literally (no resolution — no traversal),
    but a stored ".." entry is unrepresentable through the FUSE mount and
    poisons POSIX listings; the filer refuses such writes and the gateways
    answer their own error shapes. One predicate so the notion of an
    illegal path cannot drift between them."""
    return any(seg in (".", "..") for seg in path.split("/"))


# Turbo's stamp on a request it proxies: CLOCK_MONOTONIC nanoseconds at the
# moment it had the whole request (native/turbo.cpp). The engine is a thread
# of this process, so it is time.monotonic_ns()'s clock; a value in the
# future or older than this is some other clock's and is not trusted
PROXY_T0_HEADER = "X-Sweed-Proxy-T0"
PROXY_T0_MAX_AGE_S = 60.0


def parse_content_length(headers) -> int:
    """Content-Length as a non-negative int, or -1 when garbage/negative.

    A naive ``int(...)`` feeds ``rfile.read(-N)``, which blocks until the
    peer hangs up and pins the handler thread. Callers treat -1 as a 400 +
    close (the body framing is unknowable). Shared by every HTTP handler
    (JsonHandler dispatch, the S3 gateway, WebDAV) so hardening lands once.
    """
    raw = (headers.get("Content-Length") or "0").strip()
    # ascii-digits only: rejects '-5', '+5', '1_0', 'zz', '' and the
    # unicode digits ('²') where isdigit() and int() disagree
    if not (raw.isascii() and raw.isdigit()):
        return -1
    return int(raw)


class JsonHandler(BaseHTTPRequestHandler):
    """Route table based handler; subclasses set `routes` as
    [(method, path_prefix, fn)] where fn(handler, path, query, body) →
    (status, payload). Payload bytes pass through; anything else is JSON."""

    # headers and body go out as separate writes; on keep-alive
    # connections Nagle + the peer's delayed ACK turns that into ~40ms
    # per response
    disable_nagle_algorithm = True

    protocol_version = "HTTP/1.1"
    routes: list[tuple[str, str, Callable]] = []
    # Native-async fast-path routes, served directly on the aio reactor's
    # loop (no worker-thread hop): [(method, path_prefix, coroutine)]
    # where the coroutine takes a NativeRequest (server/aio.py) and
    # returns NATIVE_FALLBACK or (status, payload[, extra_headers]).
    # Threads mode ignores these entirely.
    native_routes: list[tuple[str, str, Callable]] = []
    server_ctx: Any = None
    extra_headers: Optional[dict] = None  # handlers may set per-request
    # span service tag for this daemon's server spans ("master", "filer",
    # "volume", "s3", ...); subclasses override
    trace_service: str = "http"

    def log_message(self, fmt, *args):  # stdlib chatter → V(3)
        glog.V(3).info("http: " + fmt, *args)

    def parse_request(self) -> bool:
        # the threads core's first stamp of a request: its line is read
        self._t_line = time.monotonic()
        return super().parse_request()

    def _record_serve_legs(self) -> None:
        """What this request passed before its span opened, written in
        hindsight as children of the span (which is the context's active
        one): ``serve.proxy.in``, ``serve.native.miss`` and ``serve.queue``
        from the aio core's stamps, then ``serve.parse`` up to now. The
        threads core has no loop and no pool: ``serve.parse`` from the
        request line read, nothing else."""
        now = time.monotonic()
        legs = getattr(self.connection, "serve_legs", None)
        if legs is None:
            parse_from = self._t_line
        else:
            parse_from = self.connection.t_worker
            for name, start, end in legs(self.headers.get(PROXY_T0_HEADER)):
                _trace.record_stage(name, end - start, ended_ago_s=now - end)
        _trace.record_stage("serve.parse", now - parse_from)

    @staticmethod
    def mark_streaming(fn):
        """Tag a route handler as streaming: it is called as
        fn(h, path, query, rfile, length) BEFORE the body is buffered and
        must consume exactly `length` bytes from rfile (uploads then hold
        one chunk in memory at a time instead of the whole body)."""
        fn._streaming = True
        return fn

    def _dispatch(self, method: str) -> None:
        parsed = urllib.parse.urlparse(self.path)
        query = {k: v[0] for k, v in urllib.parse.parse_qs(parsed.query).items()}
        length = parse_content_length(self.headers)
        if length < 0:
            # body framing is unknowable, so answer 400 and drop the
            # connection
            self.close_connection = True
            self._reply(400, {"error": "bad Content-Length"})
            return
        # Per-tenant admission: a tenant past its weighted-fair share is
        # paced (short sleep on this worker thread), then shed with
        # 503 + dynamic Retry-After. Internal cluster hops bypass. The
        # connection stays OPEN on shed: forcing a close makes the abuser
        # reconnect, and the accept/teardown churn costs the server more
        # than the abuser — socket-level abuse is the reaper's and the
        # keep-alive watermark's job, not the governor's.
        tenant = request_tenant(self.headers, self.client_address[0])
        decision, wait = GOVERNOR.admit(tenant)
        if decision == "shed":
            count_qos_decision(tenant, "shed")
            self.extra_headers = dict(self.extra_headers or {})
            self.extra_headers["Retry-After"] = str(dynamic_retry_after())
            self._reply(503, {"error": "tenant over rate"})
            return
        if decision == "delay":
            count_qos_decision(tenant, "delay")
            time.sleep(wait)
        elif GOVERNOR.enabled() and tenant != INTERNAL_TENANT:
            count_qos_decision(tenant, "ok")
        t0 = time.monotonic()
        # ambient deadline: parsed once, entered around the handler so
        # every downstream hop this request makes inherits the budget
        # (the transports clamp + refuse on it). Runs in BOTH cores —
        # the aio reactor bridges through this same dispatch.
        ddl = (_deadline.parse_header(
            self.headers.get(_deadline.DEADLINE_HEADER))
            if _deadline.enabled() else None)
        body = None  # read lazily: streaming handlers consume rfile directly
        for m, prefix, fn in self.routes:
            if m == method and parsed.path.startswith(prefix):
                streaming = getattr(fn, "_streaming", False)
                # server span: this runs on the request's worker thread in
                # BOTH cores (the aio reactor copies the loop context into
                # its pool), so the contextvar window is same-thread. The
                # span name is the ROUTE prefix, not the raw path — bounded
                # names; the path rides in a tag. The reply happens inside
                # the span so streamed bodies count toward the hop time.
                with _trace.start_span(
                    f"{method} {prefix}",
                    service=self.trace_service,
                    parent_header=self.headers.get(_trace.TRACE_HEADER),
                    path=parsed.path,
                ) as span:
                    if span is not None:
                        self._record_serve_legs()
                    cancelled = False
                    try:
                        with _deadline.scope(ddl):
                            if ddl is not None and _deadline.expired():
                                # budget died upstream of the handler:
                                # answer 504 without doing the work. The
                                # unread body breaks keep-alive framing,
                                # so the connection drops after reply.
                                _deadline.note("expired_inbound")
                                cancelled = True
                                raise _deadline.DeadlineExceeded(
                                    -(_deadline.remaining() or 0.0))
                            if streaming:
                                status, payload = fn(
                                    self, parsed.path, query, self.rfile,
                                    length
                                )
                            else:
                                if body is None:
                                    body = (self.rfile.read(length)
                                            if length else b"")
                                status, payload = fn(self, parsed.path,
                                                     query, body)
                    except _deadline.DeadlineExceeded as e:
                        if not cancelled:
                            _deadline.note("aborted_handler")
                        cancelled = True
                        status, payload = 504, {
                            "error": f"deadline exceeded: {e}"
                        }
                        self.close_connection = True
                    except BadRequest as e:
                        status, payload = 400, {"error": str(e)}
                        if streaming:
                            # the request body may be half-consumed;
                            # keep-alive framing is gone, so drop the
                            # connection after reply
                            self.close_connection = True
                    except Exception as e:
                        glog.exception("%s %s failed", method, parsed.path)
                        status, payload = 500, {
                            "error": f"{type(e).__name__}: {e}"
                        }
                        if streaming:
                            # the request body may be half-consumed;
                            # keep-alive framing is gone, so drop the
                            # connection after reply
                            self.close_connection = True
                    if span is not None:
                        span.tags["status"] = status
                        if status >= 500:
                            span.tags["failed"] = 1  # summed in its row
                        if cancelled:
                            # the trace tree shows WHERE the budget died
                            span.status = "cancelled"
                            span.tags["deadline"] = "exceeded"
                        elif status >= 500:
                            span.status = "error"
                        if self.extra_headers is None:
                            self.extra_headers = {
                                _trace.TRACE_ID_HEADER: span.trace_id
                            }
                        else:
                            self.extra_headers.setdefault(
                                _trace.TRACE_ID_HEADER, span.trace_id
                            )
                    glog.V(2).info("%s %s → %d", method, parsed.path, status)
                    # the reply is a stage of its own: a slow client's
                    # back-pressure (the aio core's flume) is in it, and
                    # not in the handler. Quiet: a long body's slow line is
                    # the request's own
                    with _trace.stage_span("serve.reply", quiet=True):
                        self._reply(status, payload,
                                    head_only=(method == "HEAD"))
                    dt = time.monotonic() - t0
                    SERVING.note_request_seconds(dt)
                    observe_tenant_request(tenant, dt)
                if span is not None:
                    # the closed request is a row of the stage table too,
                    # under its ROUTE's name: /status says what a route's
                    # handler costs whole, beside the legs around it
                    _trace.STAGES.add(span)
                return
        if body is None and length:
            # drain in bounded pieces for keep-alive correctness — a multi-GB
            # body to an unrouted path must not be buffered whole
            left = length
            while left > 0:
                got = self.rfile.read(min(1 << 20, left))
                if not got:
                    break
                left -= len(got)
        self._reply(404, {"error": f"no route {method} {parsed.path}"})

    def _shed_keepalive_if_overloaded(self) -> None:
        """Past the admission watermark, tell keep-alive peers to go away
        after this response: Connection: close drains established pools
        back through admission instead of letting pre-watermark clients
        hold their slots forever."""
        if serving_overloaded(self):
            self.send_header("Connection", "close")
            self.close_connection = True
            SERVING.note_keepalive_shed()

    def _reply(self, status: int, payload, head_only: bool = False) -> None:
        if isinstance(payload, StreamBody):
            self._reply_stream(status, payload, head_only)
            return
        if isinstance(payload, SendfileBody):
            self._reply_sendfile(status, payload, head_only)
            return
        if isinstance(payload, (bytes, bytearray)):
            data = bytes(payload)
            ctype = "application/octet-stream"
        else:
            data = json.dumps(payload).encode()
            ctype = "application/json"
        if self.extra_headers and "Content-Type" in self.extra_headers:
            ctype = self.extra_headers.pop("Content-Type")
        clen = str(len(data))
        if self.extra_headers and "Content-Length" in self.extra_headers:
            # HEAD answers for chunked manifests advertise the full size
            # without materializing the body
            clen = self.extra_headers.pop("Content-Length")
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", clen)
        for k, v in (self.extra_headers or {}).items():
            self.send_header(k, v)
        self.extra_headers = None
        self._shed_keepalive_if_overloaded()
        self.end_headers()
        if not head_only:  # HEAD: headers only, or keep-alive framing breaks
            _trace.add_stage_bytes(len(data))  # serve.reply's body
            try:
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError):
                # peer vanished mid-reply (e.g. aborted its own upload);
                # nothing to salvage — just stop reusing the socket
                self.close_connection = True

    def _reply_sendfile(self, status: int, body: "SendfileBody",
                        head_only: bool) -> None:
        """Zero-copy reply: headers through the normal path, then the
        needle's data region goes kernel→socket via sendfile(2). The
        shim connection of the aio reactor implements the same
        ``connection.sendfile(file, offset=, count=)`` surface with
        ``loop.sendfile``, so this code serves both modes."""
        self.send_response(status)
        ctype = "application/octet-stream"
        if self.extra_headers and "Content-Type" in self.extra_headers:
            ctype = self.extra_headers.pop("Content-Type")
        self.send_header("Content-Type", ctype)
        clen = str(body.count)
        if self.extra_headers and "Content-Length" in self.extra_headers:
            clen = self.extra_headers.pop("Content-Length")
        self.send_header("Content-Length", clen)
        for k, v in (self.extra_headers or {}).items():
            self.send_header(k, v)
        self.extra_headers = None
        self._shed_keepalive_if_overloaded()
        self.end_headers()
        if head_only:
            body.close()
            return
        sent = 0
        try:
            self.wfile.flush()  # headers first — sendfile bypasses wfile
            sent = self.connection.sendfile(
                body.file, offset=body.offset, count=body.count
            ) or 0
        except (BrokenPipeError, ConnectionResetError):
            self.close_connection = True
            return
        except Exception:
            glog.exception("sendfile reply failed after %d/%d bytes",
                           sent, body.count)
            self.close_connection = True
            return
        finally:
            body.close()
        _trace.add_stage_bytes(sent)
        if sent != body.count:
            glog.error("sendfile reply produced %d of %d bytes", sent,
                       body.count)
            self.close_connection = True

    def _reply_stream(self, status: int, body: "StreamBody",
                      head_only: bool) -> None:
        """Send a response whose bytes arrive incrementally (filer
        StreamContent analog): Content-Length up front, pieces written as
        they are produced — the daemon never holds the whole object."""
        self.send_response(status)
        ctype = "application/octet-stream"
        if self.extra_headers and "Content-Type" in self.extra_headers:
            ctype = self.extra_headers.pop("Content-Type")
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(body.length))
        for k, v in (self.extra_headers or {}).items():
            self.send_header(k, v)
        self.extra_headers = None
        self._shed_keepalive_if_overloaded()
        self.end_headers()
        if head_only:
            return
        sent = 0
        try:
            for piece in body.chunks:
                self.wfile.write(piece)
                sent += len(piece)
        except (BrokenPipeError, ConnectionResetError):
            self.close_connection = True
            return
        except Exception:
            # headers are gone; the only honest signal is a short body
            glog.exception("stream reply failed after %d/%d bytes",
                           sent, body.length)
            self.close_connection = True
            return
        _trace.add_stage_bytes(sent)
        if sent != body.length:
            glog.error("stream reply produced %d of %d bytes", sent,
                       body.length)
            self.close_connection = True

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    def do_PUT(self):
        self._dispatch("PUT")

    def do_HEAD(self):
        self._dispatch("HEAD")


def parse_byte_range(rng: str, total: int):
    """Single-range 'bytes=a-b' → (start, end) inclusive; None = serve the
    full body (absent/malformed/multi-range); 'unsatisfiable' = 416.
    Shared by the volume and filer read paths so the RFC corner cases live
    in one place."""
    spec = rng.strip()
    if not spec.startswith("bytes=") or "," in spec:
        return None
    start_s, _, end_s = spec[len("bytes="):].partition("-")
    try:
        if start_s == "":  # suffix form: last N bytes
            start, end = max(0, total - int(end_s)), total - 1
        else:
            start = int(start_s)
            end = int(end_s) if end_s else total - 1
    except ValueError:
        return None
    end = min(end, total - 1)
    if start > end or start >= total:
        return "unsatisfiable"
    return start, end


def range_headers(start: int, end: int, total: int) -> dict:
    return {
        "Content-Range": f"bytes {start}-{end}/{total}",
        "Accept-Ranges": "bytes",
    }


def unsatisfiable_range_headers(total: int) -> dict:
    return {"Content-Range": f"bytes */{total}"}


def _close_socket(sock) -> None:
    import socket as _socket

    try:
        sock.shutdown(_socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class _TrackingThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that severs live keep-alive connections on
    shutdown, with inflight-watermark admission control. Without the
    sever, a 'stopped' server keeps answering requests on established
    connections (handler threads block in readline forever) — clients
    with pooled connections then talk to a ghost."""

    # socketserver's default listen backlog is 5: a modest connection
    # burst (the c=256 probe smoke, or any pooled client warming up)
    # overflows it and the kernel drops SYNs. Match the aio reactor's
    # backlog so the escape-hatch core survives the same storms.
    request_queue_size = 2048

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self._live_conns: set = set()
        self._conns_lock = threading.Lock()
        # flipped under _conns_lock by shutdown(); any connection that
        # would register after the sever pass is closed instead of
        # becoming an untracked ghost (the PR 7 shutdown-race fix)
        self._shutting_down = False
        SERVING.register_server(self)

    def inflight_count(self) -> int:
        with self._conns_lock:
            return len(self._live_conns)

    def handler_count(self) -> int:
        return 0  # a thread a connection: nothing bounds the handlers

    def overloaded(self) -> bool:
        wm = serving_watermark()
        return wm > 0 and self.inflight_count() >= wm

    def process_request(self, request, client_address):
        wm = serving_watermark()
        with self._conns_lock:
            if self._shutting_down:
                # raced shutdown(): the sever pass may already have run,
                # so registering now would leak an unclosed connection
                _close_socket(request)
                return
            if wm > 0 and len(self._live_conns) >= wm:
                reject = True
            else:
                self._live_conns.add(request)
                reject = False
        if reject:
            SERVING.note_rejected()
            try:
                request.sendall(admission_reject_response())
            except OSError:
                pass
            _close_socket(request)
            return
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conns_lock:
            self._live_conns.discard(request)
        super().shutdown_request(request)

    def shutdown(self):
        super().shutdown()
        with self._conns_lock:
            self._shutting_down = True
            conns = list(self._live_conns)
            self._live_conns.clear()
        for c in conns:
            _close_socket(c)


def start_server(handler_cls, host: str, port: int, ssl_context=None):
    """A serving core for `handler_cls` on (host, port): the classic
    thread-per-connection `ThreadingHTTPServer`, or — with
    ``SWEED_SERVING=aio`` — the asyncio reactor (`server/aio.py`), which
    runs the exact same handler code but parks idle connections on the
    event loop instead of spending a thread each. Both expose
    shutdown()/server_close()/server_address and admission control."""
    # serving cluster traffic ⇒ this process's outbound calls are
    # intra-cluster hops (tenant-governor bypass; see _trace_headers)
    mark_cluster_process()
    if serving_mode() == "aio":
        from .aio import AioHTTPServer

        return AioHTTPServer(
            handler_cls, host, port, ssl_context=ssl_context
        ).start()
    if ssl_context is None:
        srv = _TrackingThreadingHTTPServer((host, port), handler_cls)
    else:
        import ssl as _ssl

        class _TlsServer(_TrackingThreadingHTTPServer):
            """Handshake in the WORKER thread with a deadline — wrapping the
            listening socket would run handshakes inside the single accept
            loop, letting one stalled client freeze the whole server."""

            def finish_request(self, request, client_address):
                try:
                    request.settimeout(10)
                    tls_conn = ssl_context.wrap_socket(
                        request, server_side=True
                    )
                    tls_conn.settimeout(None)
                except (_ssl.SSLError, OSError):
                    try:
                        request.close()
                    except OSError:
                        pass
                    return
                # wrap_socket DETACHED the raw socket we tracked in
                # process_request — track the live TLS socket instead or
                # shutdown() severs a dead fd and the ghost lives on
                with self._conns_lock:
                    if self._shutting_down:
                        # same shutdown race as process_request: the
                        # sever pass already ran in another thread, so
                        # the swapped-in TLS socket must die here
                        _close_socket(tls_conn)
                        return
                    self._live_conns.discard(request)
                    self._live_conns.add(tls_conn)
                try:
                    self.RequestHandlerClass(tls_conn, client_address, self)
                finally:
                    with self._conns_lock:
                        self._live_conns.discard(tls_conn)
                    try:
                        tls_conn.close()
                    except OSError:
                        pass

        srv = _TlsServer((host, port), handler_cls)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv


# -- pooled keep-alive transport ---------------------------------------------
# Every daemon talks HTTP/1.1; opening a fresh TCP connection per request
# (urllib's behavior) costs a handshake on the hottest paths — assigns,
# uploads, heartbeats, chunk fetches, replication fan-out. Connections are
# pooled per (host, port) in thread-local storage (http.client connections
# are not thread-safe) and re-dialed once when a pooled socket went stale
# (peer restarted / idle-closed).
_pool_local = threading.local()


def pool_max_idle_seconds() -> float:
    """Max idle age for a pooled keep-alive socket (0 disables reaping).

    Long-lived daemons otherwise accumulate sockets their peers closed
    hours ago: the stale-probe only catches a peer whose FIN already
    arrived, and the one-shot retry burns a round trip re-dialing. An
    idle-age ceiling (default comfortably under typical server
    keep-alive timeouts) retires old sockets BEFORE the race can
    happen. The aio pool (server/aio_transport.py) applies the same
    policy from day one."""
    raw = os.environ.get("SWEED_POOL_IDLE_S", "30").strip()
    if not (raw.isascii() and raw.isdigit()):
        return 30.0
    return float(int(raw))


def _conn_idle_expired(conn) -> bool:
    max_idle = pool_max_idle_seconds()
    if max_idle <= 0:
        return False
    since = getattr(conn, "_sweed_idle_since", None)
    return since is not None and (time.monotonic() - since) > max_idle


class _NoDelayHTTPConnection:
    """Created lazily to keep module import light."""

    _cls = None

    @classmethod
    def get(cls):
        if cls._cls is None:
            import http.client
            import socket as _socket

            class _Conn(http.client.HTTPConnection):
                def connect(self):
                    super().connect()
                    # Nagle + delayed-ACK on a reused connection turns
                    # every small request into a ~40ms round trip
                    self.sock.setsockopt(
                        _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
                    )

            cls._cls = _Conn
        return cls._cls


_IDEMPOTENT_METHODS = frozenset({"GET", "HEAD", "PUT", "DELETE"})


def _pooled_request(
    method: str,
    url: str,
    body: Optional[bytes],
    headers: Optional[dict],
    timeout: float,
    idempotent: bool = False,
) -> tuple[int, bytes, dict]:
    import http.client

    u = urllib.parse.urlsplit(url)
    key = (u.hostname, u.port)
    conns = getattr(_pool_local, "conns", None)
    if conns is None:
        conns = _pool_local.conns = {}
    path = u.path + (f"?{u.query}" if u.query else "")
    # The stale-socket retry can double-execute a request the server already
    # received (a reset can arrive after execution), so it is limited to
    # idempotent methods — mirroring Go net/http shouldRetryRequest — plus
    # POSTs the caller explicitly marks idempotent (fid-addressed uploads:
    # re-writing the same fid+bytes is a no-op overwrite). A retried
    # /dir/assign would leak a file id (ADVICE r2).
    may_retry = method in _IDEMPOTENT_METHODS or idempotent
    last_err: Optional[Exception] = None
    for attempt in (0, 1):
        conn = conns.get(key)
        if conn is not None and _conn_idle_expired(conn):
            conn.close()
            conns.pop(key, None)
            conn = None
        fresh = conn is None
        if fresh:
            conn = _NoDelayHTTPConnection.get()(
                u.hostname, u.port, timeout=timeout
            )
            conns[key] = conn
        elif conn.sock is not None:
            conn.sock.settimeout(timeout)
        try:
            conn.request(method, path, body=body, headers=headers or {})
            resp = conn.getresponse()
            data = resp.read()
            resp_headers = dict(resp.getheaders())
            if resp.will_close:
                conn.close()
                conns.pop(key, None)
            else:
                conn._sweed_idle_since = time.monotonic()
            return resp.status, data, resp_headers
        except (
            http.client.RemoteDisconnected,
            http.client.BadStatusLine,
            ConnectionResetError,
            BrokenPipeError,
        ) as e:
            # idle-close race on a REUSED socket: the peer closed before
            # sending a status line — safe to re-dial once for idempotent
            # requests. Timeouts and mid-response failures are NOT retried
            # (the request may have executed; re-sending would
            # double-assign/double-publish).
            conn.close()
            conns.pop(key, None)
            last_err = e
            if fresh or attempt or not may_retry:
                raise
        except (http.client.HTTPException, OSError):
            conn.close()
            conns.pop(key, None)
            raise
    raise last_err  # unreachable; keeps type checkers honest


def _conn_is_stale(conn) -> bool:
    """True when a pooled keep-alive socket is no longer usable: a peer
    that closed (or half-closed) the connection leaves it readable with
    EOF pending, while a healthy idle HTTP/1.1 socket has nothing to
    read. Used before NON-retryable sends (streaming bodies can't be
    rewound, so the one-shot stale retry of _pooled_request is off the
    table — probing is the next best defense)."""
    sock = getattr(conn, "sock", None)
    if sock is None:
        return False  # never connected; the dial below is fresh anyway
    import select

    try:
        readable, _, _ = select.select([sock], [], [], 0)
    except (OSError, ValueError):
        return True
    return bool(readable)


def _checkout_conn(key: tuple, timeout: float):
    """The calling thread's pooled connection for (host, port), stale-probed,
    or a fresh one. Returns (conn, conns_dict); the conn is REMOVED from the
    pool — the caller re-pools it via _repool when its response is done."""
    conns = getattr(_pool_local, "conns", None)
    if conns is None:
        conns = _pool_local.conns = {}
    conn = conns.pop(key, None)
    if conn is not None and (_conn_idle_expired(conn) or _conn_is_stale(conn)):
        conn.close()
        conn = None
    if conn is None:
        conn = _NoDelayHTTPConnection.get()(key[0], key[1], timeout=timeout)
    elif conn.sock is not None:
        conn.sock.settimeout(timeout)
    return conn, conns


def _repool(conn, key: tuple, conns: dict) -> None:
    if key in conns:  # another request pooled its own conn meanwhile
        conn.close()
    else:
        conn._sweed_idle_since = time.monotonic()
        conns[key] = conn


def http_stream_request(
    method: str,
    url: str,
    reader,
    length: int,
    headers: Optional[dict] = None,
    timeout: float = 600.0,
) -> tuple[int, bytes, dict]:
    """Request whose body streams from a file-like source over the pooled
    keep-alive transport (http://; anything else falls back to urllib).
    A consumed reader cannot be rewound, so there is NO stale-socket
    retry — instead the pooled socket is liveness-probed before the first
    byte goes out (the common stale case: peer restarted while idle)."""
    timeout = _deadline.clamp_timeout(timeout)
    hdrs = dict(_trace_headers(headers) or {})
    hdrs.setdefault("Content-Length", str(length))
    if not url.startswith("http://"):
        req = urllib.request.Request(
            url, data=reader, method=method, headers=hdrs
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read(), dict(resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, e.read(), dict(e.headers)
    u = urllib.parse.urlsplit(url)
    key = (u.hostname, u.port)
    path = u.path + (f"?{u.query}" if u.query else "")
    conn, conns = _checkout_conn(key, timeout)
    try:
        conn.blocksize = 1 << 20  # stream MB pieces, not 8KB sips
        # explicit Content-Length + file-like body: http.client streams
        # the reader in blocksize pieces (no buffering, no chunked TE)
        conn.request(method, path, body=reader, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
        resp_headers = dict(resp.getheaders())
        if resp.will_close:
            conn.close()
        else:
            _repool(conn, key, conns)
        return resp.status, data, resp_headers
    except Exception:
        conn.close()
        raise


class _PooledStreamBody:
    """File-like over a pooled connection's in-flight response body: bytes
    stay on the wire until read. Reading to EOF hands the socket back to
    the calling thread's pool; closing with unread bytes (or a read
    error) drops it — the framing is unusable mid-body."""

    def __init__(self, resp, conn, key, conns):
        self._resp, self._conn = resp, conn
        self._key, self._conns = key, conns
        self._owner = threading.get_ident()
        self._done = False

    def read(self, n: int = -1) -> bytes:
        try:
            data = self._resp.read(n)
        except Exception:
            self._discard()
            raise
        if self._resp.isclosed():
            self._settle()
        return data

    def _settle(self) -> None:
        if self._done:
            return
        self._done = True
        if self._resp.will_close or threading.get_ident() != self._owner:
            # conns is the CREATOR thread's pool; repooling from another
            # thread would share one http.client conn across threads
            self._conn.close()
        else:
            _repool(self._conn, self._key, self._conns)

    def _discard(self) -> None:
        if not self._done:
            self._done = True
            self._conn.close()

    def close(self) -> None:
        if self._resp.isclosed():
            self._settle()
        else:
            self._discard()
        try:
            self._resp.close()
        except Exception:  # sweedlint: ok broad-except socket already torn down; nothing to report
            pass


def http_stream_response(
    method: str,
    url: str,
    headers: Optional[dict] = None,
    timeout: float = 600.0,
) -> tuple[int, object, dict]:
    """Request whose RESPONSE body stays on the wire: returns (status,
    file-like body, headers) for success statuses — the caller reads
    piecewise and must close() — or (status, small error bytes, headers)
    for >= 400. http:// rides the pooled keep-alive transport (the conn is
    checked out of the pool until the body is fully read, so a nested
    request to the same peer on this thread gets its own socket);
    anything else falls back to urllib."""
    timeout = _deadline.clamp_timeout(timeout)
    headers = _trace_headers(headers)
    if not url.startswith("http://"):
        req = urllib.request.Request(url, method=method, headers=headers or {})
        try:
            resp = urllib.request.urlopen(req, timeout=timeout)
            return resp.status, resp, dict(resp.headers)
        except urllib.error.HTTPError as e:
            body = e.read()
            e.close()
            return e.code, body, dict(e.headers)
    u = urllib.parse.urlsplit(url)
    key = (u.hostname, u.port)
    path = u.path + (f"?{u.query}" if u.query else "")
    import http.client

    may_retry = method in _IDEMPOTENT_METHODS
    last_err: Optional[Exception] = None
    for attempt in (0, 1):
        conn, conns = _checkout_conn(key, timeout)
        fresh = conn.sock is None
        try:
            conn.request(method, path, headers=headers or {})
            resp = conn.getresponse()
        except (
            http.client.RemoteDisconnected,
            http.client.BadStatusLine,
            ConnectionResetError,
            BrokenPipeError,
        ) as e:
            # idle-close race on a reused socket (same discipline as
            # _pooled_request): no body was streamed yet, so a one-shot
            # re-dial is safe for idempotent methods
            conn.close()
            last_err = e
            if fresh or attempt or not may_retry:
                raise
            continue
        except Exception:
            conn.close()
            raise
        if resp.status >= 400:
            data = resp.read()
            resp_headers = dict(resp.getheaders())
            if resp.will_close:
                conn.close()
            else:
                _repool(conn, key, conns)
            return resp.status, data, resp_headers
        body = _PooledStreamBody(resp, conn, key, conns)
        return resp.status, body, dict(resp.getheaders())
    raise last_err  # unreachable; keeps type checkers honest


def http_json(
    method: str,
    url: str,
    body: Optional[dict | bytes] = None,
    timeout: float = 30.0,
) -> dict:
    data = None
    headers = {}
    if body is not None:
        if isinstance(body, dict):
            data = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        else:
            data = body
    # unreachable peers raise (like urllib's URLError did) — callers treat
    # that as a dead node; only HTTP-level errors come back as dicts.
    # http_bytes_headers pools http:// and falls back to urllib for https.
    status, payload, _ = http_bytes_headers(
        method, url, body=data, timeout=timeout, headers=headers
    )
    if status >= 400:
        try:
            return json.loads(payload or b"{}") | {"_status": status}
        except json.JSONDecodeError:
            return {
                "error": payload[:200].decode("utf-8", "replace"),
                "_status": status,
            }
    return json.loads(payload or b"{}")


def http_bytes(
    method: str,
    url: str,
    body: Optional[bytes] = None,
    timeout: float = 30.0,
    headers: Optional[dict] = None,
    idempotent: bool = False,
) -> tuple[int, bytes]:
    status, data, _ = http_bytes_headers(
        method, url, body=body, timeout=timeout, headers=headers,
        idempotent=idempotent,
    )
    return status, data


def http_bytes_headers(
    method: str,
    url: str,
    body: Optional[bytes] = None,
    timeout: float = 30.0,
    headers: Optional[dict] = None,
    idempotent: bool = False,
) -> tuple[int, bytes, dict]:
    """Like http_bytes but also returns response headers (some admin
    endpoints carry metadata such as X-Compaction-Revision there).
    ``idempotent`` opts a POST into the stale-socket one-shot retry
    (fid-addressed uploads are safe to re-send; assigns are not)."""
    timeout = _deadline.clamp_timeout(timeout)
    headers = _trace_headers(headers)
    if url.startswith("http://"):
        return _pooled_request(method, url, body, headers, timeout,
                               idempotent=idempotent)
    # https (or anything else) stays on urllib with its default TLS context
    req = urllib.request.Request(
        url, data=body, method=method, headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)
