"""Dapper-style distributed tracing for the cluster data plane.

No reference analog: `weed/stats/metrics.go` exposes Prometheus counters
but cannot answer "where did this 87 ms GET go?" across the filer →
master → volume hops. This module is the divergence (PARITY: tracing):

- ``Span``       — one timed hop (service, name, parentage, tags).
- propagation    — a ``contextvars.ContextVar`` holds the active span;
  every internal HTTP call (server/http_util.py transports) injects the
  ``X-Sweed-Trace: <trace_id>:<span_id>`` header, and every JsonHandler
  dispatch opens a server span parented on that header. Contextvars make
  this correct in BOTH serving cores: the threads core runs handlers on
  the request thread, and the aio reactor copies the loop task's context
  into its worker pool (server/aio.py), while util/pipeline.py's
  ``BoundedExecutor``/``prefetch_iter`` copy the submitting thread's
  context so chunk uploads/prefetches stay parented.
- sampling       — always-on (Dapper's head sampling degenerates to 1.0
  at this cluster's request rates); ``SWEED_TRACE=0`` is the kill switch.
- storage        — finished spans land in a process-wide bounded ring
  (``SWEED_TRACE_RING`` spans, default 2048) served at ``/debug/traces``
  by every daemon; ``weed shell trace <id>`` stitches the per-daemon
  rings back into one tree.
- slow requests  — a finished span slower than ``SWEED_TRACE_SLOW_MS``
  (default 1000) logs a glog warning with its trace id, so the trace of
  an outlier is discoverable from the daemon's own log.
- stage spans    — ``stage_span`` / ``record_stage``: the same spans for
  the steps of a long operation (a seal's pipeline legs, a recovery's
  shard fetches) and for what a bridged request passes around its handler
  (``serve.*``: the proxy's way in, the wait for a worker, the parse, the
  reply — children of the request's span, written in hindsight where they
  ended before it opened). At close each also adds itself to the process-wide
  ``STAGES`` table by name (the ``ec_codec.stages`` object of a volume
  server's /status) and, where JAX is already loaded, is written into the
  profiler's trace as a ``TraceAnnotation`` of the same name. This module
  never imports JAX itself.

Ids are random hex (os.urandom): 16 chars of trace id, 8 of span id —
the Dapper/W3C shape, sized down to this cluster's scale.
"""

from __future__ import annotations

import contextvars
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Optional

from ..util import glog
from ..util.locks import make_lock
from ..util.racecheck import instrument

TRACE_HEADER = "X-Sweed-Trace"
TRACE_ID_HEADER = "X-Sweed-Trace-Id"  # response: tells the client its trace


def enabled() -> bool:
    """Tracing kill switch; read per call so tests flip it live."""
    return os.environ.get("SWEED_TRACE", "1").strip() != "0"


def ring_capacity() -> int:
    raw = os.environ.get("SWEED_TRACE_RING", "2048").strip()
    if not (raw.isascii() and raw.isdigit()) or int(raw) < 1:
        return 2048
    return int(raw)


# parse memo for the per-span-exit threshold read: the env STRING is
# still fetched every call (live knob), but strip/float only rerun when
# it changes — this sits on every request's span-close path
_slow_cache: tuple[Optional[str], float] = (None, 1.0)


def slow_threshold_s() -> float:
    global _slow_cache
    raw = os.environ.get("SWEED_TRACE_SLOW_MS", "1000")
    cached_raw, cached = _slow_cache
    if raw == cached_raw:
        return cached
    try:
        ms = float(raw.strip())
    except ValueError:
        ms = 1000.0
    val = max(0.0, ms) / 1000.0
    # sweedlint: ok cross-domain-race a memo of one env string's parse: any thread computes the same tuple, and the assignment is GIL-atomic
    _slow_cache = (raw, val)
    return val


# ids need uniqueness, not unpredictability: a process-seeded PRNG skips
# the per-span getrandom syscall (2 per root span on the request path).
# getrandbits on a dedicated Random is a single C call — atomic under
# the GIL, so concurrent handler threads never interleave its state.
_rand = random.Random(os.urandom(16))


def _new_trace_id() -> str:
    return f"{_rand.getrandbits(64):016x}"


def _new_span_id() -> str:
    return f"{_rand.getrandbits(32):08x}"


class Span:
    """One timed hop. Mutable while open (handlers add tags/status);
    finished by the time it lands in the ring, so query-time to_dict
    sees settled state."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "service",
        "start", "duration", "tags", "status",
    )

    def __init__(
        self,
        name: str,
        service: str = "",
        trace_id: str = "",
        parent_id: str = "",
    ):
        self.trace_id = trace_id or _new_trace_id()
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.service = service
        self.start = time.time()
        self.duration = 0.0
        self.tags: dict = {}
        self.status = "ok"

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "service": self.service,
            "start": self.start,
            "duration_ms": round(self.duration * 1000.0, 3),
            "tags": dict(self.tags),
            "status": self.status,
        }


_current: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "sweed_trace_span", default=None
)


def current_span() -> Optional[Span]:
    return _current.get()


def current_trace_id() -> str:
    s = _current.get()
    return s.trace_id if s is not None else ""


def inject_header() -> Optional[str]:
    """Header value for an outbound internal HTTP call, or None when no
    span is active (requests that originate outside a trace stay clean)."""
    if not enabled():
        return None
    s = _current.get()
    if s is None:
        return None
    return f"{s.trace_id}:{s.span_id}"


def parse_header(value: Optional[str]) -> tuple[str, str]:
    """('trace_id', 'parent_span_id') from an X-Sweed-Trace value; empty
    strings for absent/garbage (a fresh root trace starts instead)."""
    if not value:
        return "", ""
    trace_id, _, parent = value.strip().partition(":")
    if not trace_id or not parent:
        return "", ""
    if not (trace_id.isascii() and trace_id.isalnum()
            and parent.isascii() and parent.isalnum()):
        return "", ""
    return trace_id, parent


@instrument
class TraceRing:
    """Process-wide bounded ring of finished spans.

    One ring per PROCESS, not per daemon: in-process test clusters share
    it (span ids stay unique, so the shell's assembler dedups cleanly),
    while production daemons — one process each — get the per-daemon
    ring the /debug/traces contract describes.

    The ring holds the finished Span objects themselves; to_dict runs at
    QUERY time (/debug/traces, tests), keeping the per-request add() to
    a lock + deque append."""

    def __init__(self, capacity: Optional[int] = None):
        self._lock = make_lock("TraceRing._lock")
        self._capacity = capacity or ring_capacity()
        self._spans: deque = deque(maxlen=self._capacity)
        self._added = 0

    def add(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
            self._added += 1

    def for_trace(self, trace_id: str) -> list[dict]:
        with self._lock:
            found = [s for s in self._spans if s.trace_id == trace_id]
        return [s.to_dict() for s in found]

    def snapshot(self, limit: int = 256) -> list[dict]:
        """Newest-last tail of the ring."""
        with self._lock:
            spans = list(self._spans)
        return [s.to_dict() for s in spans[-max(0, limit):]]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def stats(self) -> dict:
        with self._lock:
            size, added = len(self._spans), self._added
        return {
            "enabled": enabled(),
            "capacity": self._capacity,
            "size": size,
            "added": added,
            "dropped": max(0, added - size) if size >= self._capacity else 0,
        }


RING = TraceRing()


def trace_stats() -> dict:
    """Snapshot for /_status sections."""
    return RING.stats()


@instrument
class StageTable:
    """Process-wide totals of finished stage spans, by span name: ``n``
    spans and ``busy_s`` seconds inside them, plus the sums of the tags a
    stage carries — ``bytes`` where it moves bytes, ``failed`` attempts
    and ``slept_s`` of back-off where it retries. Stages close on request
    threads and on the encode pipeline's threads at once, so the rows are
    kept under one lock. A reader takes two snapshots and subtracts.
    A stage that asks another server also sums ``ok`` (asks answered),
    ``ok_s`` (seconds inside the attempts that were answered) and
    ``absent`` (asks the shard-location table answered "nowhere"); one that
    sends several asks side by side sums ``width`` (asks started together)
    and ``spares`` (asks made after one of them failed). A read-set plan
    (``ec.rebuild.plan``, ``ec.recover.plan``) sums ``width`` (shards it
    reads) and ``local`` (1 where the lost shards' local groups sufficed).
    A stage that commits staged files (``ec.seal.commit``) sums ``fsyncs``
    (files it fsync'd) and ``slow_fsyncs`` (those that took
    ``storage.commit.SLOW_FSYNC_S`` or more). A copy back of a chunk's
    result (``ec.seal.d2h``, ``ec.rebuild.d2h``) sums ``transfers`` (the
    device-to-host copies it took: 0 where the result was on the host). A
    chunk's read (``ec.seal.read``, ``ec.rebuild.read``) sums ``reads`` (the
    ``preadv`` jobs it took: a rebuild's held rows, the pieces of a seal's
    runs). A span that says which code it worked at (a ``geometry`` tag:
    ``ec.recover``, ``ec.recover.plan``, ``ec.codec.launch``) is summed
    twice: into its name's row, and into ``<name>@<geometry>``
    (``ec.recover@10+4``), so the rows of a name's geometries add up to the
    name's own."""

    SUMMED_TAGS = (
        "bytes", "failed", "slept_s", "ok", "ok_s", "absent", "width",
        "spares", "local", "fsyncs", "slow_fsyncs", "transfers", "reads",
    )

    def __init__(self):
        self._lock = make_lock("StageTable._lock")
        self._rows: dict[str, dict] = {}

    def add(self, span: Span) -> None:
        geometry = span.tags.get("geometry")
        with self._lock:
            self._sum(span.name, span)
            if geometry:
                self._sum(f"{span.name}@{geometry}", span)

    def _sum(self, name: str, span: Span) -> None:
        row = self._rows.get(name)
        if row is None:
            row = self._rows[name] = {"n": 0, "busy_s": 0.0}
        row["n"] += 1
        row["busy_s"] += span.duration
        for key in self.SUMMED_TAGS:
            if key in span.tags:
                row[key] = row.get(key, 0) + span.tags[key]

    def snapshot(self) -> dict:
        with self._lock:
            return {name: dict(row) for name, row in self._rows.items()}


STAGES = StageTable()


class _SpanScope:
    """Context manager that owns one span's contextvar window. ``span``
    is None when tracing is disabled — callers guard tag writes on it."""

    __slots__ = ("span", "_token", "_t0", "_dropped")

    def __init__(self, span: Optional[Span]):
        self.span = span
        self._token = None
        self._t0 = 0.0
        self._dropped = False

    def drop(self) -> None:
        """Leave no record of this span at exit: what it timed turned out
        not to be the thing it names (a native attempt that fell back to
        the bridged path, which opens the request's one real span)."""
        self._dropped = True

    def __enter__(self) -> Optional[Span]:
        if self.span is not None:
            self._t0 = time.perf_counter()
            self._token = _current.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.span is None:
            return
        _current.reset(self._token)
        self.span.duration = time.perf_counter() - self._t0
        if exc_type is not None:
            self.span.status = "error"
            self.span.tags.setdefault("error", exc_type.__name__)
        if not self._dropped:
            self._finish()

    def _finish(self) -> None:
        _finish(self.span, "request")


def _finish(span: Span, what: str, quiet: bool = False) -> None:
    RING.add(span)
    slow = slow_threshold_s()
    if slow and not quiet and span.duration >= slow:
        glog.warning(
            "slow %s: %s %s took %.1fms (trace %s span %s)",
            what, span.service, span.name, span.duration * 1000.0,
            span.trace_id, span.span_id,
        )


def start_span(
    name: str,
    service: str = "",
    parent_header: Optional[str] = None,
    **tags,
) -> _SpanScope:
    """Open a span: parented on ``parent_header`` (an inbound
    X-Sweed-Trace value) when given, else on the context's active span,
    else a fresh root trace. Usable as ``with start_span(...) as span:``;
    yields None (and records nothing) when tracing is off."""
    if not enabled():
        return _SpanScope(None)
    trace_id, parent_id = parse_header(parent_header)
    if not trace_id:
        cur = _current.get()
        if cur is not None:
            trace_id, parent_id = cur.trace_id, cur.span_id
    span = Span(name, service=service, trace_id=trace_id,
                parent_id=parent_id)
    if tags:
        span.tags.update(tags)
    return _SpanScope(span)


_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where JAX is already in this
    process, else None. Looked up, never imported: a chipless daemon that
    never seals never loads JAX, and tracing must not be what does."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _stage(name: str, tags: dict) -> Span:
    """A span under the context's active one, in its service."""
    cur = _current.get()
    if cur is None:
        span = Span(name)
    else:
        span = Span(name, service=cur.service, trace_id=cur.trace_id,
                    parent_id=cur.span_id)
    span.tags.update(tags)
    return span


def _finish_stage(span: Span, quiet: bool) -> None:
    STAGES.add(span)
    _finish(span, "stage", quiet)


class _StageScope(_SpanScope):
    """A span's scope that also enters the profiler's annotation of the
    same name, and closes into the stage table."""

    __slots__ = ("_quiet", "_annotated")

    def __init__(self, span: Optional[Span], quiet: bool):
        super().__init__(span)
        self._quiet = quiet
        self._annotated = None

    def __enter__(self) -> Optional[Span]:
        if self.span is not None:
            annotation = _trace_annotation()
            if annotation is not None:
                # the code a recovery decodes at goes into the export too
                geometry = self.span.tags.get("geometry")
                self._annotated = (
                    annotation(self.span.name, geometry=geometry)
                    if geometry else annotation(self.span.name)
                )
                self._annotated.__enter__()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        super().__exit__(exc_type, exc, tb)
        if self._annotated is not None:
            self._annotated.__exit__(exc_type, exc, tb)

    def _finish(self) -> None:
        _finish_stage(self.span, self._quiet)


def stage_span(name: str, quiet: bool = False, **tags) -> _StageScope:
    """Open a stage span under the context's active span: a ``start_span``
    that at close also adds itself to ``STAGES`` under ``name`` and sits in
    a JAX profiler session's trace under the same name. The tags
    ``bytes``, ``failed`` and ``slept_s`` are summed into the table; the
    stage may set them on the span it is handed while it runs. A stage
    slower than ``SWEED_TRACE_SLOW_MS`` logs one line naming it, unless
    ``quiet``: a whole operation that is always long (a seal, its pipeline,
    its commit) is found through its children, not by a line per seal."""
    if not enabled():
        return _StageScope(None, quiet)
    return _StageScope(_stage(name, tags), quiet)


def record_stage(name: str, busy_s: float, ended_ago_s: float = 0.0,
                 **tags) -> None:
    """A stage that took ``busy_s`` and ended ``ended_ago_s`` ago (now,
    unless said), for time that is not one ``with`` block on this thread: a
    transfer begun on another thread, the reads of one loop taken together,
    a request's wait for its worker written once the request's span is
    open. Ring, table and slow line as ``stage_span``; the profiler's trace
    cannot be written in hindsight."""
    if not enabled():
        return
    span = _stage(name, tags)
    # sweedlint: ok cross-domain-race a span made here, finished here: in no ring and no table until _finish_stage below
    span.start -= ended_ago_s + busy_s
    # sweedlint: ok cross-domain-race as above
    span.duration = busy_s
    _finish_stage(span, quiet=False)


def add_stage_count(key: str, n=1) -> None:
    """Add ``n`` to the tag ``key`` of the span this code runs in, if any:
    one of ``StageTable.SUMMED_TAGS`` is summed into a stage's row. The
    code that counts learns no span name."""
    span = _current.get()
    if span is not None:
        span.tags[key] = span.tags.get(key, 0) + n


def add_stage_bytes(n: int) -> None:
    """Count ``n`` bytes moved against the stage span this code runs in."""
    add_stage_count("bytes", n)


def h_debug_traces(handler, path, query, body):
    """Shared ``GET /debug/traces`` route handler: the daemon's view of
    the span ring. ``?trace=<id>`` filters to one trace; ``?limit=N``
    bounds the unfiltered tail (default 256)."""
    trace_id = query.get("trace", "").strip()
    raw = query.get("limit", "256").strip()
    limit = int(raw) if raw.isascii() and raw.isdigit() else 256
    spans = (RING.for_trace(trace_id) if trace_id
             else RING.snapshot(min(limit, 4096)))
    return 200, {
        "service": getattr(handler, "trace_service", ""),
        "ring": RING.stats(),
        "spans": spans,
    }


def assemble_tree(spans: list[dict]) -> list[dict]:
    """Parent-linked forest from a flat span list (deduped by span id):
    each node is the span dict plus a ``children`` list, children sorted
    by start time. Roots are spans whose parent is absent from the set —
    sorted by start so concurrent root fragments read chronologically."""
    by_id: dict[str, dict] = {}
    for s in spans:
        node = dict(s)
        node["children"] = []
        by_id.setdefault(node["span_id"], node)
    roots = []
    for node in by_id.values():
        parent = by_id.get(node["parent_id"]) if node["parent_id"] else None
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=lambda n: n["start"])
    roots.sort(key=lambda n: n["start"])
    return roots


def format_tree(roots: list[dict]) -> str:
    """Human-readable tree with per-hop timings for ``weed shell trace``."""
    lines: list[str] = []

    def walk(node: dict, depth: int) -> None:
        tag_bits = ""
        status = node.get("status", "ok")
        if status != "ok":
            tag_bits += f" [{status}]"
        http_status = node.get("tags", {}).get("status")
        if http_status is not None:
            tag_bits += f" ({http_status})"
        lines.append(
            f"{'  ' * depth}{node['service'] or '?'} {node['name']} "
            f"{node['duration_ms']}ms{tag_bits} "
            f"span={node['span_id']}"
        )
        for child in node["children"]:
            walk(child, depth + 1)

    for root in roots:
        walk(root, 0)
    return "\n".join(lines)
