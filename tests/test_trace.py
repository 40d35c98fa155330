"""Tracing + histogram tests (PR 12 observability).

Covers the span plumbing (parse/inject, parentage, ring bounds, kill
switch), the histogram type (cumulative buckets, quantiles, exemplars,
the Prometheus label-escaping regression), contextvars propagation
across the thread-pool seams (BoundedExecutor, prefetch_iter, the aio
reactor's worker bridge), the threads-vs-aio span-tree parity contract,
and the end-to-end acceptance path: one client request against a live
master+volume+filer cluster yields one trace id whose `weed shell trace`
tree holds filer, master and volume spans with consistent parentage —
under BOTH serving cores.
"""

from __future__ import annotations

import os
import socket
import time

import pytest

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.histogram import Histogram, _fmt_labels
from seaweedfs_tpu.stats.metrics import Counter
from seaweedfs_tpu.stats.trace import (
    RING,
    Span,
    TraceRing,
    assemble_tree,
    format_tree,
    inject_header,
    parse_header,
    start_span,
)


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _clean_ring():
    RING.clear()
    yield
    RING.clear()


# ------------------------------------------------------------ span basics


def test_parse_header_roundtrip():
    with start_span("op", service="t") as s:
        hdr = inject_header()
        assert hdr == f"{s.trace_id}:{s.span_id}"
        assert parse_header(hdr) == (s.trace_id, s.span_id)


@pytest.mark.parametrize("garbage", [
    None, "", "justtrace", ":", "abc:", ":def",
    "has space:abcd1234", "tid:pid:extra\r\nInjected: yes",
    "ффф:1234",  # non-ascii
])
def test_parse_header_rejects_garbage(garbage):
    assert parse_header(garbage) == ("", "")


def test_span_parentage_context_nesting():
    with start_span("outer", service="a") as outer:
        with start_span("inner", service="b") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        # after inner closes, the contextvar window is restored
        assert trace.current_span() is outer
    assert trace.current_span() is None


def test_explicit_header_wins_over_context_parent():
    with start_span("ambient", service="a"):
        with start_span("child", service="b",
                        parent_header="feedfacefeedface:cafe0001") as s:
            assert s.trace_id == "feedfacefeedface"
            assert s.parent_id == "cafe0001"


def test_error_span_records_status_and_tag():
    with pytest.raises(ValueError):
        with start_span("boom", service="t"):
            raise ValueError("nope")
    spans = RING.snapshot()
    assert spans[-1]["status"] == "error"
    assert spans[-1]["tags"]["error"] == "ValueError"


def test_ring_is_bounded():
    ring = TraceRing(capacity=4)
    for i in range(10):
        ring.add(Span(f"s{i}", service="t"))
    st = ring.stats()
    assert st["size"] == 4 and st["added"] == 10 and st["dropped"] == 6
    assert [s["name"] for s in ring.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_kill_switch_disables_everything(monkeypatch):
    monkeypatch.setenv("SWEED_TRACE", "0")
    with start_span("op", service="t") as s:
        assert s is None
        assert inject_header() is None
        assert trace.current_trace_id() == ""
    assert RING.snapshot() == []


def test_assemble_tree_dedups_and_links():
    with start_span("root", service="m") as root:
        with start_span("child", service="v"):
            pass
    spans = RING.for_trace(root.trace_id)
    # the shell collector sees the same span from several daemons' rings
    roots = assemble_tree(spans + [dict(spans[0])])
    assert len(roots) == 1
    assert roots[0]["name"] == "root"
    assert [c["name"] for c in roots[0]["children"]] == ["child"]
    text = format_tree(roots)
    lines = text.splitlines()
    assert lines[0].startswith("m root ")
    assert lines[1].startswith("  v child ")


# ------------------------------------------------------------- histogram


def test_histogram_cumulative_buckets_and_exposition():
    h = Histogram("t_seconds", "test", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v, trace_id="", op="get")
    assert h.count(op="get") == 4
    out = "\n".join(h.expose())
    assert 't_seconds_bucket{le="0.01",op="get"} 1' in out
    assert 't_seconds_bucket{le="0.1",op="get"} 2' in out
    assert 't_seconds_bucket{le="1.0",op="get"} 3' in out
    assert 't_seconds_bucket{le="+Inf",op="get"} 4' in out
    assert 't_seconds_count{op="get"} 4' in out


def test_histogram_quantile_interpolates():
    h = Histogram("t_seconds", "test", buckets=(0.1, 0.2, 0.4))
    for _ in range(90):
        h.observe(0.05, trace_id="", op="x")
    for _ in range(10):
        h.observe(0.3, trace_id="", op="x")
    p50 = h.quantile(0.5, op="x")
    assert p50 is not None and 0.0 < p50 <= 0.1
    p99 = h.quantile(0.99, op="x")
    assert p99 is not None and 0.2 < p99 <= 0.4
    s = h.summary(op="x")
    assert s["count"] == 100 and s["p50_ms"] <= 100 and s["p99_ms"] > 200


def test_histogram_exemplar_carries_ambient_trace_id():
    h = Histogram("t_seconds", "test", buckets=(0.1, 1.0))
    with start_span("req", service="t") as s:
        h.observe(0.05, op="get")  # trace id picked up from the span
    out = "\n".join(h.expose())
    assert f'# {{trace_id="{s.trace_id}"}} 0.05' in out


def test_fmt_labels_escapes_prometheus_specials():
    """Satellite regression: `"`, `\\` and newlines in label values must
    be escaped per the Prometheus text format, not emitted raw."""
    got = _fmt_labels({"path": 'a"b\\c\nd'})
    assert got == '{path="a\\"b\\\\c\\nd"}'
    # and through a full exposition line
    h = Histogram("t_seconds", "test", buckets=(1.0,))
    h.observe(0.5, trace_id='t"\\n', op='o"p')
    out = "\n".join(h.expose())
    assert 'op="o\\"p"' in out
    assert 'trace_id="t\\"\\\\n"' in out


def test_counter_value_is_locked_read():
    c = Counter("t_total", "test")
    c.inc(op="a")
    c.inc(op="a")
    assert c.value(op="a") == 2
    assert c.value(op="missing") == 0


# ------------------------------------- contextvars across thread seams


def test_bounded_executor_propagates_span():
    from seaweedfs_tpu.util.pipeline import BoundedExecutor

    seen = []
    with start_span("producer", service="t") as s:
        ex = BoundedExecutor(window=2, name="t")
        for _ in range(4):
            ex.submit(lambda: seen.append(trace.current_trace_id()))
        ex.drain()
    assert seen == [s.trace_id] * 4


def test_prefetch_iter_propagates_span():
    from seaweedfs_tpu.util.pipeline import prefetch_iter

    with start_span("consumer", service="t") as s:
        pairs = list(prefetch_iter(
            range(4), lambda i: (i, trace.current_trace_id()), window=3
        ))
    assert [tid for _, (_, tid) in pairs] == [s.trace_id] * 4


def test_thread_flume_bridges_bytes_not_context():
    """ThreadFlume is a pure byte channel between the handler thread and
    the aio loop: the producing thread keeps its span across blocking
    backpressure puts, and nothing leaks into the loop-side context —
    bytes cross the seam, the contextvar does not need to."""
    import asyncio
    import threading

    from seaweedfs_tpu.util.aio_pipeline import ThreadFlume

    results: dict = {}

    async def consume(flume):
        chunks = []
        async for c in flume:
            chunks.append(c)
        results["loop_tid"] = trace.current_trace_id()
        return b"".join(chunks)

    def produce(flume):
        with start_span("producer", service="t") as s:
            results["tid"] = s.trace_id
            for _ in range(8):  # window=2 → blocks on backpressure
                flume.put(b"x" * 10, timeout=5)
            results["tid_after"] = trace.current_trace_id()
        flume.close()

    async def main():
        flume = ThreadFlume(asyncio.get_running_loop(), window=2)
        t = threading.Thread(target=produce, args=(flume,), daemon=True)
        t.start()
        data = await consume(flume)
        t.join(5)
        return data

    loop = asyncio.new_event_loop()
    try:
        data = loop.run_until_complete(main())
    finally:
        loop.close()
    assert data == b"x" * 80
    assert results["tid_after"] == results["tid"]  # survives backpressure
    assert results["loop_tid"] == ""  # no context leak to the loop side


# ------------------------------------------- threads vs aio parity

from seaweedfs_tpu.server.http_util import (  # noqa: E402
    JsonHandler,
    StreamBody,
    http_bytes,
    http_bytes_headers,
    start_server,
)


class _TraceApp(JsonHandler):
    trace_service = "svc"
    self_url = ""  # set once the server is listening

    def log_message(self, fmt, *args):
        pass


def _trace_routes():
    def ping(h, path, q, body):
        return 200, {"ok": True}

    def fan(h, path, q, body):
        # outbound internal call: the transport must inject this span's
        # header so the second hop parents under it
        st, _ = http_bytes("GET", f"http://{_TraceApp.self_url}/ping")
        return 200, {"child": st}

    def stream(h, path, q, body):
        pieces = [b"ab" * 8, b"cd" * 8]
        return 200, StreamBody(sum(len(p) for p in pieces), iter(pieces))

    return [
        ("GET", "/ping", ping),
        ("GET", "/fan", fan),
        ("GET", "/stream", stream),
    ]


_TraceApp.routes = _trace_routes()


def _span_tree_shape(mode):
    """Run GET /fan under `mode`; return the (service, name, depth) shape
    of its assembled span tree."""
    os.environ["SWEED_SERVING"] = mode
    try:
        srv = start_server(_TraceApp, "127.0.0.1", free_port())
    finally:
        os.environ.pop("SWEED_SERVING", None)
    host, port = srv.server_address[:2]
    _TraceApp.self_url = f"{host}:{port}"
    try:
        st, _, hdrs = http_bytes_headers(
            "GET", f"http://{_TraceApp.self_url}/fan"
        )
        assert st == 200
        tid = {k.lower(): v for k, v in hdrs.items()}["x-sweed-trace-id"]
        # the fan span finishes with the reply, but give the ring a beat
        deadline = time.monotonic() + 5
        def closed(trace_id, name):  # a request span closes after its legs
            return any(s["name"] == name for s in RING.for_trace(trace_id))

        # ... and the hop's own span closes on ITS handler's thread, after
        # the reply that lets /fan finish: wait for both
        while (not (closed(tid, "GET /fan") and closed(tid, "GET /ping"))
               and time.monotonic() < deadline):
            time.sleep(0.01)
        spans = RING.for_trace(tid)
        # streamed replies stay inside the server span in both cores
        st2, _, hdrs2 = http_bytes_headers(
            "GET", f"http://{_TraceApp.self_url}/stream"
        )
        assert st2 == 200
        tid2 = {k.lower(): v for k, v in hdrs2.items()}["x-sweed-trace-id"]
        deadline = time.monotonic() + 5
        while not closed(tid2, "GET /stream") and time.monotonic() < deadline:
            time.sleep(0.01)
        stream_spans = RING.for_trace(tid2)
    finally:
        srv.shutdown()
        srv.server_close()

    shape = []

    def walk(node, depth):
        shape.append((node["service"], node["name"], depth))
        for c in node["children"]:
            walk(c, depth + 1)

    for root in assemble_tree(spans):
        walk(root, 0)
    # the request span closes last: its serving legs (serve.*) before it
    assert [(s["service"], s["name"]) for s in stream_spans
            if not s["name"].startswith("serve.")] == [("svc", "GET /stream")]
    assert stream_spans[-1]["name"] == "GET /stream"
    return shape


def test_threads_and_aio_emit_identical_span_trees(monkeypatch):
    """Acceptance: the same request produces the same span tree (service,
    name, parent depth) under both serving cores — the aio reactor's
    executor bridge must not lose the contextvar parentage."""
    monkeypatch.setenv("SWEED_MAX_INFLIGHT", "8192")
    monkeypatch.delenv("SWEED_SERVING", raising=False)
    shapes = {}
    for mode in ("threads", "aio"):
        RING.clear()
        shapes[mode] = _span_tree_shape(mode)
    expected = [("svc", "GET /fan", 0), ("svc", "GET /ping", 1)]

    def hops(shape):
        return [s for s in shape if not s[1].startswith("serve.")]

    assert hops(shapes["threads"]) == expected
    assert hops(shapes["aio"]) == expected
    # each hop's serving legs are its own children, and differ by core as
    # the cores do: the threads core has no loop and no pool to wait for
    legs = {mode: sorted({(name, depth) for _, name, depth in shape
                          if name.startswith("serve.")})
            for mode, shape in shapes.items()}
    assert legs["threads"] == [
        ("serve.parse", 1), ("serve.parse", 2),
        ("serve.reply", 1), ("serve.reply", 2)]
    assert legs["aio"] == sorted(
        legs["threads"] + [("serve.queue", 1), ("serve.queue", 2)])


# ------------------------------------------------- cluster end-to-end


@pytest.mark.parametrize("mode", ["threads", "aio"])
def test_cluster_trace_tree_filer_master_volume(tmp_path, monkeypatch, mode):
    """One PUT and one GET against a live master+volumes+filer cluster
    each yield one trace id whose shell-assembled tree contains filer,
    master (assign) and volume spans with consistent parentage."""
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell import commands as C

    monkeypatch.setenv("SWEED_SERVING", mode)
    monkeypatch.setenv("SWEED_TURBO", "0")  # turbo serves fids without spans
    monkeypatch.setenv("SWEED_MAX_INFLIGHT", "8192")

    master = MasterServer(port=free_port(), node_timeout=60).start()
    volumes = [
        VolumeServer(
            [str(tmp_path / f"srv{i}")],
            port=free_port(),
            master_url=master.url,
            pulse_seconds=0.5,
        ).start()
        for i in range(2)
    ]
    filer = FilerServer(
        port=free_port(), master_url=master.url, chunk_size=64 * 1024
    ).start()
    from seaweedfs_tpu.server.http_util import http_json

    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            info = http_json("GET", f"http://{master.url}/dir/status")
            nodes = [
                n
                for dc in info["topology"]["data_centers"]
                for r in dc["racks"]
                for n in r["nodes"]
            ]
            if len(nodes) >= 2:
                break
            time.sleep(0.1)

        blob = os.urandom(200_000)  # 4 chunks → assign + volume hops
        st, _, hdrs = http_bytes_headers(
            "POST", f"http://{filer.url}/t/trace.bin", blob
        )
        assert st == 201
        put_tid = {k.lower(): v for k, v in hdrs.items()}["x-sweed-trace-id"]

        st, data, hdrs = http_bytes_headers(
            "GET", f"http://{filer.url}/t/trace.bin"
        )
        assert st == 200 and data == blob
        get_tid = {k.lower(): v for k, v in hdrs.items()}["x-sweed-trace-id"]

        env = C.CommandEnv(master=master.url, filer=filer.url)

        def settle(tid, want_services):
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                spans = RING.for_trace(tid)
                ids = {s["span_id"] for s in spans}
                # a hop's serving legs land before the hop's own span
                # closes: settled is every span's parent in hand, but one
                orphans = [s for s in spans if s["parent_id"] not in ids]
                if (want_services <= {s["service"] for s in spans}
                        and len(orphans) == 1):
                    return spans
                time.sleep(0.05)
            return RING.for_trace(tid)

        # PUT: filer root, with master (assign) and volume (write) hops
        put_spans = settle(put_tid, {"filer", "master", "volume"})
        services = {s["service"] for s in put_spans}
        assert {"filer", "master", "volume"} <= services, put_spans
        roots = assemble_tree(put_spans)
        assert len(roots) == 1 and roots[0]["service"] == "filer"
        by_id = {s["span_id"] for s in put_spans}
        for s in put_spans:
            if s["span_id"] != roots[0]["span_id"]:
                assert s["parent_id"] in by_id, s

        # GET: filer root streaming from volume
        get_spans = settle(get_tid, {"filer", "volume"})
        assert {"filer", "volume"} <= {s["service"] for s in get_spans}
        roots = assemble_tree(get_spans)
        assert len(roots) == 1 and roots[0]["service"] == "filer"

        # the shell collector sees the same tree over HTTP
        report = C.trace_collect(env, put_tid)
        assert report["trace_id"] == put_tid
        assert report["span_count"] == len(put_spans)
        assert report["unreachable"] == []
        tree = report["tree"]
        assert tree.splitlines()[0].startswith("filer ")
        assert "master" in tree and "volume" in tree

        # /_status carries the new latency summaries + ring stats
        vs_url = f"{volumes[0].host}:{volumes[0].port}"
        vs_status = http_json("GET", f"http://{vs_url}/status")
        assert "request_latency" in vs_status
        assert vs_status["trace"]["enabled"] is True
        ms_status = http_json("GET", f"http://{master.url}/dir/status")
        assert ms_status["assign"]["count"] >= 1
        # /metrics speaks Prometheus text exposition with bucket counts
        st, payload, _ = http_bytes_headers(
            "GET", f"http://{master.url}/metrics"
        )
        assert st == 200
        text = payload.decode()
        assert "master_assign_seconds_bucket" in text
        assert 'le="+Inf"' in text
    finally:
        filer.stop()
        for v in volumes:
            v.stop()
        master.stop()
