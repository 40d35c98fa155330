"""The serving path of a bridged request as spans (ISSUE 39): what a request
passes between the socket and its handler, and after it — the proxy's way
in, a native attempt that fell back, the wait for a worker, the parse, the
reply — written as children of the request's own span and as rows of the
stage table, on an in-process server of each core."""

from __future__ import annotations

import http.client
import threading
import time

import pytest

from seaweedfs_tpu.server import http_util
from seaweedfs_tpu.server.http_util import (
    NATIVE_FALLBACK,
    PROXY_T0_HEADER,
    JsonHandler,
    StreamBody,
    start_server,
)
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.trace import RING, STAGES

class _App(JsonHandler):
    trace_service = "svc"
    gate = threading.Event()  # /hold parks here
    parked = threading.Semaphore(0)  # one release a handler parked

    def log_message(self, fmt, *args):
        pass


def _routes():
    def ping(h, path, q, body):
        return 200, {"ok": True}

    def blob(h, path, q, body):
        return 200, b"x" * 1000

    def stream(h, path, q, body):
        pieces = [b"ab" * 8, b"cd" * 8]
        return 200, StreamBody(32, iter(pieces))

    def boom(h, path, q, body):
        raise RuntimeError("boom")

    def hold(h, path, q, body):
        _App.parked.release()
        _App.gate.wait(10)
        return 200, {"held": True}

    return [("GET", "/ping", ping), ("HEAD", "/ping", ping),
            ("GET", "/blob", blob), ("GET", "/stream", stream),
            ("GET", "/boom", boom), ("GET", "/hold", hold),
            ("GET", "/", ping)]


async def _native_miss(req, path, q):
    return NATIVE_FALLBACK


async def _native_hit(req, path, q):
    return 200, {"native": True}


_App.routes = _routes()
_App.native_routes = [("GET", "/blob", _native_miss),
                      ("GET", "/stream", _native_hit),
                      ("GET", "/", _native_miss)]


@pytest.fixture()
def core(monkeypatch, request):
    """An in-process server of the asked core: (mode, connection factory)."""
    mode = getattr(request, "param", "aio")
    monkeypatch.setenv("SWEED_SERVING", mode)
    monkeypatch.setenv("SWEED_AIO_WORKERS", "2")
    monkeypatch.setenv("SWEED_MAX_INFLIGHT", "8192")
    monkeypatch.delenv("SWEED_TRACE", raising=False)
    _App.gate.clear()
    srv = start_server(_App, "127.0.0.1", 0)
    port = srv.server_address[1]
    conns = []

    def connect():
        conns.append(http.client.HTTPConnection("127.0.0.1", port, timeout=10))
        return conns[-1]

    yield mode, connect
    _App.gate.set()
    for c in conns:
        c.close()
    srv.shutdown()
    srv.server_close()


def get(conn, path, headers=None, method="GET"):
    """(status, body, trace id) of one request on ``conn``."""
    conn.request(method, path, headers=headers or {})
    r = conn.getresponse()
    return r.status, r.read(), r.getheader(trace.TRACE_ID_HEADER)


def spans_of(tid, request_name, timeout=5.0):
    """The trace's spans once its request span (which closes last) is in."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = RING.for_trace(tid)
        if any(s["name"] == request_name for s in spans):
            return spans
        time.sleep(0.005)
    raise AssertionError(f"{request_name} never closed: {RING.for_trace(tid)}")


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def end(span):
    return span["start"] + span["duration_ms"] / 1e3


def rows(before, after, name):
    a, b = after.get(name, {}), before.get(name, {})
    return {k: a[k] - b.get(k, 0) for k in a}


# -- the aio core: the legs of one bridged request ------------------------------
def test_a_bridged_request_is_its_span_and_its_three_legs(core):
    _, connect = core
    before = STAGES.snapshot()
    status, _, tid = get(connect(), "/ping")
    assert status == 200
    spans = by_name(spans_of(tid, "GET /ping"))
    assert sorted(spans) == ["GET /ping", "serve.parse", "serve.queue",
                             "serve.reply"]
    (request,) = spans["GET /ping"]
    legs = [spans[n][0] for n in ("serve.queue", "serve.parse", "serve.reply")]
    for leg in legs:
        assert leg["parent_id"] == request["span_id"]
        assert leg["trace_id"] == request["trace_id"]
        assert leg["service"] == "svc"
        # no child ends after its parent (a millisecond of two clocks)
        assert end(leg) <= end(request) + 1e-3
    # in time order: the wait, the parse up to the span's opening, the reply
    assert [s["start"] for s in legs] == sorted(s["start"] for s in legs)
    assert end(legs[0]) <= legs[1]["start"] + 1e-3
    assert end(legs[1]) == pytest.approx(request["start"], abs=2e-3)
    assert legs[2]["start"] >= request["start"] - 1e-3
    # and each a row of the stage table, the request under its route's name
    # (the table is the process's: another server's legs may land meanwhile)
    after = STAGES.snapshot()
    for name in ("serve.queue", "serve.parse", "serve.reply"):
        assert rows(before, after, name)["n"] >= 1, name
    assert rows(before, after, "GET /ping")["n"] == 1
    assert rows(before, after, "GET /ping")["busy_s"] == pytest.approx(
        request["duration_ms"] / 1e3, abs=1e-5)


def test_the_legs_join_the_trace_a_peer_sent(core):
    _, connect = core
    status, _, tid = get(connect(), "/ping",
                         {trace.TRACE_HEADER: "feedfacefeedface:0badf00d"})
    assert status == 200 and tid == "feedfacefeedface"
    spans = by_name(spans_of(tid, "GET /ping"))
    (request,) = spans["GET /ping"]
    assert request["parent_id"] == "0badf00d"
    for name in ("serve.queue", "serve.parse", "serve.reply"):
        assert spans[name][0]["parent_id"] == request["span_id"]


def test_the_proxys_stamp_becomes_the_way_in(core):
    _, connect = core
    before = STAGES.snapshot()
    t0 = time.monotonic_ns() - 5_000_000
    sent = time.monotonic()
    status, _, tid = get(connect(), "/ping", {PROXY_T0_HEADER: str(t0)})
    slack = time.monotonic() - sent  # the request's whole round trip
    spans = by_name(spans_of(tid, "GET /ping"))
    (way_in,) = spans["serve.proxy.in"]
    assert 5.0 <= way_in["duration_ms"] <= 5.0 + slack * 1e3 + 1.0
    assert way_in["parent_id"] == spans["GET /ping"][0]["span_id"]
    # the first of the legs, ending where the loop had the head
    assert way_in["start"] <= spans["serve.queue"][0]["start"]
    assert end(way_in) <= spans["serve.queue"][0]["start"] + 1e-3
    row = rows(before, STAGES.snapshot(), "serve.proxy.in")
    assert row["n"] >= 1
    assert row["busy_s"] >= way_in["duration_ms"] / 1e3 - 1e-5


@pytest.mark.parametrize("stamp", [
    "garbage", "", "-5", "12.5", "١٢٣",
    lambda: time.monotonic_ns() + 5_000_000_000,  # the future: another clock
    lambda: time.monotonic_ns() - 61_000_000_000,  # over a minute old
], ids=["garbage", "empty", "negative", "float", "unicode-digits", "future",
        "stale"])
def test_a_stamp_that_is_not_this_clocks_is_dropped(core, stamp):
    _, connect = core
    if callable(stamp):
        stamp = str(stamp())
        if int(stamp) <= 0:
            pytest.skip("this machine's monotonic clock is under a minute old")
    status, _, tid = get(connect(), "/ping",
                         {PROXY_T0_HEADER: stamp.encode("utf-8").decode("latin-1")})
    assert status == 200
    spans = by_name(spans_of(tid, "GET /ping"))
    assert "serve.proxy.in" not in spans and "serve.queue" in spans


def test_the_queue_span_is_the_wait_for_a_worker_not_the_hand_off(core):
    _, connect = core
    holders = [connect() for _ in range(2)]  # SWEED_AIO_WORKERS=2
    for c in holders:
        c.request("GET", "/hold")
    for _ in holders:
        assert _App.parked.acquire(timeout=5)  # both workers are parked
    waiter = connect()
    waiter.request("GET", "/ping")
    held = 0.15
    time.sleep(held)
    _App.gate.set()
    r = waiter.getresponse()
    r.read()
    tid = r.getheader(trace.TRACE_ID_HEADER)
    for c in holders:
        c.getresponse().read()
    spans = by_name(spans_of(tid, "GET /ping"))
    (queue,) = spans["serve.queue"]
    assert queue["duration_ms"] >= held * 1e3 - 5.0
    # the wait is outside the request span, which only the handler is in
    assert spans["GET /ping"][0]["duration_ms"] < held * 1e3 / 2
    assert end(queue) <= spans["GET /ping"][0]["start"] + 1e-3


def test_with_tracing_off_the_stamps_cost_no_span(core, monkeypatch):
    _, connect = core
    monkeypatch.setenv("SWEED_TRACE", "0")
    before, ring = STAGES.snapshot(), RING.stats()["added"]
    conn = connect()
    for path in ("/ping", "/blob", "/stream"):
        status, _, tid = get(conn, path, {PROXY_T0_HEADER: str(
            time.monotonic_ns() - 1_000_000)})
        assert status == 200 and tid is None
    assert STAGES.snapshot() == before
    assert RING.stats()["added"] == ring


def test_every_request_of_a_kept_connection_has_its_own_stamps(core):
    _, connect = core
    conn = connect()
    _, _, missed = get(conn, "/blob")  # reaches a native route, falls back
    _, _, plain = get(conn, "/ping")  # reaches none
    first = by_name(spans_of(missed, "GET /blob"))
    second = by_name(spans_of(plain, "GET /ping"))
    assert "serve.native.miss" in first
    assert "serve.native.miss" not in second
    assert second["serve.queue"][0]["start"] >= end(first["GET /blob"][0]) - 1e-3


# -- the native attempt ---------------------------------------------------------------
def test_a_native_attempt_that_falls_back_leaves_one_request_span(core):
    """An EC volume's GET: the native route hands it back, and the ring
    holds ONE ``GET /`` of it, the attempt its child."""
    _, connect = core
    before = STAGES.snapshot()
    status, _, tid = get(connect(), "/3,01637037d6")
    assert status == 200
    spans = spans_of(tid, "GET /")
    assert [s["name"] for s in spans].count("GET /") == 1
    named = by_name(spans)
    (request,) = named["GET /"]
    (miss,) = named["serve.native.miss"]
    assert miss["parent_id"] == request["span_id"]
    assert end(miss) <= named["serve.queue"][0]["start"] + 1e-3
    assert rows(before, STAGES.snapshot(), "serve.native.miss")["n"] >= 1
    # nothing of the attempt but that: no second request span anywhere
    time.sleep(0.05)
    recent = [s for s in RING.snapshot(64) if s["name"] == "GET /"
              and s["start"] >= request["start"] - 1.0]
    assert [s["span_id"] for s in recent] == [request["span_id"]]


def test_a_native_route_that_serves_keeps_its_request_span_as_it_was(core):
    _, connect = core
    before = STAGES.snapshot()
    status, body, tid = get(connect(), "/stream")
    assert status == 200 and b"native" in body
    spans = spans_of(tid, "GET /stream")
    assert [s["name"] for s in spans] == ["GET /stream"]
    # no leg of it in the ring, and no row under its route's name
    assert not rows(before, STAGES.snapshot(), "GET /stream").get("n")


def test_a_request_that_reaches_no_native_route_has_no_miss(core):
    _, connect = core
    _, _, tid = get(connect(), "/ping")
    assert "serve.native.miss" not in by_name(spans_of(tid, "GET /ping"))


# -- the reply, and the request's own row -------------------------------------------
@pytest.mark.parametrize("core", ["aio", "threads"], indirect=True)
@pytest.mark.parametrize("method,path,length", [
    ("GET", "/blob", 1000), ("GET", "/ping", len(b'{"ok": true}')),
    ("HEAD", "/ping", 0),
])
def test_the_reply_is_a_stage_that_sums_the_bodys_bytes(core, method, path,
                                                        length):
    _, connect = core
    before = STAGES.snapshot()
    status, _, tid = get(connect(), path, method=method)
    assert status == 200
    name = f"{method} {path}"
    spans = by_name(spans_of(tid, name))
    (reply,) = spans["serve.reply"]
    assert reply["tags"].get("bytes", 0) == length
    assert reply["parent_id"] == spans[name][0]["span_id"]
    assert reply["start"] >= spans[name][0]["start"] - 1e-3
    assert rows(before, STAGES.snapshot(), "serve.reply").get("bytes", 0) >= length


@pytest.mark.parametrize("core", ["aio", "threads"], indirect=True)
def test_a_failed_request_is_counted_in_its_routes_row(core):
    _, connect = core
    before = STAGES.snapshot()
    status, _, tid = get(connect(), "/boom")
    assert status == 500
    (request,) = by_name(spans_of(tid, "GET /boom"))["GET /boom"]
    assert request["status"] == "error" and request["tags"]["failed"] == 1
    get(connect(), "/ping")
    after = STAGES.snapshot()
    assert rows(before, after, "GET /boom") == {
        "n": 1, "busy_s": pytest.approx(request["duration_ms"] / 1e3, abs=1e-5),
        "failed": 1}
    assert not rows(before, after, "GET /ping").get("failed")


# -- the threads core ------------------------------------------------------------------
@pytest.mark.parametrize("core", ["threads"], indirect=True)
def test_the_threads_core_records_the_parse_and_the_reply_only(core):
    _, connect = core
    before = STAGES.snapshot()
    conn = connect()
    status, _, tid = get(conn, "/blob", {PROXY_T0_HEADER: str(
        time.monotonic_ns() - 1_000_000)})
    assert status == 200
    spans = by_name(spans_of(tid, "GET /blob"))
    assert sorted(spans) == ["GET /blob", "serve.parse", "serve.reply"]
    (request,) = spans["GET /blob"]
    (parse,) = spans["serve.parse"]
    assert parse["parent_id"] == request["span_id"]
    assert end(parse) == pytest.approx(request["start"], abs=2e-3)
    # a kept connection's next request is stamped anew, from ITS line's read
    time.sleep(0.05)
    _, _, again = get(conn, "/ping")
    (parse2,) = by_name(spans_of(again, "GET /ping"))["serve.parse"]
    assert parse2["duration_ms"] < 40.0
    assert rows(before, STAGES.snapshot(), "serve.parse")["n"] >= 2


# -- a stage that ended earlier -------------------------------------------------------
@pytest.mark.parametrize("ago", [0.0, 0.25])
def test_a_stage_recorded_in_hindsight_agrees_with_itself(ago):
    before = STAGES.snapshot()
    with trace.start_span("GET /hindsight", service="svc") as request:
        now = time.time()
        trace.record_stage("serve.test.leg", 0.5, ended_ago_s=ago, bytes=7)
    (leg,) = [s for s in RING.for_trace(request.trace_id)
              if s["name"] == "serve.test.leg"]
    assert leg["parent_id"] == request.span_id and leg["service"] == "svc"
    assert leg["duration_ms"] == 500.0
    assert leg["start"] == pytest.approx(now - ago - 0.5, abs=0.02)
    assert end(leg) == pytest.approx(now - ago, abs=0.02)
    assert rows(before, STAGES.snapshot(), "serve.test.leg") == {
        "n": 1, "busy_s": 0.5, "bytes": 7}


def test_a_stage_recorded_with_tracing_off_is_not_recorded(monkeypatch):
    monkeypatch.setenv("SWEED_TRACE", "0")
    before = STAGES.snapshot()
    trace.record_stage("serve.test.leg", 0.5, ended_ago_s=0.1)
    assert STAGES.snapshot() == before


# -- the serving state is reached through its module --------------------------------
def test_the_aio_core_registers_with_the_state_of_the_moment(monkeypatch):
    """``aio`` is imported by now: a state swapped in afterwards must still
    be the one a new server registers with."""
    import seaweedfs_tpu.server.aio  # noqa: F401

    fresh = http_util._ServingState()
    monkeypatch.setattr(http_util, "SERVING", fresh)
    monkeypatch.setenv("SWEED_SERVING", "aio")
    monkeypatch.setenv("SWEED_AIO_WORKERS", "7")
    srv = start_server(_App, "127.0.0.1", 0)
    try:
        assert fresh.handler_count() == 7
    finally:
        srv.shutdown()
        srv.server_close()
