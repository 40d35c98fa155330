"""The native engine's share of a proxied request's serving path (ISSUE 39):
the one header it adds to the bytes it forwards, its two sums beside
``proxied``, and the ``serve.proxy`` row of a volume server's ``/status``."""

from __future__ import annotations

import socket
import threading
import time

import pytest

from seaweedfs_tpu.server.http_util import (
    PROXY_T0_HEADER,
    http_bytes_headers,
    http_json,
)
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.trace import RING

try:
    from seaweedfs_tpu.native.turbo import TurboEngine, turbo_available
except Exception:  # pragma: no cover - loader itself failed
    def turbo_available():
        return False

pytestmark = pytest.mark.skipif(
    not turbo_available(), reason="native turbo library unavailable"
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


class _Backend:
    """A socket that answers every connection 200 and keeps what it was sent."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.seen: list[bytes] = []
        self.delay_s = 0.0
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            raw = b""
            while b"\r\n\r\n" not in raw:
                raw += conn.recv(65536)
            head, _, body = raw.partition(b"\r\n\r\n")
            want = 0
            for line in head.split(b"\r\n")[1:]:
                k, _, v = line.partition(b":")
                if k.lower() == b"content-length":
                    want = int(v)
            while len(body) < want:
                body += conn.recv(65536)
            self.seen.append(head + b"\r\n\r\n" + body)
            time.sleep(self.delay_s)
            conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok")
            conn.close()

    def close(self):
        self.sock.close()


@pytest.fixture()
def proxied():
    backend = _Backend()
    port = _free_port()
    engine = TurboEngine("127.0.0.1", port, "127.0.0.1", backend.port)
    yield engine, backend, port
    engine.stop()
    backend.close()


def ask(port: int, raw: bytes) -> bytes:
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(raw)
        got = b""
        while not got.endswith(b"ok"):
            chunk = s.recv(65536)
            if not chunk:
                break
            got += chunk
    return got


@pytest.mark.parametrize("raw", [
    b"GET /status HTTP/1.1\r\nHost: x\r\nX-Mine: 1\r\n\r\n",
    b"POST /admin/thing?a=b HTTP/1.1\r\nHost: x\r\nContent-Length: 12\r\n\r\n"
    b"hello\r\n\r\nbye",
    # more than a socket buffer holds: the send goes on where it stopped
    b"POST /admin/big HTTP/1.1\r\nHost: x\r\nContent-Length: 3145728\r\n\r\n"
    + bytes(range(256)) * 12288,
], ids=["get", "post-with-body", "post-3MiB"])
def test_the_engine_forwards_the_request_unchanged_but_for_its_stamp(proxied, raw):
    engine, backend, port = proxied
    before = time.monotonic_ns()
    assert ask(port, raw).startswith(b"HTTP/1.1 200")
    after = time.monotonic_ns()
    (seen,) = backend.seen
    line, _, rest = seen.partition(b"\r\n")
    stamp, _, rest = rest.partition(b"\r\n")
    # the request line, ONE header more, then every byte as it was sent
    assert line + b"\r\n" + rest == raw
    name, _, value = stamp.partition(b": ")
    assert name.decode() == PROXY_T0_HEADER
    # CLOCK_MONOTONIC nanoseconds: this process's time.monotonic_ns()
    assert before <= int(value) <= after


def test_the_engine_sums_the_proxied_requests_wall_and_their_connects(proxied):
    engine, backend, port = proxied
    zero = engine.counters()
    assert (zero["proxied"], zero["proxy_ns"], zero["proxy_connect_ns"]) == (0, 0, 0)
    backend.delay_s = 0.02
    n = 5
    for _ in range(n):
        ask(port, b"GET /status HTTP/1.1\r\nHost: x\r\n\r\n")
    deadline = time.monotonic() + 5
    while engine.counters()["proxy_ns"] < n * 0.02e9 and time.monotonic() < deadline:
        time.sleep(0.01)  # the sum is added once the last byte has gone
    c = engine.counters()
    assert c["proxied"] == n
    assert c["proxy_ns"] >= n * 0.02e9  # the backend's delay is inside it
    assert 0 < c["proxy_connect_ns"] <= c["proxy_ns"]
    assert set(c) == {"gets", "posts", "deletes", "proxied", "proxy_ns",
                      "proxy_connect_ns"}


@pytest.fixture()
def cluster(tmp_path):
    ms = MasterServer(host="127.0.0.1", port=_free_port(),
                      node_timeout=60).start()
    vs = VolumeServer(
        [str(tmp_path)], host="127.0.0.1", port=_free_port(),
        master_url=ms.url, pulse_seconds=0.5,
    ).start()
    assert vs.turbo is not None, "turbo should engage in the default config"
    yield ms, vs
    vs.stop()
    ms.stop()


def test_status_serves_the_engines_sums_as_the_serve_proxy_row(cluster):
    _, vs = cluster
    url = f"http://127.0.0.1:{vs.port}/status"
    for _ in range(3):
        http_json("GET", url)
    row = http_json("GET", url)["ec_codec"]["stages"]["serve.proxy"]
    # this ask included: counted when it was taken up, summed when it ends
    assert row["n"] == vs.turbo.counters()["proxied"] >= 4
    assert set(row) == {"n", "busy_s", "connect_s"}
    assert 0 < row["connect_s"] <= row["busy_s"]
    # and nowhere else: the Prometheus text keeps its four counters
    _, text, _ = http_bytes_headers("GET", f"http://127.0.0.1:{vs.port}/metrics")
    assert b"proxy_ns" not in text and b'op="proxied"' in text


def test_a_proxied_requests_tree_begins_with_the_proxys_way_in(cluster):
    _, vs = cluster
    _, _, headers = http_bytes_headers("GET", f"http://127.0.0.1:{vs.port}/status")
    tid = {k.lower(): v for k, v in headers.items()}[trace.TRACE_ID_HEADER.lower()]
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        spans = RING.for_trace(tid)
        if any(s["name"] == "GET /status" for s in spans):
            break
        time.sleep(0.01)
    names = [s["name"] for s in sorted(spans, key=lambda s: s["start"])]
    assert names[0] == "serve.proxy.in" and "serve.queue" in names
    (request,) = [s for s in spans if s["name"] == "GET /status"]
    (way_in,) = [s for s in spans if s["name"] == "serve.proxy.in"]
    assert way_in["parent_id"] == request["span_id"]
    assert 0 < way_in["duration_ms"] < 5000


def test_with_tracing_off_status_serves_no_row(cluster, monkeypatch):
    _, vs = cluster
    monkeypatch.setenv("SWEED_TRACE", "0")
    codec = http_json("GET", f"http://127.0.0.1:{vs.port}/status")["ec_codec"]
    assert "stages" not in codec
