"""A recovery asks its remote siblings at once (``Store._recover_interval``):
the plan along the shard ids (local, listed elsewhere, nowhere), the listed
ones fetched side by side on the store's own workers and exactly k - local of
them, a spare for one that fails, the caller's thread where no worker is
free — against a cluster of two callables (``RemoteShards``) whose ``fetch``
can be held, and counted in the stage table (``ec.recover.fanout``). Counts
and orderings only, never a speed."""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec.constants import Geometry, shard_ext
from seaweedfs_tpu.ec.ec_volume import NotFoundError as EcNotFoundError
from seaweedfs_tpu.server import http_util
from seaweedfs_tpu.server.http_util import JsonHandler, start_server
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.trace import RING, STAGES
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import RemoteShards, Store
from seaweedfs_tpu.util import deadline, retry

VID = 9
ME = "localhost:8080"  # Store's default ip:port, as the master would list it
GEOMETRIES = ["10+4", "12+4"]
WORKER = "ec-sibling"  # the store's workers' thread-name prefix

pytestmark = pytest.mark.usefixtures("time_limit")  # tests/conftest.py


def delta(before: dict, after: dict, stage: str, field: str):
    return (after.get(stage, {}).get(field, 0)
            - before.get(stage, {}).get(field, 0))


def on_a_worker() -> bool:
    return threading.current_thread().name.startswith(WORKER)


class Cluster:
    """A master and holders made of a dict, as ``test_ec_location_table``'s;
    a fetch made on one of the store's workers can be held at a gate, and
    every fetch says which thread made it."""

    def __init__(self, base: str):
        self.base = base
        self.where: dict[int, list[str]] = {}
        self.holds: dict[str, set[int]] = {}
        self.lookups = 0
        self.fetches: list[tuple[str, int, str]] = []  # holder, sid, thread
        self.gate: threading.Event | None = None  # held while not set
        self.flying = self.most_flying = 0
        self.seen: list[tuple[str, float | None]] = []  # trace id, deadline
        self._lock = threading.Lock()

    def place(self, url: str, *sids: int, listed: bool = True) -> None:
        self.holds.setdefault(url, set()).update(sids)
        if listed:
            for sid in sids:
                self.where.setdefault(sid, []).append(url)

    def locate(self, vid: int) -> dict:
        assert vid == VID
        with self._lock:
            self.lookups += 1
        return {sid: list(urls) for sid, urls in self.where.items()}

    def fetch(self, holder, vid, sid, offset, size) -> bytes:
        with self._lock:
            self.fetches.append((holder, sid, threading.current_thread().name))
            self.seen.append((trace.current_trace_id(), deadline.current()))
            self.flying += 1
            self.most_flying = max(self.most_flying, self.flying)
        try:
            if self.gate is not None and on_a_worker():
                assert self.gate.wait(20), "the gate was never opened"
            if sid not in self.holds.get(holder, ()):
                raise ConnectionError(f"{holder} does not answer for {sid}")
            with open(self.base + f".remote{sid:02d}", "rb") as f:
                f.seek(offset)
                return f.read(size)
        finally:
            with self._lock:
                self.flying -= 1

    def asked(self) -> list[int]:
        return sorted(sid for _, sid, _ in self.fetches)

    def wait_flying(self, n: int) -> None:
        until = time.monotonic() + 20
        while self.flying < n:
            assert time.monotonic() < until, f"never {n} fetches in flight"
            time.sleep(0.001)


@pytest.fixture(params=GEOMETRIES)
def sealed(request, tmp_path, monkeypatch):
    """A small EC volume at the parametrised geometry whose needles all lie
    on data shard 0. ``open_store(away, listed)``: shards ``away`` leave the
    store's directory; of them ``listed`` ({url: sids}) can be fetched from a
    peer, the rest are nowhere."""
    monkeypatch.setattr(retry.random, "uniform", lambda lo, hi: hi)
    # no serving core in sight, whatever earlier tests of this process left
    # registered: the store's pool is k workers wide
    monkeypatch.setattr(http_util, "SERVING", http_util._ServingState())
    geometry = Geometry.parse(request.param)
    store = Store([str(tmp_path)], ec_backend="numpy", ec_geometry=geometry)
    store.add_volume(VID)
    rng = np.random.default_rng(34)
    blobs = {i: rng.bytes(3000 + i * 7) for i in range(1, 9)}
    for i, blob in blobs.items():
        store.write_volume_needle(VID, Needle(cookie=3, id=i, data=blob))
    store.ec_encode_volume(VID)
    base = store.find_volume(VID).file_name()
    store.close()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    opened = []

    def open_store(away, listed) -> tuple[Store, Cluster]:
        for sid in away:
            os.rename(base + shard_ext(sid), base + f".remote{sid:02d}")
        s = Store([str(tmp_path)], ec_backend="numpy", ec_geometry=geometry,
                  remote_fetch_attempts=2, remote_fetch_backoff_s=0.001)
        opened.append(s)
        cluster = Cluster(base)
        s.remote_shards = RemoteShards(locate=cluster.locate, fetch=cluster.fetch)
        cluster.place(ME, *(x for x in range(geometry.total_shards)
                            if x not in away))
        for url, sids in listed.items():
            cluster.place(url, *sids)
        return s, cluster

    yield open_store, blobs, geometry
    for s in opened:
        s.close()


def six_remote(geometry: Geometry) -> tuple[list[int], dict]:
    """Shard 0 lost; k - 6 siblings local, the next six along the ids on two
    peers — and every later shard listed on a peer too, never to be asked."""
    k = geometry.data_shards
    first = list(range(k - 5, k + 1))  # the six the plan has to choose
    later = list(range(k + 1, geometry.total_shards))
    away = [0] + first + later
    return away, {"a:1": first[::2] + later, "b:1": first[1::2]}


def read(store: Store, i: int) -> bytes:
    n = Needle(id=i)
    store.read_volume_needle(VID, n)
    return n.data


def fanout(before: dict, after: dict, field: str):
    return delta(before, after, "ec.recover.fanout", field)


# -- side by side, and k - local of them ------------------------------------------
def test_six_listed_siblings_fly_together_and_the_bytes_are_the_walks(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    cluster.gate = threading.Event()
    got = {}
    reader = threading.Thread(target=lambda: got.update(data=read(store, 1)))
    before = STAGES.snapshot()
    reader.start()
    try:
        cluster.wait_flying(2)  # held at the gate: at least two at once
    finally:
        cluster.gate.set()
    reader.join(30)
    after = STAGES.snapshot()
    assert not reader.is_alive()
    assert got["data"] == blobs[1]
    assert cluster.most_flying >= 2
    assert all(t.startswith(WORKER) for _, _, t in cluster.fetches)
    assert fanout(before, after, "n") == 1
    assert fanout(before, after, "width") == 6
    assert fanout(before, after, "spares") == 0
    assert delta(before, after, "ec.recover.remote", "n") == 6


def test_exactly_k_minus_local_siblings_are_asked_never_all_listed(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    k = geometry.data_shards
    for i in blobs:
        assert read(store, i) == blobs[i]
    # each recovery: the six after the k - 6 local ones, and no parity
    # shard beyond them though a peer is listed for each
    assert cluster.asked() == sorted(list(range(k - 5, k + 1)) * len(blobs))
    assert cluster.lookups == 1


def test_a_sibling_the_table_calls_nowhere_is_passed_over_at_once(sealed):
    open_store, blobs, geometry = sealed
    k = geometry.data_shards
    # 0 lost; 2 and 3 nowhere; 4 and 5 on a peer: the walk takes 1, passes
    # over 2 and 3, asks 4 and 5 and goes on to k + 2 for the local rest
    store, cluster = open_store([0, 2, 3, 4, 5], {"a:1": [4, 5]})
    before = STAGES.snapshot()
    assert read(store, 2) == blobs[2]
    after = STAGES.snapshot()
    assert cluster.asked() == [4, 5]
    # the ask before the recovery (shard 0), then 2 and 3 inside it
    assert delta(before, after, "ec.read.remote", "absent") == 3
    assert delta(before, after, "ec.read.remote", "failed") == 0
    assert fanout(before, after, "width") == 2
    assert k + 2 < geometry.total_shards


# -- a failure: one spare, then the refresh, then the error ------------------------
def test_one_failing_holder_costs_one_spare_and_the_read_succeeds(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    k = geometry.data_shards
    cluster.holds["b:1"].discard(k - 4)  # listed, and does not answer for it
    before = STAGES.snapshot()
    assert read(store, 3) == blobs[3]
    after = STAGES.snapshot()
    spare = k + 1  # the next id along the walk
    assert set(cluster.asked()) == set(range(k - 5, k + 1)) | {spare}
    assert cluster.asked().count(spare) == 1
    assert fanout(before, after, "n") == 1
    assert fanout(before, after, "width") == 6
    assert fanout(before, after, "spares") == 1
    assert delta(before, after, "ec.recover.remote", "n") == 6
    # its own retries stay as they are: two attempts, each forgot the holder
    assert delta(before, after, "ec.read.remote", "failed") == 2
    assert store.find_ec_volume(VID).shard_holders(k - 4) == []


def test_with_no_spare_left_a_believed_table_is_taken_anew_once_then_the_error(
        sealed):
    open_store, blobs, geometry = sealed
    k, total = geometry.data_shards, geometry.total_shards
    # k - 1 siblings in all: k - 3 local, two on a peer, the rest nowhere
    away = [0, 1, 2] + list(range(k, total))
    store, cluster = open_store(away, {"a:1": [1, 2]})
    with pytest.raises(EcNotFoundError, match=f"only {k - 1} shards reachable"):
        read(store, 1)
    assert cluster.lookups == 1  # taken inside that read: nothing to refresh
    with pytest.raises(EcNotFoundError, match=f"only {k - 1} shards reachable"):
        read(store, 2)
    assert cluster.lookups == 2  # the table in hand predated this read: once
    # a shard comes up after the table was taken: the refresh finds it
    cluster.place("late:1", k)
    before = STAGES.snapshot()
    assert read(store, 3) == blobs[3]
    after = STAGES.snapshot()
    assert cluster.lookups == 3
    assert cluster.asked().count(k) == 1
    assert fanout(before, after, "width") == 2
    assert fanout(before, after, "spares") == 0  # the refresh is no spare


def test_a_short_local_sibling_is_made_up_by_a_spare(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    ev = store.find_ec_volume(VID)
    real = ev.shards[1].read_at
    ev.shards[1].read_at = lambda off, size: real(off, size)[:-1]
    before = STAGES.snapshot()
    assert read(store, 4) == blobs[4]
    after = STAGES.snapshot()
    k = geometry.data_shards
    assert cluster.asked() == list(range(k - 5, k + 2))
    assert fanout(before, after, "width") == 6
    assert fanout(before, after, "spares") == 1


# -- nothing remote: nothing changes ----------------------------------------------
def test_with_every_sibling_local_there_is_no_fanout_and_no_worker(sealed):
    open_store, blobs, geometry = sealed
    store, cluster = open_store([0, 4, 9, 12], {})
    names = {t.name for t in threading.enumerate()}
    before = STAGES.snapshot()
    for i in blobs:
        assert read(store, i) == blobs[i]
    after = STAGES.snapshot()
    n = len(blobs)
    assert delta(before, after, "ec.recover", "n") == n
    assert fanout(before, after, "n") == 0
    # three lost siblings passed over inside each recovery, as in the walk
    # (4, 9 and 12: it reaches k one id before the end), and the ask for
    # shard 0 before it
    assert delta(before, after, "ec.read.remote", "absent") == (3 + 1) * n
    assert delta(before, after, "ec.recover.remote", "n") == 0
    assert cluster.fetches == [] and cluster.lookups == 1
    assert store._sibling_workers is None
    assert {t.name for t in threading.enumerate()} - names == set()


# -- the workers are the store's --------------------------------------------------
def test_a_full_pool_sends_the_rest_to_the_callers_thread(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    k = geometry.data_shards  # no server here: k workers
    cluster.gate = threading.Event()
    got = {}

    def one(i):
        got[i] = read(store, i)

    first = threading.Thread(target=one, args=(1,), name="reader-1")
    second = threading.Thread(target=one, args=(2,), name="reader-2")
    before = STAGES.snapshot()
    try:
        first.start()
        cluster.wait_flying(6)  # six of the k workers held at the gate
        second.start()
        cluster.wait_flying(k)  # the second took the k - 6 left ...
        until = time.monotonic() + 20
        while sum(t == "reader-2" for _, _, t in cluster.fetches) < 12 - k:
            assert time.monotonic() < until  # ... and made the rest itself
            time.sleep(0.001)
    finally:
        cluster.gate.set()
    first.join(30)
    second.join(30)
    after = STAGES.snapshot()
    assert not first.is_alive() and not second.is_alive()
    assert got == {1: blobs[1], 2: blobs[2]}
    by_thread = [t for _, _, t in cluster.fetches]
    assert by_thread.count("reader-2") == 12 - k
    assert by_thread.count("reader-1") == 0
    assert sum(t.startswith(WORKER) for t in by_thread) == k
    assert fanout(before, after, "n") == 2
    assert fanout(before, after, "width") == k  # 6 and k - 6
    assert delta(before, after, "ec.recover.remote", "n") == 12
    # every worker is free again
    cluster.gate = None
    before = STAGES.snapshot()
    assert read(store, 3) == blobs[3]
    assert fanout(before, STAGES.snapshot(), "width") == 6


def test_close_joins_the_workers_and_a_later_read_makes_its_asks_itself(sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    others = set(threading.enumerate())
    assert read(store, 1) == blobs[1]
    mine = [t for t in threading.enumerate()
            if t not in others and t.name.startswith(WORKER)]
    assert 1 <= len(mine) <= geometry.data_shards
    ev = store.find_ec_volume(VID)
    store._sibling_workers.close()  # what Store.close does first
    assert not any(t.is_alive() for t in mine)
    # refused by the closed pool, made on this thread: no slower than before
    del cluster.fetches[:]
    before = STAGES.snapshot()
    data = store._recover_interval(ev, 0, 0, 64)
    with open(cluster.base + ".remote00", "rb") as f:
        assert data == f.read(64)
    assert len(cluster.fetches) == 6
    assert not any(t.startswith(WORKER) for _, _, t in cluster.fetches)
    assert fanout(before, STAGES.snapshot(), "width") == 0
    store.close()
    assert not any(t.is_alive() for t in mine)


@pytest.mark.parametrize("handlers", [0, 4, 32])
def test_the_pool_is_as_wide_as_the_serving_cores_handlers_or_k(sealed, handlers):
    open_store, _, geometry = sealed
    store, _ = open_store([], {})

    class Core:
        def handler_count(self):
            return handlers

    core = Core()
    http_util.SERVING.register_server(core)  # held weakly: gone with ``core``
    workers = store._siblings(store.find_ec_volume(VID))
    assert workers is store._siblings(store.find_ec_volume(VID))
    held = [workers._free.acquire(blocking=False) for _ in range(40)]
    assert held.count(True) == max(geometry.data_shards, handlers)


@pytest.mark.parametrize("mode,workers", [("aio", 7), ("threads", 0)])
def test_the_serving_core_says_how_many_handlers_it_runs(monkeypatch, mode, workers):
    monkeypatch.setattr(http_util, "SERVING", http_util._ServingState())
    monkeypatch.setenv("SWEED_SERVING", mode)
    monkeypatch.setenv("SWEED_AIO_WORKERS", "7")
    assert http_util.SERVING.handler_count() == 0  # no server is up
    srv = start_server(JsonHandler, "127.0.0.1", 0)
    try:
        # the aio core's worker pool; nothing bounds a thread a connection
        assert http_util.SERVING.handler_count() == workers
    finally:
        srv.shutdown()
        srv.server_close()


# -- the request's context reaches the workers --------------------------------------
def test_an_ask_on_a_worker_is_a_child_of_the_requests_recovery_and_has_its_deadline(
        sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    due = deadline.after(30)
    with trace.start_span("GET /9,01", service="test") as request, \
            deadline.scope(due):
        assert read(store, 1) == blobs[1]
    spans = RING.for_trace(request.trace_id)
    by_name: dict[str, list[dict]] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (recover,) = by_name["ec.recover"]
    assert recover["parent_id"] == request.span_id
    # seven asks in the trace: the one for shard 0 before the recovery (the
    # request's own child) and the six on the workers, children of ec.recover
    asks = by_name["ec.read.remote"]
    inside = [s for s in asks if s["parent_id"] == recover["span_id"]]
    assert len(asks) == 7 and len(inside) == 6
    assert sorted(s["tags"]["sid"] for s in inside) == sorted(
        sid for _, sid, _ in cluster.fetches)
    assert all(s["parent_id"] == recover["span_id"]
               for s in by_name["ec.recover.remote"] + by_name["ec.recover.fanout"])
    # and each fetch ran on a worker, under the request's trace and deadline
    assert all(t.startswith(WORKER) for _, _, t in cluster.fetches)
    assert cluster.seen == [(request.trace_id, due)] * 6


def test_a_spent_deadline_stops_the_asks_on_the_workers_too(sealed):
    """The transports refuse to dial once the budget is spent
    (``deadline.clamp_timeout``): a fetch on a worker has to see the same
    budget as one made on the request's thread."""
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    real = cluster.fetch

    def fetch(holder, vid, sid, offset, size):
        deadline.clamp_timeout(5.0)  # what http_bytes does before it dials
        return real(holder, vid, sid, offset, size)

    store.remote_shards = RemoteShards(locate=cluster.locate, fetch=fetch)
    assert read(store, 1) == blobs[1]  # takes the table; no deadline yet
    del cluster.fetches[:]
    with deadline.scope(time.time() - 1):
        with pytest.raises(EcNotFoundError):
            read(store, 2)
    assert cluster.fetches == []  # refused before any went on the wire


# -- many recoveries at once --------------------------------------------------------
def test_more_recoveries_than_workers_never_read_wrong_bytes_or_lose_a_worker(
        sealed):
    open_store, blobs, geometry = sealed
    away, listed = six_remote(geometry)
    store, cluster = open_store(away, listed)
    k = geometry.data_shards
    stop, wrong, reads = threading.Event(), [], [0] * 16

    def flap():  # one holder of two comes and goes
        while not stop.is_set():
            mine = set(cluster.holds["b:1"])
            cluster.holds["b:1"].clear()
            time.sleep(0.002)
            cluster.holds["b:1"].update(mine)
            time.sleep(0.004)

    def reader(j):
        while not stop.is_set():
            i = 1 + (reads[j] + j) % len(blobs)
            try:
                if read(store, i) != blobs[i]:
                    wrong.append(i)
            except EcNotFoundError:
                pass  # fewer than k reachable while the holder is away
            reads[j] += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader, args=(j,)) for j in range(16)]
    threads.append(threading.Thread(target=flap))
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert wrong == [] and min(reads) > 0
    # sixteen readers want 96 asks at once of k workers: some went to the
    # readers' own threads, and every worker came back
    assert any(not t.startswith(WORKER) for _, _, t in cluster.fetches)
    workers = store._sibling_workers
    held = [workers._free.acquire(blocking=False) for _ in range(k + 1)]
    assert held.count(True) == k
