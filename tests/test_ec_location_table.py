"""The EC volume's shard-location table (``ec/ec_volume.py``) and what the
store's remote read makes of it (``Store._remote_shard_read``): the master's
answer kept on the volume and believed for as long as the reference believes
it, "nowhere" answered at once, a listed holder's fault retried under the
policy as it was — against a cluster of two callables (``RemoteShards``)
that counts what it is asked."""

from __future__ import annotations

import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import ec_volume
from seaweedfs_tpu.ec.constants import DATA_SHARDS, TOTAL_SHARDS, shard_ext
from seaweedfs_tpu.ec.ec_volume import NotFoundError as EcNotFoundError
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.stats.trace import STAGES
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import RemoteShards, Store
from seaweedfs_tpu.util import retry

VID = 9
ME = "localhost:8080"  # Store's default ip:port, as the master would list it


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def delta(before: dict, after: dict, stage: str, field: str):
    return (after.get(stage, {}).get(field, 0)
            - before.get(stage, {}).get(field, 0))


class Cluster:
    """A master and holders made of a dict: ``where`` is what the master
    answers, ``holds`` what each holder can really serve."""

    def __init__(self, base: str):
        self.base = base
        self.where: dict[int, list[str]] = {}
        self.holds: dict[str, set[int]] = {}
        self.lookups = 0
        self.fetches: list[tuple[str, int]] = []
        self.refused = 0
        self.master_down = False
        self.lookup_takes_s = 0.0

    def place(self, url: str, *sids: int, listed: bool = True) -> None:
        self.holds.setdefault(url, set()).update(sids)
        if listed:
            for sid in sids:
                self.where.setdefault(sid, []).append(url)

    def locate(self, vid: int) -> dict:
        assert vid == VID
        self.lookups += 1
        time.sleep(self.lookup_takes_s)
        if self.master_down:
            raise ConnectionError("master unreachable")
        return {sid: list(urls) for sid, urls in self.where.items()}

    def fetch(self, holder, vid, sid, offset, size) -> bytes:
        self.fetches.append((holder, sid))
        if sid not in self.holds.get(holder, ()):
            self.refused += 1
            raise ConnectionError(f"{holder} does not answer for shard {sid}")
        with open(self.base + f".remote{sid:02d}", "rb") as f:
            f.seek(offset)
            return f.read(size)

    def wire(self, store: Store) -> None:
        store.remote_shards = RemoteShards(locate=self.locate, fetch=self.fetch)


@pytest.fixture()
def sealed(tmp_path, monkeypatch):
    """A small EC volume whose needles all lie on data shard 0 (everything
    under 1 MiB stripes there). ``away(sids)`` moves shards out of the
    store's directory to where only the cluster's holders can read them."""
    # full jitter draws from [0, d]: take d itself, so the delays are known
    monkeypatch.setattr(retry.random, "uniform", lambda lo, hi: hi)
    store = Store([str(tmp_path)], ec_backend="numpy")
    store.add_volume(VID)
    rng = np.random.default_rng(29)
    blobs = {i: rng.bytes(3000 + i * 7) for i in range(1, 9)}
    for i, blob in blobs.items():
        store.write_volume_needle(VID, Needle(cookie=3, id=i, data=blob))
    store.ec_encode_volume(VID)
    base = store.find_volume(VID).file_name()
    store.close()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    opened = []

    def open_store(*away: int) -> tuple[Store, Cluster]:
        for sid in away:
            os.rename(base + shard_ext(sid), base + f".remote{sid:02d}")
        s = Store([str(tmp_path)], ec_backend="numpy",
                  remote_fetch_attempts=3, remote_fetch_backoff_s=0.002)
        opened.append(s)
        cluster = Cluster(base)
        cluster.wire(s)
        local = [x for x in range(TOTAL_SHARDS) if x not in away]
        cluster.place(ME, *local)  # the master lists the asker's own too
        return s, cluster

    yield open_store, blobs
    for s in opened:
        s.close()


def read(store: Store, i: int) -> bytes:
    n = Needle(id=i)
    store.read_volume_needle(VID, n)
    return n.data


def remote(before: dict, after: dict, field: str):
    return delta(before, after, "ec.read.remote", field)


# -- "nowhere" is an answer -------------------------------------------------------
def test_an_absent_shard_costs_no_attempt_no_sleep_and_no_lookup(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)  # shard 0 is gone and nobody holds it
    assert read(store, 1) == blobs[1]  # takes the table: the one lookup
    assert cluster.lookups == 1
    before = STAGES.snapshot()
    for i in (2, 3, 4):
        assert read(store, i) == blobs[i]
    after = STAGES.snapshot()
    assert cluster.lookups == 1 and cluster.fetches == []
    assert remote(before, after, "n") == 3  # the span still opens
    assert remote(before, after, "absent") == 3
    assert remote(before, after, "failed") == 0
    assert remote(before, after, "slept_s") == 0
    assert remote(before, after, "ok") == 0
    assert delta(before, after, "ec.read.lookup", "n") == 0
    assert delta(before, after, "ec.recover", "n") == 3  # decoded from siblings


def test_a_shard_listed_only_under_the_asker_is_nowhere(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.where[0] = [ME]  # the master has not heard of the loss yet
    before = STAGES.snapshot()
    assert read(store, 5) == blobs[5]
    after = STAGES.snapshot()
    assert cluster.fetches == []
    assert remote(before, after, "absent") == 1
    assert remote(before, after, "failed") == 0


def test_a_store_no_volume_server_wired_asks_nobody_and_opens_no_span(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    store.remote_shards = None
    before = STAGES.snapshot()
    assert read(store, 1) == blobs[1]
    assert remote(before, STAGES.snapshot(), "n") == 0
    assert cluster.lookups == 0


# -- one lookup, whoever finds the table stale ------------------------------------
def test_concurrent_asks_that_find_the_table_stale_wait_for_one_refresh(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("peer:1", 0)
    cluster.lookup_takes_s = 0.05
    got, start = {}, threading.Barrier(8)

    def one(i):
        start.wait()
        got[i] = read(store, i)

    threads = [threading.Thread(target=one, args=(i,)) for i in blobs]
    before = STAGES.snapshot()
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    after = STAGES.snapshot()
    assert got == blobs
    assert cluster.lookups == 1
    assert delta(before, after, "ec.read.lookup", "n") == 1
    assert remote(before, after, "ok") == 8 and remote(before, after, "failed") == 0


def test_readers_and_a_holder_that_comes_and_goes_never_read_wrong_bytes(sealed):
    """More readers than cores on one volume while its only remote holder
    flaps: every read is right (fetched or decoded), the table is never
    torn, and a refresh is made for a failed fetch, not for every reader."""
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("flap:1", 0)
    stop, wrong, reads = threading.Event(), [], [0] * 24

    def flap():
        while not stop.is_set():
            cluster.holds["flap:1"].clear()
            time.sleep(0.002)
            cluster.holds["flap:1"].add(0)
            time.sleep(0.002)

    def reader(k):
        while not stop.is_set():
            i = 1 + (reads[k] + k) % len(blobs)
            if read(store, i) != blobs[i]:
                wrong.append(i)
            reads[k] += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader, args=(k,)) for k in range(24)]
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
    # the first lookup, then one at most for every fetch a holder refused
    assert cluster.refused > 0 and cluster.lookups <= 1 + cluster.refused
    ev = store.find_ec_volume(VID)
    assert ev.shard_holders(0) in ([], ["flap:1"])
    assert all(ev.shard_holders(x) == [ME] for x in range(1, TOTAL_SHARDS))


# -- a fault is still a fault -----------------------------------------------------
def test_a_listed_holder_that_fails_is_forgotten_and_the_ask_retried(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("dead:1", 0)
    cluster.holds["dead:1"].clear()  # listed, and answers nothing
    before = STAGES.snapshot()
    assert read(store, 1) == blobs[1]  # reconstructed in the end
    after = STAGES.snapshot()
    # the policy as it was: three tries of the listed holder, two sleeps
    assert cluster.fetches == [("dead:1", 0)] * 3
    assert remote(before, after, "failed") == 3
    assert remote(before, after, "slept_s") == pytest.approx(0.002 + 0.004)
    assert remote(before, after, "absent") == 0
    # each failure forgot the holder and had the next try refresh first
    assert cluster.lookups == 3
    assert delta(before, after, "ec.recover", "n") == 1


def test_a_shard_whose_only_holder_died_is_nowhere_on_the_second_look(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("dying:1", 0)
    assert read(store, 1) == blobs[1]
    assert cluster.fetches == [("dying:1", 0)]
    # the holder dies and the master reaps it; the table in hand still lists it
    cluster.holds["dying:1"].clear()
    del cluster.where[0]
    before = STAGES.snapshot()
    assert read(store, 2) == blobs[2]
    after = STAGES.snapshot()
    assert cluster.fetches == [("dying:1", 0)] * 2  # one failed fetch, no third
    assert cluster.lookups == 2
    assert remote(before, after, "failed") == 1
    assert remote(before, after, "absent") == 1
    assert remote(before, after, "slept_s") == pytest.approx(0.002)
    assert store.find_ec_volume(VID).shard_holders(0) == []
    # and from then on the ask ends at once
    assert read(store, 3) == blobs[3]
    assert cluster.lookups == 2 and len(cluster.fetches) == 2


def test_a_shard_that_moved_is_read_from_its_new_holder_after_one_failed_fetch(
        sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("old:1", 0)
    assert read(store, 1) == blobs[1]
    cluster.holds["old:1"].clear()
    cluster.where[0] = []
    cluster.place("new:1", 0)
    before = STAGES.snapshot()
    assert read(store, 2) == blobs[2]
    after = STAGES.snapshot()
    assert cluster.fetches[1:] == [("old:1", 0), ("new:1", 0)]
    assert remote(before, after, "failed") == 1 and remote(before, after, "ok") == 1
    assert delta(before, after, "ec.recover", "n") == 0  # read, not decoded
    assert store.find_ec_volume(VID).shard_holders(0) == ["new:1"]


def test_a_second_listed_holder_answers_inside_the_same_attempt(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("down:1", 0)
    cluster.holds["down:1"].clear()
    cluster.place("up:1", 0)
    before = STAGES.snapshot()
    assert read(store, 1) == blobs[1]
    after = STAGES.snapshot()
    assert cluster.fetches == [("down:1", 0), ("up:1", 0)]
    assert remote(before, after, "failed") == 0 and remote(before, after, "ok") == 1
    assert store.find_ec_volume(VID).shard_holders(0) == ["up:1"]


def test_a_lookup_that_fails_is_a_fault_and_never_nowhere(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.master_down = True
    before = STAGES.snapshot()
    assert read(store, 1) == blobs[1]  # ten local siblings: still served
    after = STAGES.snapshot()
    # the ask before the recovery: three tries, each one lookup that raised
    assert cluster.lookups == 3
    assert remote(before, after, "failed") == 3
    assert remote(before, after, "absent") == 0
    assert remote(before, after, "slept_s") == pytest.approx(0.002 + 0.004)
    assert store.find_ec_volume(VID).locations_taken() is None


def test_the_table_in_hand_stays_in_use_while_the_master_cannot_be_asked(
        sealed, monkeypatch):
    open_store, blobs = sealed
    store, cluster = open_store(0)
    cluster.place("peer:1", 0)
    now = [1000.0]
    monkeypatch.setattr(ec_volume, "_clock", lambda: now[0])
    assert read(store, 1) == blobs[1]
    taken = store.find_ec_volume(VID).locations_taken()
    now[0] += ec_volume.LOCATIONS_FRESH_ALL_S + 1  # stale by any rule
    cluster.master_down = True
    before = STAGES.snapshot()
    assert read(store, 2) == blobs[2]
    after = STAGES.snapshot()
    assert cluster.lookups == 2  # it was asked, and could not answer
    assert remote(before, after, "ok") == 1 and remote(before, after, "failed") == 0
    assert store.find_ec_volume(VID).locations_taken() == taken


# -- no read fails that a fresh look would have served -----------------------------
def test_fewer_than_data_shards_reachable_forces_one_refresh_before_giving_up(
        sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0, 1, 2, 3, 4)  # nine local siblings of shard 0
    assert read_fails(store, 1)
    assert cluster.lookups == 1  # the table was taken inside that recovery
    # shard 3 comes up on a peer after the table was taken
    cluster.place("late:1", 3)
    before = STAGES.snapshot()
    assert read(store, 2) == blobs[2]
    after = STAGES.snapshot()
    assert cluster.lookups == 2  # ONE refresh, for four absent siblings
    assert cluster.fetches == [("late:1", 3)]
    assert delta(before, after, "ec.recover.remote", "n") == 1
    # the first pass: the ask before the recovery and siblings 1-4, all
    # "nowhere"; the second: 1 and 2 again, then 3 is there
    assert remote(before, after, "absent") == 5 + 2
    assert remote(before, after, "failed") == 0


def read_fails(store: Store, i: int) -> bool:
    with pytest.raises(EcNotFoundError, match="only 9 shards reachable"):
        read(store, i)
    return True


def test_a_read_that_fails_took_the_table_anew_once_and_no_more(sealed):
    open_store, blobs = sealed
    store, cluster = open_store(0, 1, 2, 3, 4)
    assert read_fails(store, 1)  # the table was taken inside this read
    assert cluster.lookups == 1
    assert read_fails(store, 2)  # the table in hand predates this one
    assert cluster.lookups == 2 and cluster.fetches == []


# -- how long the master's answer is believed --------------------------------------
@pytest.mark.parametrize("listed,fresh_s", [
    (DATA_SHARDS - 1, ec_volume.LOCATIONS_FRESH_FEW_S),
    (DATA_SHARDS, ec_volume.LOCATIONS_FRESH_ENOUGH_S),
    (TOTAL_SHARDS - 1, ec_volume.LOCATIONS_FRESH_ENOUGH_S),
    (TOTAL_SHARDS, ec_volume.LOCATIONS_FRESH_ALL_S),
    (0, ec_volume.LOCATIONS_FRESH_FEW_S),
])
def test_the_table_is_believed_for_as_long_as_the_reference_believes_it(
        sealed, monkeypatch, listed, fresh_s):
    open_store, _ = sealed
    store, cluster = open_store()
    ev = store.find_ec_volume(VID)
    cluster.where = {sid: ["peer:1"] for sid in range(listed)}
    now = [50.0]
    monkeypatch.setattr(ec_volume, "_clock", lambda: now[0])
    assert ev.refresh_locations(cluster.locate) == 50.0
    assert sorted(sid for sid in range(TOTAL_SHARDS) if ev.shard_holders(sid)) \
        == list(range(listed))
    now[0] = 50.0 + fresh_s - 0.001
    assert ev.refresh_locations(cluster.locate) == 50.0
    assert cluster.lookups == 1
    now[0] = 50.0 + fresh_s
    assert ev.refresh_locations(cluster.locate) == now[0]
    assert cluster.lookups == 2


def test_the_three_ages_are_the_references():
    # store_ec.go cachedLookupEcShardLocations: 11 s, 7 min, 37 min
    assert (ec_volume.LOCATIONS_FRESH_FEW_S, ec_volume.LOCATIONS_FRESH_ENOUGH_S,
            ec_volume.LOCATIONS_FRESH_ALL_S) == (11.0, 420.0, 2220.0)


def test_a_table_newer_than_the_one_that_failed_is_not_taken_again(
        sealed, monkeypatch):
    open_store, _ = sealed
    store, cluster = open_store()
    ev = store.find_ec_volume(VID)
    now = [10.0]
    monkeypatch.setattr(ec_volume, "_clock", lambda: now[0])
    first = ev.refresh_locations(cluster.locate)
    now[0] = 11.0
    second = ev.refresh_locations(cluster.locate, newer_than=first)
    assert (first, second, cluster.lookups) == (10.0, 11.0, 2)
    # a second asker whose holder failed under the first table finds the
    # refresh done; one that saw the second fail asks again
    assert ev.refresh_locations(cluster.locate, newer_than=first) == 11.0
    assert cluster.lookups == 2
    now[0] = 12.0
    assert ev.refresh_locations(cluster.locate, newer_than=second) == 12.0
    assert cluster.lookups == 3


# -- the volume server's half of the seam ------------------------------------------
def test_the_master_that_knows_no_shard_answers_empty_and_a_dead_one_raises(
        tmp_path):
    master = MasterServer(port=free_port()).start()
    try:
        vs = VolumeServer([str(tmp_path)], port=free_port(),
                          master_url=master.url, ec_backend="numpy")
        assert vs.store.remote_shards.locate(4711) == {}  # its 404
        assert not hasattr(vs.store, "remote_shard_reader")
    finally:
        master.stop()
    with pytest.raises(Exception):
        vs.store.remote_shards.locate(4711)
    with pytest.raises(Exception):
        vs.store.remote_shards.fetch(master.url, 4711, 0, 0, 16)
    vs.store.close()
