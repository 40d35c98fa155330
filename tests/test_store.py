"""Store + DiskLocation + the full volume→EC lifecycle with degraded reads."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuCodec
from seaweedfs_tpu.ec.constants import shard_ext
from seaweedfs_tpu.ec.ec_volume import EcVolume, rebuild_ecx_file
from seaweedfs_tpu.ec.ec_volume import DeletedError as EcDeletedError
from seaweedfs_tpu.storage.disk_location import DiskLocation, parse_volume_base_name
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.stats.trace import STAGES
from seaweedfs_tpu.storage.store import RemoteShards, Store
from seaweedfs_tpu.storage.volume import NotFoundError


def test_parse_volume_base_name():
    assert parse_volume_base_name("3") == ("", 3)
    assert parse_volume_base_name("col_7") == ("col", 7)
    assert parse_volume_base_name("a_b_9") == ("a_b", 9)
    with pytest.raises(ValueError):
        parse_volume_base_name("nodigits")


def test_store_volume_crud(tmp_path):
    store = Store([str(tmp_path / "d1"), str(tmp_path / "d2")])
    store.add_volume(1, replica_placement="001")
    store.add_volume(2)
    assert store.has_volume(1) and store.has_volume(2)
    # volumes balance across locations
    assert {loc.volume_count() for loc in store.locations} == {1}

    n = Needle(cookie=9, id=100, data=b"store routing works")
    store.write_volume_needle(1, n)
    m = Needle(id=100)
    store.read_volume_needle(1, m)
    assert m.data == b"store routing works"

    with pytest.raises(ValueError):
        store.add_volume(1)
    with pytest.raises(NotFoundError):
        store.write_volume_needle(99, Needle(id=1))

    hb = store.collect_heartbeat()
    assert len(hb["volumes"]) == 2
    assert hb["volumes"][0]["file_count"] + hb["volumes"][1]["file_count"] == 1
    # delta queue holds heartbeat-shaped messages for instant delta beats
    assert [m["id"] for m in store.new_volumes] == [1, 2]
    assert store.delta_event.is_set()
    deltas = store.drain_deltas()
    assert [m["id"] for m in deltas["new_volumes"]] == [1, 2]
    assert not store.delta_event.is_set() and not store.new_volumes

    assert store.delete_volume(2)
    assert not store.has_volume(2)
    store.close()


def test_disk_location_reload(tmp_path):
    store = Store([str(tmp_path)])
    store.add_volume(5, collection="photos")
    store.write_volume_needle(5, Needle(cookie=1, id=1, data=b"reload me"))
    store.close()

    loc = DiskLocation(str(tmp_path))
    loc.load_existing_volumes()
    assert 5 in loc.volumes
    v = loc.find_volume(5)
    assert v.collection == "photos"
    n = Needle(id=1)
    v.read_needle(n)
    assert n.data == b"reload me"
    loc.close()


@pytest.fixture()
def ec_store(tmp_path):
    """A store with volume 10 written, sealed, and EC-encoded."""
    store = Store([str(tmp_path)], ec_backend="cpu")
    store.add_volume(10)
    rng = np.random.default_rng(3)
    blobs = {}
    # >10MB total so the 1MB small-block striping spans all 10 data shards
    for i in range(1, 41):
        blobs[i] = rng.integers(
            0, 256, int(rng.integers(200_000, 400_000)), dtype=np.uint8
        ).tobytes()
        store.write_volume_needle(10, Needle(cookie=7, id=i, data=blobs[i]))
    v = store.find_volume(10)
    base = v.file_name()
    v.read_only = True
    store.close()

    codec = CpuCodec()
    encoder.write_ec_files(base, codec)
    encoder.write_sorted_file_from_idx(base)
    encoder.save_volume_info(base + ".vif", version=3)
    # remove the plain volume like ec.encode does (command_ec_encode.go:199)
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    return str(tmp_path), base, blobs


def test_ec_volume_reads_all_local(ec_store):
    directory, base, blobs = ec_store
    store = Store([directory], ec_backend="cpu")
    assert store.find_volume(10) is None
    ev = store.find_ec_volume(10)
    assert ev is not None
    assert ev.shard_ids() == list(range(14))
    for i, want in blobs.items():
        n = Needle(id=i)
        assert store.read_volume_needle(10, n) == len(want)
        assert n.data == want
    store.close()


def test_ec_degraded_read_with_4_shards_gone(ec_store):
    directory, base, blobs = ec_store
    for sid in (0, 4, 9, 12):  # 3 data + 1 parity shard lost
        os.remove(base + shard_ext(sid))
    store = Store([directory], ec_backend="cpu")
    ev = store.find_ec_volume(10)
    assert len(ev.shard_ids()) == 10
    for i, want in blobs.items():
        n = Needle(id=i)
        store.read_volume_needle(10, n)
        assert n.data == want, f"needle {i} corrupted in degraded read"
    store.close()


def test_ec_read_fails_with_5_shards_gone(ec_store):
    directory, base, blobs = ec_store
    for sid in (0, 1, 4, 9, 12):
        os.remove(base + shard_ext(sid))
    store = Store([directory], ec_backend="cpu")
    some_needle = next(iter(blobs))
    with pytest.raises(Exception, match="shards reachable"):
        store.read_volume_needle(10, Needle(id=some_needle))
    store.close()


def test_ec_delete_and_ecj(ec_store):
    directory, base, blobs = ec_store
    store = Store([directory], ec_backend="cpu")
    ev = store.find_ec_volume(10)
    store.delete_volume_needle(10, Needle(id=5))
    with pytest.raises(EcDeletedError):
        store.read_volume_needle(10, Needle(id=5))
    assert os.path.exists(base + ".ecj")
    with open(base + ".ecj", "rb") as f:
        assert int.from_bytes(f.read(8), "big") == 5
    store.close()

    # rebuild_ecx_file replays the journal then removes it
    rebuild_ecx_file(base)
    assert not os.path.exists(base + ".ecj")
    store2 = Store([directory], ec_backend="cpu")
    with pytest.raises(EcDeletedError):
        store2.read_volume_needle(10, Needle(id=5))
    n = Needle(id=6)
    store2.read_volume_needle(10, n)
    assert n.data == blobs[6]
    store2.close()


def test_ec_heartbeat_bits(ec_store):
    directory, base, _ = ec_store
    os.remove(base + shard_ext(13))
    store = Store([directory], ec_backend="cpu")
    hb = store.collect_ec_heartbeat()
    assert hb["ec_shards"][0]["id"] == 10
    assert hb["ec_shards"][0]["ec_index_bits"] == (1 << 13) - 1  # shards 0-12
    store.close()


def test_remote_shard_reader_hook(ec_store):
    """Missing local shard + a wired cluster that lists a holder for it →
    read from the holder, no reconstruction; the lookup is made once."""
    directory, base, blobs = ec_store
    # steal shard 2 away to simulate a remote holder
    remote_path = base + ".remote02"
    os.rename(base + shard_ext(2), remote_path)
    store = Store([directory], ec_backend="cpu")

    lookups, calls = [], []

    def locate(vid):
        lookups.append(vid)
        return {2: ["holder:1"]}

    def fetch(holder, vid, sid, off, size):
        calls.append((holder, vid, sid))
        with open(remote_path, "rb") as f:
            f.seek(off)
            return f.read(size)

    store.remote_shards = RemoteShards(locate=locate, fetch=fetch)
    before = STAGES.snapshot().get("ec.recover", {}).get("n", 0)
    for i, want in blobs.items():
        n = Needle(id=i)
        store.read_volume_needle(10, n)
        assert n.data == want
    assert calls and set(calls) == {("holder:1", 10, 2)}
    assert lookups == [10]  # the table is kept on the EC volume
    assert STAGES.snapshot().get("ec.recover", {}).get("n", 0) == before
    store.close()
