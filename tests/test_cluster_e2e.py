"""In-process end-to-end: master + real Stores as volume servers.

The minimum cluster slice without transports: assign → replicated write →
lookup → read, plus EC encode + shard spread + location-aware EC read.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.cluster.master import Master
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuCodec
from seaweedfs_tpu.ec.constants import shard_ext
from seaweedfs_tpu.storage.file_id import FileId
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import RemoteShards, Store


class MiniCluster:
    def __init__(self, tmp_path, n_servers=3):
        self.stores: dict[str, Store] = {}
        self.master = Master(allocate_volume=self._allocate)
        self.nodes = {}
        for i in range(n_servers):
            ip = f"10.9.0.{i}"
            store = Store([str(tmp_path / f"srv{i}")], ip=ip, port=8080)
            url = f"{ip}:8080"
            self.stores[url] = store
            self.nodes[url] = self.master.register_data_node(
                ip, 8080, max_volume_count=10
            )

    def _allocate(self, dn, vid, option):
        store = self.stores[dn.url()]
        store.add_volume(
            vid,
            collection=option.collection,
            replica_placement=option.replica_placement,
            ttl=option.ttl,
        )

    def heartbeat_all(self):
        for url, store in self.stores.items():
            hb = store.collect_heartbeat()
            hb.update(store.collect_ec_heartbeat())
            self.master.handle_heartbeat(self.nodes[url], hb)

    def write(self, fid_str: str, data: bytes, urls: list[str]):
        """Replicated write: primary + sisters (store_replicate.go:21)."""
        fid = FileId.parse(fid_str)
        for url in urls:
            n = Needle(cookie=fid.cookie, id=fid.key, data=data)
            self.stores[url].write_volume_needle(fid.volume_id, n)

    def read(self, fid_str: str) -> bytes:
        fid = FileId.parse(fid_str)
        locs = self.master.lookup_volume(fid.volume_id)
        assert locs, f"no locations for {fid_str}"
        n = Needle(id=fid.key)
        self.stores[locs[0]["url"]].read_volume_needle(fid.volume_id, n)
        assert n.cookie == fid.cookie, "cookie mismatch"
        return n.data

    def close(self):
        for s in self.stores.values():
            s.close()


@pytest.fixture()
def cluster(tmp_path):
    c = MiniCluster(tmp_path)
    yield c
    c.close()


def test_assign_write_lookup_read(cluster):
    res = cluster.master.assign(replication="001")
    urls = [res.url] + res.replicas
    assert len(urls) == 2
    cluster.write(res.fid, b"replicated blob", urls)
    assert cluster.read(res.fid) == b"replicated blob"

    # both replicas actually hold the needle
    fid = FileId.parse(res.fid)
    for url in urls:
        n = Needle(id=fid.key)
        cluster.stores[url].read_volume_needle(fid.volume_id, n)
        assert n.data == b"replicated blob"


def test_many_files_round_trip(cluster):
    rng = np.random.default_rng(0)
    files = {}
    for _ in range(30):
        res = cluster.master.assign()
        data = rng.integers(0, 256, int(rng.integers(10, 5000)), dtype=np.uint8).tobytes()
        cluster.write(res.fid, data, [res.url] + res.replicas)
        files[res.fid] = data
    cluster.heartbeat_all()
    for fid, want in files.items():
        assert cluster.read(fid) == want


def test_heartbeat_reflects_real_state(cluster):
    res = cluster.master.assign()
    cluster.write(res.fid, b"x" * 1000, [res.url] + res.replicas)
    cluster.heartbeat_all()
    info = cluster.master.topology_info()
    sizes = [
        n["volumes"]
        for dc in info["data_centers"]
        for r in dc["racks"]
        for n in r["nodes"]
    ]
    assert sum(sizes) >= 1


def test_ec_encode_spread_and_read(cluster, tmp_path):
    """The ec.encode flow: seal a volume, encode, spread shards across
    servers, register with master, read through EC locations."""
    res = cluster.master.assign()
    fid = FileId.parse(res.fid)
    vid = fid.volume_id
    rng = np.random.default_rng(1)
    blobs = {}
    src_store = cluster.stores[res.url]
    for i in range(1, 31):
        blobs[i] = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
        src_store.write_volume_needle(vid, Needle(cookie=7, id=i, data=blobs[i]))

    v = src_store.find_volume(vid)
    v.read_only = True
    base = v.file_name()
    codec = CpuCodec()
    encoder.write_ec_files(base, codec)
    encoder.write_sorted_file_from_idx(base)
    encoder.save_volume_info(base + ".vif")

    # spread: move shards round-robin to the other servers' dirs
    urls = list(cluster.stores)
    for sid in range(14):
        target_url = urls[sid % len(urls)]
        tgt_dir = cluster.stores[target_url].locations[0].directory
        src = base + shard_ext(sid)
        dst = os.path.join(tgt_dir, os.path.basename(src))
        if os.path.abspath(src) != os.path.abspath(dst):
            os.rename(src, dst)
        # every shard holder needs the .ecx too (reference copies it with
        # the first shard — volume_grpc_erasure_coding.go:104)
        ecx_dst = os.path.join(tgt_dir, os.path.basename(base) + ".ecx")
        if not os.path.exists(ecx_dst):
            import shutil

            shutil.copyfile(base + ".ecx", ecx_dst)

    # delete the plain volume everywhere, reload stores, heartbeat
    src_store.delete_volume(vid)
    for url in urls:
        for loc in cluster.stores[url].locations:
            loc.load_existing_volumes()
    cluster.heartbeat_all()

    ec = cluster.master.lookup_ec_volume(vid)
    assert len(ec["shard_id_locations"]) == 14

    # read: each store can serve needles using its local shards + remote
    # fetch from the holders the master lists (the seam a volume server
    # wires, here without HTTP: the master's lookup, a holder's shard file)
    def locate(vid_):
        return cluster.master.lookup_ec_volume(vid_)["shard_id_locations"]

    def fetch(holder, vid_, sid, off, size):
        return cluster.stores[holder].find_ec_volume(vid_).shards[sid].read_at(
            off, size)

    reader_store = cluster.stores[urls[1]]
    reader_store.remote_shards = RemoteShards(locate=locate, fetch=fetch)
    for i, want in blobs.items():
        n = Needle(id=i)
        reader_store.read_volume_needle(vid, n)
        assert n.data == want, f"needle {i} wrong through distributed EC read"
