"""ec.decode: erasure-coded volume back to a normal volume.

Reference: `weed/shell/command_ec_decode.go` (collect shards → decode →
retire shards) and `weed/storage/erasure_coding/ec_decoder.go`
(WriteDatFile / WriteIdxFileFromEcIndex / FindDatFileSize).
"""

import os
import socket
import time

import numpy as np
import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.ec import decoder as ec_decoder
from seaweedfs_tpu.ec import encoder as ec_encoder
from seaweedfs_tpu.ec.constants import shard_ext
from seaweedfs_tpu.server.http_util import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import commands as C
from seaweedfs_tpu.shell.commands import CommandEnv
from seaweedfs_tpu.shell.shell import run_command
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --------------------------------------------------------------- unit level
def test_decode_roundtrip_bytes_identical(tmp_path):
    """encode → decode reproduces the .dat byte-for-byte and an .idx that
    serves the same live set (incl. .ecj tombstones)."""
    v = Volume(str(tmp_path), collection="", vid=5)
    rng = np.random.default_rng(3)
    for i in range(1, 40):
        v.write_needle(
            Needle(cookie=9, id=i, data=rng.bytes(4096 + 64 * i))
        )
    v.sync()
    base = v.file_name()
    original_dat = open(base + ".dat", "rb").read()
    v.close()

    ec_encoder.write_ec_files(base)
    ec_encoder.write_sorted_file_from_idx(base)
    os.unlink(base + ".dat")
    os.unlink(base + ".idx")

    dat_size = ec_decoder.decode_to_volume(base)
    assert dat_size == len(original_dat)
    assert open(base + ".dat", "rb").read() == original_dat

    v2 = Volume(str(tmp_path), collection="", vid=5)
    n = Needle(id=17)
    v2.read_needle(n)
    assert len(n.data) == 4096 + 64 * 17
    v2.close()


def test_decode_keeps_needles_written_out_of_key_order(tmp_path):
    """Keys are handed out before the upload, so racing writers append
    them out of order. The .ecx is key-sorted; an .idx copied from it in
    that order made the reload's integrity check take the highest key's
    record for the last one and truncate every needle behind it."""
    v = Volume(str(tmp_path), collection="", vid=8)
    rng = np.random.default_rng(8)
    keys = [int(k) for k in rng.permutation(np.arange(1, 31))]
    assert keys[-1] != max(keys)  # the highest key is NOT appended last
    for k in keys:
        v.write_needle(Needle(cookie=1, id=k, data=rng.bytes(3000 + 17 * k)))
    v.sync()
    base = v.file_name()
    original_dat = open(base + ".dat", "rb").read()
    v.close()
    ec_encoder.write_ec_files(base)
    ec_encoder.write_sorted_file_from_idx(base)
    os.unlink(base + ".dat")
    os.unlink(base + ".idx")

    ec_decoder.decode_to_volume(base)
    v2 = Volume(str(tmp_path), collection="", vid=8)  # runs the load check
    try:
        assert os.path.getsize(base + ".dat") == len(original_dat)
        for k in keys:
            n = Needle(id=k)
            v2.read_needle(n)
            assert len(n.data) == 3000 + 17 * k
    finally:
        v2.close()
    assert open(base + ".dat", "rb").read() == original_dat


def test_decode_with_missing_data_shards(tmp_path):
    """Missing data shards regenerate from parity before the re-interleave."""
    v = Volume(str(tmp_path), collection="", vid=6)
    rng = np.random.default_rng(4)
    for i in range(1, 25):
        v.write_needle(Needle(cookie=2, id=i, data=rng.bytes(8192)))
    v.sync()
    base = v.file_name()
    original_dat = open(base + ".dat", "rb").read()
    v.close()
    ec_encoder.write_ec_files(base)
    ec_encoder.write_sorted_file_from_idx(base)
    os.unlink(base + ".dat")
    os.unlink(base + ".idx")
    for sid in (0, 3, 7, 9):  # RS(10,4) worst case: 4 data shards gone
        os.unlink(base + shard_ext(sid))
    ec_decoder.decode_to_volume(base)
    assert open(base + ".dat", "rb").read() == original_dat


def test_decode_exact_multiple_boundary(tmp_path):
    """A .dat exactly k*LARGE long is laid out as SMALL rows by the encoder
    (strict > in both our _work_items and the Go encoder); the decoder must
    match — the reference's own WriteDatFile uses >= and corrupts this
    case. Scaled block sizes make the boundary reachable."""
    from seaweedfs_tpu.ec.constants import DATA_SHARDS

    large, small = 4096, 512
    base = str(tmp_path / "7")
    rng = np.random.default_rng(7)

    for dat_size in (
        DATA_SHARDS * large,          # the broken-in-reference boundary
        DATA_SHARDS * large - 1,
        DATA_SHARDS * large + 1,
        DATA_SHARDS * large * 3,      # multiple rows, exact
        DATA_SHARDS * small,          # small-row exact multiple
    ):
        payload = rng.bytes(dat_size)
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        ec_encoder.write_ec_files(
            base, large_block_size=large, small_block_size=small,
            chunk_bytes=small,
        )
        ec_decoder.write_dat_file(
            base, dat_size, DATA_SHARDS, large_block_size=large,
            small_block_size=small,
        )
        got = open(base + ".dat", "rb").read()
        assert got == payload, f"round-trip broke at dat_size={dat_size}"
        for s in range(14):
            os.unlink(base + shard_ext(s))


# ---------------------------------------------------------------- shell e2e
@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ecdec")
    master = MasterServer(port=free_port(), node_timeout=60).start()
    servers = [
        VolumeServer(
            [str(tmp / f"srv{i}")], port=free_port(), master_url=master.url,
            max_volume_count=10, pulse_seconds=0.4, ec_backend="cpu",
        ).start()
        for i in range(3)
    ]
    env = CommandEnv(master.url)
    deadline = time.time() + 5
    while time.time() < deadline and len(env.data_nodes()) < 3:
        time.sleep(0.1)
    yield master, servers, env
    for vs in servers:
        vs.stop()
    master.stop()


def test_shell_ec_decode_restores_normal_volume(cluster):
    master, servers, env = cluster
    rng = np.random.default_rng(12)
    blobs = {}
    vid = None
    for _ in range(25):
        a = operation.assign(master.url, collection="cold")
        v = int(a.fid.split(",")[0])
        if vid is None:
            vid = v
        if v != vid:
            continue
        data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
        operation.upload_data(a.url, a.fid, data)
        blobs[a.fid] = data
    assert blobs

    res = run_command(env, f"ec.encode -volumeId={vid} -collection=cold")
    assert res["volume"] == vid
    time.sleep(1.0)
    assert len(env.ec_shard_locations(vid)) == 14

    res = run_command(env, f"ec.decode -volumeId={vid} -collection=cold")
    assert res["volume"] == vid and res["file_count"] == len(blobs)
    time.sleep(1.0)
    # EC registration is gone; a normal volume serves the same content
    assert env.ec_shard_locations(vid) == {}
    locs = env.volume_locations(vid)
    assert len(locs) == 1 and locs[0] == res["decoded_on"]
    for fid, want in blobs.items():
        assert operation.download(master.url, fid) == want
    # shard files are retired from every server's disk
    for vs in servers:
        for loc in vs.store.locations:
            leftovers = [
                f for f in os.listdir(loc.directory) if ".ec" in f
            ]
            assert leftovers == [], (loc.directory, leftovers)
