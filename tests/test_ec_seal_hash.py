"""A seal hashes its shards as it writes them (ec/encoder.py
``_HashedShards``): the sums `write_ec_files` hands back — the .vif's —
are the SHA-256 of the committed shard files, whatever codec runs its
pipeline, over chunked bodies, ragged tails and holes; and a seal that
fails mid-way leaves no sum, no shard and no thread behind."""

from __future__ import annotations

import errno
import glob
import hashlib
import os
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuCodec, NumpyCodec, TpuCodec
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS, shard_ext
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.stats.trace import STAGES
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import faultpoints

MIB = 1 << 20
VID = 7


def make_codec(kind: str):
    if kind == "tpu-xla":  # the JAX codec on the CPU platform
        return TpuCodec(chunk_bytes=MIB, tile_bytes=MIB // 16)
    codec = {"numpy": NumpyCodec, "cpu": CpuCodec}[kind]()
    # one 10 MiB row of small blocks a chunk (`_depth_chunk` keeps it)
    codec.chunk_bytes = MIB
    return codec


# what each shape loads, needle by needle: (bytes, random or zeros), and
# how far the .dat is extended past its last needle (preallocated space)
SHAPES = {
    # three rows, three chunks; EOF cuts the third
    "body": ([(8 * MIB + 4321, True)] * 3, 0),
    # one chunk that is almost all past EOF: nine shards hold zeros only
    "ragged": ([(300_000, True)], 0),
    # a row of written zeros in the middle (encoded to nothing, skipped in
    # the files), and two rows of a true hole at the end, where only the
    # final truncate gives the files their size
    "hole": ([(2 * MIB, True), (21 * MIB, False), (MIB, True)], 22 * MIB),
}


def sha256_of(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for piece in iter(lambda: f.read(MIB), b""):
            digest.update(piece)
    return digest.hexdigest()


def load(tmp_path, shape: str, codec) -> tuple[Store, str]:
    needles, extend = SHAPES[shape]
    os.makedirs(tmp_path / "v", exist_ok=True)
    store = Store([str(tmp_path / "v")], ec_backend="numpy")
    store.add_volume(VID)
    rng = np.random.default_rng(27)
    for i, (size, random) in enumerate(needles, start=1):
        data = (rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                if random else bytes(size))
        store.write_volume_needle(VID, Needle(cookie=1, id=i, data=data))
    store._ec_codec = codec
    v = store.find_volume(VID)
    v.sync()
    base = v.file_name()
    if extend:
        os.truncate(base + ".dat", os.path.getsize(base + ".dat") + extend)
    return store, base


def seal_threads() -> list[str]:
    """A seal's own threads still alive. The copy back's workers
    (``ec-copy-back``) are not among them: the process keeps those."""
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ec-hash", "ec-h2d"))]


def keeps_holes(directory) -> bool:
    probe = os.path.join(str(directory), "hole.probe")
    with open(probe, "wb") as f:
        f.seek(4 * MIB)
        f.write(b"x")
    sparse = os.stat(probe).st_blocks * 512 < 4 * MIB
    os.remove(probe)
    return sparse


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("kind", ["numpy", "cpu", "tpu-xla"])
def test_the_vif_sums_are_the_committed_files_and_the_scrub_agrees(
        tmp_path, kind, shape):
    codec = make_codec(kind)
    store, base = load(tmp_path, shape, codec)
    try:
        dat_size = os.path.getsize(base + ".dat")
        _, items = encoder.plan_encode(codec, dat_size)
        assert len(items) == -(-dat_size // (10 * MIB))  # a row a chunk
        before = STAGES.snapshot()
        assert store.ec_encode_volume(VID) == list(range(TOTAL_SHARDS))
        after = STAGES.snapshot()
        assert not glob.glob(base + "*.tmp")
        sums = encoder.load_volume_info(base + ".vif")["shard_sums"]
        assert len(sums) == TOTAL_SHARDS
        shard_size = len(items) * MIB
        for sid in range(TOTAL_SHARDS):
            path = base + shard_ext(sid)
            assert os.path.getsize(path) == shard_size
            assert sums[sid] == sha256_of(path), sid
        # the stage that says it engaged: one record a shard, all its bytes
        hashed = {f: after["ec.seal.hash"][f]
                  - before.get("ec.seal.hash", {}).get(f, 0)
                  for f in ("n", "bytes")}
        assert hashed == {"n": TOTAL_SHARDS, "bytes": TOTAL_SHARDS * shard_size}
        if shape == "hole" and keeps_holes(tmp_path):
            # three of the five rows are zeros: skipped, not written
            for sid in range(TOTAL_SHARDS):
                st = os.stat(base + shard_ext(sid))
                assert st.st_blocks * 512 <= 2 * MIB + 64 * 1024, sid
        # the scrub's view: every shard checked against its sum, none flagged
        for loc in store.locations:
            loc.load_existing_volumes()
        ev = store.find_ec_volume(VID)
        flagged: list = []
        cursor, steps = 0, 0
        while True:
            cursor = VolumeServer._scrub_ec_step(
                ev, cursor, report=lambda vid, sid: flagged.append(sid))
            steps += 1
            if cursor == 0:
                break
        assert steps == TOTAL_SHARDS and flagged == []
    finally:
        store.close()
    assert seal_threads() == []


def test_the_bare_call_returns_the_sums_of_the_files_it_wrote(tmp_path):
    blk = 4096
    base = str(tmp_path / "1")
    rng = np.random.default_rng(28)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(1, 256, 70 * blk + 17, dtype=np.uint8).tobytes())
        f.truncate(150 * blk)
    want = None
    for codec in (NumpyCodec(), CpuCodec()):
        sums = encoder.write_ec_files(base, codec, 1 << 30, blk,
                                      chunk_bytes=2 * blk)
        assert sums == [sha256_of(base + shard_ext(s))
                        for s in range(TOTAL_SHARDS)]
        assert want in (None, sums)  # every codec writes the same bytes
        want = sums
    assert seal_threads() == []


def test_a_digest_fed_other_bytes_than_its_file_holds_is_an_error(tmp_path):
    shards = encoder._HashedShards(
        [open(tmp_path / f"s{i}", "wb") for i in range(3)])
    try:
        shards.append([np.full(10, i, dtype=np.uint8) for i in range(3)])
        shards.skip(6)
        with pytest.raises(RuntimeError, match="fed 16 bytes.*holds 20"):
            shards.finish(20)
        assert shards.finish(16) == [
            hashlib.sha256(bytes([i]) * 10 + bytes(6)).hexdigest()
            for i in range(3)]
    finally:
        shards.close()
    assert seal_threads() == []


def test_rows_hashed_side_by_side_keep_each_files_order(tmp_path):
    """More rows than cores, a short switch interval: every digest still
    sees its own rows, whole and in order."""
    rows, chunks = 4 * (os.cpu_count() or 1) + 3, 60
    rng = np.random.default_rng(29)
    data = rng.integers(0, 256, (chunks, rows, 5000), dtype=np.uint8)
    shards = encoder._HashedShards(
        [open(tmp_path / f"s{i}", "wb") for i in range(rows)])
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    deadline = time.monotonic() + 60
    try:
        for c in range(chunks):
            assert time.monotonic() < deadline
            if c % 7 == 3:
                data[c] = 0
                shards.skip(5000)
            else:
                shards.append(list(data[c]))
        sums = shards.finish(chunks * 5000)
    finally:
        sys.setswitchinterval(interval)
        shards.close()
    for i in range(rows):
        assert sums[i] == hashlib.sha256(data[:, i].tobytes()).hexdigest(), i
        assert sums[i] == sha256_of(str(tmp_path / f"s{i}"))
    assert seal_threads() == []


@pytest.mark.parametrize("fault", ["between-chunks", "in-a-row"])
@pytest.mark.parametrize("kind", ["numpy", "tpu-xla"])
def test_a_seal_that_fails_midway_commits_nothing_and_leaves_no_thread(
        tmp_path, monkeypatch, kind, fault):
    """``between-chunks``: the fault point every chunk passes fires at the
    second chunk, when the pool has hashed the first and its threads stand
    idle (a chunk's rows are awaited before the next chunk is taken, so it
    can fire at no other moment). ``in-a-row``: one shard's write fails
    while the pool holds the thirteen other rows of that chunk, slowed so
    that they are at work when it raises; they are awaited all the same."""
    store, base = load(tmp_path, "body", make_codec(kind))
    at_work = {"begun": 0, "ended": 0}
    append = encoder._HashedShards._append

    def slow_append(self, sid, row):
        if sid == 5 and self._fed[sid]:
            raise OSError(errno.ENOSPC, "no space left on device")
        at_work["begun"] += 1  # under the interpreter lock: a count, no race
        time.sleep(0.05)
        append(self, sid, row)
        at_work["ended"] += 1

    try:
        if fault == "between-chunks":
            faultpoints.arm("ec.encode.chunk", "io-error", skip=1)
            expected = faultpoints.FaultError
        else:
            monkeypatch.setattr(encoder._HashedShards, "_append", slow_append)
            expected = OSError
        with pytest.raises(expected):
            store.ec_encode_volume(VID)
        if fault == "between-chunks":
            assert faultpoints.hits("ec.encode.chunk") == 1
        else:
            # the first chunk's fourteen rows and the second's thirteen
            assert at_work == {"begun": 27, "ended": 27}
        assert seal_threads() == []
        left = sorted(os.path.basename(p) for p in glob.glob(base + ".*"))
        assert left == [f"{VID}.dat", f"{VID}.idx"]  # no .vif, no shard, no .tmp
        # still a plain volume, and a second seal goes through
        n = Needle(id=1)
        store.read_volume_needle(VID, n)
        assert len(n.data) == SHAPES["body"][0][0][0]
        faultpoints.reset()
        monkeypatch.undo()
        store.ec_encode_volume(VID)
        sums = encoder.load_volume_info(base + ".vif")["shard_sums"]
        assert sums == [sha256_of(base + shard_ext(s))
                        for s in range(TOTAL_SHARDS)]
    finally:
        faultpoints.reset()
        store.close()
