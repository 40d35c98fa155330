"""File-level EC tests, modeled on the reference's ec_test.go:

build a small fixture volume, stripe it with tiny block sizes (large=10000,
small=100 — same trick as ec_test.go:17-19 to exercise the large/small
boundary without GB files), then re-read every needle THROUGH the interval
math + shard files and byte-compare against the .dat.
"""

import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder, locate
from seaweedfs_tpu.ec.codec import Codec, CpuCodec, NumpyCodec, TpuCodec
from seaweedfs_tpu.ec.constants import shard_ext
from seaweedfs_tpu.storage import idx
from seaweedfs_tpu.storage.needle import VERSION3, Needle
from seaweedfs_tpu.storage.super_block import SuperBlock

LARGE = 10000
SMALL = 100


@pytest.fixture()
def fixture_volume(tmp_path):
    """Write a volume of ~300 random needles like the reference fixture."""
    rng = np.random.default_rng(42)
    base = str(tmp_path / "1")
    entries = []
    with open(base + ".dat", "wb") as f, open(base + ".idx", "wb") as ix:
        f.write(SuperBlock().to_bytes())
        off = 8
        for i in range(300):
            size = int(rng.integers(1, 20000))
            n = Needle(cookie=int(rng.integers(0, 2**32)), id=i + 1,
                       data=rng.integers(0, 256, size, dtype=np.uint8).tobytes())
            blob = n.to_bytes(VERSION3)
            f.write(blob)
            ix.write(idx.pack_entry(n.id, off, n.size))
            entries.append((n.id, off, n.size))
            off += len(blob)
    return base, entries


def read_ec_bytes(base, dat_size, offset, size):
    """Read a byte range through the shard files via interval math."""
    out = b""
    for iv in locate.locate_data(LARGE, SMALL, dat_size, offset, size):
        shard_id, shard_off = iv.to_shard_id_and_offset(LARGE, SMALL)
        with open(base + shard_ext(shard_id), "rb") as f:
            f.seek(shard_off)
            out += f.read(iv.size)
    return out


def test_encode_and_validate_every_needle(fixture_volume):
    base, entries = fixture_volume
    codec = CpuCodec()
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=1024)
    dat_size = os.path.getsize(base + ".dat")

    # all 14 shard files exist, same size, matching the closed-form size
    sizes = {os.path.getsize(base + shard_ext(i)) for i in range(14)}
    assert len(sizes) == 1
    assert sizes.pop() == encoder.ec_shard_base_size(dat_size, 10, LARGE, SMALL)

    with open(base + ".dat", "rb") as f:
        dat = f.read()
    for key, off, size in entries:
        from seaweedfs_tpu.storage.needle import get_actual_size

        full = get_actual_size(size, VERSION3)
        assert read_ec_bytes(base, dat_size, off, full) == dat[off : off + full], key


def test_rebuild_worst_case_bit_identical(fixture_volume):
    base, _ = fixture_volume
    codec = CpuCodec()
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=4096)
    orig = {}
    for sid in (0, 3, 10, 13):
        with open(base + shard_ext(sid), "rb") as f:
            orig[sid] = f.read()
        os.remove(base + shard_ext(sid))

    generated = encoder.rebuild_ec_files(base, codec, chunk_bytes=3000)
    assert sorted(generated) == [0, 3, 10, 13]
    for sid, want in orig.items():
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want, f"shard {sid} not bit-identical after rebuild"


@pytest.mark.parametrize(
    "gone",
    [
        (0, 1, 2, 3),          # 4 data shards: worst case
        (10, 11, 12, 13),      # parity only (composed decode rows)
        (7,),                  # single data shard
        (2, 11),               # mixed
    ],
)
def test_rebuild_pipelined_combos_bit_identical(fixture_volume, gone):
    """A rebuild's single combined matmul (the plan's matrix, `Codec.plan`) must give back
    the sealed bytes for every missing-shard shape."""
    base, _ = fixture_volume
    codec = TpuCodec(chunk_bytes=8 * 1024, tile_bytes=1024)
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=4096)
    orig = {}
    for sid in gone:
        with open(base + shard_ext(sid), "rb") as f:
            orig[sid] = f.read()
        os.remove(base + shard_ext(sid))
    generated = encoder.rebuild_ec_files(base, codec, chunk_bytes=3000)
    assert sorted(generated) == sorted(gone)
    for sid, want in orig.items():
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want, f"shard {sid} differs"


def test_rebuild_pipeline_error_raises_not_hangs(fixture_volume):
    """A device failure mid-pipeline must surface as an exception promptly,
    not deadlock the reader on a full queue (regression: the shutdown path
    must drain both queues)."""
    import threading

    base, _ = fixture_volume
    codec = TpuCodec(chunk_bytes=8 * 1024, tile_bytes=1024)
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=4096)
    os.remove(base + shard_ext(2))

    class _Exploding(TpuCodec):
        def device_put(self, data):
            raise RuntimeError("injected device failure")

    bad = _Exploding(chunk_bytes=8 * 1024, tile_bytes=1024)
    result: list = []

    def run():
        try:
            # tiny chunks → many queue items → a blocked reader if the
            # shutdown path doesn't drain
            encoder.rebuild_ec_files(base, bad, chunk_bytes=512)
            result.append("no error")
        except RuntimeError as e:
            result.append(str(e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=20)
    assert not t.is_alive(), "rebuild deadlocked on device failure"
    assert result == ["injected device failure"]


def test_rebuild_noop_when_all_present(fixture_volume):
    base, _ = fixture_volume
    codec = CpuCodec()
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=4096)
    assert encoder.rebuild_ec_files(base, codec) == []


def test_rebuild_requires_k_shards(fixture_volume):
    base, _ = fixture_volume
    codec = CpuCodec()
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=4096)
    for sid in range(5):
        os.remove(base + shard_ext(sid))
    with pytest.raises(ValueError):
        encoder.rebuild_ec_files(base, codec)


def test_write_sorted_file_from_idx(fixture_volume, tmp_path):
    base, entries = fixture_volume
    # append an overwrite and a delete to exercise latest-wins
    last_key = entries[-1][0]
    with open(base + ".idx", "ab") as ix:
        ix.write(idx.pack_entry(entries[0][0], 0, -1))  # delete first key
        ix.write(idx.pack_entry(last_key, 16, 99))  # overwrite last key
    encoder.write_sorted_file_from_idx(base)

    with open(base + ".ecx", "rb") as f:
        got = list(idx.iter_index_file(f))
    keys = [k for k, _, _ in got]
    assert keys == sorted(keys), ".ecx must be ascending by key"
    assert entries[0][0] not in keys
    by_key = {k: (o, s) for k, o, s in got}
    assert by_key[last_key] == (16, 99)


def test_vif_roundtrip(tmp_path):
    path = str(tmp_path / "1.vif")
    encoder.save_volume_info(path, version=3, replication="010")
    info = encoder.load_volume_info(path)
    assert info["version"] == 3
    assert info["replication"] == "010"
    assert encoder.load_volume_info(str(tmp_path / "none.vif"))["version"] == 0


def test_zero_tail_padding_matches_reference_semantics(tmp_path):
    """A .dat whose size is not a multiple of small*k zero-pads the tail row
    (encodeDataOneBatch, ec_encoder.go:172-176)."""
    base = str(tmp_path / "v")
    payload = bytes(range(256)) * 7  # 1792 bytes: 1 large row? no — < large*k
    with open(base + ".dat", "wb") as f:
        f.write(payload)
    codec = CpuCodec()
    encoder.write_ec_files(base, codec, LARGE, SMALL, chunk_bytes=64)
    # shard size: ceil(1792 / (100*10)) = 2 small rows → 200 bytes/shard
    assert os.path.getsize(base + shard_ext(0)) == 200
    # data shards hold the striped payload + zeros
    with open(base + shard_ext(0), "rb") as f:
        s0 = f.read()
    assert s0[:100] == payload[0:100]  # row 0 block 0
    assert s0[100:200] == payload[1000:1100]  # row 1 block 0
    with open(base + shard_ext(9), "rb") as f:
        s9 = f.read()
    assert s9[:100] == payload[900:1000]
    # row 1 shard 9 covers dat[1900:2000) → 1792-1900 < 0 → all zeros
    assert s9[100:200] == b"\x00" * 100


# -- the reader leg: every block read once, to its place in a recycled buffer --
K = 10
BLK = 4096  # one filesystem block, so that a punched segment is a real hole


# every kind of codec goes the one way through the pipeline: a host codec
# in numpy, the native one, and a JAX codec (here the XLA formulation)
CODECS = {
    "numpy": NumpyCodec,
    "cpu": CpuCodec,
    "tpu-xla": lambda: TpuCodec(chunk_bytes=16 * 1024, tile_bytes=1024),
}


def old_read_item(f, item, k, dat_size):
    """The reader this PR replaced (read, zero-filled copy, transpose; a
    row at a time for "cols"), kept as the reference for the new one."""
    fd = f.fileno()
    if item[0] == "cols":
        _, start, block_size, col, width = item
        out = np.zeros((k, width), dtype=np.uint8)
        has_data = False
        for i in range(k):
            seg_start = start + i * block_size + col
            if seg_start >= dat_size:
                continue
            n = min(width, dat_size - seg_start)
            if encoder._is_hole(fd, seg_start, n):
                continue
            f.seek(seg_start)
            buf = f.read(n)
            out[i, : len(buf)] = np.frombuffer(buf, dtype=np.uint8)
            has_data = True
        return out, has_data
    _, start, block_size, g = item
    total = g * k * block_size
    end = min(start + total, dat_size)
    if start >= dat_size or encoder._is_hole(fd, start, end - start):
        return np.zeros((k, g * block_size), dtype=np.uint8), False
    arr = np.zeros(total, dtype=np.uint8)
    for seg in range(g * k):
        seg_start = start + seg * block_size
        if seg_start >= dat_size:
            break
        n = min(block_size, dat_size - seg_start)
        if encoder._is_hole(fd, seg_start, n):
            continue
        f.seek(seg_start)
        buf = f.read(n)
        arr[seg * block_size : seg * block_size + len(buf)] = (
            np.frombuffer(buf, dtype=np.uint8))
    mat = arr.reshape(g, k, block_size).transpose(1, 0, 2)
    return np.ascontiguousarray(mat.reshape(k, g * block_size)), True


def reference_shards(dat: bytes, large: int, small: int,
                     geometry=(K, 4)) -> list[bytes]:
    """All shards (14 unless ``geometry`` says otherwise) from the .dat in
    memory: the striping rule spelt out, parity by NumpyCodec over whole
    shards."""
    k = geometry[0]
    size = encoder.ec_shard_base_size(len(dat), k, large, small)
    data = np.zeros((k, size), dtype=np.uint8)
    src = np.frombuffer(dat, dtype=np.uint8)
    pos = out = 0
    while len(dat) - pos > large * k:
        for i in range(k):
            data[i, out : out + large] = src[pos + i * large :][:large]
        pos, out = pos + large * k, out + large
    while pos < len(dat):
        for i in range(k):
            seg = src[pos + i * small :][:small]
            data[i, out : out + len(seg)] = seg
        pos, out = pos + small * k, out + small
    parity = NumpyCodec().at(*geometry).encode(data)
    return [bytes(r) for r in data] + [bytes(r) for r in parity]


def write_dat(path: str, runs: list, seed: int = 7) -> bytes:
    """A .dat of ``runs``: ("data", n) random bytes, ("hole", n) a seek the
    filesystem keeps as a hole. Returns the bytes a reader must see."""
    rng = np.random.default_rng(seed)
    image = bytearray()
    with open(path, "wb") as f:
        for kind, n in runs:
            if kind == "hole":
                f.seek(n, 1)
                image += bytes(n)
            else:
                blob = rng.integers(1, 256, n, dtype=np.uint8).tobytes()
                f.write(blob)
                image += blob
        f.truncate(len(image))
    return bytes(image)


ROW = K * BLK
# name -> (runs, large block, small block, chunk_bytes)
READER_CASES = {
    # rows of four blocks a chunk, the file ends on a row's edge
    "dense": ([("data", 12 * ROW)], 64 * BLK, BLK, 4 * BLK),
    # the last chunk ends in the middle of a block, three blocks into a row
    "ends-mid-row": ([("data", 9 * ROW + 3 * BLK + 1234)], 64 * BLK, BLK,
                     4 * BLK),
    # punched deletes: holes of whole blocks inside regions that hold data
    "punched": ([("data", 3 * BLK), ("hole", 5 * BLK), ("data", 9 * BLK),
                 ("hole", 2 * BLK), ("data", ROW + 7 * BLK), ("hole", ROW),
                 ("data", 2 * ROW + 11)], 64 * BLK, BLK, 2 * BLK),
    # whole chunks that are one hole (no buffer, no encode), data after
    "all-hole-regions": ([("data", ROW), ("hole", 8 * ROW), ("data", 2 * ROW),
                          ("hole", 4 * ROW + 5 * BLK)], 64 * BLK, BLK,
                         2 * BLK),
    # blocks wider than the chunk, large and small: "cols" items, with a
    # large row that is one hole, holes that take whole segments of a
    # small row and a tail past EOF
    "cols": ([("data", 4 * ROW), ("hole", 4 * ROW), ("data", 4 * ROW),
              ("hole", 8 * BLK), ("data", 9 * BLK),
              ("hole", 4 * BLK), ("data", 4 * BLK + 99)], 4 * BLK, 2 * BLK,
             BLK),
}


@pytest.fixture()
def poisoned(monkeypatch):
    """Every buffer the pool hands out is filled with 0xFF first, new or
    recycled: a byte the reader fails to write shows in the shards."""
    take = encoder._ChunkBuffers.take

    def poisoned_take(self, k, width):
        mat = take(self, k, width)
        mat.base[:] = 0xFF
        return mat

    monkeypatch.setattr(encoder._ChunkBuffers, "take", poisoned_take)


@pytest.mark.parametrize("kind", sorted(CODECS))
@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_seal_from_poisoned_buffers_matches_the_reference(
        tmp_path, poisoned, case, kind):
    runs, large, small, chunk = READER_CASES[case]
    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", runs)
    codec = CODECS[kind]()
    _, items = encoder.plan_encode(codec, len(image), large, small, chunk)
    kinds = {it[0] for it in items}
    assert kinds == ({"cols"} if case == "cols" else {"rows"}), kinds
    assert len(items) > encoder._POOL_BUFFERS  # buffers do come back
    encoder.write_ec_files(base, codec, large, small, chunk_bytes=chunk)
    want = reference_shards(image, large, small)
    for sid in range(14):
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"{case}: shard {sid} differs"


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_two_volumes_through_one_buffer_leave_nothing_stale(tmp_path, case):
    """Twice through ONE pool of one buffer, so that every chunk is read
    over the one before it: first a dense volume (so the buffer is full of
    data), then the case's."""
    runs, large, small, chunk = READER_CASES[case]
    size = sum(n for _, n in runs)
    first, second = str(tmp_path / "a.dat"), str(tmp_path / "b.dat")
    write_dat(first, [("data", size)], seed=1)
    write_dat(second, runs, seed=2)
    _, items = encoder.plan_encode(NumpyCodec(), size, large, small, chunk)
    pool = encoder._ChunkBuffers("ec.test", encoder._chunk_nbytes(items, K),
                                 count=1)
    no_data = 0
    for path in (first, second):
        with open(path, "rb") as f:
            fd = f.fileno()
            for item in items:
                want, has_data = old_read_item(f, item, K, size)
                segments = encoder._item_segments(fd, item, K, size)
                assert bool(segments) == has_data, item
                if not segments:
                    no_data += 1
                    continue
                mat = pool.take(K, encoder._item_width(item))
                encoder._read_item(fd, item, segments, mat)
                assert mat.flags.c_contiguous and mat.shape == want.shape
                assert np.array_equal(mat, want), item
                pool.give(mat)
    assert (no_data > 0) == (case in ("all-hole-regions", "cols")), no_data


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_rebuild_from_poisoned_buffers_pads_the_last_chunk(
        tmp_path, poisoned, monkeypatch, sparse):
    """A device codec whose launches want 256-byte multiples: every chunk's
    [width:padded] tail, and a sparse volume's hole rows, must read zeros."""
    runs = ([("data", ROW + 77), ("hole", 6 * ROW), ("data", 3 * ROW + 5)]
            if sparse else [("data", 10 * ROW + 1001)])
    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", runs)
    codec = NumpyCodec()
    encoder.write_ec_files(base, codec, 64 * BLK, BLK, chunk_bytes=2 * BLK)
    want = reference_shards(image, 64 * BLK, BLK)
    gone = (0, 4, 9, 12)
    for sid in gone:
        os.remove(base + shard_ext(sid))
    monkeypatch.setattr(codec, "alignment", lambda: 256)
    # 5000 is no multiple of 256, nor is the last chunk's width
    assert sorted(encoder.rebuild_ec_files(base, codec, chunk_bytes=5000)) == (
        list(gone))
    for sid in gone:
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid} differs"


def test_short_reads_are_read_on_not_left_stale(tmp_path, poisoned, monkeypatch):
    """A scatter read may return fewer bytes than asked, anywhere: the
    reader goes on from there, across buffers, until EOF."""
    preadv = os.preadv
    calls = []

    def short_preadv(fd, views, offset):
        want = sum(len(v) for v in views)
        cut = max(1, min(want, 1000 + 7 * len(calls)))  # never a whole block
        calls.append(cut)
        part, left = [], cut
        for v in views:
            part.append(v[:left])
            left -= len(part[-1])
            if not left:
                break
        return preadv(fd, part, offset)

    monkeypatch.setattr(encoder.os, "preadv", short_preadv)
    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", [("data", 2 * ROW + 3 * BLK + 17)])
    codec = NumpyCodec()
    encoder.write_ec_files(base, codec, 64 * BLK, BLK, chunk_bytes=2 * BLK)
    want = reference_shards(image, 64 * BLK, BLK)
    os.remove(base + shard_ext(3))
    os.remove(base + shard_ext(11))
    encoder.rebuild_ec_files(base, codec, chunk_bytes=3 * BLK)
    assert len(calls) > 14 * 3 * BLK // 2000
    for sid in range(14):
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid} differs"


@pytest.mark.parametrize("leg", ["read", "dispatch", "fetch", "write"])
def test_an_error_in_any_leg_ends_a_call_whose_reader_waits_for_a_buffer(leg):
    """Nobody gives a buffer back, so after `_POOL_BUFFERS` chunks the reader
    waits in `take`; the leg then fails on the last chunk it is handed. The
    call must end with that error, not hang."""
    import threading
    import time

    last = encoder._POOL_BUFFERS - 1
    buffers = encoder._ChunkBuffers("ec.stuck", 64)
    waiting = threading.Event()
    take = buffers.take

    def produce():
        for i in range(20):
            if i > last:
                waiting.set()  # the next take can only wait
            buf = take(1, 64)
            yield lambda i=i, buf=buf: fail_in("read", i)

    def fail_in(here, i):
        if here == leg and i == last:
            if leg != "read":
                assert waiting.wait(10)
                time.sleep(0.05)  # let the reader reach the wait
            raise RuntimeError(f"injected in {leg}")
        return i

    result: list = []

    def run():
        try:
            encoder._overlap_pipeline(
                produce,
                lambda i: fail_in("dispatch", i),
                lambda i: fail_in("write", i),
                fetch=lambda i: fail_in("fetch", i),
                op="ec.stuck", buffers=buffers)
            result.append("no error")
        except RuntimeError as e:
            result.append(str(e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=20)
    assert not t.is_alive(), f"pipeline hung on an error in {leg}"
    assert result == [f"injected in {leg}"]


# -- the buffers outlive the call: another volume's, geometry's, call's bytes --
def taken(before: dict, after: dict, op: str) -> tuple[int, int]:
    """Buffers ``op`` allocated and recycled between two snapshots."""
    return tuple(
        after.get(f"{op}.buf.{how}", {}).get("n", 0)
        - before.get(f"{op}.buf.{how}", {}).get("n", 0)
        for how in ("new", "wait"))


@pytest.mark.parametrize("kind", sorted(CODECS))
def test_other_geometries_and_a_shorter_volume_through_the_same_buffers(
        tmp_path, kept, kind):
    """Seal at 12+4, rebuild at 12+2+2 from six rows, then seal a SHORTER,
    sparse .dat at 10+4: every call after the first finds the first one's
    buffers, full of another volume's bytes in another shape, and none of
    them leaves."""
    from seaweedfs_tpu.ec.constants import Geometry
    from seaweedfs_tpu.stats.trace import STAGES

    one = CODECS[kind]()
    large, chunk = 64 * BLK, 4 * BLK
    snaps = [STAGES.snapshot()]

    def sealed(name, runs, geometry, seed):
        base = str(tmp_path / name)
        image = write_dat(base + ".dat", runs, seed=seed)
        encoder.write_ec_files(base, one.at(*geometry), large, BLK,
                               chunk_bytes=chunk)
        snaps.append(STAGES.snapshot())
        want = reference_shards(image, large, BLK, geometry)
        for sid, shard in enumerate(want):
            with open(base + shard_ext(sid), "rb") as f:
                assert f.read() == shard, f"{name}: shard {sid} differs"
        return base, want

    sealed("a", [("data", 14 * 12 * BLK + 777)], Geometry(12, 4), seed=1)
    assert 1 <= taken(*snaps[-2:], "ec.seal")[0] <= encoder._POOL_BUFFERS
    lrc = Geometry(12, 4, 2)
    base, want = sealed("b", [("data", 7 * 12 * BLK + 5)], lrc, seed=2)
    assert taken(*snaps[-2:], "ec.seal")[0] == 0
    os.remove(base + shard_ext(4))
    # 5000 is no multiple of the launch's alignment, nor of a block
    assert encoder.rebuild_ec_files(base, one.at(*lrc), chunk_bytes=5000) == [4]
    snaps.append(STAGES.snapshot())
    new, recycled = taken(*snaps[-2:], "ec.rebuild")
    assert new == 0 and recycled >= 3
    # the local group's others: a (6, width) chunk
    assert (snaps[-1]["ec.rebuild.plan"]["width"]
            - snaps[-2].get("ec.rebuild.plan", {}).get("width", 0)) == 6
    with open(base + shard_ext(4), "rb") as f:
        assert f.read() == want[4]
    # four rows a chunk: the second is one hole and takes no buffer
    sealed("c", [("data", ROW + 77), ("hole", 7 * ROW), ("data", 2 * ROW + 5),
                 ("hole", 3 * BLK)], Geometry(10, 4), seed=3)
    assert taken(*snaps[-2:], "ec.seal") == (0, 2)
    assert 1 <= len(kept) <= encoder._POOL_BUFFERS
    assert {flat.nbytes for flat in kept} == {12 * chunk}


def test_a_call_that_fails_leaves_the_kept_buffers_usable(tmp_path, kept):
    """An error closes the call's pool while chunks are in flight: what
    comes back afterwards is kept or freed, never lost to a waiting reader,
    and the next call seals right."""
    class FailsOnItsThirdLaunch(NumpyCodec):
        launches = 0

        def matmul_device(self, matrix, data):
            self.launches += 1
            if self.launches == 3:
                raise RuntimeError("injected in dispatch")
            return super().matmul_device(matrix, data)

    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", [("data", 12 * ROW + 99)])
    with pytest.raises(RuntimeError, match="injected in dispatch"):
        encoder.write_ec_files(base, FailsOnItsThirdLaunch(), 64 * BLK, BLK,
                               chunk_bytes=2 * BLK)
    assert len(kept) <= encoder._POOL_BUFFERS
    assert len({id(flat) for flat in kept}) == len(kept)
    encoder.write_ec_files(base, NumpyCodec(), 64 * BLK, BLK,
                           chunk_bytes=2 * BLK)
    assert 1 <= len(kept) <= encoder._POOL_BUFFERS
    want = reference_shards(image, 64 * BLK, BLK)
    for sid in range(14):
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid} differs"


# -- the interface the encoder drives, on every class get_codec can return ----
LOST = (0, 4, 9, 12)
CONFORMANCE_VOLUMES = {
    "dense": [("data", 12 * ROW + 3 * BLK + 1234)],
    "sparse": [("data", ROW + 77), ("hole", 6 * ROW), ("data", 3 * ROW + 5),
               ("hole", 2 * ROW)],
}


@pytest.fixture(scope="module")
def built_codecs():
    """Each backend name `get_codec` knows, built once (a JAX codec compiles
    per shape). `get_codec` refuses a NAMED ``tpu`` / ``mesh`` off a TPU,
    so on the CPU platform those two classes are built as it builds them."""
    from seaweedfs_tpu.ec.codec import get_codec
    from seaweedfs_tpu.ec.sharded import MeshCodec

    return {
        "numpy": get_codec("numpy"),
        "cpu": get_codec("cpu"),
        "tpu": TpuCodec(chunk_bytes=16 * 1024, tile_bytes=1024),
        "mesh": MeshCodec(n_devices=4, chunk_bytes=16 * 1024),
    }


@pytest.mark.parametrize("volume", sorted(CONFORMANCE_VOLUMES))
@pytest.mark.parametrize("backend", ["numpy", "cpu", "tpu", "mesh"])
def test_every_codec_answers_the_interface_and_seals_and_rebuilds_through_it(
        tmp_path, built_codecs, backend, volume):
    codec = built_codecs[backend]
    assert isinstance(codec, Codec) and codec.backend == backend
    # the interface, as the encoder calls it: no probing
    assert codec.chunk_bytes > 0
    align = codec.alignment()
    assert align >= 1
    free = codec.device_memory_free()
    assert free is None or free > 0
    rng = np.random.default_rng(28)
    piece = rng.integers(0, 256, (K, 2 * align), dtype=np.uint8)
    staged = codec.device_put(piece)
    assert staged.shape == piece.shape and staged.nbytes == piece.nbytes
    parity = np.asarray(codec.matmul_device(codec.parity_rows, staged))
    assert np.array_equal(parity, NumpyCodec().encode(piece))

    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", CONFORMANCE_VOLUMES[volume])
    sums = encoder.write_ec_files(base, codec, 64 * BLK, BLK,
                                  chunk_bytes=4 * BLK)
    want = reference_shards(image, 64 * BLK, BLK)
    assert sums == [hashlib.sha256(w).hexdigest() for w in want]
    for sid in LOST:
        os.remove(base + shard_ext(sid))
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=3 * BLK) == (
        list(LOST))
    for sid in range(14):
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid} differs"
