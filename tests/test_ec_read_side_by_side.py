"""A chunk's reads go side by side (`encoder._read_side_by_side`): the held
rows of a rebuild's read set, a row a job, and the pieces of a seal's runs,
through the one function, on threads the process keeps — held to the plain
reference (``benchmark/reference.py``, ``benchmark/reference_lrc.py``) over
the geometries and losses the benchmark's cells run, with the host codecs
and the JAX codec. On the CPU, at a few hundred KiB, with the width and the
least bytes a thread is worth forced so that the mechanism engages: bytes
and counts, never a speed."""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest
from test_ec_copy_back import (CASES, CHUNK, LARGE, SMALL, delta,
                               rebuild_chunks, reference_sums, sealed_volume,
                               sha256_of)
from test_ec_copy_back import make_codec as jax_or_numpy_codec
from test_ec_encoder import poisoned  # noqa: F401  (a fixture: 0xFF in every buffer handed out)
from test_ec_encoder import (BLK, READER_CASES, ROW, K, old_read_item,
                             reference_shards, write_dat)

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuCodec, NumpyCodec
from seaweedfs_tpu.ec.constants import Geometry, shard_ext
from seaweedfs_tpu.stats.trace import STAGES

KINDS = ["numpy", "cpu", "xla"]


def make_codec(kind: str, geometry: Geometry):
    if kind == "cpu":
        return CpuCodec().at(*geometry)
    return jax_or_numpy_codec("host" if kind == "numpy" else kind, geometry)


@pytest.fixture
def forced(monkeypatch):
    """``forced(width, least)``: so many reads at once, a thread for every
    ``least`` bytes — the two constants, at a test's size."""
    def force(width: int, least: int = 512):
        monkeypatch.setattr(encoder, "_CHUNK_READS", width)
        monkeypatch.setattr(encoder, "_LEAST_READ", least)
    return force


class _ReadSpy:
    """`encoder._pread_into` with every call noted: the file, the offset,
    the bytes asked for and the thread, and the most reads in flight at
    once; ``slow`` makes a read last long enough for the kept workers to
    take their share."""

    def __init__(self, monkeypatch, slow: float = 0.0):
        self.calls: list[tuple[str, int, int, str]] = []
        self.most = 0  # reads in flight at once
        self._running, self._lock = 0, threading.Lock()
        self._real, self._slow = encoder._pread_into, slow
        monkeypatch.setattr(encoder, "_pread_into", self)

    def __call__(self, fd, offset, views):
        views = list(views)
        with self._lock:
            self._running += 1
            self.most = max(self.most, self._running)
            self.calls.append((os.readlink(f"/proc/self/fd/{fd}"), offset,
                               sum(len(v) for v in views),
                               threading.current_thread().name))
        try:
            if self._slow:
                time.sleep(self._slow)
            self._real(fd, offset, views)
        finally:
            with self._lock:
                self._running -= 1


@pytest.mark.parametrize("width", [3, 16], ids=["under-the-rows", "over-the-rows"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("text, lost", CASES)
def test_a_seal_and_a_rebuild_read_side_by_side_are_the_references_shards(
        tmp_path, forced, poisoned, text, lost, kind, width):
    forced(width)
    geometry = Geometry.parse(text)
    codec = make_codec(kind, geometry)
    before = STAGES.snapshot()
    base, shard_size = sealed_volume(tmp_path, codec)
    sealed = STAGES.snapshot()
    want = reference_sums(base + ".dat", geometry)
    for sid in range(geometry.total_shards):
        assert sha256_of(base + shard_ext(sid)) == want["sums"][sid], sid
    # a seal's read span: one a chunk, the .dat's bytes once, and the
    # pieces its runs were cut into
    dat_size = os.path.getsize(base + ".dat")
    _, items = encoder.plan_encode(codec, dat_size, LARGE, SMALL)
    assert delta(before, sealed, "ec.seal.read", "n") == len(items)
    assert delta(before, sealed, "ec.seal.read", "bytes") == dat_size
    pieces = delta(before, sealed, "ec.seal.read", "reads")
    assert len(items) < pieces <= width * len(items)

    for sid in lost:
        os.remove(base + shard_ext(sid))
    n_read = len(codec.plan(list(lost), [
        s for s in range(geometry.total_shards) if s not in lost]).read)
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=CHUNK) == list(lost)
    after = STAGES.snapshot()
    for sid in range(geometry.total_shards):
        assert os.path.getsize(base + shard_ext(sid)) == shard_size, sid
        assert sha256_of(base + shard_ext(sid)) == want["sums"][sid], sid
    # a rebuild's: one a chunk, every shard of the read set whole, a row a
    # job whatever the width
    chunks = rebuild_chunks(codec, shard_size)
    assert delta(sealed, after, "ec.rebuild.read", "n") == chunks
    assert (delta(sealed, after, "ec.rebuild.read", "bytes")
            == n_read * shard_size)
    assert delta(sealed, after, "ec.rebuild.read", "reads") == n_read * chunks


def test_a_rebuild_reads_every_held_row_once_and_no_hole(
        tmp_path, forced, poisoned, monkeypatch):
    """The spy's account of a rebuild whose read set has holes: a chunk's
    held rows are read exactly once each, at the chunk's place, beside one
    another; a row that is a hole there is not read — and reads zeros, out
    of a buffer that held 0xFF."""
    base = str(tmp_path / "v")
    image = write_dat(base + ".dat", [("data", ROW + 77), ("hole", 6 * ROW),
                                      ("data", 3 * ROW + 5)])
    codec = NumpyCodec()
    encoder.write_ec_files(base, codec, 64 * BLK, BLK, chunk_bytes=BLK)
    want = reference_shards(image, 64 * BLK, BLK)
    # shard 5's zero blocks become holes of its file: in the chunk of the
    # .dat's second row (77 bytes, all in shard 0) the others hold zeros
    with open(base + shard_ext(5), "wb") as f:
        for at in range(0, len(want[5]), BLK):
            block = want[5][at: at + BLK]
            if any(block):
                f.seek(at)
                f.write(block)
        f.truncate(len(want[5]))
    with open(base + shard_ext(5), "rb") as f:
        if not encoder._is_hole(f.fileno(), BLK, BLK):
            pytest.skip("this filesystem keeps no holes")
    for sid in (4, 12):
        os.remove(base + shard_ext(sid))

    forced(4)
    spy = _ReadSpy(monkeypatch, slow=0.002)
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=BLK) == [4, 12]
    for sid in range(14):
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == want[sid], f"shard {sid} differs"

    read_set = [base + shard_ext(s) for s in range(14) if s not in (4, 12)][:10]
    shard_size = len(want[0])
    expected = []
    for path in read_set:
        with open(path, "rb") as f:
            expected += [(path, pos, BLK) for pos in range(0, shard_size, BLK)
                         if not encoder._is_hole(f.fileno(), pos, BLK)]
    assert sorted(c[:3] for c in spy.calls) == sorted(expected)
    assert (base + shard_ext(5), BLK, BLK) not in expected  # the hole
    assert (base + shard_ext(0), BLK, BLK) in expected
    # beside one another: the kept workers took their share
    assert {c[3] for c in spy.calls if c[3].startswith("ec-read")}
    assert 2 <= spy.most <= 4  # and never more at once than the width


def merged(spans) -> list:
    """``(offset, n)`` spans as the fewest ``[start, end]`` that cover
    them; a byte covered twice fails."""
    out: list = []
    for offset, n in sorted(spans):
        if out and out[-1][1] == offset:
            out[-1][1] = offset + n
        else:
            assert not out or out[-1][1] < offset, "a byte twice"
            out.append([offset, offset + n])
    return out


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_a_seals_runs_cut_at_view_boundaries_cover_every_byte_once(
        tmp_path, forced, monkeypatch, case):
    """Every item of every reader case, read into a buffer full of 0xFF
    with its runs cut in pieces: the reads tile the item's segments — no
    byte twice, none left out, every cut at a block's edge — and the matrix
    is the old one-thread reader's."""
    runs, large, small, chunk = READER_CASES[case]
    size = sum(n for _, n in runs)
    dat = str(tmp_path / "v.dat")
    write_dat(dat, runs)
    _, items = encoder.plan_encode(NumpyCodec(), size, large, small, chunk)
    forced(3, least=BLK)
    spy = _ReadSpy(monkeypatch)
    cut_somewhere = False
    with open(dat, "rb") as f:
        fd = f.fileno()
        for item in items:
            segments = encoder._item_segments(fd, item, K, size)
            if not segments:
                continue
            want, _ = old_read_item(f, item, K, size)
            mat = np.full((K, encoder._item_width(item)), 0xFF, dtype=np.uint8)
            spy.calls.clear()
            encoder._read_item(fd, item, segments, mat)
            assert np.array_equal(mat, want), item
            reads = sorted((offset, n) for _, offset, n, _ in spy.calls)
            edges = {offset for _, offset, _ in segments}
            assert all(offset in edges for offset, _ in reads), item
            # the reads, end to end, are the segments, end to end
            assert merged(reads) == merged((o, n) for _, o, n in segments)
            cut_somewhere |= len(reads) > len(merged(reads))
    # a "cols" item's run is one view and is never cut; a run of blocks is
    assert cut_somewhere == (case != "cols")


def test_a_volume_smaller_than_one_piece_is_read_as_it_always_was(
        tmp_path, monkeypatch):
    """At the constants as they stand a test-sized seal hops to no thread:
    one read a run, on the reader thread."""
    base = str(tmp_path / "v")
    write_dat(base + ".dat", [("data", 12 * ROW)])
    codec = NumpyCodec()
    _, items = encoder.plan_encode(codec, 12 * ROW, 64 * BLK, BLK, 4 * BLK)
    assert 12 * ROW < encoder._LEAST_READ
    spy = _ReadSpy(monkeypatch)
    before = STAGES.snapshot()
    encoder.write_ec_files(base, codec, 64 * BLK, BLK, chunk_bytes=4 * BLK)
    after = STAGES.snapshot()
    assert len(spy.calls) == len(items)
    assert not any(c[3].startswith("ec-read") for c in spy.calls)
    assert len({c[3] for c in spy.calls}) == 1  # the pipeline's reader
    assert delta(before, after, "ec.seal.read", "reads") == len(items)


def test_side_by_side_ends_every_job_begun_before_it_raises():
    """One job of many fails on whichever thread took it: the call raises
    that error only when no job is running any more, and begins none after
    the failure was seen."""
    running, begun, lock = [0], [], threading.Lock()

    def job(i):
        with lock:
            running[0] += 1
            begun.append(i)
        try:
            time.sleep(0.01)
            if i == 2:
                raise OSError("row 2 cannot be read")
            return i * i
        finally:
            with lock:
                running[0] -= 1

    with pytest.raises(OSError, match="row 2 cannot be read"):
        encoder._side_by_side(encoder._read_workers, job, list(range(40)), 4)
    assert running[0] == 0
    assert len(begun) < 40
    # and whole: the results in the jobs' order, whoever took them
    begun.clear()
    assert encoder._side_by_side(
        encoder._read_workers, lambda i: i * i, list(range(40)), 4
    ) == [i * i for i in range(40)]


def test_the_readers_workers_are_the_processs_and_few(tmp_path, forced):
    """A seal or a rebuild starts no thread for its reads: the workers are
    made by the first chunk wide enough and kept; they are not the copy
    back's."""
    forced(encoder._CHUNK_READS)
    codec = NumpyCodec()
    base, _ = sealed_volume(tmp_path, codec)

    def readers():
        return {t for t in threading.enumerate()
                if t.name.startswith("ec-read")}

    kept = readers()
    assert 1 <= len(kept) <= encoder._CHUNK_READS - 1
    os.remove(base + shard_ext(4))
    encoder.rebuild_ec_files(base, codec, chunk_bytes=CHUNK)
    encoder.write_ec_files(base, codec, large_block_size=LARGE,
                           small_block_size=SMALL)
    assert kept <= readers() and len(readers()) <= encoder._CHUNK_READS - 1
    assert encoder._read_workers() is encoder._read_workers()
    assert encoder._read_workers() is not encoder._copy_back_workers()
