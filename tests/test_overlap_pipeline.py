"""The overlap pipeline must actually overlap (VERDICT r2 weak #5).

Synthetic stages with known busy times prove wall ≈ max(stage), not
Σ(stages) — the property that makes the pipeline beat the reference's
serial read→Encode→write loop (ec_encoder.go:162-192). The seconds are
read where /status reads them: the tracer's stage table, as a delta
under an ``op`` of the test's own.
"""

import functools
import os
import time

import numpy as np

from seaweedfs_tpu.ec.encoder import _overlap_pipeline
from seaweedfs_tpu.stats.trace import STAGES

LEGS = ("read", "dispatch", "fetch", "write")


def staged(op: str, run) -> dict:
    """What ``run()``, a pipeline under ``op``, added to the stage table:
    ``wall_s``, ``<leg>_busy_s`` and ``efficiency`` = the busiest leg over
    the wall (1.0: the slowest stage hides the others)."""
    before = STAGES.snapshot()
    run()
    after = STAGES.snapshot()

    def busy(stage):
        name = f"{op}.{stage}"
        return after[name]["busy_s"] - before.get(name, {}).get("busy_s", 0.0)

    out = {f"{leg}_busy_s": busy(leg) for leg in LEGS}
    out["wall_s"] = busy("pipeline")
    out["efficiency"] = max(out[f"{leg}_busy_s"] for leg in LEGS) / out["wall_s"]
    return out


def _run(n_items, t_read, t_compute, t_write):

    def read(i):
        time.sleep(t_read)
        return i

    def produce():  # one read job a chunk
        for i in range(n_items):
            yield functools.partial(read, i)

    def compute(x):
        time.sleep(t_compute)
        return x

    def consume(x):
        time.sleep(t_write)

    return staged("ec.synthetic", lambda: _overlap_pipeline(
        produce, compute, consume, fetch=lambda x: x, op="ec.synthetic"))


def test_wall_tracks_slowest_stage_not_sum():
    n, tr, tc, tw = 10, 0.02, 0.006, 0.02
    stats = _run(n, tr, tc, tw)
    serial = n * (tr + tc + tw)
    # wall ≈ max-stage (0.2s) not Σ (0.46s); generous CI margins
    assert stats["wall_s"] < 0.65 * serial, stats
    assert stats["efficiency"] >= 0.7, stats
    # busy accounting adds up to roughly the configured sleeps
    assert stats["read_busy_s"] >= n * tr * 0.9
    assert stats["write_busy_s"] >= n * tw * 0.9


def test_slow_writer_hides_reader_and_compute():
    stats = _run(8, 0.004, 0.004, 0.03)
    assert stats["write_busy_s"] > stats["read_busy_s"]
    assert stats["efficiency"] >= 0.7, stats


def test_stats_on_real_encode(tmp_path):
    """A seal leaves its pipeline's stages in the tracer's stage table,
    each leg once a chunk with the bytes it moved; a host codec, so CI
    needs no TPU."""
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import NumpyCodec
    from seaweedfs_tpu.stats import trace

    base = str(tmp_path / "1")
    rng = np.random.default_rng(3)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes())
    codec = NumpyCodec()
    _, items = encoder.plan_encode(codec, 300_000, 8192, 1024)
    before = trace.STAGES.snapshot()
    encoder.write_ec_files(
        base, codec, large_block_size=8192, small_block_size=1024,
    )
    after = trace.STAGES.snapshot()

    def delta(name, field):
        return after[name][field] - before.get(name, {}).get(field, 0)

    assert delta("ec.seal.pipeline", "n") == 1
    assert delta("ec.seal.pipeline", "busy_s") > 0
    for leg in ("read", "dispatch", "fetch", "write", "h2d", "d2h"):
        assert delta(f"ec.seal.{leg}", "n") == len(items), leg
        assert delta(f"ec.seal.{leg}", "busy_s") > 0, leg
    assert delta("ec.seal.read", "bytes") == 300_000
    shard = os.path.getsize(base + ".ec00")
    assert delta("ec.seal.write", "bytes") == 14 * shard
    assert delta("ec.seal.d2h", "bytes") == 4 * shard


def test_four_leg_overlap_hides_dispatch_behind_fetch():
    """The r5 shape: a dedicated fetch (D2H) leg must let the compute
    (H2D+dispatch) stage of chunk i+1 run concurrently with the fetch of
    chunk i — wall ≈ max(stage), with all four busy legs accounted."""
    n, tc, tf = 8, 0.02, 0.06

    def produce():
        for i in range(n):
            yield lambda i=i: i

    def compute(x):
        time.sleep(tc)
        return x

    def fetch(x):
        time.sleep(tf)  # the dominant leg (slow-link D2H)
        return x

    def consume(x):
        pass

    stats = staged("ec.synthetic", lambda: _overlap_pipeline(
        produce, compute, consume, fetch=fetch, op="ec.synthetic"))
    # what the two legs took one after the other: the sleeps as they came
    # out (a loaded machine oversleeps), never less than as they were asked
    serial = max(n * (tc + tf),
                 stats["dispatch_busy_s"] + stats["fetch_busy_s"])
    assert stats["fetch_busy_s"] >= n * tf * 0.9
    assert stats["wall_s"] < 0.9 * serial, stats
    assert stats["efficiency"] >= 0.7, stats


def test_fetch_leg_error_propagates():
    def produce():
        for i in range(5):
            yield lambda i=i: i

    def compute(x):
        return x

    def fetch(x):
        if x == 2:
            raise RuntimeError("boom in fetch")
        return x

    seen = []

    def consume(x):
        seen.append(x)

    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="boom in fetch"):
        _overlap_pipeline(produce, compute, consume, fetch=fetch)


class _RowNeverComes:
    """A device result's row whose transfer fails."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("row 1 of chunk 3 never came")


class _OnAFakeDevice:
    """A host array behind what the copy back asks of a device result:
    it can be awaited, and hands out its rows."""

    def __init__(self, host: np.ndarray, broken: bool):
        self._host, self._broken = host, broken
        self.shape, self.dtype, self.nbytes = host.shape, host.dtype, host.nbytes

    def block_until_ready(self):
        return self

    def __getitem__(self, j):
        return _RowNeverComes() if self._broken and j == 1 else self._host[j]


def test_a_rows_transfer_that_raises_fails_the_seal_and_nothing_is_left(
        tmp_path, kept):
    """One row of one chunk fails on its way back: the seal raises that
    error, every thread of the pipeline has ended, and both chunk buffers
    — the failed chunk's and the one the pipeline dropped behind it — are
    back with the process."""
    import threading

    import pytest as _pytest

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import NumpyCodec

    class OneRowFails(NumpyCodec):
        launched = 0

        def matmul_device(self, matrix, data):
            OneRowFails.launched += 1
            return _OnAFakeDevice(self.matmul(matrix, np.asarray(data)),
                                  broken=OneRowFails.launched == 3)

    base = str(tmp_path / "1")
    rng = np.random.default_rng(5)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes())
    codec = OneRowFails()
    _, items = encoder.plan_encode(codec, 300_000, 8192, 1024)
    assert len(items) >= 5
    threads = set(threading.enumerate())
    with _pytest.raises(RuntimeError, match="row 1 of chunk 3 never came"):
        encoder.write_ec_files(
            base, codec, large_block_size=8192, small_block_size=1024)
    # the kept workers of the copy back are the process's, idle now
    left = [t.name for t in set(threading.enumerate()) - threads
            if not t.name.startswith("ec-copy-back")]
    assert left == []
    assert OneRowFails.launched >= 3
    assert len(kept) == encoder._POOL_BUFFERS
    # and the next seal, of the same process, is whole
    OneRowFails.launched = 3
    sums = encoder.write_ec_files(
        base, codec, large_block_size=8192, small_block_size=1024)
    assert len(sums) == 14
    assert len(kept) == encoder._POOL_BUFFERS


def test_a_read_that_raises_on_a_worker_fails_the_rebuild_and_nothing_is_left(
        tmp_path, kept, monkeypatch):
    """One row's read of one chunk fails on a kept worker, beside the
    reader thread's own: the rebuild raises that error, every thread of the
    pipeline has ended, no read is left in flight into a buffer — both are
    back with the process — and the next rebuild is whole."""
    import threading

    import pytest as _pytest

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import NumpyCodec
    from seaweedfs_tpu.ec.constants import shard_ext

    monkeypatch.setattr(encoder, "_LEAST_READ", 512)
    base = str(tmp_path / "1")
    rng = np.random.default_rng(6)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes())
    codec = NumpyCodec()
    sums = encoder.write_ec_files(
        base, codec, large_block_size=8192, small_block_size=1024)
    with open(base + shard_ext(4), "rb") as f:
        lost = f.read()
    os.remove(base + shard_ext(4))

    real, in_flight, state = encoder._pread_into, [0], {"armed": True}
    lock = threading.Lock()

    def pread(fd, offset, views):
        with lock:
            in_flight[0] += 1
        try:
            time.sleep(0.002)  # long enough for the workers to take a share
            on_worker = threading.current_thread().name.startswith("ec-read")
            if state["armed"] and on_worker and offset >= 8192:
                raise OSError("a row of the third chunk cannot be read")
            real(fd, offset, views)
        finally:
            with lock:
                in_flight[0] -= 1

    monkeypatch.setattr(encoder, "_pread_into", pread)
    threads = set(threading.enumerate())
    with _pytest.raises(OSError, match="third chunk cannot be read"):
        encoder.rebuild_ec_files(base, codec, chunk_bytes=4096)
    assert in_flight[0] == 0
    # the kept workers of the reads and of the copy back are the process's
    left = [t.name for t in set(threading.enumerate()) - threads
            if not t.name.startswith(("ec-read", "ec-copy-back"))]
    assert left == []
    assert len(kept) == encoder._POOL_BUFFERS
    state["armed"] = False
    os.remove(base + shard_ext(4))  # what the failed rebuild left of it
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=4096) == [4]
    with open(base + shard_ext(4), "rb") as f:
        assert f.read() == lost
    assert len(sums) == 14 and len(kept) == encoder._POOL_BUFFERS


def test_depth_chunk_splits_small_volumes():
    """A 128 MB volume under a 32 MB budget previously collapsed to one
    work item — nothing to overlap (r4 efficiency pinned at ~0.65). The
    depth-aware chunk yields several items while leaving big volumes at
    the full budgeted chunk."""
    from seaweedfs_tpu.ec.encoder import (
        LARGE_BLOCK_SIZE,
        SMALL_BLOCK_SIZE,
        _depth_chunk,
        _work_items,
    )

    mb = 1024 * 1024
    per_shard = -(-128 * mb // 10)
    chunk = _depth_chunk(32 * mb, per_shard, SMALL_BLOCK_SIZE)
    items = _work_items(128 * mb, 10, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, chunk)
    assert len(items) >= 4, (chunk, len(items))
    # big volumes: unchanged
    assert _depth_chunk(32 * mb, 3 * 1024 * mb, SMALL_BLOCK_SIZE) == 32 * mb
    # floor: never below one small block (or the budget, if smaller)
    assert _depth_chunk(32 * mb, 2 * mb, SMALL_BLOCK_SIZE) == SMALL_BLOCK_SIZE
