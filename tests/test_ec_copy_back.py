"""One copy back for both pipelines (`encoder._copy_back`): a seal's parity
rows and a rebuild's lost rows leave the device through the same function,
a row a transfer, the rows side by side — held to the plain reference
(``benchmark/reference.py``, ``benchmark/reference_lrc.py``: they import
nothing of the program) over the geometries and losses the benchmark's
cells run, with the XLA formulation, the interpreted Pallas kernel and a
host codec. On the CPU, at a few hundred KiB: bytes and counts, never a
speed."""

from __future__ import annotations

import hashlib
import os

import numpy as np
import pytest

from benchmark import reference, reference_lrc
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import NumpyCodec, TpuCodec
from seaweedfs_tpu.ec.constants import Geometry, shard_ext
from seaweedfs_tpu.stats.trace import STAGES

SMALL, LARGE = 4096, 1 << 20
CHUNK = 4 * SMALL  # the most a rebuild's chunk may be wide
# (geometry, lost shards): (k, lost rows) = (10, 1), (10, 4), (12, 4) and
# LRC(12,2,2)'s (6, 1), a shard lost alone in its local group
CASES = [("10+4", (4,)), ("10+4", (0, 4, 9, 12)), ("12+4", (0, 4, 9, 12)),
         ("12+2+2", (4,))]
KINDS = ["xla", "pallas-interpret", "host"]


def make_codec(kind: str, geometry: Geometry):
    if kind == "host":
        return NumpyCodec().at(*geometry)
    return TpuCodec(
        use_pallas=kind == "pallas-interpret", pallas_interpret=True,
        chunk_bytes=1 << 20, tile_bytes=SMALL, pallas_tile=1024,
    ).at(*geometry)


def reference_sums(dat: str, geometry: Geometry) -> dict:
    ec = {**geometry.volume_info(), "large_block_bytes": LARGE,
          "small_block_bytes": SMALL}
    plain = reference_lrc if geometry.local_parity_shards else reference
    return plain.shard_sums(dat, ec, threads=2)


def sha256_of(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def delta(before: dict, after: dict, stage: str, field: str):
    return (after.get(stage, {}).get(field, 0)
            - before.get(stage, {}).get(field, 0))


def rebuild_chunks(codec, shard_size: int) -> int:
    """Chunks of a rebuild of shards of ``shard_size`` under ``CHUNK``:
    the planner's (`_depth_chunk` cuts a small shard into about eight)."""
    chunk = encoder._depth_chunk(CHUNK, shard_size, codec.alignment())
    return -(-shard_size // chunk)


def sealed_volume(tmp_path, codec, rows: int = 13) -> tuple[str, int]:
    """A ``.dat`` of ``rows`` rows of small blocks, the last cut by EOF,
    sealed by ``codec``; its base name and the size of a shard."""
    base = str(tmp_path / "7")
    k = codec.data_shards
    rng = np.random.default_rng(40)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, rows * k * SMALL - 1234,
                             dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, codec, large_block_size=LARGE,
                           small_block_size=SMALL)
    return base, rows * SMALL


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("text, lost", CASES)
def test_a_rebuild_through_the_copy_back_is_the_references_shards(
        tmp_path, text, lost, kind):
    geometry = Geometry.parse(text)
    codec = make_codec(kind, geometry)
    base, shard_size = sealed_volume(tmp_path, codec)
    want = reference_sums(base + ".dat", geometry)
    assert want["shard_bytes"] == shard_size
    for sid in lost:
        os.remove(base + shard_ext(sid))

    before = STAGES.snapshot()
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=CHUNK) == list(lost)
    after = STAGES.snapshot()

    for sid in range(geometry.total_shards):
        assert os.path.getsize(base + shard_ext(sid)) == shard_size, sid
        assert sha256_of(base + shard_ext(sid)) == want["sums"][sid], sid
    # the copy back's span holds what it did: one a chunk, the result's
    # logical bytes, and a transfer a row where there was a device
    chunks = rebuild_chunks(codec, shard_size)
    assert chunks >= 4
    assert delta(before, after, "ec.rebuild.d2h", "n") == chunks
    assert (delta(before, after, "ec.rebuild.d2h", "bytes")
            == len(lost) * shard_size)
    assert (delta(before, after, "ec.rebuild.d2h", "transfers")
            == (0 if kind == "host" else len(lost) * chunks))
    assert (delta(before, after, "ec.rebuild.write", "bytes")
            == len(lost) * shard_size)


class _NumpySpy:
    """``numpy`` as `encoder` sees it, with every ``asarray`` of a device
    result noted: was a `_copy_back` running?"""

    def __init__(self):
        self.inside = 0  # `_copy_back` calls in flight
        self.device_copies: list[bool] = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        if hasattr(a, "block_until_ready"):
            self.device_copies.append(self.inside > 0)
        return np.asarray(a, *args, **kwargs)


@pytest.mark.parametrize("kind", ["xla", "host"])
def test_a_seal_and_a_rebuild_go_through_the_same_copy_back(
        tmp_path, monkeypatch, kind):
    spy = _NumpySpy()
    ops: list[tuple[str, int]] = []
    real = encoder._copy_back

    def spied(op, out_dev):
        ops.append((op, out_dev.shape[0]))
        spy.inside += 1
        try:
            rows = real(op, out_dev)
        finally:
            spy.inside -= 1
        assert len(rows) == out_dev.shape[0]
        assert all(isinstance(r, np.ndarray) and r.ndim == 1 for r in rows)
        return rows

    monkeypatch.setattr(encoder, "np", spy)
    monkeypatch.setattr(encoder, "_copy_back", spied)
    geometry = Geometry.parse("10+4")
    codec = make_codec(kind, geometry)
    base, shard_size = sealed_volume(tmp_path, codec)
    for sid in (4, 12):
        os.remove(base + shard_ext(sid))
    assert encoder.rebuild_ec_files(base, codec, chunk_bytes=CHUNK) == [4, 12]

    want = reference_sums(base + ".dat", geometry)
    assert [sha256_of(base + shard_ext(s)) for s in range(14)] == want["sums"]
    # both pipelines, every chunk, and nothing beside them: the seal's four
    # parity rows, then the rebuild's two
    seal = [rows for op, rows in ops if op == "ec.seal"]
    rebuild = [rows for op, rows in ops if op == "ec.rebuild"]
    assert len(seal) + len(rebuild) == len(ops)
    assert seal and set(seal) == {4}
    assert len(rebuild) == rebuild_chunks(codec, shard_size)
    assert set(rebuild) == {2}
    if kind == "host":  # on the host already: nothing is copied
        assert spy.device_copies == []
    else:  # a transfer a row, and none outside the copy back
        assert len(spy.device_copies) == 4 * len(seal) + 2 * len(rebuild)
        assert all(spy.device_copies)


def test_a_host_codecs_rows_are_handed_on_as_they_are():
    """No thread and no copy for a result that is on the host already."""
    out = np.arange(12, dtype=np.uint8).reshape(3, 4)
    before = STAGES.snapshot()
    rows = encoder._copy_back("ec.test-host", out)
    after = STAGES.snapshot()
    assert len(rows) == 3
    assert all(np.shares_memory(row, out) for row in rows)
    assert delta(before, after, "ec.test-host.d2h", "n") == 1
    assert delta(before, after, "ec.test-host.d2h", "bytes") == 12
    assert delta(before, after, "ec.test-host.d2h", "transfers") == 0


def test_the_copy_backs_workers_are_the_processs_and_few():
    """A seal or a rebuild starts no thread for its copy back: the workers
    are made once and kept, as the chunk buffers are."""
    import threading

    codec = make_codec("xla", Geometry.parse("10+4"))
    data = np.random.default_rng(1).integers(
        0, 256, (10, 2 * SMALL), dtype=np.uint8)
    want = NumpyCodec().matmul(codec.parity_rows, data)
    for _ in range(3):
        out_dev = codec.matmul_device(codec.parity_rows,
                                      codec.device_put(data))
        rows = encoder._copy_back("ec.test-kept", out_dev)
        assert np.array_equal(np.stack(rows), want)
    kept = [t for t in threading.enumerate()
            if t.name.startswith("ec-copy-back")]
    assert 1 <= len(kept) <= encoder._COPY_BACK_TRANSFERS - 1
    assert encoder._copy_back_workers() is encoder._copy_back_workers()
