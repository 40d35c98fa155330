"""Codec backend cross-checks: numpy vs C++ vs JAX must be bit-identical."""

import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ec.codec import (
    Codec,
    CpuCodec,
    NumpyCodec,
    TpuCodec,
    get_codec,
)


@pytest.fixture(scope="module")
def codecs():
    return {
        "numpy": NumpyCodec(),
        "cpu": CpuCodec(),
        "tpu": TpuCodec(chunk_bytes=8 * 65536, tile_bytes=65536),
    }


@pytest.fixture(scope="module")
def data():
    return np.random.default_rng(7).integers(0, 256, (10, 40000), dtype=np.uint8)


def test_encode_identical_across_backends(codecs, data):
    outs = {name: c.encode(data) for name, c in codecs.items()}
    base = outs["numpy"]
    for name, out in outs.items():
        assert np.array_equal(base, out), f"{name} diverges from numpy"


def test_encode_rejects_wrong_shard_count(codecs):
    with pytest.raises(ValueError):
        codecs["numpy"].encode(np.zeros((9, 10), dtype=np.uint8))


def test_reconstruct_all_4loss_combinations(data):
    """Every possible 4-shard loss (C(14,4)=1001) reconstructs bit-identically."""
    codec = CpuCodec()
    shards = codec.encode_shards(data[:, :2000])
    orig = [row.copy() for row in shards]
    for dead in itertools.combinations(range(14), 4):
        work = [None if i in dead else orig[i] for i in range(14)]
        out = codec.reconstruct(work)
        for i in dead:
            assert np.array_equal(out[i], orig[i]), f"loss {dead} shard {i}"


def test_reconstruct_insufficient_shards(codecs, data):
    codec = codecs["numpy"]
    shards = [r.copy() for r in codec.encode_shards(data[:, :100])]
    work = [None] * 5 + list(shards[5:])
    with pytest.raises(ValueError):
        codec.reconstruct(work)


def test_reconstruct_data_only(codecs, data):
    codec = codecs["cpu"]
    shards = [r.copy() for r in codec.encode_shards(data[:, :1000])]
    work = [None if i in (2, 11) else shards[i] for i in range(14)]
    out = codec.reconstruct_data(work)
    assert np.array_equal(out[2], shards[2])
    assert out[11] is None  # parity untouched in data-only mode


def test_tpu_codec_matches_on_awkward_widths(codecs):
    rng = np.random.default_rng(3)
    for width in (1, 7, 65536, 65537, 3 * 65536 + 11):
        d = rng.integers(0, 256, (10, width), dtype=np.uint8)
        assert np.array_equal(codecs["tpu"].encode(d), codecs["cpu"].encode(d)), width


def test_alt_geometries(codecs):
    rng = np.random.default_rng(4)
    for k, m in ((6, 3), (12, 4)):
        d = rng.integers(0, 256, (k, 3000), dtype=np.uint8)
        ref = NumpyCodec(k, m).encode(d)
        assert np.array_equal(ref, CpuCodec(k, m).encode(d))
        assert np.array_equal(
            ref, TpuCodec(k, m, chunk_bytes=8 * 65536, tile_bytes=65536).encode(d)
        )


def test_verify(codecs, data):
    codec = codecs["cpu"]
    shards = codec.encode_shards(data[:, :500])
    assert codec.verify(shards)
    shards[12, 100] ^= 1
    assert not codec.verify(shards)


def test_get_codec_factory():
    assert isinstance(get_codec("numpy"), NumpyCodec)
    assert isinstance(get_codec("cpu"), CpuCodec)
    with pytest.raises(ValueError):
        get_codec("cuda")


def test_pallas_fused_kernel_interpret():
    """The fused Pallas kernel (unpack→MXU matmul→mod2→repack in VMEM) must
    produce the same bytes as the oracle. CI has no TPU, so this runs the
    kernel in interpreter mode; the real-TPU path is exercised by chip_smoke.py."""
    rng = np.random.default_rng(5)
    ref = NumpyCodec()
    tp = TpuCodec(
        chunk_bytes=16 * 1024,
        tile_bytes=4096,
        use_pallas=True,
        pallas_tile=4096,
        pallas_interpret=True,
    )
    for width in (4096, 8192, 5000, 777):
        d = rng.integers(0, 256, (10, width), dtype=np.uint8)
        assert np.array_equal(ref.encode(d), tp.encode(d)), width
    # reconstruct through the same fused kernel
    d = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    full = ref.encode_shards(d)
    shards = [None, None, full[2], full[3], None, *full[5:13], None]
    out = tp.reconstruct(shards)
    assert all(np.array_equal(out[i], full[i]) for i in range(14))


def test_bit_matrix_planewise_is_permutation():
    from seaweedfs_tpu.ec import gf

    m = gf.build_matrix(10, 14)[10:]
    a = gf.gf_matrix_to_bit_matrix(m)
    b = gf.bit_matrix_planewise(m)
    R, C = m.shape
    for p in range(R):
        for i in range(8):
            for d in range(C):
                for j in range(8):
                    assert b[i * R + p, j * C + d] == a[p * 8 + i, d * 8 + j]


def test_alt_geometries_fused_kernel_and_mesh():
    """RS(6,3)/RS(12,4) (BASELINE.md alt geometries) through the FUSED
    Pallas kernel (interpret mode off-TPU) and the mesh codec — the same
    code paths the defaults use, at the other supported shapes."""
    import jax

    from seaweedfs_tpu.ec.sharded import MeshCodec, build_mesh

    rng = np.random.default_rng(9)
    for k, m in ((6, 3), (12, 4)):
        d = rng.integers(0, 256, (k, 4096 + 777), dtype=np.uint8)
        ref = NumpyCodec(k, m).encode(d)
        fused = TpuCodec(k, m, chunk_bytes=64 * 1024, tile_bytes=64 * 1024,
                         use_pallas=True, pallas_tile=1024,
                         pallas_interpret=True)
        assert np.array_equal(ref, fused.encode(d)), (k, m)
        if len(jax.devices()) >= 4:
            mesh = MeshCodec(k, m, mesh=build_mesh(4), chunk_bytes=64 * 1024)
            assert np.array_equal(ref, mesh.encode(d)), ("mesh", k, m)
        # reconstruction at alt shapes too (klauspost Reconstruct parity)
        shards = list(fused.encode_shards(d))
        shards[0] = shards[k] = None
        fused.reconstruct(shards)
        assert np.array_equal(shards[0], d[0]) and np.array_equal(
            shards[k], ref[0]
        )


def test_matmul_device_splits_oversized_widths():
    """Widths beyond chunk_bytes must stream through chunk-sized launches
    (one huge grid used to RESOURCE_EXHAUST on-device, VERDICT r3 weak #1)
    and still produce byte-identical output."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    codec = TpuCodec(chunk_bytes=64 * 1024, tile_bytes=64 * 1024)
    # 5 chunks + a tile-aligned tail
    n = 5 * 64 * 1024 + 64 * 1024
    d = rng.integers(0, 256, (10, n), dtype=np.uint8)
    ref = NumpyCodec().encode(d)
    out = np.asarray(codec.matmul_device(codec.parity_rows, jnp.asarray(d)))
    assert np.array_equal(ref, out)


def test_budgeted_chunk_caps_against_free_hbm():
    from seaweedfs_tpu.ec.encoder import _budgeted_chunk

    class Fake(Codec):
        def __init__(self, free):
            self._free = free

        def device_memory_free(self):
            return self._free

        def alignment(self):
            return 65536

    # plenty free: chunk unchanged
    assert _budgeted_chunk(Fake(64 << 30), 32 << 20, 14) == 32 << 20
    # tight pool: capped to an alignment multiple, never zero
    capped = _budgeted_chunk(Fake(256 << 20), 32 << 20, 14)
    assert capped < 32 << 20 and capped % 65536 == 0 and capped >= 65536
    # no stats (a host codec, JAX on the CPU platform): untouched
    assert _budgeted_chunk(NumpyCodec(), 8 << 20, 14) == 8 << 20


def test_plan_encode_caps_explicit_chunk():
    """An explicit chunk_bytes fixes pipeline depth but must NOT bypass the
    HBM budget — a caller asking for 32MB on a starved chip gets the capped
    plan, not RESOURCE_EXHAUSTED (same contract as rebuild_ec_files)."""
    from seaweedfs_tpu.ec.encoder import plan_encode

    class Starved(NumpyCodec):
        def device_memory_free(self):
            return 256 << 20

        def alignment(self):
            return 65536

    chunk, items = plan_encode(Starved(), 1 << 20, chunk_bytes=32 << 20)
    assert chunk < 32 << 20 and chunk % 65536 == 0
    assert items
    # and without stats the explicit request is honored verbatim
    chunk, _ = plan_encode(NumpyCodec(), 1 << 20, chunk_bytes=32 << 20)
    assert chunk == 32 << 20


def test_native_kernel_reports_variant():
    """The native lib self-reports which rs_matmul inner loop compiled in,
    so bench artifacts can distinguish a stale/slow build from a host
    without AVX2 (BENCH r4 recorded 0.028 GB/s with no provenance)."""
    from seaweedfs_tpu.native import lib

    assert lib.kernel_variant() in ("gfni", "avx2", "scalar")


def test_rs_matmul_out_validation():
    """A wrong-shape/dtype/layout out buffer is rejected, not written past."""
    pytest.importorskip("seaweedfs_tpu.native")
    from seaweedfs_tpu.native import lib

    matrix = CpuCodec().parity_rows
    d = np.arange(10 * 1024, dtype=np.uint8).reshape(10, 1024)
    ok = np.empty((4, 1024), dtype=np.uint8)
    assert np.array_equal(lib.rs_matmul(matrix, d, out=ok), lib.rs_matmul(matrix, d))
    for bad in (
        np.empty((4, 512), dtype=np.uint8),        # wrong width
        np.empty((3, 1024), dtype=np.uint8),       # wrong rows
        np.empty((4, 1024), dtype=np.uint16),      # wrong dtype
        np.empty((4, 2048), dtype=np.uint8)[:, ::2],  # non-contiguous
    ):
        with pytest.raises(ValueError):
            lib.rs_matmul(matrix, d, out=bad)


# -- what the benchmark's launcher reaches by name (benchmark/daemon_main.py) --
WRAPPED = """
import collections, contextlib, json
import jax, numpy as np
from benchmark import daemon_main
from seaweedfs_tpu.ec.codec import TpuCodec
from seaweedfs_tpu.ec.sharded import MeshCodec

for module, cls, attr, _ in daemon_main.SPANS:  # every name resolves
    getattr(daemon_main._owner(module, cls), attr)

entered = collections.Counter()

@contextlib.contextmanager
def counted(name, **tags):
    entered[name] += 1
    yield

jax.profiler.TraceAnnotation = counted
daemon_main.wrap_spans()
data = np.random.default_rng(1).integers(0, 256, (10, 5 * 4096 + 17), np.uint8)
out = {}
for codec in (TpuCodec(chunk_bytes=8192, tile_bytes=1024),
              MeshCodec(n_devices=4, chunk_bytes=8192)):
    entered.clear()
    codec.matmul(codec.parity_rows, data)
    out[codec.backend] = [entered["matmul"], entered["matmul_device"],
                          sum(codec.launches.snapshot().values())]
print(json.dumps(out))
"""


def test_the_launchers_wrappers_meet_each_codec_call_once():
    """`wrap_spans` wraps `matmul` and `matmul_device` of `TpuCodec` and of
    `MeshCodec`, one after the other. The two classes are siblings that
    share a `matmul`, so one call enters the `matmul` wrapper once and the
    `matmul_device` wrapper once a launch; were one a subclass of the other,
    its calls would enter two of each and double the launcher's spans. In a
    child: the wrapping is global to a process."""
    import json
    import os
    import subprocess
    import sys

    from seaweedfs_tpu.ec.sharded import MeshCodec

    assert not issubclass(MeshCodec, TpuCodec)
    assert not issubclass(TpuCodec, MeshCodec)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", WRAPPED], cwd=root,
                       env={**os.environ, "PYTHONPATH": root},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # 5 * 4096 + 17 columns in chunks of 8192: three launches
    assert out == {"tpu": [1, 3, 3], "mesh": [1, 3, 3]}
