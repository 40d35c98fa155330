"""Multi-chip sharded encode on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

from seaweedfs_tpu.ec import sharded
from seaweedfs_tpu.ec.codec import NumpyCodec


def test_factor_mesh():
    # default: tp=1 — columns shard with no collectives so every device
    # runs the fused kernel at full rate
    for n, want in ((1, (1, 1, 1)), (2, (2, 1, 1)), (4, (2, 2, 1)), (8, (4, 2, 1))):
        assert sharded.factor_mesh(n) == want
    dp, sp, tp = sharded.factor_mesh(6)
    assert dp * sp * tp == 6
    # explicit tp: the psum formulation stays available
    for n, want in ((2, (1, 1, 2)), (4, (2, 1, 2)), (8, (2, 2, 2))):
        assert sharded.factor_mesh(n, tp=2) == want
    with pytest.raises(ValueError):
        sharded.factor_mesh(3, tp=2)


def test_mesh_codec_pallas_interpret_composes_with_shard_map():
    """The fused Pallas kernel as the per-device body under shard_map
    (interpret mode: no TPU in CI). Bytes must match the numpy oracle."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("not enough devices")
    mesh = sharded.build_mesh(4)  # (dp=2, sp=2, tp=1)
    codec = sharded.MeshCodec(
        mesh=mesh, chunk_bytes=64 * 1024, use_pallas=True, pallas_tile=1024,
        pallas_interpret=True,
    )
    assert codec.use_pallas
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (10, 3 * 4096 + 123), dtype=np.uint8)
    assert np.array_equal(codec.encode(data), NumpyCodec().encode(data))


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_sharded_encode_matches_oracle(n_devices):
    import jax

    if len(jax.devices()) < n_devices:
        pytest.skip("not enough devices")
    mesh = sharded.build_mesh(n_devices)
    codec = NumpyCodec()
    enc = sharded.make_sharded_encode(mesh, codec.parity_rows)
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (2 * dp, 10, 512 * sp), dtype=np.uint8)
    out = np.asarray(enc(data))
    for b in range(data.shape[0]):
        assert np.array_equal(out[b], codec.encode(data[b])), b


def test_graft_entry_single_chip():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = np.asarray(jax.jit(fn)(*args))
    ref = NumpyCodec().encode(np.asarray(args[0]))
    assert np.array_equal(out, ref)


def test_graft_entry_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_mesh_codec_matmul_and_reconstruct():
    from seaweedfs_tpu.ec.sharded import MeshCodec

    rng = np.random.default_rng(7)
    mc = MeshCodec(n_devices=8, chunk_bytes=4096)
    ref = NumpyCodec()
    for n in (4096, 1000, 8192 + 13):
        d = rng.integers(0, 256, (10, n), dtype=np.uint8)
        assert np.array_equal(mc.encode(d), ref.encode(d)), n
    d = rng.integers(0, 256, (10, 2048), dtype=np.uint8)
    full = ref.encode_shards(d)
    shards = [None, full[1], None, *full[3:12], None, full[13]]
    out = mc.reconstruct(shards)
    assert all(np.array_equal(out[i], full[i]) for i in range(14))


@pytest.mark.parametrize("kind", ["cpu", "tpu-xla", "mesh"])
def test_write_ec_files_is_the_same_bytes_from_every_codec(tmp_path, kind):
    """One path through the encoder for every codec: what a JAX codec, the
    mesh and the native kernel write is what the numpy codec writes."""
    import glob
    import os

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import CpuCodec, TpuCodec

    rng = np.random.default_rng(8)
    payload = rng.integers(0, 256, 50_001, dtype=np.uint8).tobytes()
    base_a = str(tmp_path / "1")
    base_b = str(tmp_path / "2")
    for b in (base_a, base_b):
        with open(b + ".dat", "wb") as f:
            f.write(payload)

    codec = {
        "cpu": CpuCodec,
        "tpu-xla": lambda: TpuCodec(
            chunk_bytes=4096, tile_bytes=4096, pallas_tile=4096),
        "mesh": lambda: sharded.MeshCodec(n_devices=4, chunk_bytes=4096),
    }[kind]()
    encoder.write_ec_files(base_a, codec, large_block_size=8192, small_block_size=512)
    encoder.write_ec_files(
        base_b, NumpyCodec(), large_block_size=8192, small_block_size=512
    )
    for pa in sorted(glob.glob(base_a + ".ec[0-9][0-9]")):
        pb = base_b + pa[-5:]
        assert open(pa, "rb").read() == open(pb, "rb").read(), os.path.basename(pa)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("rows", [1, 4])
def test_a_mesh_sharded_result_comes_back_whole(rows, tp):
    """The encoder's one copy back, handed a result spread by columns over
    four devices (or over two, each piece held twice): ``rows`` whole host
    rows, every byte in its place, each gathered by its own transfer."""
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.stats.trace import STAGES

    mc = sharded.MeshCodec(mesh=sharded.build_mesh(4, tp=tp), chunk_bytes=4096)
    rng = np.random.default_rng(40)
    data = rng.integers(0, 256, (10, 4 * mc.alignment()), dtype=np.uint8)
    matrix = mc.parity_rows[:rows]
    out_dev = mc.matmul_device(matrix, mc.device_put(data))
    assert len(out_dev.sharding.device_set) == 4
    op = f"ec.test-mesh{rows}-{tp}"
    back = encoder._copy_back(op, out_dev)
    assert len(back) == rows
    assert np.array_equal(np.stack(back), NumpyCodec().matmul(matrix, data))
    stage = STAGES.snapshot()[f"{op}.d2h"]
    assert (stage["n"], stage["bytes"]) == (1, rows * data.shape[1])
    assert stage["transfers"] == rows


def test_a_rebuild_on_the_mesh_is_the_numpy_codecs(tmp_path):
    import os

    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.constants import shard_ext

    base = str(tmp_path / "1")
    rng = np.random.default_rng(9)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 200_001, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, NumpyCodec(), large_block_size=8192,
                           small_block_size=512)
    want = {}
    for sid in (0, 4, 9, 12):
        with open(base + shard_ext(sid), "rb") as f:
            want[sid] = f.read()
        os.remove(base + shard_ext(sid))
    mc = sharded.MeshCodec(n_devices=4, chunk_bytes=4096)
    assert encoder.rebuild_ec_files(base, mc, chunk_bytes=4096) == [0, 4, 9, 12]
    for sid, data in want.items():
        with open(base + shard_ext(sid), "rb") as f:
            assert f.read() == data, sid
