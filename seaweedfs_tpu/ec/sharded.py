"""Multi-chip EC encode: SPMD over a jax.sharding.Mesh.

How RS encode scales across a TPU slice, mapped to ML-parallelism vocabulary:

- **dp** (batch): independent volumes/row-batches encode on different chips —
  the analog of the reference spreading `VolumeEcShardsGenerate` calls across
  volume servers (`shell/command_ec_encode.go:92`).
- **sp** (sequence): one volume's byte-columns are split across chips — the
  shard-row dimension is embarrassingly parallel, like sequence parallelism
  without the ring (parity is columnwise, no cross-column dependence).
- **tp** (tensor): the GF(2) bit-contraction (8k rows) is split across chips;
  partial parity sums are combined with an int32 ``psum`` over ICI and then
  reduced mod 2 (XOR is addition mod 2, so summing partial counts commutes).

All variants produce bytes identical to the single-chip kernel.
"""

from __future__ import annotations

import numpy as np

from ..util.jaxenv import import_jax
from . import gf
from .codec import JaxCodec, build_pallas_gf_matmul, xla_gf_matmul
from .constants import DATA_SHARDS, PARITY_SHARDS


def factor_mesh(n_devices: int, tp: int = 1) -> tuple[int, int, int]:
    """Split n into (dp, sp, tp) axis sizes, preferring balance.

    tp defaults to 1: the RS contraction dim is tiny (80 bits for RS(10,4)),
    so splitting it buys nothing and costs a psum per chunk, while dp/sp
    shard columns with NO collectives and let each device run the fused
    Pallas kernel at its full single-chip rate. tp>1 stays supported (the
    psum formulation) for callers that want the contraction split."""
    if n_devices % tp:
        raise ValueError(f"tp={tp} does not divide n_devices={n_devices}")
    dp = sp = 1
    n = n_devices // tp
    while n % 2 == 0:
        if dp <= sp:
            dp *= 2
        else:
            sp *= 2
        n //= 2
    dp *= n  # odd remainder onto dp
    return dp, sp, tp


def build_mesh(n_devices: int | None = None, tp: int = 1):
    devices = import_jax().devices()
    from jax.sharding import Mesh

    if n_devices is None:
        n_devices = len(devices)
    devices = np.array(devices[:n_devices])
    dp, sp, tp = factor_mesh(n_devices, tp)
    return Mesh(devices.reshape(dp, sp, tp), ("dp", "sp", "tp"))


def _shard_map(body, mesh, in_specs, out_specs):
    """jax.shard_map with the replication check off — the body uses
    axis_index, which the checker can't see through."""
    return import_jax().shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_sharded_encode(mesh, matrix: np.ndarray, process_local: bool = False):
    """Jitted batched encode step over a (dp, sp, tp) mesh.

    fn(data: uint8[B, k, N]) → parity uint8[B, m, N], with B sharded over
    'dp', N over 'sp', and the bit-contraction over 'tp' (psum over ICI).
    B % dp == 0, N % (sp * tile) requirements are the caller's to satisfy.

    With ``process_local=True`` the mesh may span processes
    (jax.distributed): the caller passes only its process's dp-slice of
    the batch, inputs are assembled into global arrays with
    ``make_array_from_process_local_data``, and the returned parity is a
    global array whose addressable shards are this process's dp rows —
    the multi-host layout where dp rides DCN and sp/tp ride ICI
    (docs/SCALING.md)."""
    jax = import_jax()
    from jax.sharding import NamedSharding, PartitionSpec as P

    bitmat_np = gf.gf_matrix_to_bit_matrix(matrix).astype(np.int8)  # (8m, 8k)
    tp = mesh.shape["tp"]
    if bitmat_np.shape[1] % tp:
        raise ValueError(f"contraction dim {bitmat_np.shape[1]} not divisible by tp={tp}")

    data_sharding = NamedSharding(mesh, P("dp", None, "sp"))
    out_sharding = NamedSharding(mesh, P("dp", None, "sp"))

    def spmd_encode(bitmat_slices, data):
        # bitmat_slices: local int8[1, 8m, 8k/tp] of the stack sharded over
        # 'tp'; data: uint8[b, k, n], replicated over 'tp' — every rank
        # unpacks all bits and contracts its slice of them
        return xla_gf_matmul(jax, bitmat_slices[0], data, tp_axis="tp")

    eight_m, eight_k = bitmat_np.shape
    bitmat_stacked = bitmat_np.reshape(eight_m, tp, eight_k // tp).transpose(1, 0, 2)

    fn = _shard_map(
        spmd_encode,
        mesh=mesh,
        in_specs=(P("tp", None, None), P("dp", None, "sp")),
        out_specs=P("dp", None, "sp"),
    )

    bitmat_sharding = NamedSharding(mesh, P("tp", None, None))
    jitted = jax.jit(
        fn, in_shardings=(bitmat_sharding, data_sharding),
        out_shardings=out_sharding,
    )

    if process_local:
        # tp/sp axes must live within each process (dp is the only axis
        # allowed to cross the process boundary — the DCN axis); enforce
        # it here rather than letting make_array_from_process_local_data
        # fail with an opaque addressability error downstream
        dp_axis = mesh.axis_names.index("dp")  # axes addressed by NAME
        for i, dp_slice in enumerate(np.moveaxis(mesh.devices, dp_axis, 0)):
            procs = {d.process_index for d in dp_slice.flat}
            if len(procs) != 1:
                raise ValueError(
                    "process_local=True requires the sp/tp axes to stay "
                    f"within one process; dp slice {i} spans processes "
                    f"{sorted(procs)}"
                )
        # every process's local portion of the bit matrix is therefore
        # the full array; data is dp-sliced
        bitmat_global = jax.make_array_from_process_local_data(
            bitmat_sharding, bitmat_stacked
        )

        def encode_step(local_data):
            gdata = jax.make_array_from_process_local_data(
                data_sharding, local_data
            )
            return jitted(bitmat_global, gdata)

        return encode_step

    def encode_step(data):
        return jitted(bitmat_stacked, data)

    return encode_step


class MeshCodec(JaxCodec):
    """Codec whose matmul runs SPMD over a jax.sharding.Mesh.

    Drop-in for the volume server's ``store.ec_codec``: `/admin/ec/generate`
    → ``encoder.write_ec_files(base, store.ec_codec)`` runs unchanged, with
    each chunk's columns sharded over the (dp, sp) axes and the GF(2)
    bit-contraction split over 'tp' (partial parity counts combined with an
    int32 psum over ICI, then reduced mod 2). Shard bytes are identical to
    every other backend.

    Per-device compute: with tp == 1 on TPU devices, each device runs the
    SAME fused Pallas kernel as the single-chip TpuCodec on its column slice
    (pallas_call composes with shard_map), so the mesh path inherits the
    full single-chip rate with zero collectives. With tp > 1 (or on CPU CI
    meshes) the XLA bit-matmul formulation runs per shard, with the partial
    GF(2) counts psum'd over ICI.
    """

    backend = "mesh"

    def __init__(
        self,
        data_shards: int = DATA_SHARDS,
        parity_shards: int = PARITY_SHARDS,
        mesh=None,
        n_devices: int | None = None,
        chunk_bytes: int = 8 * 1024 * 1024,
        use_pallas: bool | None = None,
        pallas_tile: int = 32 * 1024,
        pallas_interpret: bool = False,
    ):
        mesh = mesh if mesh is not None else build_mesh(n_devices)
        self._tp = mesh.shape["tp"]
        super().__init__(
            data_shards, parity_shards, devices=mesh.devices.flat,
            chunk_bytes=chunk_bytes,
            # the fused kernel computes whole GF bytes per tile; a tp split
            # needs int partial sums across devices, which only the XLA
            # body expresses
            use_pallas=use_pallas if self._tp == 1 else False,
            pallas_tile=pallas_tile, pallas_interpret=pallas_interpret,
        )
        self.mesh = mesh
        # columns shard over dp×sp together; tp splits the contraction
        self._col_axes = ("dp", "sp")
        self._n_cols_shards = mesh.shape["dp"] * mesh.shape["sp"]
        # the newest result, kept so that /status can show which devices
        # hold its pieces: whether a launch really spread over the mesh.
        # One slot, the same list in every geometry's view (Codec.at)
        self._last_out = [None]

    def describe(self) -> dict:
        out = super().describe()
        last = self._last_out[0]
        out["last_output_devices"] = sorted(
            {s.device.id for s in last.addressable_shards}
        ) if last is not None else []
        return out

    # -- device placement (the streaming encoder's overlap pipeline) ---------
    def alignment(self) -> int:
        if self.use_pallas:
            # each device's local slice must be a whole number of kernel tiles
            return self._n_cols_shards * self.pallas_tile
        return self._n_cols_shards * 8

    def device_put(self, data: np.ndarray):
        """Place (k, N) bytes on the mesh, columns sharded over dp×sp."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return self._jax.device_put(
            data, NamedSharding(self.mesh, P(None, self._col_axes))
        )

    def _stacked_bitmat(self, matrix: np.ndarray):
        key = (matrix.tobytes(), self.use_pallas)
        cached = self._bitmat_cache.get(key)
        if cached is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if self.use_pallas:
                # planewise expansion, replicated on every device (tiny)
                bm = gf.bit_matrix_planewise(matrix).astype(np.int8)
                cached = self._jax.device_put(
                    bm, NamedSharding(self.mesh, P(None, None))
                )
            else:
                bm = gf.gf_matrix_to_bit_matrix(matrix).astype(np.int8)  # (8R, 8k)
                eight_r, eight_k = bm.shape
                if eight_k % self._tp:
                    raise ValueError(
                        f"contraction dim {eight_k} not divisible by tp={self._tp}"
                    )
                stacked = bm.reshape(
                    eight_r, self._tp, eight_k // self._tp
                ).transpose(1, 0, 2)  # (tp, 8R, 8k/tp)
                cached = self._jax.device_put(
                    stacked, NamedSharding(self.mesh, P("tp", None, None))
                )
            self._bitmat_cache[key] = cached
        return cached

    def _spmd_fn(self, n_out_rows: int, k: int):
        key = (n_out_rows, k)
        fn = self._jit_cache.get(key)
        if fn is None:
            jax = self._jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            col_axes = self._col_axes
            if self.use_pallas:
                tile = self.pallas_tile
                interpret = self._pallas_interpret

                def body(bitmat, data):
                    # data: the device-local (k, n_loc) column slice; the
                    # fused kernel runs at full single-chip rate per device,
                    # no collectives (columns are embarrassingly parallel)
                    n_loc = data.shape[1]
                    return build_pallas_gf_matmul(
                        jax, n_out_rows, k, n_loc, tile, interpret
                    )(bitmat, data)

                bitmat_spec = P(None, None)
            else:
                def body(bitmat_slices, data):
                    # bitmat_slices: local (1, 8R, 8k/tp); data: local (k, n_loc)
                    return xla_gf_matmul(
                        jax, bitmat_slices[0], data, tp_axis="tp"
                    )

                bitmat_spec = P("tp", None, None)
            mapped = _shard_map(
                body,
                mesh=self.mesh,
                in_specs=(bitmat_spec, P(None, col_axes)),
                out_specs=P(None, col_axes),
            )
            fn = jax.jit(
                mapped,
                out_shardings=NamedSharding(self.mesh, P(None, col_axes)),
            )
            self._jit_cache[key] = fn
        return fn

    def matmul_device(self, matrix: np.ndarray, data_dev):
        """(R×k) @ (k×N) on mesh-resident data; N % alignment() == 0."""
        out = self._spmd_fn(*matrix.shape)(self._stacked_bitmat(matrix), data_dev)
        self.launches.add("pallas" if self.use_pallas else "xla", self.geometry)
        self._last_out[0] = out
        return out
