"""Erasure codecs: encode/reconstruct with pluggable backends
(tpu | mesh | cpu | numpy), and the one read-set planner (`read_plan`).

All backends compute the same function — GF(2^8) matmul with the code's
matrix (`code_matrix`: klauspost's for RS(k, m), gf.build_matrix; the local
reconstruction code's, gf.lrc_matrix) — so shard bytes are identical
regardless of where they were computed. What a rebuild or a degraded read
has to read follows from the matrix too: `read_plan` solves over the rows
of the shards that are present, and says no where they do not decode.
Mirrors the reference's use of
`reedsolomon.Encoder` (Encode/Reconstruct/ReconstructData — call sites
`weed/storage/erasure_coding/ec_encoder.go:179,270`,
`weed/storage/store_ec.go:367`).

`Codec` is the whole interface the file-level encoder (ec/encoder.py)
uses: the shard counts and matrices, ``chunk_bytes``, ``alignment()``,
``device_put``, ``matmul_device`` and ``device_memory_free()``. A process
builds ONE codec (`get_codec`); a volume sealed at another geometry is
served by that codec's view at it (`Codec.at`): the same devices, caches and
launch counts under another matrix. A host
codec (`NumpyCodec`, `CpuCodec`) implements ``matmul`` and inherits the
rest: its "device" is the host's memory. The JAX codecs (`TpuCodec` here,
`MeshCodec` in ec/sharded.py) share `JaxCodec` and express the GF(2^8)
matmul as a GF(2) bit-matrix matmul: bytes are unpacked to bits,
multiplied by the 8×-expanded bit matrix with an int8 MXU matmul, reduced
mod 2, and repacked — fused in one Pallas kernel on a TPU
(`build_pallas_gf_matmul`), as plain XLA elsewhere (`xla_gf_matmul`). See
gf.gf_matrix_to_bit_matrix.
"""

from __future__ import annotations

import copy
import functools
import os
from typing import NamedTuple, Optional, Sequence

import numpy as np

from ..stats import trace
from ..util import jaxenv
from ..util.locks import make_lock
from . import gf
from .constants import DATA_SHARDS, PARITY_SHARDS, Geometry


class Undecodable(ValueError):
    """The shards that are left do not determine the shards that are
    wanted: the loss is refused, never answered."""


class ReadPlan(NamedTuple):
    """What rebuilds a set of wanted shards: ``read``, the shards to read,
    ascending; ``matrix``, (wanted × read), whose product with the read
    shards' bytes is the wanted shards' bytes, in the order they were
    asked; ``local``, whether the read set lies inside the wanted shards'
    own local groups."""

    read: tuple[int, ...]
    matrix: np.ndarray
    local: bool


@functools.lru_cache(maxsize=None)  # a handful of geometries a process
def code_matrix(geometry: Geometry) -> np.ndarray:
    """The (k + m) × k encode matrix of a geometry, identity on top:
    klauspost's inverted Vandermonde for RS(k, m), `gf.lrc_matrix` for a
    local reconstruction code. Shared and read-only."""
    k, local = geometry.data_shards, geometry.local_parity_shards
    if local:
        matrix = gf.lrc_matrix(k, local, geometry.global_parity_shards)
    else:
        matrix = gf.build_matrix(k, geometry.total_shards)
    matrix.setflags(write=False)
    return matrix


@functools.lru_cache(maxsize=4096)  # a degraded read plans every interval
def read_plan(geometry: Geometry, wanted: tuple[int, ...],
              present: tuple[int, ...]) -> ReadPlan:
    """THE read-set planner, of a rebuild, of a degraded read and of the
    shell's gather alike: the fewest of the ``present`` shards whose rows
    of the code's matrix span the rows of the ``wanted`` ones, and the
    matrix over them. Raises `Undecodable` when no set of them does.

    The present shards are walked — the other members of the wanted
    shards' local groups first, then the rest in the order given (a caller
    puts what is cheap to read first) — and one is kept if its row is
    independent of those kept before, until the wanted rows lie in the
    span. The kept rows are independent, so each wanted row is ONE
    combination of them, and the shards with a coefficient in any are the
    one smallest subset of the kept that will do: the read set. A shard
    lost alone in its local group is so rebuilt from the group's others
    (six of LRC(12,2,2)'s sixteen) and everything else from at most k. For
    RS(k, m) any k rows are independent and no coefficient of an inverse
    of them is zero: the answer is the first k present and the rows
    klauspost's Reconstruct inverts, to the byte."""
    matrix = code_matrix(geometry)
    k, n = matrix.shape[1], matrix.shape[0]
    mt = gf.get_mul_table()
    groups = {s for w in wanted for s in geometry.local_group(w)}
    walk = [s for s in present if s in groups] + [
        s for s in present if s not in groups
    ]
    # every row carried as [its k coefficients | the combination of shards'
    # rows it stands for]; the basis is kept reduced (a pivot's column is
    # zero in every other row, in the residuals too)
    basis: list[tuple[int, np.ndarray]] = []  # (pivot column, row)
    residual = np.zeros((len(wanted), k + n), dtype=np.uint8)
    residual[:, :k] = matrix[list(wanted)]
    for sid in walk:
        if not residual[:, :k].any():
            break
        if sid in wanted:
            continue
        row = np.zeros(k + n, dtype=np.uint8)
        row[:k] = matrix[sid]
        row[k + sid] = 1
        for col, kept in basis:
            if row[col]:
                row ^= mt[row[col], kept]
        lead = np.flatnonzero(row[:k])
        if not len(lead):
            continue  # in the span of those kept: it brings nothing
        col = int(lead[0])
        row = mt[gf.gal_inverse(int(row[col])), row]
        basis = [
            (c, kept ^ mt[kept[col], row] if kept[col] else kept)
            for c, kept in basis
        ] + [(col, row)]
        for r in residual:
            if r[col]:
                r ^= mt[r[col], row]
    if residual[:, :k].any():
        raise Undecodable(
            f"ec geometry {geometry}: shards {sorted(present)} do not "
            f"determine shards {sorted(wanted)}"
        )
    over = residual[:, k:]
    read = tuple(int(s) for s in np.flatnonzero(over.any(axis=0)))
    rows = np.ascontiguousarray(over[:, list(read)])
    rows.setflags(write=False)
    return ReadPlan(read, rows, groups.issuperset(read))


class Codec:
    """Base: shard-count bookkeeping + reconstruct planning (host-side),
    and the interface the encoder's pipeline drives. A backend implements
    ``matmul``; one that holds a device also overrides the staging hooks
    below."""

    # columns of a chunk the encoder hands to one matmul_device call
    chunk_bytes = 8 * 1024 * 1024

    def __init__(self, data_shards: int = DATA_SHARDS, parity_shards: int = PARITY_SHARDS):
        self._set_geometry(data_shards, parity_shards)
        # this codec at every geometry asked of it (`at`), itself among them
        self._views: dict[Geometry, Codec] = {self.geometry: self}
        self._views_lock = make_lock("Codec._views_lock")

    def _set_geometry(self, data_shards: int, parity_shards: int,
                      local_parity_shards: int = 0) -> None:
        """All a codec holds that is tied to a geometry: a few hundred
        bytes of host-side bookkeeping."""
        self.geometry = Geometry(data_shards, parity_shards, local_parity_shards)
        self.matrix = code_matrix(self.geometry)
        self.parity_rows = self.matrix[data_shards:]

    data_shards = property(lambda self: self.geometry.data_shards)
    parity_shards = property(lambda self: self.geometry.parity_shards)
    total_shards = property(lambda self: self.geometry.total_shards)

    def at(self, data_shards: int, parity_shards: int,
           local_parity_shards: int = 0) -> "Codec":
        """This codec at another geometry (``codec.at(*geometry)``): a view
        that owns the matrix and the shard counts and SHARES everything a
        process has one of — the devices, the jit and bit-matrix caches,
        the launch counts, the kernel's prep tables. Built once a geometry
        and kept, so a server that seals at 12+2+2 reads and rebuilds the
        10+4 volumes it holds through one chip and one ``ec_codec`` of
        /status."""
        geometry = Geometry(
            data_shards, parity_shards, local_parity_shards
        ).checked()
        with self._views_lock:
            view = self._views.get(geometry)
            if view is None:
                # shallow: every attribute but the geometry's is the same
                # object in the view as here
                view = copy.copy(self)
                view._set_geometry(*geometry)
                self._views[geometry] = view
        return view

    def plan(self, wanted: Sequence[int], present: Sequence[int]) -> ReadPlan:
        """`read_plan` at this codec's geometry."""
        return read_plan(self.geometry, tuple(wanted), tuple(present))

    # -- backend hooks -------------------------------------------------------
    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        """(R×k GF matrix) @ (k×N bytes) → (R×N bytes). Backend-specific."""
        raise NotImplementedError

    def alignment(self) -> int:
        """Column widths fed to matmul_device must be multiples of this."""
        return 1

    def device_put(self, data: np.ndarray):
        """Stage (k, N) host bytes where matmul_device wants them. A host
        codec computes on them where they are."""
        return data

    def matmul_device(self, matrix: np.ndarray, data):
        """``matmul`` on staged data (a `device_put`), N a multiple of
        `alignment`; the result stays where it was computed until the
        caller copies it back (``np.asarray``)."""
        return self.matmul(matrix, np.asarray(data))

    def device_memory_free(self) -> Optional[int]:
        """Free bytes of the tightest device's memory, or None where
        nothing bounds a chunk but the host's memory."""
        return None

    # -- public API ----------------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """data (k, N) → parity (m, N)."""
        if data.shape[0] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data rows, got {data.shape[0]}")
        return self.matmul(self.parity_rows, data)

    def encode_shards(self, data: np.ndarray) -> np.ndarray:
        """data (k, N) → all shards (k+m, N) (data rows pass through)."""
        return np.concatenate([data, self.encode(data)], axis=0)

    def reconstruct(
        self,
        shards: list[Optional[np.ndarray]],
        data_only: bool = False,
        wanted: Optional[Sequence[int]] = None,
    ) -> list[np.ndarray]:
        """Fill in missing (None) shards in place and return the list: the
        ``wanted`` ones (klauspost's ReconstructSome), or every missing
        one, or with ``data_only`` every missing data shard — from the
        fewest of the present ones that determine them (`read_plan`), in
        one matmul. Raises `Undecodable` when the present ones do not.

        Bit-identical to klauspost Encoder.Reconstruct / ReconstructData.
        """
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shards")
        present = [i for i, s in enumerate(shards) if s is not None]
        if wanted is None:
            limit = self.data_shards if data_only else self.total_shards
            wanted = range(limit)
        wanted = [i for i in wanted if shards[i] is None]
        if not wanted:
            return shards  # nothing to do
        plan = self.plan(wanted, present)
        rebuilt = self.matmul(
            plan.matrix, np.stack([shards[i] for i in plan.read])
        )
        for j, i in enumerate(wanted):
            shards[i] = rebuilt[j]
        return shards

    def reconstruct_data(self, shards: list[Optional[np.ndarray]]) -> list[np.ndarray]:
        """Rebuild only missing data shards (store_ec.go ReconstructData path)."""
        return self.reconstruct(shards, data_only=True)

    def verify(self, shards: np.ndarray) -> bool:
        """Check parity rows match the data rows (klauspost Encoder.Verify)."""
        expect = self.encode(np.asarray(shards[: self.data_shards]))
        return bool(np.array_equal(expect, shards[self.data_shards :]))

    backend = ""  # the get_codec name; set by each backend class
    kernel = "host"

    def describe(self) -> dict:
        """What this codec runs on — the ``ec_codec`` object of a volume
        server's /status. Host codecs hold no device."""
        return {
            "backend": self.backend,
            "platform": "cpu",
            "device_kind": "host",
            "device_count": 0,
            "mesh": None,
            "kernel": self.kernel,
        }


class NumpyCodec(Codec):
    """Pure-numpy GF matmul: low/high-nibble product tables gathered with
    ``np.take`` over contiguous column blocks. GF(2^8) multiplication is
    GF(2)-linear, so mul(c, v) == mul(c, v & 0x0F) ^ mul(c, v & 0xF0) exactly
    — same bytes as the 256×256-table oracle loop, but the gathers hit two
    cache-resident 16-entry tables and the ≤256 KB block working set stays
    in L2 across the whole (r, c) loop nest. The tables are derived once per
    matrix (gf.nibble_tables) and cached, mirroring the native kernel's prep
    blob — the old path walked the full mul table per call."""

    _BLOCK = 1 << 16  # per-row block bytes; (k+R)·block stays L2-resident
    backend = kernel = "numpy"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._tab_cache: dict[bytes, np.ndarray] = {}

    def _tables(self, matrix: np.ndarray) -> np.ndarray:
        key = matrix.tobytes()
        cached = self._tab_cache.get(key)
        if cached is None:
            cached = gf.nibble_tables(matrix)
            self._tab_cache[key] = cached
        return cached

    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        tabs = self._tables(matrix)  # (R, k, 2, 16)
        rows, k = matrix.shape
        n = data.shape[1]
        out = np.zeros((rows, n), dtype=np.uint8)
        for pos in range(0, n, self._BLOCK):
            blk = data[:, pos : pos + self._BLOCK]
            lo_idx = blk & 0x0F
            hi_idx = blk >> 4
            for r in range(rows):
                acc = out[r, pos : pos + self._BLOCK]
                for c in range(k):
                    if not matrix[r, c]:
                        continue
                    acc ^= np.take(tabs[r, c, 0], lo_idx[c])
                    acc ^= np.take(tabs[r, c, 1], hi_idx[c])
        return out


class CpuCodec(Codec):
    """C++ native kernel (seaweedfs_tpu/native). The kernel's per-matrix
    coefficient prep (GFNI affine qwords / PSHUFB nibble tables, depending
    on the build) is derived once and cached here — encode calls the same
    parity matrix forever, and rederiving the tables per call is a
    cold-start cliff on every first chunk."""

    backend = "cpu"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        from seaweedfs_tpu.native import lib

        self._lib = lib
        self._prep_cache: dict[bytes, np.ndarray] = {}
        self.kernel = f"native-{lib.kernel_variant()}"

    def _prep(self, matrix: np.ndarray) -> np.ndarray:
        key = matrix.tobytes()
        cached = self._prep_cache.get(key)
        if cached is None:
            cached = self._lib.rs_prep(matrix)
            self._prep_cache[key] = cached
        return cached

    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        return self._lib.rs_matmul(matrix, data, prep=self._prep(matrix))


class LaunchCounter:
    """Device launches by kernel name and by the geometry of the codec
    view that launched. Launches come from the encode pipeline's dispatch
    thread and from request threads doing degraded reads at once, so the
    counts are kept under a lock."""

    def __init__(self):
        self._lock = make_lock("LaunchCounter._lock")
        self._n = {"pallas": 0, "xla": 0}
        self._by_geometry: dict[Geometry, int] = {}

    def add(self, kernel: str, geometry: Geometry) -> None:
        with self._lock:
            self._n[kernel] += 1
            self._by_geometry[geometry] = self._by_geometry.get(geometry, 0) + 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._n)

    def by_geometry(self) -> dict:
        """``{"10+4": n, "12+4": n}``: each geometry that has launched;
        the counts add up to `snapshot`'s."""
        with self._lock:
            return {str(g): n for g, n in self._by_geometry.items()}


def build_pallas_gf_matmul(jax, n_out_rows: int, k: int, n_cols: int,
                           tile: int, interpret: bool = False):
    """The fused GF(2^8) matmul Pallas kernel: unpack → MXU bit-matmul →
    mod-2 → repack, all inside VMEM per column tile.

    Returns the raw pallas_call (callers jit it, or trace it inside a
    shard_map body — pallas_call composes with shard_map, so the same fused
    kernel is the per-device compute of the mesh codec).  Takes
    (bitmat_planewise int8[8R, 8k], data uint8[k, n_cols]) → uint8[R, n_cols].
    """
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    T = min(tile, n_cols)
    if n_cols % T:
        raise ValueError(f"n_cols {n_cols} not a multiple of tile {T}")
    R, K = n_out_rows, k
    rb, kb = R * 8, K * 8

    def kernel(bitmat_ref, data_ref, out_ref):
        data = data_ref[...].astype(jnp.int32)  # (K, T)
        # bit-plane-major unpack: row j*K+d = bit j of input byte row d
        bits = jnp.concatenate(
            [(data >> j) & 1 for j in range(8)], axis=0
        ).astype(jnp.int8)  # (kb, T)
        acc = lax.dot_general(
            bitmat_ref[...],
            bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (rb, T), row i*R+p = bit i of output byte row p
        obits = acc & 1
        out = obits[:R, :]
        for i in range(1, 8):
            out = out | (obits[i * R : (i + 1) * R, :] << i)
        out_ref[...] = out.astype(jnp.uint8)

    return pl.pallas_call(
        kernel,
        grid=(n_cols // T,),
        in_specs=[
            pl.BlockSpec((rb, kb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((K, T), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((R, T), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((R, n_cols), jnp.uint8),
        interpret=interpret,
        # stable name: a profiler trace finds the kernel by it
        name=f"gf_matmul_r{R}_k{K}",
    )


def xla_gf_matmul(jax, bitmat, data, tp_axis: Optional[str] = None):
    """The XLA formulation of the GF(2^8) matmul, over the last two axes
    of ``data`` uint8[..., k, n]: unpack → int8 bit-matmul → mod 2 →
    repack, with ``bitmat`` int8[8R, 8k] (gf.gf_matrix_to_bit_matrix) →
    uint8[..., R, n]. What runs wherever the fused Pallas kernel does not
    (CPU tests); traced inside a jit or a shard_map body.

    ``tp_axis`` names a mesh axis the bit-contraction is split over:
    ``bitmat`` is then this device's int8[8R, 8k/tp] column slice, the
    device contracts it against its slice of the bits, and the partial
    counts are summed over the axis before the mod 2 (XOR is addition
    mod 2, so summing counts commutes with it)."""
    jnp = jax.numpy
    *lead, k, n = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[..., None, :] >> shifts[:, None]) & jnp.uint8(1)
    bits = bits.reshape(*lead, k * 8, n).astype(jnp.int8)
    if tp_axis is not None:
        rows = bitmat.shape[1]
        bits = jax.lax.dynamic_slice_in_dim(
            bits, jax.lax.axis_index(tp_axis) * rows, rows, axis=-2
        )
    acc = jnp.einsum(
        "ok,...kn->...on", bitmat, bits, preferred_element_type=jnp.int32
    )
    if tp_axis is not None:
        acc = jax.lax.psum(acc, axis_name=tp_axis)
    out_bits = (acc & 1).astype(jnp.uint8).reshape(*lead, -1, 8, n)
    weights = (jnp.uint8(1) << shifts)[:, None]
    return jnp.sum(out_bits * weights, axis=-2, dtype=jnp.uint32).astype(jnp.uint8)


class JaxCodec(Codec):
    """What the JAX-backed codecs share: the kernel choice, the launch
    counts, the jit and bit-matrix caches, the ``ec_codec`` object of
    /status, the HBM budget and the one host-side ``matmul`` loop. A
    subclass places data (``alignment``, ``device_put``) and launches
    (``matmul_device``). `TpuCodec` and `MeshCodec` are siblings, each
    with a ``matmul`` and a ``matmul_device`` of its own to ``getattr``:
    a tracer that wraps both classes' methods wraps each call once, where
    one codec subclassing the other would nest the wrappers."""

    mesh = None  # a jax.sharding.Mesh where launches are sharded over one

    def __init__(self, data_shards: int = DATA_SHARDS,
                 parity_shards: int = PARITY_SHARDS, *, devices,
                 chunk_bytes: int, use_pallas: Optional[bool],
                 pallas_tile: int, pallas_interpret: bool):
        super().__init__(data_shards, parity_shards)
        self._jax = jaxenv.import_jax()
        self.devices = list(devices)
        self.chunk_bytes = chunk_bytes
        if use_pallas is None:
            # Mosaic (the Pallas TPU compiler) needs a real TPU
            use_pallas = all(d.platform == "tpu" for d in self.devices)
        self.use_pallas = use_pallas
        self.pallas_tile = pallas_tile
        self._pallas_interpret = pallas_interpret
        if not use_pallas:
            self.kernel = "xla"
        else:
            self.kernel = "pallas-interpret" if pallas_interpret else "pallas"
        self._jit_cache: dict = {}
        self._bitmat_cache: dict = {}
        # device launches by kernel, so /status shows that no launch took a
        # path the operator did not ask for
        self.launches = LaunchCounter()

    def describe(self) -> dict:
        """The ``ec_codec`` object of a volume server's /status: enough for
        an operator (or chip_smoke.py) to assert the device, the kernel and
        the compile cache through the normal entry point, without importing
        JAX beside the daemon."""
        jax = self._jax
        first = self.devices[0]
        return {
            "backend": self.backend,
            "platform": first.platform,
            "device_kind": first.device_kind,
            "device_count": jax.device_count(),
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "kernel": self.kernel,
            "pallas_tile": self.pallas_tile,
            "launches": self.launches.snapshot(),
            "geometries": self.launches.by_geometry(),
            "devices": [
                {
                    "id": d.id,
                    "peak_bytes_in_use": (d.memory_stats() or {}).get(
                        "peak_bytes_in_use"
                    ),
                }
                for d in self.devices
            ],
            "compile_cache_dir": jaxenv.compile_cache_dir(),
            "compiles": jaxenv.compile_counts(),
            "x64": bool(jax.config.jax_enable_x64),
            "versions": {
                "jax": jax.__version__,
                "jaxlib": _dist_version("jaxlib"),
                "libtpu": _dist_version("libtpu"),
                "runtime": first.client.platform_version,
            },
        }

    def device_memory_free(self) -> Optional[int]:
        """Free HBM bytes of the tightest of the codec's devices (every
        device holds the same share of each chunk, so the fullest one
        bounds the chunk): a snapshot, so callers budget with headroom.
        None only where the platform keeps no allocator stats (the CPU
        backend); a TPU that reports none is an error, not a licence to
        skip the budget."""
        free = [_device_memory_free(d) for d in self.devices]
        return None if None in free else min(free)

    def matmul(self, matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
        out_rows, _ = matrix.shape
        n = data.shape[1]

        # One chunk/pad/slice loop for every kernel. Every chunk (tails
        # included) is padded to an alignment multiple: zeros encode to zeros
        # and are sliced off, and fixed widths bound the set of compiled
        # kernel shapes (Mosaic pays seconds per new shape, and arbitrary
        # tail widths would hand it unaligned lane dimensions).
        align = self.alignment()
        out = np.empty((out_rows, n), dtype=np.uint8)
        pos = 0
        while pos < n:
            end = min(pos + self.chunk_bytes, n)
            piece = data[:, pos:end]
            width = end - pos
            if width % align:
                padded = align * -(-width // align)
                piece = np.pad(piece, ((0, 0), (0, padded - width)))
            # one synchronous round trip: stage, launch, copy back
            with trace.stage_span(
                "ec.codec.launch", bytes=piece.nbytes,
                geometry=str(self.geometry),
            ):
                res = np.asarray(
                    self.matmul_device(matrix, self.device_put(piece))
                )
            out[:, pos:end] = res[:, :width]
            pos = end
        return out


class TpuCodec(JaxCodec):
    """JAX bit-matmul kernel on the process's default JAX device.

    On a TPU the fused Pallas kernel (Mosaic) is the only kernel; on any
    other platform the XLA formulation runs (CPU tests), or the Pallas
    kernel in interpret mode when asked. Which one a codec got is part of
    :meth:`describe` — it is never chosen by swallowing an error.

    Data is processed in fixed-size column chunks so the jit traces once;
    the tail chunk is zero-padded to the chunk width (zeros encode to zeros
    and are sliced off, so output bytes are unaffected).
    """

    backend = "tpu"

    def __init__(
        self,
        *args,
        chunk_bytes: int = 32 * 1024 * 1024,
        tile_bytes: int = 4 * 1024 * 1024,
        use_pallas: Optional[bool] = None,
        pallas_tile: int = 32 * 1024,
        pallas_interpret: bool = False,
        **kwargs,
    ):
        if chunk_bytes % tile_bytes:
            raise ValueError("chunk_bytes must be a multiple of tile_bytes")
        # a backend that cannot start (chip held by another process, no
        # runtime) raises here, at construction — never a quiet XLA fallback
        super().__init__(
            *args, devices=jaxenv.import_jax().devices()[:1],
            chunk_bytes=chunk_bytes, use_pallas=use_pallas,
            pallas_tile=pallas_tile, pallas_interpret=pallas_interpret,
            **kwargs,
        )
        self.tile_bytes = tile_bytes

    def _kernel(self, n_out_rows: int, k: int):
        """Jitted tiled bit-matmul for a (n_out_rows × k) matrix shape.

        One launch covers a whole chunk (amortizing dispatch latency),
        while a fori_loop over column tiles keeps the 8× bit-expansion
        intermediate at tile size instead of chunk size in HBM.
        """
        key = (n_out_rows, k)
        fn = self._jit_cache.get(key)
        if fn is None:
            jax = self._jax
            jnp = jax.numpy
            lax = jax.lax
            tile = self.tile_bytes

            @jax.jit
            def gf_bit_matmul(bitmat, data):
                kk, n = data.shape
                if n <= tile:
                    return xla_gf_matmul(jax, bitmat, data)
                n_tiles = n // tile  # callers pad chunks to tile multiples

                def body(i, out):
                    piece = lax.dynamic_slice(data, (0, i * tile), (kk, tile))
                    res = xla_gf_matmul(jax, bitmat, piece)
                    return lax.dynamic_update_slice(out, res, (0, i * tile))

                out = jnp.zeros((bitmat.shape[0] // 8, n), dtype=jnp.uint8)
                return lax.fori_loop(0, n_tiles, body, out)

            fn = gf_bit_matmul
            self._jit_cache[key] = fn
        return fn

    def _pallas_fused(self, n_out_rows: int, k: int, n_cols: int):
        """Fused Pallas kernel: unpack → MXU bit-matmul → mod-2 → repack,
        all inside VMEM per column tile.

        The XLA formulation (_kernel) materialises the 8×-expanded bit planes
        and the int32 accumulator in HBM — ~43 bytes of HBM traffic per input
        byte. Fused, traffic drops to read-input + write-output (1.4 B/B for
        RS(10,4)), which is what moves the encode rate past the 8 GB/s/chip
        target. Equivalent of the klauspost SIMD Encode loop
        (`weed/storage/erasure_coding/ec_encoder.go:179`), reformulated for
        the MXU rather than translated.

        Grid steps walk column tiles; Pallas double-buffers the (k, T) input
        and (R, T) output blocks automatically, overlapping DMA with compute.
        """
        key = ("pallas", n_out_rows, k, n_cols)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jax.jit(
                build_pallas_gf_matmul(
                    self._jax, n_out_rows, k, n_cols, self.pallas_tile,
                    self._pallas_interpret,
                )
            )
            self._jit_cache[key] = fn
        return fn

    def _bitmat(self, matrix: np.ndarray, planewise: bool = False):
        """Device-resident bit matrix, cached so repeated calls (a seal's
        chunks, a degraded read's decode) don't re-expand or re-upload it."""
        key = (matrix.tobytes(), planewise)
        cached = self._bitmat_cache.get(key)
        if cached is None:
            expand = gf.bit_matrix_planewise if planewise else gf.gf_matrix_to_bit_matrix
            cached = self._jax.device_put(expand(matrix).astype(np.int8))
            self._bitmat_cache[key] = cached
        return cached

    def alignment(self) -> int:
        return self.pallas_tile if self.use_pallas else self.tile_bytes

    def device_put(self, data: np.ndarray):
        """Stage host bytes into HBM (async; the overlap pipeline's H2D leg)."""
        return self._jax.device_put(data)

    def matmul_device(self, matrix: np.ndarray, data_dev):
        """Device-resident matmul: data_dev is a jax array (k, N) already in
        HBM; returns a jax array (R, N). N must be tile-aligned (or ≤ one
        tile). Widths beyond chunk_bytes are split into chunk-sized launches
        (one huge Mosaic grid would materialise grid-wide buffers and
        RESOURCE_EXHAUST; bounded launches stream through the same HBM
        working set regardless of N). The zero-copy path of the streaming
        encoder's overlap pipeline."""
        n = data_dev.shape[1]
        if n > self.chunk_bytes:
            outs = []
            pos = 0
            while pos < n:
                end = min(pos + self.chunk_bytes, n)
                outs.append(self.matmul_device(matrix, data_dev[:, pos:end]))
                pos = end
            return self._jax.numpy.concatenate(outs, axis=1)
        if self.use_pallas:
            if n % min(self.pallas_tile, n):
                # every caller pads to alignment(); a ragged width is a
                # caller bug, not a reason to leave the fused kernel for
                # the XLA formulation behind the operator's back
                raise ValueError(
                    f"width {n} is not a multiple of the kernel tile "
                    f"{self.pallas_tile}: pad to alignment() first"
                )
            fn = self._pallas_fused(matrix.shape[0], matrix.shape[1], n)
            self.launches.add("pallas", self.geometry)
            return fn(self._bitmat(matrix, planewise=True), data_dev)
        kernel = self._kernel(*matrix.shape)
        self.launches.add("xla", self.geometry)
        return kernel(self._bitmat(matrix), data_dev)


def _device_memory_free(device) -> Optional[int]:
    stats = device.memory_stats()
    if stats is None:
        if device.platform == "tpu":
            raise RuntimeError(
                f"{device} reports no memory_stats(): cannot budget HBM "
                "for the encode pipeline"
            )
        return None
    return max(0, stats["bytes_limit"] - stats["bytes_in_use"])


@functools.lru_cache(maxsize=None)  # /status is polled; two names, ever
def _dist_version(name: str) -> Optional[str]:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return None


_BACKENDS = {c.backend: c for c in (NumpyCodec, CpuCodec, TpuCodec)}
_CHIP_BACKENDS = ("tpu", "mesh")  # names that promise TPU devices


def get_codec(
    backend: str | None = None,
    data_shards: int = DATA_SHARDS,
    parity_shards: int = PARITY_SHARDS,
    **kwargs,
) -> Codec:
    """Codec factory.

    A backend NAMED by the caller or by $SWEED_EC_BACKEND is the backend
    you get, or an error: 'tpu' (one chip, TpuCodec) and 'mesh' (SPMD over
    all visible devices, sharded.MeshCodec) mean TPU devices and refuse any
    other platform; a named backend that cannot be built raises instead of
    becoming another one. Unnamed, the default is TpuCodec on whatever
    device JAX gives (the CPU codecs where JAX is absent), and a fallback
    is logged."""
    if backend is None:
        backend = os.environ.get("SWEED_EC_BACKEND", "")
    named = bool(backend)
    if not named:
        try:
            jaxenv.import_jax()
            backend = "tpu"
        except ImportError:
            backend = "cpu"
    if backend == "mesh":
        from .sharded import MeshCodec  # deferred: sharded imports this module

        cls = MeshCodec
    else:
        try:
            cls = _BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown ec backend {backend!r} (want tpu|cpu|numpy|mesh)"
            ) from None
    try:
        codec = cls(data_shards, parity_shards, **kwargs)
    except ImportError as e:
        if named:
            raise
        from ..util import glog

        glog.warning("ec backend %s unavailable (%s); using numpy", backend, e)
        return NumpyCodec(data_shards, parity_shards)
    if named and backend in _CHIP_BACKENDS:
        first = codec.devices[0]
        if {d.platform for d in codec.devices} != {"tpu"}:
            held = (
                " (this process is held to the cpu platform: JAX_PLATFORMS "
                "or an earlier host-only use of JAX)"
                if jaxenv.platforms() == "cpu" else ""
            )
            raise RuntimeError(
                f"ec backend {backend!r} was asked for but JAX offers "
                f"platform {first.platform!r} ({first.device_kind}){held}; "
                "name cpu or numpy, or leave the backend unset, to run "
                "without a chip"
            )
    return codec
