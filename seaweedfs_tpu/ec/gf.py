"""GF(2^8) arithmetic and matrices, klauspost/reedsolomon-compatible.

The field is GF(2^8) with reduction polynomial x^8+x^4+x^3+x^2+1 (0x11D) and
generator alpha=2 — the same field as Backblaze's JavaReedSolomon and
klauspost/reedsolomon (the dependency behind the reference's EC path,
`go.mod:46`, call sites `weed/storage/erasure_coding/ec_encoder.go:179,270`).

The RS generator matrix reproduces klauspost's default construction exactly
(an "inverted Vandermonde": vm(total,k) * inverse(vm[:k,:k])), so parity and
reconstructed shards are bit-identical to the Go path. Addition is XOR;
multiplication uses log/exp tables.

Everything here is numpy/uint8 host code: matrices are tiny (≤14×10); bulk
data work happens in codec_tpu (JAX) or codec_cpu (C++).
"""

from __future__ import annotations

import numpy as np

GENERATOR_POLYNOMIAL = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1
FIELD_SIZE = 256


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)  # doubled for mod-free indexing
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GENERATOR_POLYNOMIAL
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    log[0] = -1  # undefined
    return exp, log


EXP_TABLE, LOG_TABLE = _build_tables()


def gal_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP_TABLE[LOG_TABLE[a] + LOG_TABLE[b]])


def gal_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF division by zero")
    if a == 0:
        return 0
    return int(EXP_TABLE[(LOG_TABLE[a] - LOG_TABLE[b]) % 255])


def gal_exp(a: int, n: int) -> int:
    """a**n in the field (klauspost galois.go galExp): a=0,n>0 → 0; n=0 → 1."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP_TABLE[(LOG_TABLE[a] * n) % 255])


def gal_inverse(a: int) -> int:
    return gal_div(1, a)


# -- full 256x256 multiplication table (for C++ kernel init & vectorized ops)
def mul_table() -> np.ndarray:
    """MUL[a, b] = a*b over GF(2^8), shape (256, 256) uint8."""
    la = LOG_TABLE.copy()
    la[0] = 0
    s = la[:, None] + la[None, :]
    out = EXP_TABLE[s]
    out[0, :] = 0
    out[:, 0] = 0
    return out.astype(np.uint8)


_MUL_TABLE: np.ndarray | None = None


def get_mul_table() -> np.ndarray:
    global _MUL_TABLE
    if _MUL_TABLE is None:
        _MUL_TABLE = mul_table()
    return _MUL_TABLE


def nibble_tables(matrix: np.ndarray) -> np.ndarray:
    """Low/high-nibble product tables for every coefficient of ``matrix``:
    shape (R, k, 2, 16) uint8 where [r, c, 0, x] = matrix[r,c]·x and
    [r, c, 1, x] = matrix[r,c]·(x<<4). Multiplication is GF(2)-linear, so
    mul(c, v) == lo[v & 0x0F] ^ hi[v >> 4] exactly (klauspost's PSHUFB
    table formulation, derived host-side for the numpy fallback)."""
    mt = get_mul_table()
    coefs = np.ascontiguousarray(matrix, dtype=np.uint8).reshape(-1)
    lo = mt[coefs][:, np.arange(16)]
    hi = mt[coefs][:, np.arange(16) << 4]
    return (
        np.stack([lo, hi], axis=1)
        .reshape(*matrix.shape, 2, 16)
        .astype(np.uint8, copy=True)
    )


# -- matrices (uint8 2-D numpy arrays) ---------------------------------------
def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Matrix product over GF(2^8)."""
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    mt = get_mul_table()
    # products[i,k,j] = a[i,k]*b[k,j]; XOR-reduce over k
    products = mt[a[:, :, None], b[None, :, :]]
    return np.bitwise_xor.reduce(products, axis=1)


def mat_identity(n: int) -> np.ndarray:
    return np.eye(n, dtype=np.uint8)


def mat_invert(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inversion over GF(2^8). Raises if singular."""
    n = m.shape[0]
    if m.shape[1] != n:
        raise ValueError("not square")
    mt = get_mul_table()
    work = np.concatenate([m.astype(np.uint8), mat_identity(n)], axis=1)
    for col in range(n):
        # find pivot
        pivot = -1
        for r in range(col, n):
            if work[r, col] != 0:
                pivot = r
                break
        if pivot < 0:
            raise np.linalg.LinAlgError("singular matrix over GF(2^8)")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        # scale pivot row to 1
        inv = gal_inverse(int(work[col, col]))
        work[col] = mt[inv, work[col]]
        # eliminate other rows
        for r in range(n):
            if r != col and work[r, col] != 0:
                work[r] = work[r] ^ mt[int(work[r, col]), work[col]]
    return work[:, n:].copy()


def vandermonde(rows: int, cols: int) -> np.ndarray:
    """vm[r, c] = r**c over the field (klauspost matrix.go vandermonde)."""
    out = np.zeros((rows, cols), dtype=np.uint8)
    for r in range(rows):
        for c in range(cols):
            out[r, c] = gal_exp(r, c)
    return out


def build_matrix(data_shards: int, total_shards: int) -> np.ndarray:
    """The klauspost default RS encode matrix (reedsolomon.go buildMatrix):

    vm(total, k) * inverse(vm[:k, :k]) — identity on the top k rows, parity
    rows below. Any k rows of the result are invertible (MDS).
    """
    if not 0 < data_shards < total_shards <= FIELD_SIZE:
        raise ValueError(f"bad geometry k={data_shards} n={total_shards}")
    vm = vandermonde(total_shards, data_shards)
    top_inv = mat_invert(vm[:data_shards, :data_shards])
    return mat_mul(vm, top_inv)


def lrc_matrix(data_shards: int, local: int, global_: int) -> np.ndarray:
    """The encode matrix of a Local Reconstruction Code (Huang et al.,
    Erasure Coding in Windows Azure Storage, ATC'12, sec. 2.1-2.2) with two
    local groups: identity on the top k rows; then a local parity a group,
    the XOR of its k / 2 data shards (a 0/1 row); then the global parities,
    row j the (j + 1)-th powers of one coefficient a data shard. The
    coefficients carry the paper's rule from GF(2^4) to GF(2^8): the first
    group's are the non-zero bytes whose low nibble is zero, ``(i + 1) <<
    4``, the second's those whose high nibble is zero, ``i + 1`` — so no
    two are equal, and no sum of two of one group equals a sum of two of
    the other unless both are zero. That makes LRC(12,2,2) decode every
    loss the code's structure allows (all of three shards, 1,568 of the
    1,820 of four; tests/test_ec_lrc.py walks them). Which loss decodes is
    never assumed from this: `codec.read_plan` solves over the rows."""
    if local != 2 or data_shards % 2 or not 0 < data_shards // 2 <= 15:
        raise ValueError(
            f"bad lrc geometry k={data_shards} l={local} g={global_}"
        )
    size = data_shards // 2
    coefficients = [(i + 1) << 4 for i in range(size)] + [
        i + 1 for i in range(size)
    ]
    rows = [np.eye(data_shards, dtype=np.uint8)]
    groups = np.zeros((2, data_shards), dtype=np.uint8)
    groups[0, :size] = groups[1, size:] = 1
    rows.append(groups)
    rows.append(np.array(
        [[gal_exp(c, j + 1) for c in coefficients] for j in range(global_)],
        dtype=np.uint8,
    ).reshape(global_, data_shards))
    return np.concatenate(rows, axis=0)


def parity_matrix(data_shards: int, parity_shards: int) -> np.ndarray:
    """Just the parity rows (m × k) of the encode matrix."""
    return build_matrix(data_shards, data_shards + parity_shards)[data_shards:]


# -- GF(2) bit-matrix expansion (the TPU formulation) ------------------------
def gf_matrix_to_bit_matrix(m: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) matrix (R×C) into its GF(2) bit-matrix (8R×8C).

    Multiplication by a constant c is GF(2)-linear on the 8 bits of the
    operand: column j of the 8×8 block for c is the byte c*(2^j). With data
    bytes unpacked to bits, parity = bitmat @ bits (mod 2) — a plain integer
    matmul that XLA maps onto the TPU MXU.

    Bit index convention: row block p, bit i ↦ row p*8+i (bit i of output
    byte); col block d, bit j ↦ col d*8+j (bit j of input byte).
    """
    rows, cols = m.shape
    mt = get_mul_table()
    powers = (1 << np.arange(8)).astype(np.uint8)  # 2^j
    # prod[r, c, j] = m[r,c] * 2^j  (uint8)
    prod = mt[m[:, :, None], powers[None, None, :]]
    # bits[r, c, j, i] = bit i of prod
    bits = (prod[..., None] >> np.arange(8)) & 1
    # reorder to (r, i, c, j) → (8R, 8C)
    out = bits.transpose(0, 3, 1, 2).reshape(rows * 8, cols * 8)
    return out.astype(np.uint8)


def bit_matrix_planewise(m: np.ndarray) -> np.ndarray:
    """Bit matrix with bit-plane-major ordering, for the fused Pallas kernel.

    Same GF(2) matrix as gf_matrix_to_bit_matrix but rows ordered i*R+p
    (output bit-plane i, byte row p) and columns j*C+d (input bit-plane j,
    byte column d). With this layout the kernel can unpack operand bytes as
    8 whole-array scalar shifts concatenated along the row axis — no
    per-element vector shifts — and repack the result with 8 static row
    slices. Pure reindexing: parity bytes are unchanged.
    """
    rows, cols = m.shape
    b = gf_matrix_to_bit_matrix(m).reshape(rows, 8, cols, 8)
    return b.transpose(1, 0, 3, 2).reshape(rows * 8, cols * 8).copy()
