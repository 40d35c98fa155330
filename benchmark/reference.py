"""The plain reference: RS(k, m) over GF(2^8) in numpy, and the striping of
a ``.dat`` into shard files, written from the published description
(klauspost/reedsolomon's inverted-Vandermonde matrix, polynomial 0x11D;
SeaweedFS ``ec_encoder.go``: rows of ``k`` large blocks while more than a
row of them remains, then rows of ``k`` small blocks, zero-padded).

It imports nothing of the program and never touches JAX. The benchmark
hashes what it computes and holds every seal, every rebuilt shard and —
through the needles read back — every recovered byte to it.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D


def _tables():
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    return 0 if a == 0 or b == 0 else int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, v in enumerate(row):
                acc ^= gf_mul(v, b[t][j])
            out[i][j] = acc
    return out


def mat_invert(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    work = [list(row) + [int(i == j) for j in range(n)]
            for i, row in enumerate(m)]
    for c in range(n):
        pivot = next((r for r in range(c, n) if work[r][c]), None)
        if pivot is None:
            raise ValueError("singular matrix")
        work[c], work[pivot] = work[pivot], work[c]
        inv = gf_inv(work[c][c])
        work[c] = [gf_mul(v, inv) for v in work[c]]
        for r in range(n):
            if r != c and work[r][c]:
                f = work[r][c]
                work[r] = [v ^ gf_mul(f, w) for v, w in zip(work[r], work[c])]
    return [row[n:] for row in work]


def coding_matrix(k: int, total: int) -> list[list[int]]:
    """klauspost's default matrix: the ``total x k`` Vandermonde matrix
    (row r = r^0 .. r^(k-1)) times the inverse of its top square, so the
    first ``k`` rows are the identity."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(total)]
    return mat_mul(vm, mat_invert(vm[:k]))


def rows_times(rows: list[list[int]], data: np.ndarray) -> np.ndarray:
    """(R x k GF matrix) times (k x N bytes): one table lookup and one XOR
    per coefficient. Even widths go two bytes at a time through a
    65,536-entry table of byte pairs, which halves the lookups."""
    n = data.shape[1]
    pairs = n % 2 == 0 and data.flags.c_contiguous
    out = np.zeros((len(rows), n), dtype=np.uint8)
    src = data.view(np.uint16) if pairs else data
    dst = out.view(np.uint16) if pairs else out
    values = np.arange(256, dtype=np.int32)
    product = np.empty(src.shape[1], dtype=src.dtype)
    for r, row in enumerate(rows):
        for c, coeff in enumerate(row):
            if coeff == 0:
                continue
            table = np.zeros(256, dtype=np.uint8)
            table[1:] = EXP[LOG[values[1:]] + LOG[coeff]]
            if pairs:  # both bytes of a pair are multiplied alike
                wide = table.astype(np.uint16)
                table = (wide[:, None] << 8 | wide[None, :]).reshape(-1)
            np.take(table, src[c], out=product)
            np.bitwise_xor(dst[r], product, out=dst[r])
    return out


def shard_size(dat_size: int, k: int, large: int, small: int) -> int:
    """Bytes of each shard file of a ``.dat`` of ``dat_size`` bytes."""
    n_large = 0
    left = dat_size
    while left > large * k:
        n_large += 1
        left -= large * k
    n_small = -(-left // (small * k)) if left > 0 else 0
    return n_large * large + n_small * small


def stripe(dat_path: str, k: int, large: int, small: int) -> np.ndarray:
    """The ``.dat`` as a (rows, k, small) array, zero-padded to whole rows:
    ``[:, s, :]`` is data shard ``s``. Small-block regime only (at most one
    row of large blocks): block b of the file is block b // k of shard
    b % k."""
    dat_size = os.path.getsize(dat_path)
    if dat_size > large * k:
        raise ValueError("the plain reference stripes small-block volumes only")
    size = shard_size(dat_size, k, large, small)
    padded = np.zeros(size * k, dtype=np.uint8)
    with open(dat_path, "rb") as f:
        if f.readinto(memoryview(padded)[:dat_size]) != dat_size:
            raise IOError(f"short read of {dat_path}")
    return padded.reshape(size // small, k, small)


def shard_sums(dat_path: str, ec: dict, threads: int = 8) -> dict:
    """SHA-256 of each of the ``k + m`` shard files a correct seal of
    ``dat_path`` writes, and their common size."""
    k, m = ec["data_shards"], ec["parity_shards"]
    large, small = ec["large_block_bytes"], ec["small_block_bytes"]
    blocks = stripe(dat_path, k, large, small)
    n_rows = blocks.shape[0]
    parity_rows = coding_matrix(k, k + m)[k:]
    parity = np.empty((n_rows, m, small), dtype=np.uint8)
    # slabs of block rows across threads: take and xor release the GIL
    edges = np.linspace(0, n_rows, threads + 1).astype(np.int64)

    def slab(i: int) -> None:
        lo, hi = int(edges[i]), int(edges[i + 1])
        if hi > lo:
            data = blocks[lo:hi].transpose(1, 0, 2).reshape(k, -1)
            out = rows_times(parity_rows, data)
            parity[lo:hi] = out.reshape(m, hi - lo, small).transpose(1, 0, 2)

    def digest(s: int) -> str:
        src, col = (blocks, s) if s < k else (parity, s - k)
        h = hashlib.sha256()
        for row in range(n_rows):
            h.update(src[row, col])
        return h.hexdigest()

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(slab, range(threads)))
        sums = list(pool.map(digest, range(k + m)))
    return {
        "shard_bytes": n_rows * small,
        "sums": sums,
        "dat_bytes": os.path.getsize(dat_path),
    }
