"""The plain reference of the configuration ``lrc1222``: Azure's Local
Reconstruction Code LRC(12,2,2) over GF(2^8), written from the paper's
description (Huang et al., *Erasure Coding in Windows Azure Storage*,
USENIX ATC'12, sec. 2.1-2.2) and SeaweedFS's striping of a ``.dat``.

Sixteen fragments, in shard order: 0-5 = x0..x5 and 6-11 = y0..y5, the
twelve data fragments in two local groups of six; 12 = px = sum x_i and
13 = py = sum y_i, a local parity a group; 14 = p0 = sum a_i x_i +
sum b_i y_i and 15 = p1 = sum a_i^2 x_i + sum b_i^2 y_i, two global
parities over all twelve. The coefficients follow the paper's rule (sec.
2.2), carried from GF(2^4) and three a group to GF(2^8), polynomial 0x11D,
and six a group: the a's are distinct non-zero elements whose low four bits
are zero, the b's distinct non-zero elements whose high four bits are zero,
so a_i != b_j and a_i + a_j != b_s + b_t unless both sums are zero. The
assignment itself (a_i = (i + 1) << 4, b_i = i + 1) is ``assumed`` in the
configuration: Azure's production coefficients are not published.

It imports the benchmark's own ``reference.py`` (field tables, the product
of rows with bytes, the striping) and nothing of the program, and never
touches JAX.
"""

from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .reference import gf_mul, rows_times, stripe

K, GROUPS, GLOBALS = 12, 2, 2
GROUP = K // GROUPS  # data fragments a local group


def coefficients() -> list[int]:
    """One coefficient a data fragment: a_0..a_5, then b_0..b_5."""
    return [(i + 1) << 4 for i in range(GROUP)] + [i + 1 for i in range(GROUP)]


def parity_rows() -> list[list[int]]:
    """The four parity rows over the twelve data fragments: px, py, p0, p1."""
    c = coefficients()
    px = [int(i < GROUP) for i in range(K)]
    py = [int(i >= GROUP) for i in range(K)]
    return [px, py, c, [gf_mul(v, v) for v in c]]


def coding_matrix() -> list[list[int]]:
    """Sixteen rows over twelve columns: the identity, then `parity_rows`."""
    identity = [[int(r == c) for c in range(K)] for r in range(K)]
    return identity + parity_rows()


def members(group: int) -> list[int]:
    """The seven fragments of a local group: six data and its local parity."""
    return [*range(group * GROUP, (group + 1) * GROUP), K + group]


def decodable(lost) -> bool:
    """The paper's counting rule (sec. 2.2, "Maximally Recoverable"): a local
    parity repairs one loss of its group; what it does not repair, and every
    lost global parity, takes one of the two global equations."""
    lost = set(lost)
    beyond_local = sum(
        max(len(lost & set(members(g))) - 1, 0) for g in range(GROUPS)
    )
    return beyond_local + len(lost & {K + GROUPS, K + GROUPS + 1}) <= GLOBALS


def read_set(lost) -> list[int]:
    """The fewest fragments that rebuild ``lost``, by the paper's argument
    (sec. 2.1): a fragment lost alone among its group's seven is the sum of
    the six others, so a loss of that kind in each group reads those groups'
    others and nothing else; any other loss is solved over all twelve data
    fragments, so it reads the data that survives and, for every data
    fragment that does not, one surviving parity — a group's local parity
    for the first of its losses, global parities for the rest. ValueError
    for a loss the code does not decode."""
    lost = set(lost)
    if not decodable(lost):
        raise ValueError(f"LRC(12,2,2) does not decode the loss of {sorted(lost)}")
    by_group = [lost & set(members(g)) for g in range(GROUPS)]
    if all(len(l) <= 1 for l in by_group) and not lost - set().union(*by_group):
        return sorted(
            s for g, l in enumerate(by_group) if l for s in members(g)
            if s not in lost
        )
    read = [s for s in range(K) if s not in lost]
    globals_left = [s for s in (K + GROUPS, K + GROUPS + 1) if s not in lost]
    for g, l in enumerate(by_group):
        unknown = len([s for s in l if s < K])
        if unknown and K + g not in lost:
            read.append(K + g)
            unknown -= 1
        for _ in range(unknown):
            read.append(globals_left.pop(0))
    return sorted(read)


def shard_sums(dat_path: str, ec: dict, threads: int = 8) -> dict:
    """SHA-256 of each of the sixteen shard files a correct LRC(12,2,2) seal
    of ``dat_path`` writes, and their common size: ``reference.shard_sums``
    with this code's parity rows."""
    if (ec["data_shards"], ec["parity_shards"],
            ec["local_parity_shards"]) != (K, GROUPS + GLOBALS, GROUPS):
        raise ValueError(f"the reference is LRC(12,2,2), the configuration {ec}")
    small = ec["small_block_bytes"]
    blocks = stripe(dat_path, K, ec["large_block_bytes"], small)
    n_rows, m = blocks.shape[0], GROUPS + GLOBALS
    rows = parity_rows()
    parity = np.empty((n_rows, m, small), dtype=np.uint8)
    # slabs of block rows across threads: take and xor release the GIL
    edges = np.linspace(0, n_rows, threads + 1).astype(np.int64)

    def slab(i: int) -> None:
        lo, hi = int(edges[i]), int(edges[i + 1])
        if hi > lo:
            data = blocks[lo:hi].transpose(1, 0, 2).reshape(K, -1)
            out = rows_times(rows, data)
            parity[lo:hi] = out.reshape(m, hi - lo, small).transpose(1, 0, 2)

    def digest(s: int) -> str:
        src, col = (blocks, s) if s < K else (parity, s - K)
        h = hashlib.sha256()
        for row in range(n_rows):
            h.update(src[row, col])
        return h.hexdigest()

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(slab, range(threads)))
        sums = list(pool.map(digest, range(K + m)))
    return {
        "shard_bytes": n_rows * small,
        "sums": sums,
        "dat_bytes": os.path.getsize(dat_path),
    }
