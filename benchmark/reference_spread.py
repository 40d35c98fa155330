"""The plain reference of a spread EC volume: which server holds which shard,
which shards and servers a needle's bytes lie on, and a needle's bytes read
back from any ``k`` of the shard files.

Written from the published descriptions — SeaweedFS
``command_ec_encode.go:209`` ``balancedEcDistribution`` (the shards go
round-robin over the volume servers, the source first so that it keeps its
share), ``ec_encoder.go`` (small-block striping: block ``b`` of the ``.dat``
is block ``b // k`` of shard ``b % k``), ``needle.go`` version 3 (cookie 4,
id 8, size 4, then data size 4 and the data) — on ``reference.py``'s
RS(k, m). It imports nothing of the program; numpy and the standard library.
"""

from __future__ import annotations

import os
import struct

import numpy as np

from . import reference

NEEDLE_HEADER = struct.Struct(">IQI")  # cookie, id, size
DATA_SIZE = struct.Struct(">I")


def spread_plan(servers: list[str], source: str, total: int) -> dict[str, list[int]]:
    """server -> the shards it holds after ``ec.encode``: the servers in
    order of their address, the source moved to the front, shard ``s`` to
    server ``s mod n``."""
    order = sorted(servers)
    order.remove(source)
    order.insert(0, source)
    return {
        server: [s for s in range(total) if s % len(order) == at]
        for at, server in enumerate(order)
    }


def holder_of(plan: dict[str, list[int]]) -> dict[int, str]:
    return {s: server for server, shards in plan.items() for s in shards}


def intervals(offset: int, length: int, k: int, small: int) -> list[tuple[int, int, int]]:
    """(shard, offset in the shard file, bytes) of each piece of the
    ``.dat``'s bytes ``[offset, offset + length)``, in read order.
    Small-block regime only."""
    out = []
    while length > 0:
        block, inner = divmod(offset, small)
        take = min(length, small - inner)
        out.append((block % k, (block // k) * small + inner, take))
        offset, length = offset + take, length - take
    return out


def needs(offset: int, length: int, k: int, small: int,
          plan: dict[str, list[int]], at: str, dead: str) -> dict:
    """What server ``at`` must do to read ``[offset, offset + length)``
    with server ``dead`` gone: pieces it holds, pieces a live server
    holds, pieces that were on the dead one and have to be decoded."""
    where = holder_of(plan)
    out = {"local": 0, "remote": 0, "lost": 0}
    for shard, _, _ in intervals(offset, length, k, small):
        holder = where[shard]
        out["local" if holder == at else "lost" if holder == dead else "remote"] += 1
    return out


def read_range(shard_files: dict[int, str], offset: int, length: int,
               ec: dict) -> bytes:
    """Bytes ``[offset, offset + length)`` of the sealed ``.dat`` from the
    shard files given (shard id -> path, at least ``k`` of them): a piece
    whose shard is among them is read, any other is decoded from the first
    ``k`` of them by the inverse of their rows of the coding matrix."""
    k, total = ec["data_shards"], ec["data_shards"] + ec["parity_shards"]
    have = sorted(shard_files)[:k]
    if len(have) < k:
        raise ValueError(f"{len(shard_files)} shard files cannot decode RS({k}, {total - k})")
    decode = None
    out = []
    for shard, at, n in intervals(offset, length, k, ec["small_block_bytes"]):
        if shard in shard_files:
            out.append(_pread(shard_files[shard], at, n))
            continue
        if decode is None:
            matrix = reference.coding_matrix(k, total)
            decode = reference.mat_invert([matrix[s] for s in have])
        rows = np.stack([
            np.frombuffer(_pread(shard_files[s], at, n), dtype=np.uint8)
            for s in have
        ])
        out.append(reference.rows_times([decode[shard]], rows)[0].tobytes())
    return b"".join(out)


def payload(record: bytes) -> tuple[int, bytes]:
    """(needle id, data) of one version-3 needle record."""
    _, key, size = NEEDLE_HEADER.unpack_from(record)
    if size == 0:
        return key, b""
    (n,) = DATA_SIZE.unpack_from(record, NEEDLE_HEADER.size)
    start = NEEDLE_HEADER.size + DATA_SIZE.size
    return key, record[start:start + n]


def _pread(path: str, offset: int, n: int) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        data = os.pread(fd, n, offset)
    finally:
        os.close(fd)
    if len(data) != n:
        raise IOError(f"{path}: {len(data)} of {n} bytes at {offset}")
    return data
