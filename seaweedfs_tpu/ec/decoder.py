"""EC volume → normal volume (the reverse of the encoder).

Mirrors `weed/storage/erasure_coding/ec_decoder.go`: the .dat is the data
shards' blocks re-interleaved (large rows first, then the small-block
tail), the .idx is the .ecx entries plus tombstones replayed from .ecj,
and the .dat size is recovered from the highest .ecx entry end. Backing
`ec.decode` (`weed/shell/command_ec_decode.go`) / the volume server's
VolumeEcShardsToVolume rpc.

Missing data shards are first regenerated from parity through the codec
(`encoder.rebuild_ec_files`), so whatever shards the volume's code decodes
from will do (any k of an RS volume). The geometry is the volume's own (its
.vif, `encoder.volume_geometry`).
"""

from __future__ import annotations

import os
import struct

import numpy as np

from ..storage import idx as idx_mod
from ..storage.needle import get_actual_size
from ..storage.super_block import SUPER_BLOCK_SIZE, SuperBlock
from ..storage.types import (
    NEEDLE_ID_SIZE,
    OFFSET_SIZE,
    TOMBSTONE_FILE_SIZE,
    needle_map_entry_size,
    size_is_valid,
)
from .codec import get_codec
from .constants import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, shard_ext
from .encoder import rebuild_ec_files, volume_geometry

_COPY_CHUNK = 8 * 1024 * 1024


def read_ec_volume_version(base_file_name: str) -> int:
    """The superblock rides at the head of shard 0 (ec_decoder.go:72)."""
    with open(base_file_name + shard_ext(0), "rb") as f:
        head = f.read(SUPER_BLOCK_SIZE)
        extra = struct.unpack(">H", head[6:8])[0]
        if extra:
            head += f.read(extra)
    return SuperBlock.from_bytes(head).version


def find_dat_file_size(
    base_file_name: str, offset_size: int = OFFSET_SIZE
) -> int:
    """Highest entry end in .ecx ≈ the original .dat size
    (FindDatFileSize, ec_decoder.go:45 — trailing deletes don't matter)."""
    version = read_ec_volume_version(base_file_name)
    dat_size = 0
    with open(base_file_name + ".ecx", "rb") as f:
        for key, offset, size in idx_mod.iter_index_file(f, offset_size):
            if not size_is_valid(size):
                continue
            end = offset + get_actual_size(size, version)
            dat_size = max(dat_size, end)
    return dat_size


def write_idx_file_from_ec_index(
    base_file_name: str, offset_size: int = OFFSET_SIZE
) -> None:
    """.ecx (+ .ecj tombstones) → .idx (WriteIdxFileFromEcIndex).

    The .ecx is key-sorted; the .idx is written in OFFSET order — append
    order. A Volume's load-time check trusts the last .idx entry to name
    the last record and cuts the .dat after it (storage/volume.py), and
    keys are handed out before the upload, so whenever writers raced the
    highest key is not the last record: a key-ordered .idx made the
    reload after `ec.decode` truncate live needles away."""
    entry = needle_map_entry_size(offset_size)
    raw = np.fromfile(base_file_name + ".ecx", dtype=np.uint8)
    rows = raw[: len(raw) - len(raw) % entry].reshape(-1, entry)
    offsets = (
        rows[:, NEEDLE_ID_SIZE : NEEDLE_ID_SIZE + 4].copy().view(">u4")
        .ravel().astype(np.uint64)
    )
    if offset_size > 4:  # 5-byte offsets keep the high byte last
        offsets |= rows[:, NEEDLE_ID_SIZE + 4].astype(np.uint64) << np.uint64(32)
    with open(base_file_name + ".idx", "wb") as dst:
        dst.write(rows[np.argsort(offsets, kind="stable")].tobytes())
        ecj = base_file_name + ".ecj"
        if os.path.exists(ecj):
            with open(ecj, "rb") as jf:
                while True:
                    rec = jf.read(8)
                    if len(rec) < 8:
                        break
                    (key,) = struct.unpack(">Q", rec)
                    dst.write(
                        idx_mod.pack_entry(
                            key, 0, TOMBSTONE_FILE_SIZE, offset_size
                        )
                    )


def write_dat_file(
    base_file_name: str,
    dat_size: int,
    data_shards: int,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
) -> None:
    """Re-interleave the volume's ``data_shards`` data shards into the
    original .dat (WriteDatFile, ec_decoder.go:153): full 1GB rows
    round-robin, then 1MB small-block rows for the tail."""
    inputs = [
        open(base_file_name + shard_ext(s), "rb") for s in range(data_shards)
    ]
    try:
        with open(base_file_name + ".dat", "wb") as dat:
            remaining = dat_size

            src_sizes = {id(f): os.path.getsize(f.name) for f in inputs}

            def copy_n(src, n):
                from .encoder import _is_hole

                left = n
                src_size = src_sizes[id(src)]
                while left > 0:
                    step = min(left, _COPY_CHUNK)
                    pos = src.tell()
                    if pos + step > src_size:
                        step_avail = src_size - pos
                        if step_avail <= 0:
                            raise IOError(
                                f"shard truncated: wanted {left} more bytes"
                            )
                        step = min(step, step_avail)
                    # shard holes (sparse sealed volumes) stay holes in the
                    # rebuilt .dat; the trailing truncate fixes the size
                    if _is_hole(src.fileno(), pos, step):
                        src.seek(step, 1)
                        dat.seek(step, 1)
                        left -= step
                        continue
                    buf = src.read(step)
                    if not buf:
                        raise IOError(
                            f"shard truncated: wanted {left} more bytes"
                        )
                    if buf.count(0) == len(buf):
                        dat.seek(len(buf), 1)
                    else:
                        dat.write(buf)
                    left -= len(buf)

            # strict >: an exact multiple of k*LARGE is laid out as small
            # rows by the encoder (our _work_items AND the reference's
            # encodeDatFile, ec_encoder.go:214, both use >). The reference
            # DECODER (WriteDatFile, ec_decoder.go:172) uses >= — a real
            # boundary bug that silently corrupts exact-multiple volumes;
            # verified empirically with scaled block sizes, so we diverge.
            while remaining > data_shards * large_block_size:
                for src in inputs:
                    copy_n(src, large_block_size)
                    remaining -= large_block_size
            while remaining > 0:
                for src in inputs:
                    to_read = min(remaining, small_block_size)
                    if to_read <= 0:
                        break
                    copy_n(src, to_read)
                    remaining -= to_read
            dat.truncate(dat_size)
    finally:
        for f in inputs:
            f.close()


def decode_to_volume(
    base_file_name: str, offset_size: int = OFFSET_SIZE, codec=None
) -> int:
    """Shards → .dat + .idx at the geometry the volume's .vif records;
    regenerates missing data shards first (with the caller's codec — a
    cpu-configured server must not fall back to the tpu default). Returns
    the reconstructed .dat size."""
    geometry = volume_geometry(base_file_name)
    missing_data = [
        s
        for s in range(geometry.data_shards)
        if not os.path.exists(base_file_name + shard_ext(s))
    ]
    if missing_data:
        codec = (codec or get_codec()).at(*geometry)
        rebuild_ec_files(base_file_name, codec)
    dat_size = find_dat_file_size(base_file_name, offset_size)
    write_dat_file(base_file_name, dat_size, geometry.data_shards)
    write_idx_file_from_ec_index(base_file_name, offset_size)
    return dat_size
