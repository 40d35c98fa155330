"""Fixture and traffic source: what the machine can hold, the seeded volume
and where each needle of the sealed volume lives.

Copied from ``chip_smoke.py`` (PR 21) and cut to what a benchmark run needs;
the benchmark imports nothing from ``chip_smoke.py`` or ``bench.py``. The
layout math (needle record size, small-block striping) is the benchmark's
own, so the program cannot move what the yardstick reads.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import resource
import shutil
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MiB = 1 << 20

# a needle record on disk (version 3): 16-byte header, the body fields the
# upload path writes for a nameless, mime-less blob, CRC, timestamp, padding
NEEDLE_HEADER = 16
NEEDLE_ALIGN = 8
RECORD_OVERHEAD = 64  # upper estimate per record, for planning only
ECX_ENTRY = struct.Struct(">QIi")  # key, offset / 8, size


def record_bytes(needle_size: int) -> int:
    """Bytes of one needle record in the .dat, from the ``size`` field of
    its index entry: header + size + CRC (4) + timestamp (8), padded to 8
    (a record that is already aligned still gets a full pad)."""
    body = needle_size + 4 + 8
    pad = NEEDLE_ALIGN - ((NEEDLE_HEADER + body) % NEEDLE_ALIGN)
    return NEEDLE_HEADER + body + pad


# -- what the machine can hold ------------------------------------------------
def filesystem_of(path: str) -> str:
    """The type of the filesystem ``path`` lives on, as the kernel names it
    (``ext4``, ``overlay``, ``tmpfs``), or ``unknown``."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, fs = line.split()[:3]
                at = mount.rstrip("/")
                if (path == mount or path.startswith(at + "/")) and (
                    len(mount) >= len(best)
                ):
                    best, kind = mount, fs
    except OSError:
        pass
    return kind


def machine_limits(data_dir: str) -> dict:
    """The largest file a process here may write, the room under
    ``data_dir`` and the filesystem it is on. A soft limit is the user's own
    to lift (the daemon child inherits it); a hard limit is the machine's
    and stays."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    return {
        "file_size_limit": None if hard == resource.RLIM_INFINITY else hard,
        "disk_free": shutil.disk_usage(data_dir).free,
        "data_fs": filesystem_of(data_dir),
    }


def require_room(limits: dict, dat_bytes: int, file_margin: int,
                 peak_disk_factor: float, disk_slack: int) -> None:
    """Fail — never cut — when the machine cannot hold the configured
    volume: every machine measures the same volume or none."""
    limit = limits["file_size_limit"]
    if limit is not None and limit - file_margin < dat_bytes:
        raise SystemExit(
            f"a file here may hold {limit} bytes (RLIMIT_FSIZE); the "
            f"configured .dat of {dat_bytes} bytes plus {file_margin} of "
            "margin does not fit, and the benchmark does not cut the volume"
        )
    need = int(dat_bytes * peak_disk_factor) + disk_slack
    if limits["disk_free"] < need:
        raise SystemExit(
            f"the data directory has {limits['disk_free']} bytes free; a "
            f"run peaks at ~{peak_disk_factor}x the volume and needs {need}"
        )


# -- seeded data ----------------------------------------------------------------
def needle_bytes(seed: int, index: int, size: int) -> bytes:
    """Needle ``index``'s payload: a pure function of the seed."""
    return np.random.Generator(np.random.SFC64([seed, index])).bytes(size)


def plan_sizes(plan_seed: int, target: int, mix: list[dict]) -> list[int]:
    """The volume's payload sizes, until their records pass ``target``:
    log-uniform inside each bucket of ``mix``. ``plan_seed`` is the
    configuration's, NOT the run's: every run and every seed loads the same
    multiset of sizes, so the .dat has the same length on every machine."""
    rng = np.random.default_rng([plan_seed, 0xB10B])
    weights = [b["weight"] for b in mix]
    sizes, total = [], 0
    while True:
        b = mix[int(rng.choice(len(mix), p=weights))]
        size = int(np.exp(rng.uniform(np.log(b["lo"]), np.log(b["hi"]))))
        # an upper estimate of the record (header, body fields, checksum,
        # timestamp, padding): the .dat never passes the target
        if total + size + RECORD_OVERHEAD > target:
            return sizes
        sizes.append(size)
        total += size + RECORD_OVERHEAD


def shuffled(seed: int, sizes: list[int]) -> list[int]:
    """The run's write order of the fixed size plan."""
    order = np.random.default_rng([seed, 0x0D3]).permutation(len(sizes))
    return [sizes[i] for i in order]


@dataclasses.dataclass
class Loaded:
    """What was written to one volume: fid, size and SHA-256 per needle."""

    vid: int
    fids: list[str]
    sizes: list[int]
    sums: list[str]


def load_volume(master: str, collection: str, replication: str, seed: int,
                sizes: list[int], threads: int = 8) -> Loaded:
    """Grow exactly one volume in ``collection`` and fill it over HTTP."""
    from seaweedfs_tpu import operation
    from seaweedfs_tpu.server.http_util import http_json

    r = http_json(
        "POST",
        f"http://{master}/vol/grow?collection={collection}&count=1"
        f"&replication={replication}",
    )
    if r.get("error") or r.get("count") != 1:
        raise RuntimeError(f"vol/grow: {r}")
    fids: list[str] = []
    url = ""
    while len(fids) < len(sizes):
        a = operation.assign(
            master, count=min(4096, len(sizes) - len(fids)),
            collection=collection,
        )
        url = a.url
        fids += [a.fid] + [f"{a.fid}_{j}" for j in range(1, a.count)]
    vids = {int(f.split(",")[0]) for f in fids}
    if len(vids) != 1:
        raise RuntimeError(f"collection {collection} spread over {vids}")

    def put(i: int) -> str:
        data = needle_bytes(seed, i, sizes[i])
        operation.upload_data(url, fids[i], data, compress=False)
        return hashlib.sha256(data).hexdigest()

    with ThreadPoolExecutor(threads) as pool:
        sums = list(pool.map(put, range(len(sizes))))
    return Loaded(vids.pop(), fids, sizes, sums)


def fid_key(fid: str) -> int:
    """The needle id of ``<vid>,<key hex><cookie 8 hex>[_delta]``."""
    body = fid.split(",", 1)[1]
    delta = 0
    if "_" in body:
        body, d = body.split("_", 1)
        delta = int(d)
    return int(body[:-8], 16) + delta


class Layout:
    """Where each needle of a sealed volume lives: its record's extent in
    the .dat from the ``.ecx``, and the shard each 1 MiB block of it sits
    on. Small-block regime only — the configured volume is below one
    large-block row, and a larger one is refused here."""

    def __init__(self, base: str, loaded: Loaded, ec: dict):
        k, small = ec["data_shards"], ec["small_block_bytes"]
        shard_size = os.path.getsize(base + ".ec00") if os.path.exists(
            base + ".ec00") else os.path.getsize(base + ".ec01")
        if shard_size * k > ec["large_block_bytes"] * k:
            raise ValueError("volume reaches the large-block regime")
        self.k, self.small = k, small
        entries = {}
        with open(base + ".ecx", "rb") as f:
            raw = f.read()
        for key, off8, size in ECX_ENTRY.iter_unpack(raw):
            entries[key] = (off8 * NEEDLE_ALIGN, size)
        self.extent = []
        for fid in loaded.fids:
            off, size = entries[fid_key(fid)]
            self.extent.append((off, record_bytes(size)))

    def intervals(self, i: int) -> list[tuple[int, int]]:
        """(shard, bytes) of every block interval of needle ``i``'s record,
        in read order."""
        off, left = self.extent[i]
        out = []
        while left > 0:
            block, inner = divmod(off, self.small)
            take = min(left, self.small - inner)
            out.append((block % self.k, take))
            off, left = off + take, left - take
        return out

    def lost_widths(self, i: int, lost: tuple[int, ...]) -> list[int]:
        """Bytes of each interval of needle ``i`` that sits on a lost data
        shard: each is one recovery, one device launch."""
        gone = set(lost)
        return [n for s, n in self.intervals(i) if s in gone]
