#!/usr/bin/env python3
"""chip_smoke.py — seal, read degraded and rebuild a real-size volume
through the daemons, on the chip.

The quickest proof that the served EC path still starts and answers right
on a TPU.  Through the normal entry points only (``python -m seaweedfs_tpu
server``, HTTP, ``python -m seaweedfs_tpu shell -c``):

1. start master + volume server as ONE child process with ``-ec.backend tpu``
   and read the device it got from ``/status``;
2. load one volume over HTTP with seeded blobs (a Haystack/f4-shaped size
   mix, tens of KB to a few MB) until its ``.dat`` holds one full 10 GiB
   large-block row plus a small-block tail;
3. ``ec.encode`` it; check 14 shards of the planned size, the ``.vif`` sums,
   and every parity byte against ``CpuCodec`` (streamed over the whole
   volume) and ``NumpyCodec`` (seeded windows in both block regimes) —
   references that never touch JAX;
4. GET a seeded sample of needles and compare SHA-256 with what was
   written (healthy EC read);
5. delete shards 0, 4, 9 and 12 and GET needles that live on the lost data
   shards: every byte comes out of the decode kernel on the chip;
6. ``ec.rebuild``; the four rebuilt files must hash to the ``.vif`` sums;
7. answer one ``/_query`` (compared with ``query/engine.py``) and THEN seal
   a second volume whose kernel shapes are new to the process; while that
   daemon holds the chip, a neighbour started with ``-ec.backend cpu``
   answers the same query and must not open the chip to do so;
8. restart the daemon and seal a third volume of the same shapes: the
   persistent compile cache must answer;
9. with four or more devices, a ``-ec.backend mesh`` daemon repeats
   seal → degraded read → rebuild on the same data and must spread every
   launch over four devices.

This process never imports JAX: the daemon child owns the chip, and a chip
belongs to one process.  One daemon that is given the chip runs at a time
and has exited before the next starts.

Run with no arguments it REQUIRES the chip and asks for the full size; it
exits non-zero if the daemon's device is not a TPU, if the kernel ran
interpreted or on the XLA path, or if any step failed.  Where the machine
cannot hold that size — a file-size limit (``ulimit -f``) below the ``.dat``,
or a data directory with too little free space — the volume is cut to what
fits and the cut is printed under ``reduced``.  ``--size <small>`` is the dry
run (tests, debugging): the daemon gets the unnamed default backend, so it
runs under ``JAX_PLATFORMS=cpu`` and says ``platform: cpu``; block sizes
are the reference's either way, so a small volume never reaches the
large-block regime and says so under ``reduced``.

Every wall time printed is smoke wall-clock on a shared host, a bring-up
fact and not a benchmark.  The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import hashlib
import json
import os
import resource
import shutil
import signal
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from seaweedfs_tpu import native, operation  # noqa: E402
from seaweedfs_tpu.ec import encoder  # noqa: E402
from seaweedfs_tpu.ec.codec import CpuCodec, NumpyCodec  # noqa: E402
from seaweedfs_tpu.ec.constants import (  # noqa: E402
    DATA_SHARDS,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS,
    shard_ext,
)
from seaweedfs_tpu.ec.locate import locate_data  # noqa: E402
from seaweedfs_tpu.query import engine as query_engine  # noqa: E402
from seaweedfs_tpu.server.http_util import http_bytes, http_json  # noqa: E402
from seaweedfs_tpu.storage import idx as idx_mod  # noqa: E402
from seaweedfs_tpu.storage.file_id import parse_path  # noqa: E402
from seaweedfs_tpu.storage.needle import get_actual_size  # noqa: E402
from seaweedfs_tpu.util.netports import free_port  # noqa: E402

MiB, GiB = 1 << 20, 1 << 30
LARGE_ROW = LARGE_BLOCK_SIZE * DATA_SHARDS
# one full large-block row plus a small-block tail (ISSUE 21): the smallest
# volume that walks both work-item kinds and the large→small switch-over
FULL_SIZE = LARGE_ROW + 96 * MiB
REFERENCE_VOLUME = 30 * GiB  # -volumeSizeLimitMB default
LOST_SHARDS = (0, 4, 9, 12)  # three data, one parity
COLLECTION = "smoke"
SHELL_TIMEOUT = 900.0  # seconds one shell command (a seal, a rebuild) may take
# the .dat beside its 14 staged shards is 2.4x the volume; the two small
# volumes, the compile cache and the logs make up the rest
PEAK_DISK_FACTOR = 2.6
DISK_SLACK = 512 * MiB
# the .dat is the largest file of a run and ends up to one needle (4 MiB)
# and a little bookkeeping past its target
FILE_MARGIN = 8 * MiB
MIN_SIZE = 4 * MiB


def say(msg: str) -> None:
    print(msg, flush=True)


def parse_size(text: str) -> int:
    mult = {"k": 1 << 10, "m": MiB, "g": GiB}.get(text[-1].lower())
    return int(float(text[:-1]) * mult) if mult else int(text)


def sha256_file(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(8 * MiB), b""):
            digest.update(chunk)
    return digest.hexdigest()


# -- what the machine can hold ------------------------------------------------
def machine_limits(data_dir: str) -> dict:
    """The largest file a process here may write and the room under
    ``data_dir``. A soft file-size limit is the user's own to lift, so it is
    raised to the hard one (the daemon children inherit it); a hard limit is
    the machine's and stays."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    return {
        "file_size_limit": None if hard == resource.RLIM_INFINITY else hard,
        "disk_free": shutil.disk_usage(data_dir).free,
    }


def fit_size(want: int, limits: dict) -> tuple[int, list[str]]:
    """The volume size this machine can hold, at most ``want``, and why it
    is less when it is. Found out before any byte is loaded: met half-way,
    a limit is an HTTP 500 from the volume server minutes into the load."""
    size, why = want, []
    file_limit = limits["file_size_limit"]
    if file_limit is not None and file_limit - FILE_MARGIN < size:
        size = file_limit - FILE_MARGIN
        why.append(
            f"a file here may hold {file_limit} bytes (RLIMIT_FSIZE) and "
            "the .dat is one file"
        )
    by_disk = int((limits["disk_free"] - DISK_SLACK) / PEAK_DISK_FACTOR)
    if by_disk < size:
        size = by_disk
        why.append(
            f"the data directory has {limits['disk_free']} bytes free and "
            f"a run peaks at ~{PEAK_DISK_FACTOR}x the volume"
        )
    if size < MIN_SIZE:
        raise RuntimeError(
            f"no room for a volume of even {MIN_SIZE} bytes: {'; '.join(why)}"
        )
    return size, why


# -- the daemon child ---------------------------------------------------------
class Daemon:
    """master + volume server as one child process (`server` subcommand),
    in its own session so that stop() takes every thread and helper with
    it. The child owns the chip; this process only speaks HTTP to it."""

    def __init__(self, data_dir: str, backend: str, log_path: str):
        self.data_dir = data_dir
        self.backend = backend
        self.log_path = log_path
        self.master = f"127.0.0.1:{free_port()}"
        self.volume = f"127.0.0.1:{free_port()}"
        self.proc: subprocess.Popen | None = None

    def __enter__(self) -> "Daemon":
        cmd = [
            sys.executable, "-m", "seaweedfs_tpu", "server",
            "-dir", self.data_dir,
            "-master.port", self.master.rsplit(":", 1)[1],
            "-port", self.volume.rsplit(":", 1)[1],
            "-max", "16",
        ]
        if self.backend:
            cmd += ["-ec.backend", self.backend]
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        os.makedirs(self.data_dir, exist_ok=True)
        self._log = open(self.log_path, "ab")
        self._log.write(f"\n==== {' '.join(cmd)}\n".encode())
        self._log.flush()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        t0 = time.monotonic()
        try:
            self._wait_ready()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        # includes the backend start when one was named: the daemon serves
        # only after it has its device
        self.start_wall_s = round(time.monotonic() - t0, 2)
        return self

    def _wait_ready(self, timeout: float = 180.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"daemon exited with {self.proc.returncode} before it "
                    f"served:\n{self.log_tail()}"
                )
            try:
                self.status()
                nodes = http_json(
                    "GET", f"http://{self.master}/dir/status", timeout=2.0
                )["topology"]["data_centers"]
                if nodes:
                    return
            except (OSError, KeyError, ValueError):
                pass
            time.sleep(0.2)
        raise RuntimeError(f"daemon not ready in {timeout}s:\n{self.log_tail()}")

    def status(self) -> dict:
        return http_json("GET", f"http://{self.volume}/status", timeout=10.0)

    def codec(self) -> dict:
        return self.status()["ec_codec"]

    def log_tail(self, lines: int = 30) -> str:
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-lines:]).decode(
                "utf-8", "replace"
            )

    def shell(self, command: str, timeout: float) -> str:
        """One command through the operator's shell, as its own (JAX-free)
        process; a failing command fails the step."""
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.master, "-c", command],
            cwd=REPO, capture_output=True, text=True, timeout=timeout,
            env=dict(os.environ, PYTHONPATH=REPO),
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"shell -c {command!r} exited {r.returncode}:\n"
                f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}\n"
                f"-- daemon log --\n{self.log_tail()}"
            )
        return r.stdout

    def __exit__(self, *exc) -> None:
        """Stop the child and wait until it is gone: the next daemon needs
        the chip this one holds."""
        if self.proc is not None and self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGINT)
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                pass
        if self.proc is not None:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(self.proc.pid, signal.SIGKILL)  # stragglers too
            self.proc.wait(timeout=30)
        self._log.close()


# -- seeded data ----------------------------------------------------------------
def needle_bytes(seed: int, index: int, size: int) -> bytes:
    """Needle ``index``'s payload: a pure function of the seed."""
    return np.random.Generator(np.random.SFC64([seed, index])).bytes(size)


def plan_sizes(seed: int, target: int) -> list[int]:
    """Payload sizes until the volume passes ``target``: 70% photo-scale
    (16–256 KiB), 25% 256 KiB–2 MiB, 5% 2–4 MiB, log-uniform inside each
    bucket — the Haystack/f4 shape of many small blobs and a heavy tail."""
    rng = np.random.default_rng([seed, 0xB10B])
    buckets = ((16 << 10, 256 << 10), (256 << 10, 2 * MiB), (2 * MiB, 4 * MiB))
    sizes, total = [], 0
    while total < target + MiB:
        lo, hi = buckets[int(rng.choice(3, p=(0.70, 0.25, 0.05)))]
        size = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        sizes.append(size)
        total += size + 40  # header, checksum, timestamp, padding
    return sizes


@dataclasses.dataclass
class Loaded:
    """What was written to one volume: fid, size and SHA-256 per needle."""

    vid: int
    fids: list[str]
    sizes: list[int]
    sums: list[str]


def load_volume(d: Daemon, collection: str, seed: int, sizes: list[int],
                extra: dict[int, bytes] | None = None,
                threads: int = 8) -> Loaded:
    """Grow exactly one volume in ``collection`` and fill it over HTTP.
    ``extra`` overrides the payload of given needle indexes (the CSV)."""
    r = http_json(
        "POST",
        f"http://{d.master}/vol/grow?collection={collection}&count=1"
        "&replication=000",
    )
    if r.get("error") or r.get("count") != 1:
        raise RuntimeError(f"vol/grow: {r}")
    fids: list[str] = []
    url = ""
    while len(fids) < len(sizes):
        a = operation.assign(
            d.master, count=min(4096, len(sizes) - len(fids)),
            collection=collection,
        )
        url = a.url
        fids += [a.fid] + [f"{a.fid}_{j}" for j in range(1, a.count)]
    vids = {int(f.split(",")[0]) for f in fids}
    if len(vids) != 1:
        raise RuntimeError(f"collection {collection} spread over {vids}")
    extra = extra or {}

    def put(i: int) -> str:
        data = extra.get(i) or needle_bytes(seed, i, sizes[i])
        operation.upload_data(url, fids[i], data, compress=False)
        return hashlib.sha256(data).hexdigest()

    with ThreadPoolExecutor(threads) as pool:
        sums = list(pool.map(put, range(len(sizes))))
    return Loaded(vids.pop(), fids, sizes, sums)


# -- references that never touch JAX ------------------------------------------------
def check_shards(base: str, dat_size: int, seed: int) -> dict:
    """The sealed shard set against plain references: 14 files of the
    planned size, each hashing to its ``.vif`` sum, every parity byte equal
    to ``CpuCodec`` of the data shards (one streamed pass over the whole
    volume), and ``NumpyCodec`` agreeing on seeded column windows in both
    block regimes. Data-shard bytes are vouched for by the needle reads."""
    want = encoder.ec_shard_base_size(dat_size, DATA_SHARDS)
    paths = [base + shard_ext(s) for s in range(TOTAL_SHARDS)]
    sizes = {os.path.getsize(p) for p in paths}
    if sizes != {want}:
        raise AssertionError(f"shard sizes {sizes}, planned {want}")
    sums = encoder.load_volume_info(base + ".vif").get("shard_sums")
    if not sums or len(sums) != TOTAL_SHARDS:
        raise AssertionError(f".vif carries no shard sums: {sums!r}")

    cpu = CpuCodec()
    files = [open(p, "rb") for p in paths]
    digests = [hashlib.sha256() for _ in paths]
    chunk = 8 * MiB
    m = TOTAL_SHARDS - DATA_SHARDS
    # a flat buffer, viewed (rows, n) per step: the native kernel wants
    # C-contiguous operands, and the last step is narrower
    data_buf = np.empty(DATA_SHARDS * chunk, dtype=np.uint8)
    try:
        with ThreadPoolExecutor(TOTAL_SHARDS) as pool:
            def pull(s: int) -> bytes:
                buf = files[s].read(chunk)
                digests[s].update(buf)
                return buf

            for pos in range(0, want, chunk):
                bufs = list(pool.map(pull, range(TOTAL_SHARDS)))
                n = len(bufs[0])
                data = data_buf[: DATA_SHARDS * n].reshape(DATA_SHARDS, n)
                for s in range(DATA_SHARDS):
                    data[s] = np.frombuffer(bufs[s], dtype=np.uint8)
                expect = cpu.encode(data)
                for j in range(m):
                    got = np.frombuffer(bufs[DATA_SHARDS + j], dtype=np.uint8)
                    if not np.array_equal(expect[j], got):
                        raise AssertionError(
                            f"parity shard {DATA_SHARDS + j} differs from "
                            f"CpuCodec in [{pos}, {pos + n})"
                        )
        got_sums = [dg.hexdigest() for dg in digests]
        if got_sums != sums:
            bad = [s for s in range(TOTAL_SHARDS) if got_sums[s] != sums[s]]
            raise AssertionError(f"shards {bad} do not hash to their .vif sums")

        # NumpyCodec windows: the large-block part of a shard is
        # [0, n_large·1 GiB), the small-block part is the rest
        npc = NumpyCodec()
        n_large = (dat_size - 1) // LARGE_ROW
        split = n_large * LARGE_BLOCK_SIZE
        rng = np.random.default_rng([seed, 0x5EED])
        window = 256 << 10
        regimes = {"small": (split, want)}
        if split:
            regimes["large"] = (0, split)
        windows = 0
        for lo, hi in regimes.values():
            for _ in range(6):
                off = int(rng.integers(lo, max(lo + 1, hi - window)))
                n = min(window, hi - off)
                rows = []
                for f in files:
                    f.seek(off)
                    rows.append(np.frombuffer(f.read(n), dtype=np.uint8))
                if not np.array_equal(
                    npc.encode(np.stack(rows[:DATA_SHARDS])),
                    np.stack(rows[DATA_SHARDS:]),
                ):
                    raise AssertionError(
                        f"parity differs from NumpyCodec at shard offset {off}"
                    )
                windows += 1
    finally:
        for f in files:
            f.close()
    return {
        "shard_bytes": want,
        "vif_sums": "match",
        "cpu_codec_bytes_checked": want * DATA_SHARDS,
        "cpu_codec_kernel": cpu.kernel,
        "numpy_codec_windows": windows,
        "regimes": sorted(regimes),
    }


class Layout:
    """Where each needle of a sealed volume lives, from its ``.ecx`` and
    the reference's interval math — so samples can be drawn per block
    regime and per shard."""

    def __init__(self, base: str, loaded: Loaded):
        shard_size = os.path.getsize(base + shard_ext(0))
        self.dat_size = DATA_SHARDS * shard_size  # what EcVolume uses
        self.large_end = ((self.dat_size - 1) // LARGE_ROW) * LARGE_ROW
        with open(base + ".ecx", "rb") as f:
            entries = {k: (off, size) for k, off, size in idx_mod.iter_index_file(f)}
        self.offset, self.shards = [], []
        for fid in loaded.fids:
            off, size = entries[parse_path(fid.split(",", 1)[1])[0]]
            ivs = locate_data(
                LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, self.dat_size, off,
                get_actual_size(size, 3),
            )
            self.offset.append(off)
            self.shards.append(
                {iv.to_shard_id_and_offset()[0] for iv in ivs}
            )

    def regime(self, i: int) -> str:
        return "large" if self.offset[i] < self.large_end else "small"

    def sample(self, seed: int, count: int, on_shards=None) -> list[int]:
        """``count`` needle indexes, half from each regime the volume has,
        optionally only needles with an interval on one of ``on_shards``."""
        rng = np.random.default_rng([seed, 0x5A3F, len(on_shards or ())])
        pools: dict[str, list[int]] = {}
        for i in range(len(self.offset)):
            if on_shards is None or self.shards[i] & set(on_shards):
                pools.setdefault(self.regime(i), []).append(i)
        picked: list[int] = []
        # smallest pool first, so a regime that cannot fill its half (the
        # small-block tail holds few needles) leaves its share to the other
        for n_left, pool in enumerate(
            sorted(pools.values(), key=len), start=-len(pools)
        ):
            take = min(len(pool), (count - len(picked)) // -n_left)
            picked += [pool[j] for j in rng.choice(len(pool), take, replace=False)]
        return picked


def read_back(d: Daemon, loaded: Loaded, indexes: list[int],
              threads: int = 8) -> dict:
    """GET needles and compare SHA-256 with what was written."""
    def get(i: int) -> int:
        status, data = http_bytes(
            "GET", f"http://{d.volume}/{loaded.fids[i]}", timeout=120.0
        )
        if status != 200:
            raise AssertionError(f"GET {loaded.fids[i]}: HTTP {status}")
        if hashlib.sha256(data).hexdigest() != loaded.sums[i]:
            raise AssertionError(
                f"needle {loaded.fids[i]} ({len(data)} bytes) differs from "
                "what was written"
            )
        return len(data)

    with ThreadPoolExecutor(threads) as pool:
        nbytes = sum(pool.map(get, indexes))
    return {"needles": len(indexes), "bytes": nbytes}


# -- the run ----------------------------------------------------------------------
class Smoke:
    def __init__(self, args):
        self.args = args
        self.full = args.size is None
        self.wanted = FULL_SIZE if self.full else args.size
        os.makedirs(args.data_dir, exist_ok=True)
        self.limits = machine_limits(args.data_dir)
        self.size, self.cut_why = fit_size(self.wanted, self.limits)
        # sampled reads: the full run's counts are ISSUE 21's, not options
        self.healthy_reads, self.degraded_reads = (
            (1000, 120) if self.full else (40, 16)
        )
        self.seed = args.seed
        self.root = os.path.join(args.data_dir, f"smoke-{args.seed}")
        self.data_dir = os.path.join(self.root, "vol")
        self.log_path = os.path.join(args.out_dir, "chip_smoke_daemon.log")
        self.report: dict = {
            "seed": self.seed,
            "requires_chip": self.full,
            "target_dat_bytes": self.size,
            "machine": self.limits,
            "reduced": self._reduced(),
            "wall_clock_note": "smoke wall-clock on a shared host; not a benchmark",
            "steps": {},
        }
        self.device: dict | None = None

    def _reduced(self) -> list[str]:
        cuts = [
            f"volume of {self.size / GiB:.2f} GiB where the reference seals "
            f"{REFERENCE_VOLUME // GiB} GiB (-volumeSizeLimitMB): a smoke's "
            "time limit and ~2.6x the volume in peak disk",
            "one node holds all 14 shards (no spread across servers)",
        ]
        if self.cut_why:
            cuts.append(
                f"cut further, from {self.wanted} to {self.size} bytes, by "
                f"this machine: {'; '.join(self.cut_why)}"
            )
        if self.size <= LARGE_ROW:
            cuts.append(
                "below one 10 GiB large-block row: only the small-block "
                "regime is walked"
            )
        return cuts

    @contextlib.contextmanager
    def step(self, name: str, d: Daemon | None = None):
        """One named step: pass/fail, smoke wall-clock, and the daemon's
        compile counts across it. A failure is recorded AND re-raised —
        no step fails quietly."""
        rec: dict = {"ok": False}
        self.report["steps"][name] = rec
        before = d.codec().get("compiles") if d else None
        t0 = time.monotonic()
        say(f"[{name}] ...")
        try:
            yield rec
            rec["ok"] = True
        finally:
            rec["smoke_wall_s"] = round(time.monotonic() - t0, 2)
            if d is not None and d.proc.poll() is None:
                after = d.codec().get("compiles")
                if after:
                    base = before or dict.fromkeys(after, 0)
                    rec["compiles"] = {k: after[k] - base[k] for k in after}
            say(f"[{name}] {'ok' if rec['ok'] else 'FAILED'} {json.dumps(rec)}")

    def daemon(self, backend: str, data_dir: str | None = None) -> Daemon:
        return Daemon(data_dir or self.data_dir, backend, self.log_path)

    def assert_device(self, d: Daemon, backend: str) -> dict:
        """The device, kernel and cache the daemon reports through /status.
        With the chip required, anything but a compiled Pallas kernel on a
        TPU — and any launch on the XLA path — fails the run."""
        c = d.codec()
        if backend and not c.get("resolved"):
            raise AssertionError(f"named backend not resolved at start: {c}")
        if c.get("resolved") and self.full:
            if c["platform"] != "tpu" or c["backend"] != backend:
                raise AssertionError(f"asked for {backend} on a TPU, got {c}")
            if c["kernel"] != "pallas" or c["launches"]["xla"]:
                raise AssertionError(
                    f"kernel {c['kernel']}, launches {c['launches']}: the "
                    "fused kernel compiled by Mosaic is the only path allowed"
                )
        if c.get("x64"):
            raise AssertionError("x64 is on process-wide in the daemon")
        return c

    def base(self, vid: int, collection: str = COLLECTION) -> str:
        return os.path.join(self.data_dir, f"{collection}_{vid}")

    def seal(self, d: Daemon, vid: int, collection: str = COLLECTION) -> int:
        dat_size = os.path.getsize(self.base(vid, collection) + ".dat")
        d.shell(f"ec.encode -volumeId={vid}", timeout=SHELL_TIMEOUT)
        return dat_size

    def ec_cycle(self, d: Daemon, tag: str, backend: str, loaded: Loaded,
                 dat_size: int) -> None:
        """verify shards → healthy reads → lose four shards → degraded
        reads → rebuild → verify, on a volume that was just sealed."""
        base = self.base(loaded.vid)
        with self.step(f"{tag}verify-shards") as rec:
            rec.update(check_shards(base, dat_size, self.seed))
        layout = Layout(base, loaded)
        with self.step(f"{tag}healthy-read", d) as rec:
            picked = layout.sample(self.seed, self.healthy_reads)
            rec.update(read_back(d, loaded, picked))
            rec["regimes"] = sorted({layout.regime(i) for i in picked})
            self.assert_device(d, backend)
        with self.step(f"{tag}degraded-read", d) as rec:
            before = d.codec()["launches"]  # resolved: this daemon sealed
            r = http_json(
                "POST",
                f"http://{d.volume}/admin/ec/delete_shards?volume={loaded.vid}"
                f"&shards={','.join(map(str, LOST_SHARDS))}",
            )
            if sorted(r.get("removed", [])) != list(LOST_SHARDS):
                raise AssertionError(f"delete_shards: {r}")
            lost_data = [s for s in LOST_SHARDS if s < DATA_SHARDS]
            picked = layout.sample(self.seed, self.degraded_reads, lost_data)
            if not picked:
                raise AssertionError("no needle lives on a lost data shard")
            rec.update(read_back(d, loaded, picked))
            rec["regimes"] = sorted({layout.regime(i) for i in picked})
            c = self.assert_device(d, backend)
            launched = sum(c["launches"].values()) - sum(before.values())
            rec["device_launches"] = launched
            if launched < len(picked):
                raise AssertionError(
                    f"{len(picked)} degraded reads but {launched} device "
                    "launches: some were not decoded by the codec"
                )
        with self.step(f"{tag}rebuild", d) as rec:
            self.wait_shard_count(d, loaded.vid, TOTAL_SHARDS - len(LOST_SHARDS))
            out = d.shell(f"ec.rebuild -volumeId={loaded.vid}", timeout=SHELL_TIMEOUT)
            rec["rebuilt"] = json.loads(out)["rebuilt"]
            if rec["rebuilt"] != list(LOST_SHARDS):
                raise AssertionError(f"ec.rebuild rebuilt {rec['rebuilt']}")
            sums = encoder.load_volume_info(base + ".vif")["shard_sums"]
            with ThreadPoolExecutor(len(LOST_SHARDS)) as pool:
                got = list(pool.map(
                    sha256_file, [base + shard_ext(s) for s in LOST_SHARDS]
                ))
            bad = [s for s, g in zip(LOST_SHARDS, got) if g != sums[s]]
            if bad:
                raise AssertionError(
                    f"rebuilt shards {bad} do not hash to their seal-time sums"
                )
            # mounted again: a needle from a rebuilt shard reads healthy
            read_back(d, loaded, picked[:8])
            self.assert_device(d, backend)

    def wait_shard_count(self, d: Daemon, vid: int, want: int) -> None:
        """The master learns of lost shards from a delta heartbeat."""
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r = http_json(
                "GET", f"http://{d.master}/dir/lookup_ec?volumeId={vid}"
            )
            if len(r.get("shard_id_locations", {})) == want:
                return
            time.sleep(0.2)
        raise AssertionError(f"master never saw {want} shards of {vid}: {r}")

    def small_volume(self, d: Daemon, collection: str) -> Loaded:
        """A second-size volume (a few 10 MiB small-block rows): its chunk
        plan, and so every kernel shape, differs from the big volume's.
        Needle 0 is the CSV the query reads."""
        sizes = plan_sizes(
            self.seed + 1, max(8 * MiB, min(48 * MiB, self.size // 2))
        )
        sizes[0] = len(self.csv_blob)
        return load_volume(
            d, collection, self.seed + 1, sizes, {0: self.csv_blob}
        )

    @functools.cached_property
    def csv_blob(self) -> bytes:
        rng = np.random.default_rng([self.seed, 0xC5F])
        regions = ("east", "west", "north", "south")
        rows = [
            f"{i},{regions[int(r)]},{s / 1000:.3f},r{i:07d}"
            for i, (r, s) in enumerate(zip(
                rng.integers(0, 4, 20000), rng.integers(0, 2_000_000, 20000)
            ))
        ]
        return ("id,region,score,name\n" + "\n".join(rows) + "\n").encode()

    def ask_query(self, d: Daemon, fid: str) -> dict:
        """One /_query over the CSV needle (numeric + string predicate):
        the answer equals query/engine.py's and the scan ran under JAX."""
        req = {
            "fid": fid, "input": "csv",
            "select": ["id", "name", "score"],
            "where": {"and": [
                {"field": "region", "op": "=", "value": "east"},
                {"field": "score", "op": ">", "value": 1900.25},
            ]},
        }
        got = http_json("POST", f"http://{d.volume}/_query", req, timeout=120.0)
        want = query_engine.run_query(
            self.csv_blob, input_format="csv", select=req["select"],
            where=req["where"],
        )
        if got.get("rows") != want or not want:
            raise AssertionError(
                f"/_query answered {got.get('count')} rows, "
                f"query/engine.py {len(want)}: {str(got)[:300]}"
            )
        _, metrics = http_bytes("GET", f"http://{d.volume}/metrics")
        scans = [
            ln for ln in metrics.decode().splitlines()
            if ln.startswith("sweed_query_scans_total{")
        ]
        if not any('backend="jax-' in ln for ln in scans):
            raise AssertionError(f"the scan did not run under JAX: {scans}")
        return {"rows": len(want), "scans": scans}

    def neighbour_query(self) -> None:
        """A second daemon on the same host, started with `-ec.backend cpu`
        while the first still holds the chip, answers the same /_query.
        The scan computes on the host CPU, but asking JAX for that device
        opens every backend unless the platform list is pinned first: a
        neighbour that was not given the chip must hold itself to `cpu`."""
        with self.step("neighbour-query") as rec, self.daemon(
            "cpu", os.path.join(self.root, "neighbour")
        ) as n:
            csv = load_volume(
                n, "neighbour", self.seed, [len(self.csv_blob)],
                {0: self.csv_blob},
            )
            rec.update(self.ask_query(n, csv.fids[0]))
            c = n.codec()
            rec.update(backend=c["backend"], jax_platforms=c["jax_platforms"])
            if c["backend"] != "cpu" or c["jax_platforms"] != "cpu":
                raise AssertionError(
                    f"the neighbour could open the chip for a query: {c}"
                )

    def query_then_seal(self, d: Daemon, backend: str) -> dict:
        """One /_query answered by the daemon, then a seal whose kernel
        shapes are new to the process. Process-wide x64 from the first
        query used to take the EC path down with it (ISSUE 21)."""
        with self.step("load-volume-2", d) as rec:
            second = self.small_volume(d, "smoke2")
            rec.update(vid=second.vid, needles=len(second.fids))
        with self.step("query", d) as rec:
            rec.update(self.ask_query(d, second.fids[0]))
        self.neighbour_query()
        with self.step("seal-after-query", d) as rec:
            dat_size = self.seal(d, second.vid, "smoke2")
            rec.update(check_shards(self.base(second.vid, "smoke2"), dat_size, self.seed))
            rec.update(read_back(d, second, list(range(min(16, len(second.fids))))))
            self.assert_device(d, backend)
        # on the chip the tile is 32 KiB, so this volume's chunk widths are
        # shapes the process has not compiled; the CPU's XLA path pads
        # everything this small to one 4 MiB tile, so the dry run cannot tell
        rec["shapes_new_to_process"] = bool(rec["compiles"]["requests"])
        if self.full and not rec["shapes_new_to_process"]:
            raise AssertionError(
                "the seal after the query compiled nothing: its kernel "
                "shapes were not new to the process, so it proves nothing"
            )
        return rec

    def run(self) -> None:
        backend = "tpu" if self.full else ""
        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.data_dir)
        os.makedirs(self.args.out_dir, exist_ok=True)
        with contextlib.suppress(FileNotFoundError):
            os.remove(self.log_path)
        for cut in self.report["reduced"]:
            say(f"reduced: {cut}")

        with self.step("native-build") as rec:
            # before anything loads them: build/ may hold libraries made on
            # another host (the loaders key on source + flags + this CPU)
            for so, src in (("_sweed_native.so", "sweed_native.cpp"),
                            ("_sweed_turbo.so", "turbo.cpp")):
                native.ensure_built(so, src)
            rec["cpu_codec_kernel"] = CpuCodec().kernel

        with self.daemon(backend) as d:
            with self.step("start-daemon", d) as rec:
                c = self.assert_device(d, backend)
                rec.update(ec_codec=c, daemon_start_wall_s=d.start_wall_s)
                if c.get("resolved"):
                    self.note_device(c)
            with self.step("load-volume", d) as rec:
                loaded = load_volume(
                    d, COLLECTION, self.seed, plan_sizes(self.seed, self.size)
                )
                rec.update(
                    vid=loaded.vid, needles=len(loaded.fids),
                    payload_bytes=sum(loaded.sizes),
                    dat_bytes=os.path.getsize(self.base(loaded.vid) + ".dat"),
                )
                if rec["dat_bytes"] < self.size:
                    raise AssertionError(
                        f".dat holds {rec['dat_bytes']} bytes, wanted {self.size}"
                    )
            with self.step("seal", d) as rec:
                dat_size = self.seal(d, loaded.vid)
                c = self.assert_device(d, backend)
                self.note_device(c)
                rec.update(dat_bytes=dat_size, launches=c.get("launches"))
            self.ec_cycle(d, "", backend, loaded, dat_size)
            first = self.query_then_seal(d, backend)
            with self.step("load-volume-3", d) as rec:
                third = self.small_volume(d, "smoke3")
                rec.update(vid=third.vid, needles=len(third.fids))
            self.report["ec_codec_final"] = d.codec()

        # a fresh process, same shapes as volume 2: what the first daemon
        # compiled, the persistent cache now answers
        with self.daemon(backend) as d:
            with self.step("seal-warm-cache", d) as rec:
                dat_size = self.seal(d, third.vid, "smoke3")
                rec.update(check_shards(self.base(third.vid, "smoke3"), dat_size, self.seed))
                c = self.assert_device(d, backend)
                rec.update(
                    compiles_total=c.get("compiles"),
                    compile_cache_dir=c.get("compile_cache_dir"),
                    daemon_start_wall_s=d.start_wall_s,
                )
            cold, warm = first["compiles"], rec["compiles_total"]
            rec["cold_seal_compiled"] = cold["compiled"]
            # (a cache that was warm before this run — the machine keeps
            # JAX_COMPILATION_CACHE_DIR between calls, or the smoke ran here
            # before — answers the first seal too: 0 and 0 is a pass)
            if (c.get("compile_cache_dir") and warm["compiled"]
                    and warm["compiled"] >= cold["compiled"]):
                raise AssertionError(
                    f"a cache at {c['compile_cache_dir']} but the second seal "
                    f"compiled {warm['compiled']} programs, the first "
                    f"{cold['compiled']}"
                )

        dev = self.device  # noted at the first seal
        if dev["platform"] == "tpu" and dev["count"] >= 4:
            self.mesh_phase(loaded)
        else:
            plural = "" if dev["count"] == 1 else "s"
            skipped = f"{dev['count']} device{plural} ({dev['platform']})"
            self.report["steps"]["mesh"] = {"ok": True, "skipped": skipped}
            say(f"[mesh] skipped: {skipped}")

    def note_device(self, c: dict) -> None:
        self.device = {
            "platform": c["platform"],
            "kind": c["device_kind"],
            "count": c["device_count"],
        }
        self.report["device"] = self.device
        self.report["versions"] = c.get("versions")

    def mesh_phase(self, loaded: Loaded) -> None:
        """Four chips: `-ec.backend mesh` repeats seal → degraded read →
        rebuild on the same volume (decoded back to a plain volume first),
        and every launch must leave pieces on four distinct devices, each
        of whose memory high-water marks moved."""
        with self.daemon("mesh") as d:
            with self.step("mesh-start", d) as rec:
                c = self.assert_device(d, "mesh")
                rec.update(ec_codec=c, daemon_start_wall_s=d.start_wall_s)
                peaks0 = {x["id"]: x["peak_bytes_in_use"] for x in c["devices"]}
                if len(peaks0) < 4:
                    raise AssertionError(f"mesh over {len(peaks0)} devices: {c}")
            with self.step("mesh-decode", d) as rec:
                out = d.shell(
                    f"ec.decode -volumeId={loaded.vid}", timeout=SHELL_TIMEOUT
                )
                rec["dat_bytes"] = json.loads(out)["dat_size"]
            with self.step("mesh-seal", d) as rec:
                dat_size = self.seal(d, loaded.vid)
                c = self.assert_device(d, "mesh")
                rec.update(
                    dat_bytes=dat_size, launches=c["launches"],
                    output_devices=c["last_output_devices"],
                )
            self.ec_cycle(d, "mesh-", "mesh", loaded, dat_size)
            with self.step("mesh-spread", d) as rec:
                c = d.codec()
                peaks1 = {x["id"]: x["peak_bytes_in_use"] for x in c["devices"]}
                rec.update(
                    mesh=c["mesh"], output_devices=c["last_output_devices"],
                    peak_bytes_moved={
                        str(i): peaks1[i] - peaks0[i] for i in sorted(peaks1)
                    },
                )
                if len(set(c["last_output_devices"])) < 4:
                    raise AssertionError(
                        f"the result sat on devices {c['last_output_devices']}"
                    )
                idle = [i for i in peaks1 if peaks1[i] <= peaks0[i]]
                if idle:
                    raise AssertionError(
                        f"peak_bytes_in_use never moved on devices {idle}"
                    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--size", type=parse_size, default=None,
                   help="dry run at this .dat size (e.g. 24m) with the "
                        "unnamed default backend; omit to require the chip "
                        "at the full size")
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--data-dir", default=os.path.join(REPO, "chip_smoke_data"),
                   help="where the volumes live while the smoke runs")
    p.add_argument("--out-dir", default=os.path.join(REPO, "chiprun_out"),
                   help="where chip_smoke.json and the daemon's log go "
                        "(the chip tool brings chiprun_out/ back)")
    args = p.parse_args(argv)

    smoke = Smoke(args)
    t0 = time.monotonic()
    ok = False
    try:
        smoke.run()
        ok = all(s.get("ok") for s in smoke.report["steps"].values())
    finally:
        smoke.report["ok"] = ok
        smoke.report["smoke_wall_s"] = round(time.monotonic() - t0, 1)
        os.makedirs(args.out_dir, exist_ok=True)
        with open(os.path.join(args.out_dir, "chip_smoke.json"), "w") as f:
            json.dump(smoke.report, f, indent=1)
        shutil.rmtree(smoke.root, ignore_errors=True)
    if not ok or smoke.device is None:
        say("chip_smoke: FAILED")
        return 1
    say(json.dumps(smoke.report, indent=1))
    say(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
