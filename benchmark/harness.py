"""What every traffic generator shares: the run's directories, the daemon
and its device, the loaded volume, the operator's commands, the waits on
the master, and the comparison that decides ``correct``."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time
import urllib.error
from concurrent.futures import ThreadPoolExecutor

from . import fixture, reference
from .daemon import ROOT, Daemon, get_json, post_json

MiB = 1 << 20


def say(msg: str) -> None:
    print(msg, flush=True)


def sha256_file(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(8 * MiB), b""):
            digest.update(chunk)
    return digest.hexdigest()


class Check:
    """The numbers compared, each beside its limit; ``correct`` is all of
    them inside. Every comparison of this benchmark is exact (limit 0): a
    count of things that differ from the plain reference or from what the
    configuration guarantees."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    def count(self, name: str, differing: int, limit: int = 0) -> None:
        self.rows.append((name, differing, limit))

    @property
    def correct(self) -> bool:
        return all(value <= limit for _, value, limit in self.rows)

    def report(self) -> dict:
        """Each number compared beside its limit: as the run's last lines
        on standard error, and returned for the result's line."""
        for name, value, limit in self.rows:
            verdict = "ok" if value <= limit else "FAILED"
            print(f"[compare] {name}: {value} (limit {limit}) {verdict}",
                  file=sys.stderr, flush=True)
        return {name: {"value": value, "limit": limit}
                for name, value, limit in self.rows}


class Run:
    def __init__(self, args, t0: float, cell: dict, cfg: dict, mix: dict):
        self.args, self.t0 = args, t0
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.seed = args.seed
        self.rehearsal = args.rehearsal
        self.trace = bool(args.trace)
        self.ec = cfg["ec"]
        self.k = self.ec["data_shards"]
        self.total = self.k + self.ec["parity_shards"]
        volume = dict(cfg["volume"])
        if self.rehearsal:
            volume.update(cfg["rehearsal"]["volume"])
        self.volume = volume
        tag = f"{cell['name']}-s{args.seed}-t{args.trace}"
        self.out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", tag)
        # one stated medium, the same for every machine: the volume lives
        # on the checkout's own disk, where a seal's fsync ends
        if cfg["data_medium"] != "disk":
            raise SystemExit(
                f"data_medium {cfg['data_medium']!r}: the harness keeps a "
                "run's volume on the checkout's disk and knows no other medium"
            )
        self.data_dir = os.path.join(ROOT, ".bench_data", tag)
        self.trace_dir = os.path.join(self.out_dir, "trace")
        for d in (self.out_dir, self.data_dir):
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d)
        self.check = Check()
        self.daemon: Daemon | None = None
        self.loaded: fixture.Loaded | None = None
        self.codec_at_start: dict = {}
        self.reference_s = 0.0

    # -- set-up ---------------------------------------------------------------
    def require_room(self) -> None:
        limits = fixture.machine_limits(self.data_dir)
        m = self.cfg["machine"]
        fixture.require_room(
            limits, self.volume["dat_target_bytes"], m["file_margin_bytes"],
            m["peak_disk_factor"], m["disk_slack_bytes"],
        )
        say(f"[machine] {json.dumps(limits)}")

    def start_daemon(self) -> Daemon:
        d = Daemon(
            self.data_dir, os.path.join(self.out_dir, "daemon.log"),
            self.cfg["daemon"], trace=self.trace, control=self.args.control,
            rehearsal=self.rehearsal, trace_dir=self.trace_dir,
        )
        say(f"[daemon] {' '.join(d.command())}")
        self.daemon = d.__enter__()
        say(f"[daemon] serving after {d.start_wall_s:.2f} s")
        return d

    def stop_daemon(self) -> None:
        if self.daemon is not None:
            self.daemon.__exit__(None, None, None)
            self.daemon = None

    def require_device(self) -> dict:
        """The device through /status. Outside a rehearsal anything but the
        configured backend on TPU devices, as many as the cell asks for,
        ends the run with no result."""
        c = self.daemon.codec()
        if self.rehearsal:
            return c
        want = self.cfg["daemon"]["ec_backend"]
        if not c.get("resolved") or c["platform"] != "tpu" or c["backend"] != want:
            raise SystemExit(f"asked for backend {want} on a TPU, got {c}")
        if c["device_count"] < self.cell["chips"]:
            raise SystemExit(
                f"the cell needs {self.cell['chips']} chips, JAX offers "
                f"{c['device_count']}"
            )
        return c

    def load(self) -> fixture.Loaded:
        v = self.volume
        plan = fixture.plan_sizes(
            v["size_plan_seed"], v["dat_target_bytes"], self.cfg["blob_mix"]
        )
        sizes = fixture.shuffled(self.seed, plan)
        t = time.monotonic()
        self.loaded = fixture.load_volume(
            self.daemon.master, v["collection"], v["replication"], self.seed,
            sizes,
        )
        self.base = os.path.join(
            self.data_dir, f"{v['collection']}_{self.loaded.vid}"
        )
        self.dat_bytes = os.path.getsize(self.base + ".dat")
        # the plain volume's file outlives ec.encode's delete of it: the
        # plain reference reads it once the daemon has gone
        self.kept_dat = self.base + ".reference-dat"
        os.link(self.base + ".dat", self.kept_dat)
        say(f"[load] {len(sizes)} needles, .dat {self.dat_bytes} bytes, "
            f"{time.monotonic() - t:.2f} s")
        return self.loaded

    @property
    def env(self):
        from seaweedfs_tpu.shell.commands import CommandEnv

        return CommandEnv(master=self.daemon.master)

    def setup_seconds(self) -> float:
        return time.monotonic() - self.t0

    # -- the operator's steps and the master's view ----------------------------
    def shard_path(self, sid: int) -> str:
        return f"{self.base}.ec{sid:02d}"

    def vif_sums(self) -> list[str]:
        with open(self.base + ".vif") as f:
            return json.load(f).get("shard_sums") or []

    def shard_size_faults(self, sids) -> int:
        want = reference.shard_size(
            self.dat_bytes, self.k, self.ec["large_block_bytes"],
            self.ec["small_block_bytes"],
        )
        return sum(
            1 for s in sids
            if not os.path.exists(self.shard_path(s))
            or os.path.getsize(self.shard_path(s)) != want
        )

    def delete_shards(self, sids) -> None:
        vid = self.loaded.vid
        r = post_json(
            f"http://{self.daemon.volume}/admin/ec/delete_shards?volume={vid}"
            f"&shards={','.join(map(str, sids))}"
        )
        if sorted(r.get("removed", [])) != sorted(sids):
            raise RuntimeError(f"delete_shards {sids}: {r}")

    def unmount(self) -> None:
        post_json(
            f"http://{self.daemon.volume}/admin/ec/unmount"
            f"?volume={self.loaded.vid}"
        )

    def wait_shard_count(self, want: int, timeout: float = 30.0) -> None:
        """The master learns of shards from delta heartbeats."""
        deadline = time.monotonic() + timeout
        r = {}
        while time.monotonic() < deadline:
            try:
                r = get_json(
                    f"http://{self.daemon.master}/dir/lookup_ec"
                    f"?volumeId={self.loaded.vid}"
                )
            except urllib.error.HTTPError as e:
                if e.code != 404:  # 404: the master knows no shard of it
                    raise
                r = {}
            if len(r.get("shard_id_locations") or {}) == want:
                return
            time.sleep(0.02)
        raise RuntimeError(f"master never saw {want} shards: {r}")

    def hash_shards(self, sids) -> dict[int, str]:
        sids = list(sids)
        with ThreadPoolExecutor(len(sids)) as pool:
            return dict(zip(sids, pool.map(
                lambda s: sha256_file(self.shard_path(s)), sids
            )))

    # -- after the window --------------------------------------------------------
    def reference_sums(self) -> dict:
        """The plain reference's shard sums, computed once the daemon has
        gone: its time is outside set-up and outside the window."""
        t = time.monotonic()
        ref = reference.shard_sums(
            self.kept_dat, self.ec, threads=min(12, os.cpu_count() or 4)
        )
        self.reference_s = time.monotonic() - t
        say(f"[reference] {self.total} shard sums of {ref['shard_bytes']} "
            f"bytes in {self.reference_s:.2f} s (not set-up)")
        return ref

    def status_check(self, before: dict, after: dict) -> None:
        """Device, kernel and compiles as /status reports them."""
        launched = {
            k: after["launches"][k] - before["launches"].get(k, 0)
            for k in after["launches"]
        }
        self.check.count("xla_path_launches", after["launches"].get("xla", 0))
        self.check.count(
            "compile_requests_in_window",
            after["compiles"]["requests"] - before["compiles"]["requests"],
        )
        kernel = "pallas-interpret" if self.rehearsal else "pallas"
        self.check.count("kernel_is_not_" + kernel, int(after["kernel"] != kernel))
        platform = "cpu" if self.rehearsal else "tpu"
        self.check.count("platform_is_not_" + platform,
                         int(after["platform"] != platform))
        self.check.count("x64_is_on", int(bool(after.get("x64"))))
        if sum(launched.values()) <= 0:
            self.check.count("window_without_device_launch", 1)

    def device_block(self, codec: dict) -> dict:
        peaks = [d.get("peak_bytes_in_use") or 0 for d in codec.get("devices", [])]
        return {
            "platform": codec["platform"],
            "kind": codec["device_kind"],
            "count": codec["device_count"],
            "memory_peak_bytes": max(peaks) if peaks else 0,
        }

    def cleanup(self) -> None:
        self.stop_daemon()
        shutil.rmtree(self.data_dir, ignore_errors=True)
