"""Traffic kind ``open-loop-get-2codes``: ``open-loop-get``'s independent
readers (its arrivals, its window, its latencies) on a node that holds
volumes of TWO codes, each degraded: one sealed before the operator changed
code, one sealed since.

Set-up is the migration itself. The node starts as it was
(``daemon_before``), both volumes are loaded and the first is sealed; the
node stops and starts again as it serves now (``daemon``, another
``-ec.geometry``) on the same directory, mounts what it finds and seals the
second volume. Then the mix's lost shards go from BOTH and one GET warms
every (volume, read width, padded width) the request list will decode at.
Each request's volume is drawn evenly by the seed; inside a volume the
needles are ``open-loop-get``'s stratified sample.

``harness.Run`` holds one volume. Each volume here is a shallow copy of
the run that holds that volume's own collection, seed, code and files and
shares the rest (the daemon, the directories, the one ``Check``).

Every comparison is made against ``benchmark/reference_mixed.py``: both
seals' shard sums, every GET's bytes, and — which holds the mechanism — the
window's recoveries were planned at the reference's widths and both codes
launched on the device.
"""

from __future__ import annotations

import copy
import hashlib
import http.client
import json
import os
import time

import numpy as np

from .. import fixture, reference_mixed, stages, stats
from ..daemon import Daemon
from ..harness import Run, say
from . import open_loop_get as olg

PLANS = "recoveries_planned_at_another_width_than_the_reference"
LAUNCHES = "codes_with_no_launch_in_the_window"
CODES = "volumes_served_at_another_code_than_they_were_sealed_with"


# -- set-up: the migration -------------------------------------------------------
def volumes_of(run: Run) -> list[Run]:
    """One copy of the run a volume of the configuration, in its order."""
    out = []
    for i, spec in enumerate(run.cfg["volumes"]):
        v = copy.copy(run)
        v.name = spec["collection"]
        v.sealed_by = spec["sealed_by"]
        v.volume = dict(run.volume, collection=spec["collection"])
        # bytes and order of its own: no seed the driver draws lies so high
        v.seed = run.seed + (i << 40)
        v.ec = run.cfg[spec["ec"]]
        v.k = v.ec["data_shards"]
        v.total = reference_mixed.total_shards(v.ec)
        v.code = reference_mixed.name(v.ec)
        out.append(v)
    return out


def start_before(run: Run) -> None:
    """The node as it sealed the old volumes: ``Run.start_daemon`` with the
    configuration's ``daemon_before``. Never traced: the window is not
    its."""
    d = Daemon(
        run.data_dir, os.path.join(run.out_dir, "daemon.log"),
        run.cfg["daemon_before"], control=run.args.control,
        rehearsal=run.rehearsal,
    )
    say(f"[daemon] {' '.join(d.command())}")
    run.daemon = d.__enter__()
    say(f"[daemon] serving after {d.start_wall_s:.2f} s")


def seal(run: Run, v: Run) -> None:
    from seaweedfs_tpu.shell import commands

    t = time.monotonic()
    commands.ec_encode(run.env, v.loaded.vid, delete_original=True)
    v.sealed = {"vif_sums": v.vif_sums(),
                "size_faults": v.shard_size_faults(range(v.total))}
    say(f"[seal] {v.name}: {v.total} shards at {v.code} in "
        f"{time.monotonic() - t:.2f} s")


def wait_plain_volume(run: Run, vid: int, timeout: float = 30.0) -> None:
    """The restarted node has told its master of the plain volume."""
    deadline = time.monotonic() + timeout
    while not run.env.volume_locations(vid):
        if time.monotonic() > deadline:
            raise RuntimeError(f"volume {vid} never mounted after the restart")
        time.sleep(0.02)


def prepare(run: Run) -> dict:
    """Everything up to two sealed, degraded, mounted volumes under the
    daemon of the window."""
    run.require_room()
    vols = volumes_of(run)
    start_before(run)
    run.require_device()
    for v in vols:
        # both under the first master: one that starts anew numbers its
        # first volume 1 again
        v.daemon = run.daemon
        v.load()
    for v in vols:
        if v.sealed_by == "daemon_before":
            seal(run, v)
    run.stop_daemon()
    d = run.start_daemon()
    run.require_device()
    lost = list(run.mix["lost_shards"])
    for v in vols:
        v.daemon = d
        if v.sealed_by == "daemon_before":
            v.wait_shard_count(v.total)
        else:
            wait_plain_volume(run, v.loaded.vid)
            seal(run, v)
    for v in vols:
        v.delete_shards(lost)
        v.wait_shard_count(v.total - len(lost))
        v.layout = fixture.Layout(v.base, v.loaded, v.ec)
        # by lost data shard, what the reference plans for its recovery:
        # (shards read, 1 where the shard's own local group sufficed)
        v.plan = {
            s: (len(reference_mixed.read_set(v.ec, s, lost)),
                int(reference_mixed.is_local(v.ec, s, lost)))
            for s in lost if s < v.k
        }
    # one list of needles for the window: a volume's follow the one before
    run.loaded = fixture.Loaded(
        0, *(sum((getattr(v.loaded, f) for v in vols), [])
             for f in ("fids", "sizes", "sums")),
    )
    first, at = 0, []
    for v in vols:
        at.append(first)
        first += len(v.loaded.fids)
    codec = d.codec()
    return {
        "volumes": vols, "first": at,
        "align": codec.get("pallas_tile", 1) * (
            codec["device_count"] if codec.get("mesh") else 1),
    }


# -- the request list ---------------------------------------------------------------
def request_list(state: dict, n: int, seed: int) -> list[int]:
    """``n`` indexes into the window's one list of needles: each request's
    volume drawn evenly by the seed, and inside a volume
    ``open-loop-get``'s stratified sample of as many needles as fell to
    it."""
    vols = state["volumes"]
    which = np.random.default_rng([seed, 0x2C0D]).integers(0, len(vols), n)
    picked = np.empty(n, dtype=np.int64)
    for j, (v, first) in enumerate(zip(vols, state["first"])):
        slots = np.flatnonzero(which == j)
        own = olg.request_list(v.loaded, len(slots), seed + j)
        picked[slots] = np.asarray(own, dtype=np.int64) + first
    return [int(i) for i in picked]


def located(state: dict, i: int) -> tuple[Run, int]:
    """(the volume, the needle's index inside it) of a window index."""
    for v, first in zip(reversed(state["volumes"]), reversed(state["first"])):
        if i >= first:
            return v, i - first
    raise IndexError(i)


def recoveries(state: dict, i: int) -> list[tuple[Run, int, int]]:
    """(volume, lost shard, bytes) of every interval of needle ``i`` that
    sits on a lost data shard: each is one recovery, one device launch."""
    v, own = located(state, i)
    return [(v, s, n) for s, n in v.layout.intervals(own) if s in v.plan]


def warm(run: Run, state: dict, picked: list[int]) -> tuple[int, int]:
    """One GET for every distinct (volume, shards its decode reads, padded
    width) among the request list's recoveries, so that the window launches
    only programs that exist. Returns (shapes covered, GETs that failed or
    differed)."""
    align = state["align"]
    seen: set[tuple] = set()
    conn = http.client.HTTPConnection(run.daemon.volume, timeout=120)
    gets = bad = 0
    for i in picked:
        shapes = {
            (v.name, v.plan[s][0], -(-w // align))
            for v, s, w in recoveries(state, i)
        }
        if shapes - seen:
            seen |= shapes
            conn.request("GET", "/" + run.loaded.fids[i])
            r = conn.getresponse()
            body = r.read()
            if r.status != 200 or (
                hashlib.sha256(body).hexdigest() != run.loaded.sums[i]
            ):
                say(f"[warm] GET {run.loaded.fids[i]}: HTTP {r.status}, "
                    f"{len(body)} bytes: not what was written")
                bad += 1
            gets += 1
    conn.close()
    by_read = sorted({(name, read) for name, read, _ in seen})
    say(f"[warm] {gets} GETs cover {len(seen)} shapes: (volume, shards read) "
        f"{by_read}, each at its padded widths")
    return len(seen), bad


# -- after the window ---------------------------------------------------------------
def planned(state: dict, log: list[dict]) -> dict:
    """What the reference plans for the window's recoveries, by code and in
    all: their number, the shards they read and how many of them the
    wanted shard's own local group sufficed for."""
    out = {"": {"n": 0, "width": 0, "local": 0}}
    for v in state["volumes"]:
        out["@" + v.code] = {"n": 0, "width": 0, "local": 0}
    for r in log:
        for v, s, _ in recoveries(state, r["needle"]):
            width, local = v.plan[s]
            for row in (out[""], out["@" + v.code]):
                row["n"] += 1
                row["width"] += width
                row["local"] += local
    return out


def plan_faults(status: dict, want: dict) -> int:
    """The window's recoveries that were not planned as the reference plans
    them, by the program's own stage table (``ec_codec.stages`` of
    ``/status``, the window's two snapshots): ``ec.recover.plan`` holds one
    record a recovery, with ``width`` (shards its decode reads) and
    ``local``. The table holds sums, so a row that differs counts every
    recovery the reference gives that row; a program that serves no such
    stage has all of them counted. The rows by code
    (``ec.recover.plan@10+4``) are compared where the program serves them:
    the sums over both codes could hide two that cancel."""
    ctx = {"status": status}
    faults = 0
    for suffix, row in want.items():
        got = {f: stages.delta(ctx, "ec.recover.plan" + suffix, f) for f in row}
        served = suffix == "" or got["n"] is not None
        say(f"[plans] ec.recover.plan{suffix}: {got}, the reference {row}"
            + ("" if served else " (not served: not compared)"))
        if served and row["n"] and got != row:
            faults = max(faults, row["n"])
    return faults


def launch_faults(before: dict, after: dict, vols: list[Run]) -> int:
    """Codes of the node's volumes that launched nothing in the window, by
    ``ec_codec.geometries`` of ``/status``."""
    was, now = before.get("geometries") or {}, after.get("geometries") or {}
    grown = {v.code: now.get(v.code, 0) - was.get(v.code, 0) for v in vols}
    say(f"[launches] by code in the window: {grown}")
    return sum(1 for n in grown.values() if n <= 0)


def code_faults(run: Run, vols: list[Run]) -> int:
    """Volumes whose ``.vif`` or whose entry in the serving node's
    ``/status`` names another code than the configuration seals them with:
    the node of the window seals at its own code and reads each volume at
    the volume's."""
    served = {e["collection"]: e["geometry"] for e in run.daemon.status()["ec"]}
    faults = 0
    for v in vols:
        with open(v.base + ".vif") as f:
            vif = json.load(f)
        recorded = reference_mixed.name({
            key: vif.get(key, 0) for key in
            ("data_shards", "parity_shards", "local_parity_shards")})
        say(f"[codes] {v.name}: sealed at {v.code}, its .vif says {recorded}, "
            f"served at {served.get(v.name)}")
        faults += int(not recorded == served.get(v.name) == v.code)
    return faults


def beside(state: dict, status: dict, log: list[dict]) -> dict:
    """What stands beside the metrics on the run's line: by volume the
    share of GETs that recovered and their median, by code the mean shards
    a recovery read."""
    out: dict = {}
    ctx = {"status": status}
    for v in state["volumes"]:
        mine = [r for r in log if located(state, r["needle"])[0] is v]
        waits = [r["latency_s"] * 1e3 for r in mine if r["recoveries"]]
        out[f"{v.name}.gets"] = len(mine)
        out[f"{v.name}.recovering_share"] = len(waits) / max(1, len(mine))
        out[f"{v.name}.recovering_get_p50_ms"] = (
            stats.median(waits) if len(waits) >= 20 else None)
        out[f"{v.name}.plan_width_mean"] = stages.ratio(
            ctx, ("ec.recover.plan@" + v.code, "width"),
            ("ec.recover.plan@" + v.code, "n"))
    return out


def run_cell(run: Run) -> dict:
    mix = run.mix
    seconds = run.args.seconds
    state = prepare(run)
    d, vols = run.daemon, state["volumes"]
    n = max(1, round(mix["rate_get_per_s"] * seconds))
    picked = request_list(state, n, run.seed)
    due = olg.arrivals(n, seconds, run.seed)
    shapes, warm_failed = warm(run, state, picked)
    run.require_device()
    before = d.codec()
    setup_s = run.setup_seconds()
    say(f"[setup] {setup_s:.3f} s")

    # a traced run traces the whole window, as open-loop-get does
    if run.trace:
        d.profiler("start")
    window_t0 = time.monotonic()
    log = olg.window(run, picked, due, mix["client_threads"], mix["timeout_s"])
    window_s = time.monotonic() - window_t0
    after = d.codec()
    if run.trace:
        d.profiler("stop")
    wrong_code = code_faults(run, vols)
    run.stop_daemon()

    failed = sum(not r["ok"] for r in log)
    for r in log:
        r["recoveries"] = len(recoveries(state, r["needle"]))
    status = {"before": before, "after": after}
    differing = 0
    for v in vols:
        t = time.monotonic()
        ref = reference_mixed.shard_sums(
            v.kept_dat, v.ec, threads=min(12, os.cpu_count() or 4))
        run.reference_s += time.monotonic() - t
        say(f"[reference] {v.name}: {v.total} shard sums of "
            f"{ref['shard_bytes']} bytes at {v.code} (not set-up)")
        differing += int(v.sealed["vif_sums"] != ref["sums"])
    check = run.check
    check.count("seals_whose_vif_sums_differ_from_reference", differing)
    check.count("shard_files_of_unplanned_size",
                sum(v.sealed["size_faults"] for v in vols))
    check.count("needles_failed_or_differing_from_what_was_written",
                failed + warm_failed)
    check.count(PLANS, plan_faults(status, planned(state, log)))
    check.count(LAUNCHES, launch_faults(before, after, vols))
    check.count(CODES, wrong_code)
    run.status_check(before, after)

    end_to_end, summary = olg.latencies(run, log, seconds)
    if summary:  # a rehearsal prints no reading
        extra = beside(state, status, log)
        say("[volumes] " + "; ".join(
            f"{name} {value:.6g}" for name, value in extra.items()
            if value is not None))
        summary.update(extra)
    return {
        "attempted": n,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": end_to_end,
        "summary": summary,
        "counts": {"gets": n, "shapes_warmed": shapes},
        "readings": {"gets": log},
        "status": status,
        "client": {"gets": log, "seconds": seconds,
                   "lost_shards": list(mix["lost_shards"])},
    }
