"""Traffic kind ``maintain-cycle``: whole cycles of seal + rebuild on one
sealed-size volume, on a schedule: the mix's ``cycles`` a window, one every
``seconds / cycles`` (16 and 3.19 s at ``run_seconds`` 51).

One cycle: ``ec.encode`` keeping the plain volume (timed) -> the mix's lost
shards deleted -> ``ec.rebuild`` (timed) -> rebuilt shards hashed -> all
shards, ``.ecx`` and ``.vif`` dropped -> ``os.sync()``, so that one cycle's
write-back does not land in the next cycle's clock. Cycle ``i`` begins at
``i * seconds / cycles`` from the window's start: the generator sleeps until
it is due, outside both clocks (``paused_s``), so a window makes the same
number of seals and rebuilds, and writes the same bytes to the machine's
disk, however fast the program is. A cycle that ends after the next was due
starts the next at once and is counted (``client.late_cycles``); the window
ends after ``cycles`` cycles or with its seconds, whichever is first, and a
cycle that has begun when the seconds run out is finished and counted: a
stall long enough costs the window its last cycle or cycles. A rehearsal
(tiny window, CPU) has every cycle late and so runs its cycles back to back
until the seconds are over, as every window did until PR 47. The run's rates
are taken over ALL of the window's seals and ALL of its rebuilds: bytes sealed
over the seconds spent sealing, bytes rebuilt over the seconds spent
rebuilding, so a stall in any operation shows. ``seal_rate`` is an end-to-end
metric in the cells ``BENCHMARK.json`` lists under it; in the others the same
number is the per-layer ``client.seal_rate`` (PERF.md section 2), and it is
on every run's ``[readings]`` line and in its ``readings`` under that name.
Beside them, on every run's ``[readings]`` line
and as per-layer metrics, stand the medians of the per-operation readings
(``client.seal_rate_p50``, ``client.rebuild_rate_p50``), the operations that
took more than twice the median of their kind (``client.stalled_ops``: what
tells a stalled run from a slow one) and the share of the window outside both
clocks and outside the pause (``client.untimed_share``).
"""

from __future__ import annotations

import os
import time

from .. import stats
from ..harness import Run, say

MB = 1e6
STALLED = 2.0  # an operation is stalled beyond this many medians of its kind


def cycle(run: Run, lost: list[int], log: list[dict]) -> None:
    from seaweedfs_tpu.shell import commands

    vid, total = run.loaded.vid, run.total
    rec: dict = {"ok": False}
    log.append(rec)
    t = time.monotonic()
    commands.ec_encode(run.env, vid, delete_original=False)
    rec["seal_s"] = time.monotonic() - t
    rec["vif_sums"] = run.vif_sums()
    rec["size_faults"] = run.shard_size_faults(range(total))
    run.delete_shards(lost)
    run.wait_shard_count(total - len(lost))
    t = time.monotonic()
    out = commands.ec_rebuild(run.env, vid)
    rec["rebuild_s"] = time.monotonic() - t
    rec["rebuilt"] = out["rebuilt"]
    rec["rebuilt_sums"] = run.hash_shards(lost)
    rec["size_faults"] += run.shard_size_faults(range(total))
    # back to a plain, sealed-size volume
    run.unmount()
    run.delete_shards(range(total))
    os.remove(run.base + ".vif")
    run.wait_shard_count(0)
    os.sync()
    rec["ok"] = True


def rates(dat_bytes: int, seal_s: list[float], rebuild_s: list[float]) -> dict:
    """MB/s over all of a window's seals and over all of its rebuilds: the
    bytes moved over the seconds the operations took together."""
    if not seal_s:
        return {"seal_rate": None, "rebuild_rate": None}
    return {"seal_rate": dat_bytes * len(seal_s) / MB / sum(seal_s),
            "rebuild_rate": dat_bytes * len(rebuild_s) / MB / sum(rebuild_s)}


def median_rate(dat_bytes: int, seconds: list[float]):
    """MB/s of a window's median operation of one kind: the steadier
    statistic beside a rate, which carries every stall. None for a window
    without one."""
    if not seconds:
        return None
    return stats.median(dat_bytes / s / MB for s in seconds)


def stalled_ops(seal_s: list[float], rebuild_s: list[float]):
    """The window's seals and rebuilds that took more than twice the median
    of their kind. None for a window without an operation."""
    if not seal_s and not rebuild_s:
        return None
    return sum(
        sum(s > STALLED * stats.median(kind) for s in kind)
        for kind in (seal_s, rebuild_s) if kind
    )


def beside(dat_bytes: int, seal_s: list[float], rebuild_s: list[float]) -> dict:
    """What stands beside the rates on every run's line, under the names of
    the per-layer metrics that read the same."""
    return {
        "client.seal_rate_p50": median_rate(dat_bytes, seal_s),
        "client.rebuild_rate_p50": median_rate(dat_bytes, rebuild_s),
        "client.stalled_ops": stalled_ops(seal_s, rebuild_s),
    }


def due_s(i: int, seconds: float, cycles: int) -> float:
    """When cycle ``i`` of a scheduled window is due, from its start."""
    return i * seconds / cycles


def window(seconds: float, cycles: int, one_cycle,
           clock=time.monotonic, sleep=time.sleep) -> dict:
    """One window's cycles at their pace. ``one_cycle(i)`` makes cycle ``i``
    and says whether the window goes on. Cycle ``i`` waits until
    ``due_s(i, seconds, cycles)`` from the window's start (the seconds slept:
    ``paused_s``); one whose turn comes after it was due begins at once and
    is counted (``late_cycles``), and the window makes at most ``cycles``.
    No cycle begins once the seconds are over, and one that has begun is
    finished."""
    paused_s, late, begun, goes_on = 0.0, 0, 0, True
    t0 = clock()
    while goes_on and clock() - t0 < seconds and begun < cycles:
        wait = due_s(begun, seconds, cycles) - (clock() - t0)
        if wait > 0:
            t = clock()
            sleep(wait)
            paused_s += clock() - t
        elif begun:  # the first is due as the window opens
            late += 1
        goes_on = one_cycle(begun)
        begun += 1
    return {"window_s": clock() - t0, "paused_s": paused_s,
            "late_cycles": late, "begun": begun}


def run_cell(run: Run) -> dict:
    mix = run.mix
    lost = list(mix["lost_shards"])
    run.require_room()
    d = run.start_daemon()
    run.require_device()
    run.load()
    warm: list[dict] = []
    for _ in range(mix["warm_cycles"]):
        cycle(run, lost, warm)
    run.require_device()
    before = d.codec()
    setup_s = run.setup_seconds()
    say(f"[setup] {setup_s:.3f} s")

    log: list[dict] = []
    failed = 0
    traced = False

    def one_cycle(_i: int) -> bool:
        nonlocal failed, traced
        tracing = run.trace and not traced
        if tracing:  # after the pause: the traced window is the cycle alone
            d.profiler("start")
        try:
            cycle(run, lost, log)
        except Exception as e:  # counted, reported, and the run is not correct
            say(f"[cycle {len(log)}] FAILED: {e!r}\n{d.log_tail(12)}")
            failed += 1
            return False
        finally:
            if tracing:
                d.profiler("stop")
                traced = True
        r = log[-1]
        say(f"[cycle {len(log)}] seal {r['seal_s']:.4f} s, "
            f"rebuild {r['rebuild_s']:.4f} s")
        return True

    paced = window(run.args.seconds, mix["cycles"], one_cycle)
    window_s, paused_s = paced["window_s"], paced["paused_s"]
    late_cycles = paced["late_cycles"]
    after = d.codec()
    run.stop_daemon()

    done = [r for r in log if r["ok"]]
    ref = run.reference_sums()
    check = run.check
    every = warm + done
    check.count("seals_whose_vif_sums_differ_from_reference",
                sum(r["vif_sums"] != ref["sums"] for r in every))
    check.count("rebuilt_shards_differing_from_reference", sum(
        r["rebuilt_sums"][s] != ref["sums"][s] for r in every for s in lost
    ))
    check.count("rebuilds_of_other_shards_than_lost",
                sum(sorted(r["rebuilt"]) != sorted(lost) for r in every))
    check.count("shard_files_of_unplanned_size",
                sum(r["size_faults"] for r in every))
    check.count("failed_operations", failed)
    check.count("window_without_a_whole_cycle", int(not done))
    run.status_check(before, after)

    seal_s = [r["seal_s"] for r in done]
    rebuild_s = [r["rebuild_s"] for r in done]
    end_to_end = rates(run.dat_bytes, seal_s, rebuild_s)
    summary = {"client.seal_rate": end_to_end["seal_rate"],
               **beside(run.dat_bytes, seal_s, rebuild_s),
               "client.late_cycles": late_cycles, "paused_s": paused_s}
    if done and not run.rehearsal:  # a rehearsal prints no rate
        say(f"[readings] seal_rate {end_to_end['seal_rate']:.2f} MB/s over "
            f"{len(done)} seals in {sum(seal_s):.3f} s; rebuild_rate "
            f"{end_to_end['rebuild_rate']:.2f} MB/s over {len(done)} rebuilds "
            f"in {sum(rebuild_s):.3f} s; " + "; ".join(
                f"{name} {value:.6g}" for name, value in summary.items()
            ) + f"; window {window_s:.3f} s")  # the pause is inside it
    return {
        "attempted": 2 * len(done) + failed,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": end_to_end,
        "summary": {} if run.rehearsal else summary,
        "counts": {"seals": len(done), "rebuilds": len(done)},
        "readings": {"seal_s": seal_s, "rebuild_s": rebuild_s,
                     "dat_bytes": run.dat_bytes, "paused_s": paused_s,
                     "late_cycles": late_cycles},
        "status": {"before": before, "after": after},
        "client": {"dat_bytes": run.dat_bytes, "seals": len(done),
                   "rebuilds": len(done), "traced_cycles": int(traced),
                   "seal_s": seal_s, "rebuild_s": rebuild_s,
                   "window_s": window_s, "paused_s": paused_s,
                   "late_cycles": late_cycles},
    }
