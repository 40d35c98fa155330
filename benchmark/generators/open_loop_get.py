"""Traffic kind ``open-loop-get``: independent readers of a sealed volume
that has lost shards.

Set-up seals the volume as an operator does (the plain volume goes), drops
the mix's lost shards and warms exactly the kernel shapes the request list
will use. The window offers GETs at the mix's fixed rate, open loop: the
arrival times are those of a Poisson process with the window's count of
requests, each GET is clocked from when it was DUE, and no request waits
for another. The sizes requested are the same for every seed: one needle
from each of N equal strata of the volume's needles ordered by size; the
seed picks the needle inside each stratum, the order and the arrivals.
"""

from __future__ import annotations

import hashlib
import http.client
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import fixture, stats
from ..harness import Run, say


def request_list(loaded: fixture.Loaded, n: int, seed: int) -> list[int]:
    """``n`` needle indexes: one drawn uniformly from each of ``n`` equal
    strata of the needles ordered by size, in a seeded order. Where ``n``
    passes the number of needles, further passes stratify what is left of
    ``n`` the same way (a volume far smaller than its window: rehearsals)."""
    rng = np.random.default_rng([seed, 0x6E7])
    by_size = sorted(range(len(loaded.sizes)), key=lambda i: (loaded.sizes[i], i))
    picked: list[int] = []
    while len(picked) < n:
        m = min(n - len(picked), len(by_size))
        edges = np.linspace(0, len(by_size), m + 1).astype(np.int64)
        picked += [
            by_size[int(rng.integers(edges[j], edges[j + 1]))] for j in range(m)
        ]
    return [picked[i] for i in rng.permutation(n)]


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets of a Poisson process given that ``n`` requests fall
    in the window: ``n`` sorted uniform draws."""
    rng = np.random.default_rng([seed, 0xA771])
    return np.sort(rng.uniform(0.0, seconds, n))


def prepare(run: Run) -> dict:
    """Everything up to a sealed, degraded, mounted volume."""
    from seaweedfs_tpu.shell import commands

    mix = run.mix
    run.require_room()
    d = run.start_daemon()
    run.require_device()
    loaded = run.load()
    commands.ec_encode(run.env, loaded.vid, delete_original=True)
    state = {
        "vif_sums": run.vif_sums(),
        "size_faults": run.shard_size_faults(range(run.total)),
    }
    lost = list(mix["lost_shards"])
    run.delete_shards(lost)
    run.wait_shard_count(run.total - len(lost))
    state["layout"] = fixture.Layout(run.base, loaded, run.ec)
    state["lost_data"] = tuple(s for s in lost if s < run.k)
    codec = d.codec()
    state["align"] = codec.get("pallas_tile", 1) * (
        codec["device_count"] if codec.get("mesh") else 1
    )
    return state


def warm(run: Run, state: dict, picked: list[int]) -> tuple[int, int]:
    """One GET for every distinct padded width among the request list's
    recoveries, so that the window launches only programs that exist.
    Returns (padded widths covered, GETs that failed or differed)."""
    layout, lost, align = state["layout"], state["lost_data"], state["align"]
    seen: set[int] = set()
    conn = http.client.HTTPConnection(run.daemon.volume, timeout=120)
    gets = bad = 0
    for i in picked:
        widths = {-(-w // align) for w in layout.lost_widths(i, lost)}
        if widths - seen:
            seen |= widths
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
    say(f"[warm] {gets} GETs cover {len(seen)} padded widths x "
        f"{len(lost)} missing rows")
    return len(seen), bad


def window(run: Run, picked: list[int], due: np.ndarray, threads: int,
           timeout_s: float) -> list[dict]:
    """Offer the requests at their due times; one record per request."""
    loaded, address = run.loaded, run.daemon.volume
    local = threading.local()
    log: list[dict | None] = [None] * len(picked)
    t0 = time.monotonic() + 0.05

    def get(j: int) -> None:
        i = picked[j]
        rec = {"needle": i, "size": loaded.sizes[i], "due": float(due[j]),
               "ok": False}
        log[j] = rec
        sent = time.monotonic()
        rec["lag_s"] = sent - (t0 + due[j])
        try:
            conn = getattr(local, "conn", None)
            if conn is None:
                conn = local.conn = http.client.HTTPConnection(
                    address, timeout=timeout_s
                )
            conn.request("GET", "/" + loaded.fids[i])
            r = conn.getresponse()
            body = r.read()
            done = time.monotonic()
            rec["status"] = r.status
        except (OSError, http.client.HTTPException) as e:
            done = time.monotonic()
            rec["error"] = repr(e)
            local.conn = None
            body = b""
        rec["latency_s"] = done - (t0 + due[j])
        rec["done"] = done - t0
        rec["ok"] = (
            rec.get("status") == 200
            and hashlib.sha256(body).hexdigest() == loaded.sums[i]
        )

    with ThreadPoolExecutor(threads) as pool:
        for j in range(len(picked)):
            wait = t0 + due[j] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            pool.submit(get, j)
    return log


def backlog_growth(log: list[dict], seconds: float) -> float:
    """Median latency of the window's last quarter over that of its second
    quarter: well above 1 where a queue grows through the run."""
    def med(lo: float, hi: float) -> float:
        part = [r["latency_s"] for r in log if lo <= r["due"] < hi]
        return stats.median(part) if part else float("nan")

    return med(0.75 * seconds, seconds) / med(0.25 * seconds, 0.5 * seconds)


TAILS = (90, 95, 99)  # every run reads all three; BENCHMARK.json names one


def latencies(run: Run, log: list[dict], seconds: float) -> tuple[dict, dict]:
    """(the window's latencies under the names an end-to-end metric may
    have, what stands beside them on the run's line): the median and each
    tail over ALL of the window's GETs, a tail left out where fewer than ten
    samples lie beyond it. A rehearsal prints no latency."""
    lat_ms = [r["latency_s"] * 1e3 for r in log]
    end_to_end = {"get_p50_ms": stats.median(lat_ms)}
    for p in TAILS:
        end_to_end[f"get_p{p}_ms"] = stats.percentile_or_none(lat_ms, p)
    summary = dict(
        end_to_end, gets=len(log), max_ms=max(lat_ms),
        backlog_growth=backlog_growth(log, seconds),
        sched_lag_p99_ms=stats.percentile_or_none(
            [r["lag_s"] * 1e3 for r in log], 99, min_beyond=1),
    )
    if run.rehearsal:
        return end_to_end, {}
    say("[readings] " + "; ".join(
        f"{name} {value:.6g}" for name, value in summary.items()
        if value is not None))
    return end_to_end, summary


def run_cell(run: Run) -> dict:
    mix = run.mix
    seconds = run.args.seconds
    state = prepare(run)
    d = run.daemon
    n = max(1, round(mix["rate_get_per_s"] * seconds))
    picked = request_list(run.loaded, n, run.seed)
    due = arrivals(n, seconds, run.seed)
    shapes, warm_failed = warm(run, state, picked)
    run.require_device()
    before = d.codec()
    setup_s = run.setup_seconds()
    say(f"[setup] {setup_s:.3f} s")

    # a traced run traces the whole window: starting or stopping the
    # profiler stalls the daemon for seconds, which inside the window would
    # be read as the system's own latency
    if run.trace:
        d.profiler("start")
    window_t0 = time.monotonic()
    log = window(run, picked, due, mix["client_threads"], mix["timeout_s"])
    window_s = time.monotonic() - window_t0
    after = d.codec()
    if run.trace:
        d.profiler("stop")
    run.stop_daemon()

    failed = sum(not r["ok"] for r in log)
    for r in log:  # intervals of this needle on a lost data shard
        r["recoveries"] = len(
            state["layout"].lost_widths(r["needle"], state["lost_data"])
        )
    ref = run.reference_sums()
    check = run.check
    check.count("seals_whose_vif_sums_differ_from_reference",
                int(state["vif_sums"] != ref["sums"]))
    check.count("shard_files_of_unplanned_size", state["size_faults"])
    check.count("needles_failed_or_differing_from_what_was_written",
                failed + warm_failed)
    run.status_check(before, after)

    end_to_end, summary = latencies(run, log, seconds)
    return {
        "attempted": n,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": end_to_end,
        "summary": summary,
        "counts": {"gets": n, "shapes_warmed": shapes},
        "readings": {"gets": log},
        "status": {"before": before, "after": after},
        "client": {"gets": log, "seconds": seconds,
                   "lost_shards": list(mix["lost_shards"])},
    }
