#!/usr/bin/env python3
"""The builder's knee sweep of a read cell (not part of a run):

    python3 benchmark/tools/sweep.py --workload warm1.read-degraded \
        --seed 1 --rates 6,10,14,18,22,26 --seconds 15

One daemon, one sealed and degraded volume; then one open-loop window per
rate, each with its own stratified request list, warmed first. Prints one
JSON line per rate: offered and completed GET/s, p50 / p95, the backlog
growth (median latency of the last quarter over the second quarter) and
how long the queue took to drain after the last arrival. The knee is the
highest rate with no growing window at or beneath it; a cell's rate lies
between 0.6 and 0.8 of it. ``--refine N`` adds a second pass in the same
daemon: N rates evenly between the knee the first pass found and the lowest
rate that grew. The last line names the knee of all the windows. Every GET of
every window goes to ``chiprun_out/benchmark/sweep-*.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402
from benchmark.generators import open_loop_get as olg  # noqa: E402
from benchmark.harness import Run  # noqa: E402
from benchmark.run import load_json, named  # noqa: E402


def grows(row: dict) -> bool:
    """A window whose backlog grew: the last quarter's median latency well
    over the second quarter's, or a queue still draining a second and a
    half after the last arrival."""
    return row["backlog_growth"] > 1.3 or row["drain_s"] > 1.5


def knee(rows: list[dict]) -> tuple[float | None, float | None]:
    """(the highest rate with no growing window at or beneath it, the
    lowest rate whose window grew). A fifteen-second window's quarters
    swing, so a window that grew is believed: the rule errs low."""
    below = above = None
    for row in sorted(rows, key=lambda r: r["rate_offered"]):
        if grows(row):
            above = row["rate_offered"]
            break
        below = row["rate_offered"]
    return below, above


def refine_rates(rows: list[dict], n: int) -> list[float]:
    """``n`` rates evenly between the knee and the lowest rate that grew."""
    below, above = knee(rows)
    if not n or below is None or above is None:
        return []
    return [round(below + (above - below) * (i + 1) / (n + 1), 1)
            for i in range(n)]


def passes(args, rows: list[dict]):
    """The rates asked for, then the second pass around the knee that the
    rows gathered so far show."""
    yield from (float(r) for r in args.rates.split(","))
    yield from refine_rates(rows, args.refine)


def say_knee(rows: list[dict]) -> None:
    below, above = knee(rows)
    print(json.dumps({
        "knee": below, "lowest_rate_that_grew": above,
        "cell_rate_from": None if below is None else round(0.6 * below, 1),
        "cell_rate_to": None if below is None else round(0.8 * below, 1),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--refine", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace, args.control = 0, ""

    bench = load_json("BENCHMARK.json")
    cell = named(bench["workloads"], args.workload, "workload")
    cfg = load_json(named(bench["configs"], cell["config"], "config")["file"])
    mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    run = Run(args, time.monotonic(), cell, cfg, mix)
    dump, rows = {}, []
    try:
        state = olg.prepare(run)
        layout, lost = state["layout"], state["lost_data"]
        for step, rate in enumerate(passes(args, rows)):
            n = max(1, round(rate * args.seconds))
            picked = olg.request_list(run.loaded, n, args.seed + step)
            due = olg.arrivals(n, args.seconds, args.seed + step)
            olg.warm(run, state, picked)
            before = run.daemon.codec()
            log = olg.window(run, picked, due, mix["client_threads"],
                             mix["timeout_s"])
            after = run.daemon.codec()
            for r in log:
                r["recoveries"] = len(layout.lost_widths(r["needle"], lost))
            lat = [r["latency_s"] * 1e3 for r in log]
            last_done = max(r["done"] for r in log)
            row = {
                "rate_offered": rate,
                "gets": n,
                "failed": sum(not r["ok"] for r in log),
                "rate_completed": n / last_done,
                "p50_ms": stats.median(lat),
                "p95_ms": stats.percentile_or_none(lat, 95, 3),
                "max_ms": max(lat),
                "backlog_growth": olg.backlog_growth(log, args.seconds),
                "drain_s": last_done - float(due[-1]),
                "lag_p99_ms": stats.percentile_or_none(
                    [r["lag_s"] * 1e3 for r in log], 99, 1),
                "recovering_share": sum(r["recoveries"] > 0 for r in log) / n,
                "compile_requests": after["compiles"]["requests"]
                - before["compiles"]["requests"],
            }
            print(json.dumps(row), flush=True)
            rows.append(row)
            dump[str(rate)] = log
        say_knee(rows)
    finally:
        run.cleanup()
    out = os.path.join(ROOT, "chiprun_out", "benchmark",
                       f"sweep-{args.workload}-s{args.seed}.json")
    with open(out, "w") as f:
        json.dump(dump, f)


if __name__ == "__main__":
    main()
