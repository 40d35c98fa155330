#!/usr/bin/env python3
"""The builder's knee sweep of a read cell (not part of a run):

    python3 benchmark/tools/sweep.py --workload warm1.read-degraded \
        --seed 1 --rates 6,10,14,18,22,26 --seconds 15

One daemon, one sealed and degraded volume; then one open-loop window per
rate, each with its own stratified request list, warmed first. Prints one
JSON line per rate: offered and completed GET/s, p50 / p95, the backlog
growth (median latency of the last quarter over the second quarter) and
how long the queue took to drain after the last arrival. The knee is the
highest rate whose backlog does not grow; the cell's rate is 0.6 of it.
Every GET of every window goes to ``chiprun_out/benchmark/sweep-*.json``.
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


def pct(values, p, min_beyond):
    try:
        return stats.percentile(values, p, min_beyond=min_beyond)
    except ValueError:
        return None  # too few samples to carry this tail


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace, args.control = 0, ""

    bench = load_json("BENCHMARK.json")
    cell = named(bench["workloads"], args.workload, "workload")
    cfg = load_json(named(bench["configs"], cell["config"], "config")["file"])
    mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    run = Run(args, time.monotonic(), cell, cfg, mix)
    dump = {}
    try:
        state = olg.prepare(run)
        layout, lost = state["layout"], state["lost_data"]
        for step, rate in enumerate(float(r) for r in args.rates.split(",")):
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
                "p95_ms": pct(lat, 95, 3),
                "max_ms": max(lat),
                "backlog_growth": olg.backlog_growth(log, args.seconds),
                "drain_s": last_done - float(due[-1]),
                "lag_p99_ms": pct([r["lag_s"] * 1e3 for r in log], 99, 1),
                "recovering_share": sum(r["recoveries"] > 0 for r in log) / n,
                "compile_requests": after["compiles"]["requests"]
                - before["compiles"]["requests"],
            }
            print(json.dumps(row), flush=True)
            dump[str(rate)] = log
    finally:
        run.cleanup()
    out = os.path.join(ROOT, "chiprun_out", "benchmark",
                       f"sweep-{args.workload}-s{args.seed}.json")
    with open(out, "w") as f:
        json.dump(dump, f)


if __name__ == "__main__":
    main()
