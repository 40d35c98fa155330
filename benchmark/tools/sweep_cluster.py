#!/usr/bin/env python3
"""The builder's knee sweep of a cluster read cell (not part of a run):

    python3 benchmark/tools/sweep_cluster.py --workload spread4.read-nodeloss \
        --seed 1 --rates 40,70,100,130,160 --seconds 15

``sweep.py``'s rule on ``cluster_open_loop_get``'s cluster: one cluster, one
volume sealed, spread and robbed of its source; then one open-loop window
per rate over the survivors, each with its own stratified request list and
its own draw of servers, warmed first. One JSON line per rate: offered and
completed GET/s, p50 / p95, the backlog growth (median latency of the last
quarter over the second quarter), the drain after the last arrival, and
what the survivors did per GET. The knee is the highest rate with no
growing window at or beneath it; the cell's rate lies between 0.6 and 0.8 of
it (``--refine N``: a second pass of N rates above it, as in ``sweep.py``). Every GET of every window goes
to ``chiprun_out/benchmark/sweep-*.json``.
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
from benchmark.generators import cluster_open_loop_get as cg  # noqa: E402
from benchmark.harness import Run  # noqa: E402
from benchmark.run import load_json, named  # noqa: E402
from benchmark.generators.open_loop_get import backlog_growth  # noqa: E402
from benchmark.tools.sweep import passes, say_knee  # noqa: E402


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
        with cg.cluster_of(run) as cluster:
            state = cg.prepare(run, cluster)
            survivors = state["survivors"]
            print(json.dumps({"setup_s": run.setup_seconds()}), flush=True)
            for step, rate in enumerate(passes(args, rows)):
                seed = args.seed + step
                n = max(1, round(rate * args.seconds))
                picked = cg.request_list(run.loaded, n, seed)
                due = cg.arrivals(n, args.seconds, seed)
                to = cg.targets(n, len(survivors), seed)
                cg.warm(run, state, picked)
                before = cg.merged([cluster.codec(i) for i in survivors], [])
                log = cg.window(run, state, picked, due, to,
                                mix["client_threads"], mix["timeout_s"])
                after = cg.merged([cluster.codec(i) for i in survivors], [])
                cg.annotate(state, log)
                lat = [r["latency_s"] * 1e3 for r in log]
                last_done = max(r["done"] for r in log)
                per_get = {
                    name: cg.stage_delta(before, after, stage, field) / n
                    for name, (stage, field) in {
                        "remote_ok": ("ec.read.remote", "ok"),
                        "remote_failed": ("ec.read.remote", "failed"),
                        "lookups": ("ec.read.lookup", "n"),
                        "recoveries": ("ec.recover", "n"),
                    }.items()
                }
                row = {
                    "rate_offered": rate,
                    "gets": n,
                    "failed": sum(not r["ok"] for r in log),
                    "rate_completed": n / last_done,
                    "p50_ms": stats.median(lat),
                    "p95_ms": stats.percentile_or_none(lat, 95, 3),
                    "max_ms": max(lat),
                    "backlog_growth": backlog_growth(log, args.seconds),
                    "drain_s": last_done - float(due[-1]),
                    "lag_p99_ms": stats.percentile_or_none(
                        [r["lag_s"] * 1e3 for r in log], 99, 1),
                    "recovering_share": sum(r["recoveries"] > 0 for r in log) / n,
                    "recovering_p50_ms": stats.median(
                        [r["latency_s"] * 1e3 for r in log if r["recoveries"]] or [0.0]),
                    "per_get": per_get,
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
