#!/usr/bin/env python3
"""The builder's knee sweep of a read cell of kind ``open-loop-get-2codes``
(not part of a run): ``tools/sweep.py`` for a node with two volumes.

    python3 benchmark/tools/sweep_2codes.py --workload mixed1.read-2codes \
        --seed 1 --rates 60,90,120,150,180 --seconds 15 --refine 4

One set-up (the migration: two daemons, two seals), then one open-loop
window per rate under the daemon of the window, each with its own request
list, warmed first. The rows, the rule that reads a knee from them and the
second pass around it are ``sweep.py``'s own; a row here also says what
share of the GETs went to each volume. Every GET of every window goes to
``chiprun_out/benchmark/sweep-*.json``.
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
from benchmark.generators import open_loop_get_2codes as two  # noqa: E402
from benchmark.harness import Run  # noqa: E402
from benchmark.run import load_json, named  # noqa: E402
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
        state = two.prepare(run)
        print(json.dumps({"setup_s": run.setup_seconds()}), flush=True)
        for step, rate in enumerate(passes(args, rows)):
            n = max(1, round(rate * args.seconds))
            picked = two.request_list(state, n, args.seed + step)
            due = olg.arrivals(n, args.seconds, args.seed + step)
            two.warm(run, state, picked)
            before = run.daemon.codec()
            log = olg.window(run, picked, due, mix["client_threads"],
                             mix["timeout_s"])
            after = run.daemon.codec()
            for r in log:
                r["recoveries"] = len(two.recoveries(state, r["needle"]))
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
                "share_by_volume": {
                    v.name: sum(two.located(state, r["needle"])[0] is v
                                for r in log) / n
                    for v in state["volumes"]},
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
