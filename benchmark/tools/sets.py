#!/usr/bin/env python3
"""The builder's sets of runs of one cell, as the contract asks: the runs of
a set each with another seed, the sets with the same seeds, every run a new
process at ``run_seconds``:

    python3 benchmark/tools/sets.py --workload warm1.maintain --sets 2 \
        --seeds 2147480001,2147480002,... [--control wrong-codec] [--seconds S]

Result lines go to ``chiprun_out/sets/<cell>.<tag><set>.jsonl`` (read them
with ``spread.py``). A line carries, under ``readings``, what a later session
needs to derive a bound again without the chip: a read window's p50 / p90 /
p95 / p99, its backlog growth and the generator's lag; a maintain window's
in-run medians and stalled operations. Each run's in-run readings, where they are few
(a maintain window's seals and rebuilds; a read window's GETs are megabytes),
go to ``...<set>.readings.jsonl``: the sets share their seeds, so the second
would overwrite the first's run directories. The tail of a run that printed
no result is shown.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def keep_readings(args, seed: str, set_path: str) -> None:
    readings = os.path.join(
        ROOT, "chiprun_out", "benchmark",
        f"{args.workload}-s{seed}-t{args.trace}", "readings.json")
    if os.path.exists(readings) and os.path.getsize(readings) < (64 << 10):
        with open(readings) as f, open(
            set_path.replace(".jsonl", ".readings.jsonl"), "a"
        ) as out:
            out.write(f.read().strip() + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default="")
    ap.add_argument("--tag", default="set")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    for s in range(args.sets):
        path = os.path.join(out_dir, f"{args.workload}.{args.tag}{s}.jsonl")
        with open(path, "w") as out:
            for seed in args.seeds.split(","):
                cmd = [*bench["command"], "--workload", args.workload,
                       "--seed", seed, "--seconds", str(seconds),
                       "--trace", str(args.trace)]
                if args.control:
                    cmd += ["--control", args.control]
                t = time.monotonic()
                r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
                wall = time.monotonic() - t
                lines = r.stdout.strip().splitlines()
                last = lines[-1] if lines else ""
                if r.returncode == 0 and last.startswith('{"correct"'):
                    out.write(last + "\n")
                    out.flush()
                    keep_readings(args, seed, path)
                    got = json.loads(last)
                    shown = {k: round(v["value"], 3) for k, v in got["metrics"].items()}
                    beside = {k: round(v, 3) for k, v in
                              got.get("readings", {}).items() if v is not None}
                    print(f"set {s} seed {seed}: correct={got['correct']} "
                          f"failed={got['failed']}/{got['attempted']} {shown} "
                          f"{beside} wall {wall:.0f} s", flush=True)
                    if not got["correct"]:
                        print({k: v for k, v in got["compared"].items()
                               if v["value"] > v["limit"]}, flush=True)
                else:
                    print(f"set {s} seed {seed}: exit {r.returncode}, no "
                          f"result\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}",
                          flush=True)


if __name__ == "__main__":
    main()
