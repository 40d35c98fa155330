#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``breakdown`` when traced), then ``readings`` (what the generator read
beside the metrics: the tails, the in-run medians, the stalled operations) and, last,
``compared``: each number compared beside its limit, which are also the
last lines on standard error. Every in-run reading is on earlier lines and
in ``chiprun_out/benchmark/<cell>-s<seed>-t<trace>/``.

This process never imports JAX: the daemon child owns the chip.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is clocked from the first line of the run

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import layers  # noqa: E402
from benchmark.harness import Run, say  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``group`` this cell reports: those that list it under
    ``workloads`` and those that list nothing."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def reduce_trace(run: Run) -> dict | None:
    """The trace, reduced in a child held to the CPU platform."""
    out = os.path.join(run.out_dir, "trace_reduced.json")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         run.trace_dir, out],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        raise SystemExit(f"trace reduction failed:\n{r.stderr[-3000:]}")
    shutil.rmtree(run.trace_dir, ignore_errors=True)  # tens of MB, reduced
    with open(out) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    # the builder's: a CPU rehearsal at a tiny size, the wrong-codec control
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", default="", choices=["", "wrong-codec"])
    args = ap.parse_args()

    bench = load_json("BENCHMARK.json")
    cell = named(bench["workloads"], args.workload, "workload")
    config = named(bench["configs"], cell["config"], "config")
    cfg = load_json(config["file"])
    mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    generator = importlib.import_module(
        "benchmark.generators." + mix["kind"].replace("-", "_")
    )
    run = Run(args, T0, cell, cfg, mix)
    try:
        out = generator.run_cell(run)
    finally:
        run.cleanup()

    with open(os.path.join(run.out_dir, "readings.json"), "w") as f:
        json.dump({"cell": cell["name"], "seed": args.seed,
                   "setup_s": out["setup_s"], "window_s": out["window_s"],
                   "reference_s": run.reference_s, **out["readings"]}, f)
    codec = out["status"]["after"]
    device = run.device_block(codec)
    line: dict = {
        "correct": run.check.correct,
        "attempted": out["attempted"],
        "failed": out["failed"],
    }
    metrics: dict = {}
    if args.trace:
        trace = reduce_trace(run)
        ctx = {"trace": trace, "status": out["status"], "client": out["client"],
               "device_kind": device["kind"], "cell": cell["name"]}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = layers.load_reader(m["name"]).read(ctx)
            if value is None:
                say(f"[layer] {m['name']}: nothing to read")
            elif args.rehearsal and m["unit"] != "count":
                say(f"[layer] {m['name']}: read (a rehearsal prints counts only)")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if not args.rehearsal:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
    elif not args.rehearsal:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    if args.rehearsal:
        # a CPU rehearsal proves bytes and control flow: counts, no speed
        for name, n in out["counts"].items():
            metrics["rehearsal." + name] = {"value": n, "unit": "count"}
    line["metrics"] = metrics
    line["device"] = device
    if out["summary"]:  # what stands beside the metrics, traced or not
        line["readings"] = out["summary"]
    line["compared"] = run.check.report()  # last on stderr, last in the line
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
