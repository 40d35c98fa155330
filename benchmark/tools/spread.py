#!/usr/bin/env python3
"""The builder's reading of its own sets of runs: from files of result
lines (one run's last line per line), each metric's median and spread —
the distance between the first and third quartile as a share of the
median — per set, and the bound that follows (five times the widest).

    python3 benchmark/tools/spread.py set_a.jsonl set_b.jsonl

What a line carries under ``readings`` is read the same way, on the same
runs, under ``readings.<name>``: for a maintain cell the in-run medians'
spread stands beside the rates', for a read cell each tail's beside the
others'.

Two sets of the same seeds are also pairs of the tree against itself. The
last rows read them as the driver reads a PR, set 0 as the parent and set 1
as the change, against ``BENCHMARK.json``'s bounds: each side's spread (the
run farthest from the median left out where that narrows it) and the
distance of the two medians, each to stay under half the bound. ``setup_s``
is judged by its median alone, each side's first run left out.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.stats import iqr_share  # noqa: E402


def values_of(runs: list[dict], name: str) -> list[float]:
    """One metric or ``readings.<name>`` over a set's runs, in run order."""
    if name.startswith("readings."):
        got = [r.get("readings", {}).get(name[len("readings."):]) for r in runs]
    else:
        got = [r["metrics"].get(name, {}).get("value") for r in runs]
    return [v for v in got if v is not None]


def trimmed_spread(values: list[float]) -> float:
    """The spread, with the run farthest from the median left out where
    that narrows it."""
    whole = iqr_share(values)
    if len(values) < 5:
        return whole
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return min(whole, iqr_share(rest) * statistics.median(rest) / mid)


def null_check(sets: list[list[dict]], bench: dict) -> list[dict]:
    """Set 0 read against set 1 as parent against change."""
    rows = []
    for m in bench["end_to_end"]:
        a, b = (values_of(s, m["name"]) for s in sets)
        if len(a) < 4 or len(b) < 4:
            continue
        setup = m["name"] == "setup_s"
        if setup:  # each side's first run compiles
            a, b = a[1:], b[1:]
        mid_a, mid_b = statistics.median(a), statistics.median(b)
        row = {
            "null_check": m["name"], "bound": m["bound"],
            "median": [mid_a, mid_b],
            "medians_apart": abs(mid_b - mid_a) / mid_a,
        }
        if setup:  # its median alone, and only if worse
            row["resolved"] = (mid_b - mid_a) / mid_a < m["bound"]
        else:
            row["spread"] = [trimmed_spread(a), trimmed_spread(b)]
            row["resolved"] = (max(row["spread"]) < m["bound"] / 2
                               and row["medians_apart"] < m["bound"] / 2)
        rows.append(row)
    return rows


def main() -> None:
    sets = []
    for path in sys.argv[1:]:
        with open(path) as f:
            sets.append([json.loads(l) for l in f if l.startswith('{"correct"')])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    names += sorted({"readings." + m for s in sets for r in s
                     for m, v in r.get("readings", {}).items() if v is not None})
    for name in names:
        row = {"metric": name}
        for i, runs in enumerate(sets):
            values = values_of(runs, name)
            if not values:
                continue
            first, rest = values[0], values[1:]
            row[f"set{i}"] = {
                "n": len(values), "first": first,
                "median": statistics.median(values),
                "spread": (iqr_share(values) if len(values) >= 4
                           and statistics.median(values) else None),
                "median_wo_first": statistics.median(rest) if rest else None,
                "values": values,
            }
        spreads = [row[f"set{i}"]["spread"] for i in range(len(sets))
                   if f"set{i}" in row and row[f"set{i}"]["spread"]]
        if spreads:
            row["widest"] = max(spreads)
            row["bound_x5"] = max(0.01, 5 * max(spreads))
        print(json.dumps(row))
    if len(sets) == 2:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            for row in null_check(sets, json.load(f)):
                print(json.dumps(row))
    wrong = [r for s in sets for r in s if not r["correct"]]
    print(json.dumps({"runs": sum(len(s) for s in sets), "not_correct": len(wrong)}))


if __name__ == "__main__":
    main()
