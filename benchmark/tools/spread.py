#!/usr/bin/env python3
"""The builder's reading of its own sets of runs: from files of result
lines (one run's last line per line), each metric's median and spread —
the distance between the first and third quartile as a share of the
median — per set, and the bound that follows (five times the widest).

    python3 benchmark/tools/spread.py set_a.jsonl set_b.jsonl
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.stats import iqr_share  # noqa: E402


def main() -> None:
    sets = []
    for path in sys.argv[1:]:
        with open(path) as f:
            sets.append([json.loads(l) for l in f if l.startswith('{"correct"')])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        row = {"metric": name}
        for i, runs in enumerate(sets):
            values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            first, rest = values[0], values[1:]
            row[f"set{i}"] = {
                "n": len(values), "first": first,
                "median": statistics.median(values),
                "spread": iqr_share(values) if len(values) >= 4 else None,
                "median_wo_first": statistics.median(rest) if rest else None,
                "values": values,
            }
        spreads = [row[f"set{i}"]["spread"] for i in range(len(sets))
                   if row[f"set{i}"]["spread"] is not None]
        if spreads:
            row["widest"] = max(spreads)
            row["bound_x5"] = max(0.01, 5 * max(spreads))
        print(json.dumps(row))
    wrong = [r for s in sets for r in s if not r["correct"]]
    print(json.dumps({"runs": sum(len(s) for s in sets), "not_correct": len(wrong)}))


if __name__ == "__main__":
    main()
