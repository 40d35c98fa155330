#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
time as the union of its operations' intervals, time by operation name,
the host spans the launcher wrote, and every idle gap of the device
attributed to the host span that was open in it.

``python3 benchmark/trace_reduce.py <trace dir or .xplane.pb> <out.json>``
runs in a process of its own held to the CPU platform: reading a trace
needs JAX's reader, never a device. ``reduce_planes`` is plain Python over
plain lists, and is what the tests drive.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

# the spans daemon_main.py writes; anything else on a host plane is noise
SPAN_NAMES = (
    "handler", "ec_encode_volume", "write_ec_files", "rebuild_ec_files",
    "_recover_interval", "reconstruct", "matmul", "matmul_device",
)
# a TPU plane carries its operations on this line; "Steps" and
# "XLA Modules" cover the same time again
OP_LINE = "XLA Ops"
TOP = 10


def load_xplane(path: str) -> list[dict]:
    """The trace as plain data: planes -> lines -> (name, start s, end s,
    stats). Only device planes and the host's annotated spans are kept."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")
        ))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if host and e.name not in SPAN_NAMES:
                    continue
                start = e.start_ns * 1e-9
                stats = dict(e.stats) if host else {}
                events.append(
                    (e.name, start, start + e.duration_ns * 1e-9, stats)
                )
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Length of the union of ``intervals`` and the merged intervals."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged), merged


def op_name(name: str) -> str:
    """The operation's own name. A TPU trace names an operation by its
    whole HLO line (``%gf_matmul_r4_k10.1 = u8[4,1048576]... custom-call(``
    ...); ``gf_matmul_r4_k10.1`` and ``gf_matmul_r4_k10.7`` are one kernel."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def attribute_gaps(gaps: list[tuple[float, float]], spans: list[dict]) -> dict:
    """Seconds of ``gaps`` by the host span open in them: where several are
    open, the one that began last (of two that began together, the
    shorter: the innermost on one thread); where none is, ``none``."""
    out: dict[str, float] = {}
    ordered = sorted(spans, key=lambda s: (s["start"], -s["end"]))
    starts = [s["start"] for s in ordered]
    cuts = sorted({t for s in spans for t in (s["start"], s["end"])})
    for lo, hi in gaps:
        inner = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts, hi)]
        points = [lo, *inner, hi]
        for a, b in zip(points, points[1:]):
            mid = (a + b) / 2
            name = "none"
            for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                if ordered[i]["end"] > mid:
                    name = ordered[i]["name"]
                    break
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_planes(planes: list[dict]) -> dict:
    spans, devices = [], {}
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        device = plane["name"].startswith("/device:")
        lines = plane["lines"]
        if device and any(l["name"] == OP_LINE for l in lines):
            lines = [l for l in lines if l["name"] == OP_LINE]
        ops: dict[str, float] = {}
        intervals = []
        for thread, line in enumerate(lines):
            for name, start, end, stats in line["events"]:
                lo, hi = min(lo, start), max(hi, end)
                if device:
                    intervals.append((start, end))
                    key = op_name(name)
                    ops[key] = ops.get(key, 0.0) + (end - start)
                else:
                    spans.append({
                        "name": name, "start": start, "end": end,
                        "thread": f"{plane['name']}/{thread}", "stats": stats,
                    })
        if device and intervals:
            busy, merged = union_seconds(intervals)
            devices[plane["name"]] = {"busy_s": busy, "ops": ops,
                                      "merged": merged}
    if hi < lo:
        raise ValueError("the trace holds no device operation and no span")
    window = hi - lo
    gaps_by: dict[str, float] = {}
    ops_all: dict[str, float] = {}
    for dev in devices.values():
        edges = [lo] + [t for iv in dev.pop("merged") for t in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, s in attribute_gaps(gaps, spans).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + s / len(devices)
        for name, s in dev["ops"].items():
            ops_all[name] = ops_all.get(name, 0.0) + s
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:TOP]  # noqa: E731
    return {
        "window_s": window,
        "bounds": [lo, hi],
        "busy_s": (
            sum(d["busy_s"] for d in devices.values()) / len(devices)
            if devices else 0.0
        ),
        "devices": devices,
        "device_op_seconds": ops_all,
        "device_ops": [[n, s] for n, s in top(ops_all)],
        "idle_gaps": [[n, s] for n, s in top(gaps_by)],
        "spans": spans,
    }


def spans_named(reduced: dict, name: str) -> list[dict]:
    return [s for s in reduced["spans"] if s["name"] == name]


def inside(inner: dict, outer: dict) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def main() -> None:
    src, dst = sys.argv[1], sys.argv[2]
    reduced = reduce_planes(load_xplane(src))
    with open(dst, "w") as f:
        json.dump(reduced, f)


if __name__ == "__main__":
    main()
