"""Shared by the benchmark's rehearsal tests: run one cell's command as the
driver does, on the CPU, at the rehearsal's tiny size."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def maintain_cells() -> list[str]:
    """Every cell whose traffic file's ``kind`` starts with
    ``maintain-cycle``, in BENCHMARK.json's order: the cells that seal and
    rebuild, whatever later PRs add."""
    cells = []
    for cell in bench()["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["kind"].startswith("maintain-cycle"):
                cells.append(cell["name"])
    return cells


def stage_ctx(before: dict, after: dict, gets: int = 40) -> dict:
    """A reader's context around two hand-built ``/status`` snapshots (each
    ``{"stages": {...}}``, or ``{}`` for a daemon that serves no table)."""
    codec = {"compiles": {"requests": 0}, "launches": {}}
    return {
        "trace": None, "cell": "x.y", "device_kind": "TPU v5 lite",
        "client": {"gets": [{}] * gets},
        "status": {"before": dict(codec, **before), "after": dict(codec, **after)},
    }


def run_cell(workload: str, seed: int, *extra: str, root: str = ROOT,
             seconds: float = 1.5, trace: int = 0, rehearsal: bool = True):
    """(exit code, the last line of stdout parsed or None, all output)."""
    cmd = [sys.executable, *bench()["command"][1:],
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    if rehearsal:
        cmd.append("--rehearsal")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=300)
    lines = r.stdout.strip().splitlines()
    line = None
    if lines and lines[-1].startswith("{"):
        line = json.loads(lines[-1])
    return r.returncode, line, r.stdout + r.stderr


def assert_contract_line(line: dict) -> None:
    assert line is not None
    assert LINE_KEYS <= set(line), line
    assert set(line) <= LINE_KEYS | {"breakdown", "readings", "compared"}
    # each number compared beside its limit, under a key that comes last
    assert list(line)[-1] == "compared" and line["compared"]
    for name, row in line["compared"].items():
        assert set(row) == {"value", "limit"}, (name, row)
    assert line["correct"] == all(
        row["value"] <= row["limit"] for row in line["compared"].values())
    # a CPU rehearsal's line carries no reading beside its counts
    assert "readings" not in line
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] >= 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}, (name, m)
        # a CPU rehearsal prints counts only: never a rate, a time or a share
        assert m["unit"] == "count", (name, m)


def copy_benchmark(dst: str, with_program: bool = True) -> str:
    """A checkout holding BENCHMARK.json and the benchmark's paths (and,
    unless told otherwise, the program beside them)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    if with_program:
        os.symlink(os.path.join(ROOT, "seaweedfs_tpu"),
                   os.path.join(dst, "seaweedfs_tpu"))
    return dst
