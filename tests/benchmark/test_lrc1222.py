"""The configuration ``lrc1222`` and its cell ``lrc1222.maintain-1lost-local``
(PR 36): Azure's LRC(12,2,2), sealed, rebuilt and compared with
``benchmark/reference_lrc.py``, and the one comparison that holds the
mechanism — a rebuild reads the six shards the reference plans, not twelve.
Rehearsed on the CPU with the kernel interpreted: counts and control flow,
never a speed."""

import ast
import json
import os

import pytest

from bench_util import ROOT, assert_contract_line, bench, run_cell

CELL = "lrc1222.maintain-1lost-local"
READ_SET = "rebuilds_that_read_another_set_than_the_reference_plans"


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


# -- the rehearsals ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced():
    rc, line, out = run_cell(CELL, 2_147_483_636, trace=1, seconds=3)
    return {"rc": rc, "line": line, "out": out}


def test_the_traced_rehearsal_is_correct_and_prints_the_contract_line(traced):
    assert traced["rc"] == 0, traced["out"][-3000:]
    assert_contract_line(traced["line"])
    assert traced["line"]["correct"] is True, traced["out"][-3000:]
    assert traced["line"]["failed"] == 0
    assert all(row["value"] == 0 for row in traced["line"]["compared"].values())
    metrics = traced["line"]["metrics"]
    assert metrics["codec.compiled_in_window.maintain"]["value"] == 0
    assert metrics["rehearsal.seals"]["value"] >= 1
    assert metrics["rehearsal.rebuilds"]["value"] >= 1


def test_the_daemon_seals_at_the_code_and_the_reference_is_the_lrc_one(traced):
    (started,) = [l for l in traced["out"].splitlines() if l.startswith("[daemon] /")]
    assert "-ec.geometry 12+2+2" in started
    assert "[reference] 16 shard sums" in traced["out"]
    compared = traced["line"]["compared"]
    assert list(compared)[-1] == READ_SET  # beside maintain-cycle's, after them
    assert compared[READ_SET] == {"value": 0, "limit": 0}
    (said,) = [l for l in traced["out"].splitlines() if l.startswith("[read-set]")]
    rebuilds = traced["line"]["metrics"]["rehearsal.rebuilds"]["value"]
    assert f"ec.rebuild.plan n {rebuilds}, width {6 * rebuilds} " in said
    # the same number as a per-layer metric (ISSUE 38): six shards a rebuild,
    # the lost shard's local group and not the code's twelve
    assert traced["line"]["metrics"]["encoder.rebuild_shards_read"] == {
        "value": 6, "unit": "count"}


# what warm1.maintain-1lost reported when this cell was added: all of the
# maintain cells' metrics but six whose lists two tests then held to two
# cells (ISSUE 38 computes those lists; this cell is on them now)
AS_THE_SINGLE_DISK_CELL = [
    "client.untimed_share", "store.seal_tail_share", "encoder.mib_per_launch",
    "encoder.rebuild_mib_per_launch", "codec.compiled_in_window.maintain",
    "kernel.gf_matmul_roofline", "kernel.gf_matmul_rate",
    "device.idle_share.maintain", "device.peak_hbm", "encoder.stage_busy.read",
    "encoder.stage_busy.dispatch", "encoder.stage_busy.fetch",
    "encoder.stage_busy.write", "encoder.rebuild_stage_busy.read",
    "encoder.rebuild_stage_busy.write", "link.h2d_rate", "link.d2h_rate",
    "store.seal_hash_share", "store.seal_commit_share"]


def test_the_rehearsal_reads_what_the_single_disk_cell_reads(traced):
    listing = {m["name"]: m["workloads"] for m in bench()["per_layer"]}
    for name in AS_THE_SINGLE_DISK_CELL:
        at = listing[name].index("warm1.maintain-1lost")
        assert listing[name].index(CELL) > at, name  # appended, nothing moved
    for name, cells in listing.items():
        # on the line (a count), or said to be read or to have nothing to read
        reported = (name in traced["line"]["metrics"]
                    or f"[layer] {name}:" in traced["out"])
        assert reported == (CELL in cells), name


def test_wrong_codec_comes_out_not_correct():
    rc, line, out = run_cell(CELL, 2_147_483_637, "--control", "wrong-codec",
                             seconds=2)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is False
    failed = [l for l in out.splitlines()
              if l.startswith("[compare]") and "FAILED" in l]
    assert any("seals_whose_vif_sums_differ_from_reference" in l for l in failed)
    # the control clears coefficient [0, 0] of every matrix: the seal writes
    # px without x0, and the rebuild of x4 from (x0, x1, x2, x3, x5, px) then
    # leaves x0 out once more — the two cancel, so the seals' sums are what
    # gives the control away here, not the rebuilt shard
    assert line["compared"]["seals_whose_vif_sums_differ_from_reference"]["value"] \
        == line["metrics"]["rehearsal.seals"]["value"] + 1  # and the warm cycle's
    # it read the six shards the reference plans all the same
    assert line["compared"][READ_SET]["value"] == 0


# -- the comparison that holds the read set -------------------------------------------------
def snapshots(n: int, width: int, read_bytes: int, plan: bool = True):
    before = {"stages": {"ec.rebuild.read": {"n": 8, "busy_s": 0.1, "bytes": 600},
                         "ec.rebuild.plan": {"n": 1, "busy_s": 0.0, "width": 6,
                                             "local": 1}}}
    after = {"stages": {"ec.rebuild.read": {"n": 8 + 8 * n, "busy_s": 0.9,
                                            "bytes": 600 + read_bytes}}}
    if plan:
        after["stages"]["ec.rebuild.plan"] = {
            "n": 1 + n, "busy_s": 0.0, "width": 6 + width, "local": 1 + n}
    else:
        del before["stages"]["ec.rebuild.plan"]
    return before, after


@pytest.mark.parametrize("name, snaps, want", [
    ("six shards a rebuild", snapshots(5, 30, 5 * 6 * 100), 0),
    ("six, holes not read", snapshots(5, 30, 5 * 6 * 100 - 77), 0),
    ("twelve shards a rebuild", snapshots(5, 60, 5 * 12 * 100), 5),
    ("a plan of six, but twelve shards' bytes read", snapshots(5, 30, 5 * 12 * 100), 5),
    ("no ec.rebuild.plan at all", snapshots(5, 0, 5 * 12 * 100, plan=False), 5),
    ("a rebuild without a plan", snapshots(4, 24, 5 * 6 * 100), 5),
    ("no stage table", ({}, {}), 5),
])
def test_a_program_that_reads_another_set_has_every_rebuild_counted(
        name, snaps, want):
    from benchmark.generators.maintain_cycle_lrc import read_set_faults

    before, after = snaps
    assert read_set_faults({"before": before, "after": after}, rebuilds=5,
                           planned=6, shard_bytes=100) == want


def test_the_generator_is_maintain_cycles_with_the_other_reference():
    from benchmark import reference_lrc
    from benchmark.generators import maintain_cycle, maintain_cycle_lrc

    # maintain-cycle's own run_cell, and with it its cycle, rates and beside:
    # the module brings no second one
    assert maintain_cycle_lrc.maintain_cycle is maintain_cycle
    for shared in ("cycle", "rates", "beside", "median_rate", "stalled_ops"):
        assert not hasattr(maintain_cycle_lrc, shared)
    assert reference_lrc.read_set([4]) == [0, 1, 2, 3, 5, 12]
    mix = load("traffic", "maintain-1lost-local.json")
    assert mix["kind"] == "maintain-cycle-lrc" and mix["lost_shards"] == [4]
    assert mix["warm_cycles"] == load("traffic", "maintain-1lost.json")["warm_cycles"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference_lrc.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." * node.level + (node.module or ""))
    assert imported == {"__future__", "hashlib", "os", "concurrent.futures",
                        "numpy", ".reference"}


# -- what BENCHMARK.json and the configuration say ----------------------------------------------
def test_the_cell_and_the_configuration_come_after_those_that_were_there():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    assert cells[:7] == [
        "warm1.maintain", "warm1.read-degraded", "mesh4.maintain",
        "warm1.read-1lost", "spread4.read-nodeloss", "geom124.maintain",
        "warm1.maintain-1lost"]
    assert cells.count(CELL) == 1 and cells.index(CELL) >= 7
    cell = b["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lrc1222", "maintain-1lost-local", 1)
    configs = [c["name"] for c in b["configs"]]
    assert configs[:4] == ["warm1", "mesh4", "spread4", "geom124"]
    (lrc,) = [c for c in b["configs"] if c["name"] == "lrc1222"]
    assert lrc["file"] == "benchmark/configs/lrc1222.json"
    assert lrc["reduced"] == ["volume.dat_target_bytes", "servers"]
    # the configuration's first cell; a later one comes after it
    assert [w["name"] for w in b["workloads"] if w["config"] == "lrc1222"][0] == CELL
    listed = {m["name"]: m.get("workloads") for m in b["end_to_end"]}
    assert listed["rebuild_rate"][:5] == [
        "warm1.maintain", "mesh4.maintain", "geom124.maintain",
        "warm1.maintain-1lost", CELL]
    # seal_rate where the cell's sets held it, else the same rate per layer
    # (PR 47; PERF.md section 2)
    (beside,) = [m for m in b["per_layer"] if m["name"] == "client.seal_rate"]
    assert (CELL in listed["seal_rate"]) != (CELL in beside["workloads"])


def test_lrc1222_is_geom124_but_for_the_code():
    geom, lrc = load("configs", "geom124.json"), load("configs", "lrc1222.json")
    differing = {key for key in set(geom) | set(lrc) if geom.get(key) != lrc.get(key)}
    assert differing == {"name", "source", "deployment", "daemon", "ec",
                         "guarantees", "reduced", "assumed"}
    assert lrc["daemon"] == dict(
        geom["daemon"], args=["-max", "16", "-ec.geometry", "12+2+2"])
    assert lrc["ec"] == dict(geom["ec"], local_parity_shards=2)
    assert lrc["guarantees"]["stored_bytes_per_user_byte"] == 1.3333
    assert "refused" in lrc["guarantees"]["reads"]
    assert {key for key in lrc["guarantees"]
            if lrc["guarantees"][key] != geom["guarantees"][key]} == {
        "shard_bytes", "reads"}
    # the cut is geom124's and no further
    assert sorted(lrc["reduced"]) == sorted(geom["reduced"])
    assert lrc["reduced"]["volume.dat_target_bytes"] == \
        geom["reduced"]["volume.dat_target_bytes"]
    assert set(lrc["assumed"]) == set(geom["assumed"])
    assert "(i + 1) << 4" in lrc["assumed"]["ec.matrix"]
    assert len(lrc["source"]) <= 200 and "sec. 2.1-2.2" in lrc["source"]
    from benchmark import reference
    assert reference.shard_size(1_064_846_680, 12, 1 << 30, 1 << 20) == 89_128_960
