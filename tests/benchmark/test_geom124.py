"""The cells PR 33 added: ``geom124.maintain`` (the same node sealing at
RS(12,4)) and ``warm1.maintain-1lost`` (the single-disk rebuild). Rehearsed on
the CPU with the kernel interpreted: counts and control flow, never a speed."""

import json
import os

import pytest

from bench_util import ROOT, assert_contract_line, bench, run_cell


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


# -- the traced rehearsals -----------------------------------------------------------
@pytest.fixture(scope="module", params=[
    ("geom124.maintain", 16), ("warm1.maintain-1lost", 14),
], ids=lambda p: p[0])
def traced(request):
    cell, total = request.param
    rc, line, out = run_cell(cell, 2_147_483_400 + total, trace=1, seconds=3)
    return {"cell": cell, "total": total, "rc": rc, "line": line, "out": out}


def test_the_traced_rehearsal_is_correct_and_prints_the_contract_line(traced):
    assert traced["rc"] == 0, traced["out"][-3000:]
    assert_contract_line(traced["line"])
    assert traced["line"]["correct"] is True, traced["out"][-3000:]
    assert traced["line"]["failed"] == 0
    assert all(row["value"] == 0 for row in traced["line"]["compared"].values())


def test_nothing_compiles_inside_the_rehearsals_window(traced):
    metrics = traced["line"]["metrics"]
    assert metrics["codec.compiled_in_window.maintain"]["value"] == 0
    assert metrics["rehearsal.seals"]["value"] >= 1
    assert metrics["rehearsal.rebuilds"]["value"] >= 1


def test_the_traced_rehearsal_reads_the_maintain_layers_and_the_six_once_pinned(traced):
    out, metrics = traced["out"], traced["line"]["metrics"]
    for name in ("store.seal_tail_share", "encoder.mib_per_launch",
                 "encoder.rebuild_mib_per_launch", "client.untimed_share",
                 "encoder.stage_busy.write", "encoder.rebuild_stage_busy.write",
                 "store.seal_hash_share", "store.seal_commit_share"):
        assert f"[layer] {name}: read" in out, out[-3000:]
    # since ISSUE 38 the six that two tests held to two cells are every
    # maintain cell's: a count is on the line, the rest are said to be read
    for name in ONCE_PINNED:
        if name == "client.stalled_ops":
            assert metrics[name]["unit"] == "count"
        else:
            assert f"[layer] {name}: read" in out, name
    # the planner's read set, exact: k shards for a Reed-Solomon volume
    k = 12 if traced["cell"] == "geom124.maintain" else 10
    assert metrics["encoder.rebuild_shards_read"] == {"value": k, "unit": "count"}


def test_the_daemon_was_started_at_the_configurations_geometry(traced):
    (started,) = [l for l in traced["out"].splitlines() if l.startswith("[daemon] /")]
    assert ("-ec.geometry 12+4" in started) == (traced["cell"] == "geom124.maintain")
    assert f"[reference] {traced['total']} shard sums" in traced["out"]


def test_the_single_disk_rebuild_rebuilds_shard_four_alone():
    mix = load("traffic", "maintain-1lost.json")
    assert mix["kind"] == "maintain-cycle" and mix["lost_shards"] == [4]
    assert mix["warm_cycles"] == load("traffic", "maintain.json")["warm_cycles"]
    rc, line, out = run_cell("warm1.maintain-1lost", 2_147_483_431, seconds=1.5)
    assert rc == 0 and line["correct"] is True, out[-3000:]
    # every cycle's ec.rebuild gave back exactly the mix's lost shards
    assert line["compared"]["rebuilds_of_other_shards_than_lost"] == {
        "value": 0, "limit": 0}
    assert line["compared"]["rebuilt_shards_differing_from_reference"]["value"] == 0
    assert line["metrics"]["rehearsal.rebuilds"]["value"] >= 1


def test_wrong_codec_at_twelve_plus_four_comes_out_not_correct():
    rc, line, out = run_cell("geom124.maintain", 2_147_483_441, "--control",
                             "wrong-codec", seconds=2)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is False
    failed = [l for l in out.splitlines()
              if l.startswith("[compare]") and "FAILED" in l]
    assert any("seals_whose_vif_sums_differ_from_reference" in l for l in failed)
    assert any("rebuilt_shards_differing_from_reference" in l for l in failed)


# -- what BENCHMARK.json and the configuration say -------------------------------------
ONCE_PINNED = ["client.seal_rate_p50", "client.rebuild_rate_p50", "client.stalled_ops",
               "encoder.read_rate", "encoder.rebuild_read_rate",
               "encoder.buffer_reuse_share"]
NEW_CELLS = ["geom124.maintain", "warm1.maintain-1lost"]


def test_the_two_cells_come_after_the_five_on_one_chip_each():
    cells = {w["name"]: (at, w) for at, w in enumerate(bench()["workloads"])}
    (at_geom, geom), (at_1lost, one_lost) = (cells[name] for name in NEW_CELLS)
    assert 5 <= at_geom < at_1lost
    assert (geom["config"], geom["traffic"], geom["chips"]) == (
        "geom124", "maintain", 1)
    assert (one_lost["config"], one_lost["traffic"], one_lost["chips"]) == (
        "warm1", "maintain-1lost", 1)


def test_the_cells_report_every_maintain_metric_the_six_once_pinned_too():
    b = bench()
    listed = {m["name"]: m.get("workloads") for m in b["end_to_end"]}
    assert set(NEW_CELLS) <= set(listed["rebuild_rate"])
    # since PR 47 a maintain cell whose sets did not hold seal_rate carries
    # the same rate per layer (client.seal_rate; PERF.md section 2)
    (beside,) = [m for m in b["per_layer"] if m["name"] == "client.seal_rate"]
    assert set(NEW_CELLS) <= set(listed["seal_rate"]) | set(beside["workloads"])
    for m in b["per_layer"]:
        listed = set(NEW_CELLS) & set(m["workloads"])
        if m is beside:  # the cells WITHOUT seal_rate, whichever they are
            continue
        if "warm1.maintain" not in m["workloads"]:
            assert not listed, m["name"]
        else:
            assert listed == set(NEW_CELLS), m["name"]
            # appended: the cells that were listed are where they were
            assert m["workloads"][:2] == ["warm1.maintain", "mesh4.maintain"]
    assert {m["name"] for m in b["per_layer"]} >= set(ONCE_PINNED)


def test_the_cells_and_configurations_that_were_there_keep_their_places():
    assert [w["name"] for w in bench()["workloads"][:5]] == [
        "warm1.maintain", "warm1.read-degraded", "mesh4.maintain",
        "warm1.read-1lost", "spread4.read-nodeloss"]
    assert [c["name"] for c in bench()["configs"][:3]] == [
        "warm1", "mesh4", "spread4"]
    (geom,) = [c for c in bench()["configs"] if c["name"] == "geom124"]
    assert geom["file"] == "benchmark/configs/geom124.json"
    assert geom["reduced"] == ["volume.dat_target_bytes", "servers"]


def test_geom124_is_warm1_but_for_the_code():
    warm1, geom = load("configs", "warm1.json"), load("configs", "geom124.json")
    differing = {key for key in set(warm1) | set(geom) if warm1.get(key) != geom.get(key)}
    assert differing == {"name", "source", "deployment", "daemon", "ec",
                         "guarantees", "reduced", "assumed"}
    assert geom["daemon"] == dict(
        warm1["daemon"], args=["-max", "16", "-ec.geometry", "12+4"])
    assert geom["ec"] == dict(warm1["ec"], data_shards=12, parity_shards=4)
    changed = {key for key in geom["guarantees"]
               if geom["guarantees"][key] != warm1["guarantees"][key]}
    assert changed == {"shard_bytes", "reads", "stored_bytes_per_user_byte"}
    assert geom["guarantees"]["stored_bytes_per_user_byte"] == 1.3333
    assert "any 12 of the 16" in geom["guarantees"]["reads"]
    assert sorted(geom["reduced"]) == sorted(warm1["reduced"])
    assert geom["reduced"]["volume.dat_target_bytes"] == \
        warm1["reduced"]["volume.dat_target_bytes"]
    assert set(geom["assumed"]) == set(warm1["assumed"]) | {"ec.matrix"}
    # the same volume: 85 rows of twelve 1 MiB blocks, sixteen shards
    assert geom["volume"] == warm1["volume"]
    from benchmark import reference
    assert reference.shard_size(1_064_846_680, 12, 1 << 30, 1 << 20) == 89_128_960
