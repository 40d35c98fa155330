"""The configuration ``mixed1`` and its cell ``mixed1.read-2codes`` (PR 44):
a node between two codes — an RS(10,4) volume sealed before the operator
changed ``-ec.geometry`` and an LRC(12,2,2) volume sealed since, both
without shards 0, 4, 9, 12, read through one codec — compared with
``benchmark/reference_mixed.py``, and the comparisons that hold the
mechanism: every recovery planned at the reference's width (ten, twelve or
six by volume and wanted shard), both codes launched, each volume served at
the code its ``.vif`` records. Rehearsed on the CPU with the kernel
interpreted: counts and control flow, never a speed."""

import ast
import json
import os
import re

import pytest

from bench_util import ROOT, assert_contract_line, bench, run_cell, stage_ctx

from benchmark import layers, reference_mixed
from benchmark.generators import open_loop_get_2codes as two

CELL = "mixed1.read-2codes"
NEW = ["store.recover_local_share", "store.recover_ms.rs104",
       "store.recover_ms.lrc1222"]
LOST = [0, 4, 9, 12]


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


# -- the rehearsals ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced():
    rc, line, out = run_cell(CELL, 2_147_483_844, trace=1, seconds=3)
    return {"rc": rc, "line": line, "out": out}


def plans_said(out: str) -> dict:
    """``[plans] <row>: <the program's>, the reference <its>`` by row."""
    said = {}
    for row, got, want in re.findall(
            r"^\[plans\] (\S+): (\{.*?\}), the reference (\{.*?\})", out, re.M):
        said[row] = (ast.literal_eval(got), ast.literal_eval(want))
    return said


def test_the_traced_rehearsal_is_correct_and_prints_the_contract_line(traced):
    assert traced["rc"] == 0, traced["out"][-3000:]
    assert_contract_line(traced["line"])
    assert traced["line"]["correct"] is True, traced["out"][-3000:]
    assert traced["line"]["failed"] == 0
    compared = traced["line"]["compared"]
    assert all(row == {"value": 0, "limit": 0} for row in compared.values())
    assert {two.PLANS, two.LAUNCHES, two.CODES,
            "seals_whose_vif_sums_differ_from_reference",
            "needles_failed_or_differing_from_what_was_written",
            "compile_requests_in_window"} <= set(compared)
    metrics = traced["line"]["metrics"]
    assert metrics["codec.compiled_in_window.reads"]["value"] == 0
    assert metrics["rehearsal.gets"]["value"] == traced["line"]["attempted"]
    # three decode shapes behind the one warm-up rule
    assert ("(volume, shards read) [('new', 6), ('new', 12), ('old', 10)]"
            in traced["out"])


def test_the_node_seals_at_its_own_code_and_the_old_volume_keeps_ten_plus_four(traced):
    started = [l for l in traced["out"].splitlines() if l.startswith("[daemon] /")]
    assert len(started) == 2  # the migration: the node as it was, then as it is
    assert "-ec.geometry" not in started[0]
    assert started[1].endswith("-ec.geometry 12+2+2")
    out = traced["out"]
    assert out.index("[seal] old: 14 shards at 10+4") < out.index(started[1]) \
        < out.index("[seal] new: 16 shards at 12+2+2")
    assert "[codes] old: sealed at 10+4, its .vif says 10+4, served at 10+4" in out
    assert ("[codes] new: sealed at 12+2+2, its .vif says 12+2+2, served at 12+2+2"
            in out)
    assert "[reference] old: 14 shard sums" in out
    assert "[reference] new: 16 shard sums" in out


def test_the_windows_recoveries_are_planned_as_the_reference_plans_them(traced):
    said = plans_said(traced["out"])
    assert set(said) == {"ec.recover.plan", "ec.recover.plan@10+4",
                         "ec.recover.plan@12+2+2"}
    for row, (got, want) in said.items():
        assert got == want and want["n"] > 0, row
    rs, lrc = said["ec.recover.plan@10+4"][1], said["ec.recover.plan@12+2+2"][1]
    assert rs["width"] == 10 * rs["n"] and rs["local"] == 0
    assert 0 < lrc["local"] < lrc["n"]
    assert lrc["width"] == 6 * lrc["local"] + 12 * (lrc["n"] - lrc["local"])
    whole = said["ec.recover.plan"][1]
    assert whole == {f: rs[f] + lrc[f] for f in whole}
    grown = ast.literal_eval(re.search(
        r"^\[launches\] by code in the window: (\{.*\})", traced["out"], re.M).group(1))
    assert grown == {"10+4": rs["n"], "12+2+2": lrc["n"]}  # a launch a recovery


def test_the_rehearsal_reads_its_layers_and_the_three_new_ones(traced):
    listing = {m["name"]: m["workloads"] for m in bench()["per_layer"]}
    for name, cells in listing.items():
        reported = (name in traced["line"]["metrics"]
                    or f"[layer] {name}: read" in traced["out"])
        if name == "device.idle_share.reads":  # a device trace: the chip's alone
            reported = f"[layer] {name}: nothing to read" in traced["out"]
        assert reported == (CELL in cells), name
    for name in NEW:
        assert listing[name] == [CELL]
        assert f"[layer] {name}: read" in traced["out"]
    # the launcher's wrappers are not this cell's to read (D3)
    assert CELL not in listing["store.recover_ms"] + listing["store.recover_share"]


def test_wrong_codec_comes_out_not_correct():
    rc, line, out = run_cell(CELL, 2_147_483_845, "--control", "wrong-codec",
                             seconds=2)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is False
    compared = line["compared"]
    # both seals went through the altered matrix, each against its own reference
    assert compared["seals_whose_vif_sums_differ_from_reference"]["value"] == 2
    assert compared["needles_failed_or_differing_from_what_was_written"]["value"] > 0
    # and it planned the reference's read sets at both codes all the same
    assert compared[two.PLANS]["value"] == 0
    assert compared[two.LAUNCHES]["value"] == 0


# -- the comparison that holds the read sets ----------------------------------------------
WANT = {"": {"n": 50, "width": 484, "local": 6},
        "@10+4": {"n": 30, "width": 300, "local": 0},
        "@12+2+2": {"n": 20, "width": 184, "local": 6}}


def snapshots(rows: dict, split: bool = True):
    """Two ``/status`` snapshots between which ``ec.recover.plan`` grew by
    ``rows`` (the key's suffix a row), from something already there."""
    before, after = {}, {}
    for suffix, row in rows.items():
        if suffix and not split:
            continue
        was = {"n": 7, "busy_s": 0.01, "width": 70, "local": 0}
        before["ec.recover.plan" + suffix] = was
        after["ec.recover.plan" + suffix] = {
            "busy_s": 0.02, **{f: was[f] + row[f] for f in row}}
    return {"before": {"stages": before}, "after": {"stages": after}}


def rows(**changed):
    out = {suffix: dict(row) for suffix, row in WANT.items()}
    for key, value in changed.items():
        suffix, field = key.split("__")
        out[{"all": "", "rs": "@10+4", "lrc": "@12+2+2"}[suffix]][field] = value
    return out


@pytest.mark.parametrize("name, status, want", [
    ("as the reference plans", snapshots(rows()), 0),
    ("a program that keeps no row a code: the sums alone", snapshots(rows(), split=False), 0),
    ("the LRC volume read at twelve throughout",
     snapshots(rows(all__width=520, all__local=0, lrc__width=240, lrc__local=0)), 50),
    ("the RS volume planned at twelve, the sums hiding it",
     snapshots(rows(rs__width=360, lrc__width=124)), 30),
    ("the sums off, no row a code", snapshots(rows(all__width=520), split=False), 50),
    ("a recovery without a plan", snapshots(rows(all__n=49, lrc__n=19)), 50),
    ("no ec.recover.plan at all", {"before": {"stages": {}}, "after": {"stages": {}}}, 50),
    ("no stage table", {"before": {}, "after": {}}, 50),
])
def test_a_program_that_plans_another_read_set_has_every_such_recovery_counted(
        name, status, want):
    assert two.plan_faults(status, WANT) == want


def test_a_code_that_launched_nothing_in_the_window_is_counted():
    import types

    vols = [types.SimpleNamespace(code="10+4"), types.SimpleNamespace(code="12+2+2")]
    before = {"geometries": {"10+4": 5, "12+2+2": 9}}
    assert two.launch_faults(before, {"geometries": {"10+4": 8, "12+2+2": 12}}, vols) == 0
    assert two.launch_faults(before, {"geometries": {"10+4": 8, "12+2+2": 9}}, vols) == 1
    assert two.launch_faults({}, {"geometries": {"12+2+2": 3}}, vols) == 1
    assert two.launch_faults({}, {}, vols) == 2  # a codec that counts by no code


# -- the three readers ------------------------------------------------------------------
def reader_ctx(split: bool = True):
    before = {"ec.recover.plan": {"n": 10, "busy_s": 0.0, "width": 100, "local": 0},
              "ec.recover": {"n": 10, "busy_s": 0.05}}
    after = {"ec.recover.plan": {"n": 60, "busy_s": 0.0, "width": 584, "local": 6},
             "ec.recover": {"n": 60, "busy_s": 0.35}}
    if split:
        before["ec.recover@10+4"] = {"n": 10, "busy_s": 0.05}
        after["ec.recover@10+4"] = {"n": 40, "busy_s": 0.23}
        after["ec.recover@12+2+2"] = {"n": 20, "busy_s": 0.12}  # first seen in the window
    return stage_ctx({"stages": before}, {"stages": after})


def test_the_three_readers_give_the_values_of_their_definitions():
    read = {name: layers.load_reader(name).read for name in NEW}
    ctx = reader_ctx()
    assert read["store.recover_local_share"](ctx) == pytest.approx(100 * 6 / 50)
    assert read["store.recover_ms.rs104"](ctx) == pytest.approx(1e3 * 0.18 / 30)
    assert read["store.recover_ms.lrc1222"](ctx) == pytest.approx(1e3 * 0.12 / 20)
    # the parent of the PR that brought the rows by code: its plan rows are
    # read, the two times by code find nothing and do not raise
    parent = reader_ctx(split=False)
    assert read["store.recover_local_share"](parent) == pytest.approx(12.0)
    assert read["store.recover_ms.rs104"](parent) is None
    assert read["store.recover_ms.lrc1222"](parent) is None
    for name in NEW:
        assert read[name](stage_ctx({}, {})) is None  # SWEED_TRACE=0


def test_the_three_stand_together_after_pr_39s_last_and_are_read_from_the_programs_table():
    # together and in order, directly after PR 39's last entry, wherever
    # later PRs' entries stand (ISSUE 47: the fifth pin loosened as the four)
    names = [m["name"] for m in bench()["per_layer"]]
    at = names.index("store.seal_slow_fsyncs") + 1
    entries = bench()["per_layer"][at:at + 3]
    assert [m["name"] for m in entries] == NEW
    for m, (unit, better) in zip(entries, [("%", "higher"), ("ms", "lower"),
                                           ("ms", "lower")]):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_span", "layer": "store / commit",
                     "moves": "get_p50_ms", "workloads": [CELL]}
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".py")) as f:
            source = f.read()
        assert "stages.ratio" in source and "trace_reduce" not in source


# -- the reference ----------------------------------------------------------------------
def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference_mixed.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported |= {"." * node.level + (node.module or "") + ":" + a.name
                         for a in node.names}
    assert imported == {"__future__:annotations", ".:reference", ".:reference_lrc"}


def test_the_reference_gives_three_read_sets_for_the_one_loss():
    cfg = load("configs", "mixed1.json")
    rs, lrc = cfg["ec_before"], cfg["ec"]
    widths = {(reference_mixed.name(ec), s): len(reference_mixed.read_set(ec, s, LOST))
              for ec in (rs, lrc) for s in (0, 4, 9)}
    assert widths == {("10+4", 0): 10, ("10+4", 4): 10, ("10+4", 9): 10,
                      ("12+2+2", 0): 12, ("12+2+2", 4): 12, ("12+2+2", 9): 6}
    # the identity of the configuration: with a group's local parity gone,
    # LRC(12,2,2) reads as much as RS(10,4) does, ten shards a recovery
    assert sum(w for (code, _), w in widths.items() if code == "12+2+2") / 3 == 10
    assert [reference_mixed.is_local(lrc, s, LOST) for s in (0, 4, 9)] == [
        False, False, True]
    assert not any(reference_mixed.is_local(rs, s, LOST) for s in (0, 4, 9))
    assert reference_mixed.decodable(lrc, LOST) and reference_mixed.decodable(rs, LOST)


# -- what BENCHMARK.json and the configuration say ----------------------------------------
def test_the_cell_and_the_configuration_come_after_those_that_were_there():
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    assert cells[:8] == [
        "warm1.maintain", "warm1.read-degraded", "mesh4.maintain",
        "warm1.read-1lost", "spread4.read-nodeloss", "geom124.maintain",
        "warm1.maintain-1lost", "lrc1222.maintain-1lost-local"]
    assert cells.count(CELL) == 1 and cells.index(CELL) >= 8
    cell = b["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixed1", "read-2codes", 1)
    assert len(cell["why"]) <= 200
    configs = [c["name"] for c in b["configs"]]
    assert configs[:5] == ["warm1", "mesh4", "spread4", "geom124", "lrc1222"]
    (mixed,) = [c for c in b["configs"] if c["name"] == "mixed1"]
    assert mixed["file"] == "benchmark/configs/mixed1.json"
    assert mixed["reduced"] == ["volume.dat_target_bytes", "servers", "volumes"]
    assert len(mixed["source"]) <= 200 and len(mixed["why"]) <= 200
    listed = {m["name"]: m.get("workloads") for m in b["end_to_end"]}
    assert listed["get_p50_ms"][:3] == [
        "warm1.read-degraded", "warm1.read-1lost", "spread4.read-nodeloss"]
    assert CELL in listed["get_p50_ms"] and CELL not in listed["get_p90_ms"]
    (p90,) = [m for m in b["per_layer"] if m["name"] == "client.get_p90_ms"]
    assert p90["workloads"][0] == "warm1.read-degraded" and CELL in p90["workloads"]


def test_mixed1_is_warm1_before_and_lrc1222_since():
    warm1, lrc = load("configs", "warm1.json"), load("configs", "lrc1222.json")
    mixed = load("configs", "mixed1.json")
    assert mixed["daemon_before"] == warm1["daemon"] and mixed["ec_before"] == warm1["ec"]
    assert mixed["daemon"] == lrc["daemon"] and mixed["ec"] == lrc["ec"]
    assert mixed["blob_mix"] == warm1["blob_mix"]
    assert mixed["volume"] == {k: v for k, v in warm1["volume"].items()
                               if k != "collection"}
    assert mixed["volumes"] == [
        {"collection": "old", "ec": "ec_before", "sealed_by": "daemon_before"},
        {"collection": "new", "ec": "ec", "sealed_by": "daemon"}]
    assert mixed["guarantees"]["old_volume"] == warm1["guarantees"]
    assert mixed["guarantees"]["new_volume"] == lrc["guarantees"]
    assert ".vif records" in mixed["guarantees"]["code_of_a_volume"]
    assert "0, 4, 9, 12" in mixed["guarantees"]["reads"]
    # two volumes and their shards at once: 2 + 1.4 + 1.3333 of one .dat
    assert 4.74 < mixed["machine"]["peak_disk_factor"] <= 5.5
    assert set(mixed["assumed"]) == set(lrc["assumed"]) | {"volumes.read_share"}
    assert mixed["rehearsal"] == warm1["rehearsal"]
    mix = load("traffic", "read-2codes.json")
    degraded = load("traffic", "read-degraded.json")
    assert mix["kind"] == "open-loop-get-2codes"
    assert mix["lost_shards"] == degraded["lost_shards"] == LOST
    assert (mix["client_threads"], mix["timeout_s"]) == (
        degraded["client_threads"], degraded["timeout_s"])
    assert isinstance(mix["rate_get_per_s"], float) and "swept" in mix["rate_origin"]
    assert f"at {mix['rate_get_per_s']:g}/s" in next(
        w["why"] for w in bench()["workloads"] if w["name"] == CELL)


def test_each_requests_volume_is_drawn_evenly_and_stratified_inside_it():
    import types

    from benchmark import fixture

    sizes = [1000 + 37 * i for i in range(400)]
    vols = [types.SimpleNamespace(loaded=fixture.Loaded(v, [""] * 400, sizes, [""] * 400))
            for v in (1, 2)]
    state = {"volumes": vols, "first": [0, 400]}
    picked = two.request_list(state, 1000, 9)
    assert picked == two.request_list(state, 1000, 9)
    assert picked != two.request_list(state, 1000, 10)
    to_new = sum(i >= 400 for i in picked)
    assert 440 <= to_new <= 560  # a fair coin a request
    for v, lo in zip(vols, (0, 400)):
        own = sorted(sizes[i - lo] for i in picked if lo <= i < lo + 400)
        # one request from each stratum of that volume's needles by size
        assert own[0] < sizes[20] and own[-1] > sizes[-20]
        assert two.located(state, lo)[0] is v and two.located(state, lo + 399) == (v, 399)
