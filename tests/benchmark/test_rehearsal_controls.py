"""The comparison that decides ``correct`` has to fail when the timed path
is broken underneath; a traced run reads its layers; and a later PR's cell,
configuration, mix and layer metric are found as files, editing none."""

import json
import os
import re

import pytest

from bench_util import assert_contract_line, copy_benchmark, run_cell


@pytest.mark.parametrize("cell,compared", [
    ("warm1.maintain", "seals_whose_vif_sums_differ_from_reference"),
    ("warm1.read-degraded", "needles_failed_or_differing_from_what_was_written"),
    ("warm1.read-1lost", "needles_failed_or_differing_from_what_was_written"),
])
def test_wrong_codec_comes_out_not_correct(cell, compared):
    """One coefficient of every matrix handed to the device is altered
    where the answer is produced; the rest of the run is the cell's own."""
    rc, line, out = run_cell(cell, 2_147_483_100 + len(cell), "--control",
                             "wrong-codec", seconds=2)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is False
    failed = [l for l in out.splitlines() if l.startswith("[compare]") and "FAILED" in l]
    assert any(compared in l for l in failed), failed


@pytest.mark.parametrize("cell,read,counted", [
    ("warm1.maintain",
     ["store.seal_tail_share", "encoder.mib_per_launch",
      "encoder.rebuild_mib_per_launch", "client.seal_rate_p50",
      "client.rebuild_rate_p50", "client.untimed_share"],
     "codec.compiled_in_window.maintain"),
    ("warm1.read-degraded",
     ["store.recover_ms", "store.recover_share", "store.recovering_get_p50_ms"],
     "codec.launches_per_read"),
])
def test_traced_run_reads_its_layers(cell, read, counted):
    rc, line, out = run_cell(cell, 2_147_483_200 + len(cell), trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    assert counted in line["metrics"]
    for name in read:  # read from the spans, and kept off a rehearsal's line
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]


KINDS = ["config", "traffic", "layer_metric"]


@pytest.fixture(scope="module")
def dropped_in(tmp_path_factory):
    """A copy of the benchmark to which a later PR has added one cell: a
    configuration, a traffic mix and a per-layer metric, as new files and
    new entries of BENCHMARK.json. No file that was there is edited."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("later_pr")))
    b = os.path.join(root, "benchmark")
    before = {
        os.path.join(d, p): os.path.getmtime(os.path.join(d, p))
        for d, _, files in os.walk(b) for p in files
    }
    with open(os.path.join(b, "configs", "warm1.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "later1"
    cfg["rehearsal"]["volume"]["dat_target_bytes"] = 8 << 20
    with open(os.path.join(b, "configs", "later1.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "read-degraded.json")) as f:
        mix = json.load(f)
    mix["lost_shards"] = [1]
    with open(os.path.join(b, "traffic", "read-1st-lost.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "layer_metrics", "client.gets_sent.py"), "w") as f:
        f.write(
            'LAYER = "client"\nUNIT = "count"\nMOVES = "get_p50_ms"\n'
            'SOURCE = "program_counter"\n\n\ndef read(ctx):\n'
            '    return len(ctx["client"]["gets"])\n'
        )
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "later1", "source": "a later PR", "reduced": [],
        "file": "benchmark/configs/later1.json", "why": "dropped in",
    })
    bench["workloads"].append({
        "name": "later1.read-1st-lost", "config": "later1",
        "traffic": "read-1st-lost", "chips": 1, "why": "dropped in",
    })
    bench["per_layer"].append({
        "name": "client.gets_sent", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client", "moves": "get_p50_ms",
        "workloads": ["later1.read-1st-lost"],
    })
    for m in bench["end_to_end"]:
        if m["name"].startswith("get_"):
            m["workloads"].append("later1.read-1st-lost")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, line, out = run_cell("later1.read-1st-lost", 2_147_483_300, root=root,
                             trace=1, seconds=2)
    edited = [p for p, at in before.items() if os.path.getmtime(p) != at]
    return {"rc": rc, "line": line, "out": out, "edited": edited}


@pytest.mark.parametrize("kind", KINDS)
def test_a_later_prs_files_are_found_without_editing_one(dropped_in, kind):
    assert dropped_in["rc"] == 0, dropped_in["out"][-3000:]
    assert dropped_in["edited"] == []
    line, out = dropped_in["line"], dropped_in["out"]
    assert line["correct"] is True, out[-3000:]
    if kind == "config":  # its smaller rehearsal volume was the one loaded
        loaded = re.search(r"\[load\] \d+ needles, \.dat (\d+) bytes", out)
        assert int(loaded.group(1)) <= 8 << 20, out[-3000:]
    elif kind == "traffic":  # one missing row, not read-degraded's three
        assert "x 1 missing rows" in out, out[-3000:]
    else:
        assert line["metrics"]["client.gets_sent"] == {
            "value": line["attempted"], "unit": "count"}
