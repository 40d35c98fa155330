"""``store.remote_absent_per_get`` (ISSUE 29): the reader's arithmetic on a
hand-built context, what it gives under a program that keeps no
shard-location table, its entry in BENCHMARK.json, and the three read cells
rehearsed against what a degraded read does now — an ask for a shard nobody
holds is answered "nowhere" by the table: counted, never attempted."""

import json
import re

import pytest

from bench_util import assert_contract_line, bench, run_cell

from benchmark import layers

NAME = "store.remote_absent_per_get"
READ_CELLS = ["warm1.read-degraded", "warm1.read-1lost", "spread4.read-nodeloss"]
BEFORE = {"ec.read.remote": {"n": 10, "busy_s": 1.0, "failed": 0, "absent": 6},
          "ec.recover": {"n": 3, "busy_s": 0.9}}
AFTER = {"ec.read.remote": {"n": 150, "busy_s": 1.5, "failed": 0, "absent": 76,
                            "ok": 60},
         "ec.recover": {"n": 20, "busy_s": 1.4}}


def ctx_with(before, after, gets=40):
    codec = {"compiles": {"requests": 0}, "launches": {}}
    return {
        "trace": None, "cell": "x.y", "device_kind": "TPU v5 lite",
        "client": {"gets": [{}] * gets},
        "status": {"before": dict(codec, **before), "after": dict(codec, **after)},
    }


def test_the_metric_is_declared_as_the_issue_names_it():
    entry = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert entry == [bench()["per_layer"][-1]]  # appended, nothing moved
    assert entry[0] == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_span", "layer": "store / commit",
        "moves": "get_p95_ms", "workloads": READ_CELLS,
    }
    reader = layers.load_reader(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "store / commit", "count", "get_p95_ms", "program_span")


def test_reader_gives_the_value_of_its_definition():
    read = layers.load_reader(NAME).read
    got = read(ctx_with({"stages": BEFORE}, {"stages": AFTER}))
    assert got == pytest.approx((76 - 6) / 40, rel=1e-12)


def test_a_program_that_keeps_no_table_reads_zero_and_does_not_raise():
    read = layers.load_reader(NAME).read
    # the parent of the PR that brought the table: the stage ran, no ``absent``
    old_before = {"ec.read.remote": {"n": 10, "busy_s": 1.0, "failed": 30}}
    old_after = {"ec.read.remote": {"n": 50, "busy_s": 3.0, "failed": 150}}
    assert read(ctx_with({"stages": old_before}, {"stages": old_after})) == 0.0


def test_reader_finds_nothing_where_no_table_of_stages_is_served():
    read = layers.load_reader(NAME).read
    assert read(ctx_with({}, {})) is None  # SWEED_TRACE=0
    assert read(ctx_with({"stages": BEFORE}, {})) is None
    assert read(ctx_with({"stages": {}}, {"stages": {}})) is None  # never ran
    assert read(ctx_with({"stages": BEFORE}, {"stages": AFTER}, gets=0)) is None


@pytest.mark.parametrize("cell", READ_CELLS[:2])
def test_rehearsed_read_cell_counts_the_asks_the_table_answers(cell):
    rc, line, out = run_cell(cell, 2_147_483_900 + len(cell), trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["metrics"][NAME]["unit"] == "count"
    launches = m["codec.launches_per_read"]
    # a recovery is one launch: the ask before it and, in read-degraded,
    # the three lost siblings inside it — all "nowhere", none attempted
    asks = 4 if cell == "warm1.read-degraded" else 1
    assert launches > 0
    assert m[NAME] == pytest.approx(asks * launches)
    assert m["store.remote_failed_per_get"] == 0
    for name in ("store.degraded_remote_ms", "store.degraded_decode_ms",
                 "codec.launch_ms"):  # a number still, with no failed ask in it
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]  # a rehearsal prints counts only


def test_rehearsed_cluster_cell_reads_live_shards_and_asks_no_dead_one():
    cell = READ_CELLS[2]
    rc, line, out = run_cell(cell, 2_147_483_929, trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["device"]["count"] == 4
    spread = json.loads(re.search(r"^\[spread\] (.*)$", out, re.M).group(1))
    assert sorted(spread.values()) == [[0, 4, 8, 12], [1, 5, 9, 13], [2, 6, 10], [3, 7, 11]]
    assert re.search(r"^\[kill\] server \d \(shards \[0, 4, 8, 12\]\) SIGKILLed", out, re.M)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["store.remote_ok_per_get"] > 0  # the remote path did the work
    assert 6 <= m["store.recover_remote_siblings"] <= 7
    # the dead server's shards: four asks a recovery, answered by the table
    assert m[NAME] == pytest.approx(4 * m["codec.launches_per_read"])
    assert m["store.remote_failed_per_get"] == 0
    # the master is asked when a table is taken, not per ask
    assert m["master.lookup_ec_per_get"] < 0.1 * m["store.remote_ok_per_get"]
    assert m["codec.compiled_in_window.reads"] == 0
    for name in ("store.remote_read_ms", "peer.shard_serve_ms",
                 "cluster.get_share_max", "store.degraded_remote_ms",
                 "store.recovering_get_p50_ms", "codec.launch_ms"):
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]
