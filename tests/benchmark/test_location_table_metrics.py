"""``store.remote_absent_per_get`` (ISSUE 29): the reader's arithmetic on a
hand-built context, what it gives under a program that keeps no
shard-location table, and its entry in BENCHMARK.json. The three read cells
rehearsed against what a degraded read does now are cases of
``test_stage_metrics.py``'s and ``test_spread4.py``'s rehearsals (ISSUE 31)."""

import pytest

from bench_util import bench, stage_ctx as ctx_with

from benchmark import layers

NAME = "store.remote_absent_per_get"
READ_CELLS = ["warm1.read-degraded", "warm1.read-1lost", "spread4.read-nodeloss"]
BEFORE = {"ec.read.remote": {"n": 10, "busy_s": 1.0, "failed": 0, "absent": 6},
          "ec.recover": {"n": 3, "busy_s": 0.9}}
AFTER = {"ec.read.remote": {"n": 150, "busy_s": 1.5, "failed": 0, "absent": 76,
                            "ok": 60},
         "ec.recover": {"n": 20, "busy_s": 1.4}}


def test_the_metric_is_declared_as_the_issue_names_it():
    entry = [m for m in bench()["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1  # declared once, wherever later entries stand
    # since ISSUE 31 the read cells' end-to-end tail is the 90th percentile,
    # and warm1.read-degraded reports none: a metric read in all three read
    # cells names the latency all three report
    # the three that were there; a later read cell joins by being appended
    assert entry[0].pop("workloads")[:3] == READ_CELLS
    assert entry[0] == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_span", "layer": "store / commit",
        "moves": "get_p50_ms",
    }
    reader = layers.load_reader(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "store / commit", "count", "get_p50_ms", "program_span")


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
