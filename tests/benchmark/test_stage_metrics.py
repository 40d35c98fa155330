"""The per-layer metrics read from the program's stage table
(``ec_codec.stages`` of ``/status``, two snapshots around the window): each
reader's arithmetic on a hand-built context, nothing to read where a daemon
serves no table, and the readers on a rehearsal of the cells that list them."""

import pytest

from bench_util import assert_contract_line, bench, run_cell
from bench_util import stage_ctx as ctx_with

from benchmark import layers, stages

BEFORE = {
    "ec.seal": {"n": 1, "busy_s": 5.0, "bytes": 1000},
    "ec.seal.pipeline": {"n": 1, "busy_s": 3.0},
    "ec.seal.read": {"n": 8, "busy_s": 1.0, "bytes": 1000},
    "ec.seal.h2d": {"n": 8, "busy_s": 0.5, "bytes": 10**9},
    "ec.read.remote": {"n": 10, "busy_s": 1.0, "failed": 30, "slept_s": 0.9},
    "ec.recover": {"n": 3, "busy_s": 0.9, "bytes": 300},
    "ec.codec.launch": {"n": 3, "busy_s": 0.03, "bytes": 900},
}
AFTER = {
    "ec.seal": {"n": 3, "busy_s": 15.0, "bytes": 3000},
    "ec.seal.pipeline": {"n": 3, "busy_s": 11.0},
    "ec.seal.read": {"n": 24, "busy_s": 3.0, "bytes": 3000},
    "ec.seal.dispatch": {"n": 16, "busy_s": 1.0, "bytes": 2000},
    "ec.seal.fetch": {"n": 16, "busy_s": 6.0, "bytes": 800},
    "ec.seal.write": {"n": 16, "busy_s": 4.0, "bytes": 2800},
    "ec.seal.h2d": {"n": 24, "busy_s": 2.5, "bytes": 5 * 10**9},
    "ec.seal.d2h": {"n": 16, "busy_s": 0.5, "bytes": 2 * 10**9},
    "ec.seal.hash": {"n": 28, "busy_s": 2.5, "bytes": 4200},
    "ec.seal.commit": {"n": 2, "busy_s": 1.5},
    "ec.rebuild.pipeline": {"n": 2, "busy_s": 4.0},
    "ec.rebuild.read": {"n": 16, "busy_s": 3.0, "bytes": 2000},
    "ec.rebuild.write": {"n": 16, "busy_s": 1.0, "bytes": 800},
    "ec.read.remote": {"n": 50, "busy_s": 3.0, "failed": 150, "slept_s": 2.7},
    "ec.recover": {"n": 13, "busy_s": 3.4, "bytes": 1300},
    "ec.recover.decode": {"n": 10, "busy_s": 0.05},
    "ec.codec.launch": {"n": 13, "busy_s": 0.07, "bytes": 3900},
}
# each definition worked by hand from the two tables above
WANT = {
    "encoder.stage_busy.read": 100 * 2.0 / 8.0,
    "encoder.stage_busy.dispatch": 100 * 1.0 / 8.0,
    "encoder.stage_busy.fetch": 100 * 6.0 / 8.0,
    "encoder.stage_busy.write": 100 * 4.0 / 8.0,
    "encoder.rebuild_stage_busy.read": 100 * 3.0 / 4.0,
    "encoder.rebuild_stage_busy.write": 100 * 1.0 / 4.0,
    "link.h2d_rate": 4.0 / 2.0,
    "link.d2h_rate": 2.0 / 0.5,
    "store.seal_hash_share": 100 * 2.5 / 10.0,
    "store.seal_commit_share": 100 * 1.5 / 10.0,
    "store.degraded_decode_ms": 1000 * 0.05 / 10,
    "codec.launch_ms": 1000 * 0.04 / 10,
    "store.remote_failed_per_get": 120 / 40,
}
NEW = [m for m in bench()["per_layer"] if m["name"] in WANT]


def test_every_metric_of_the_stage_table_is_in_benchmark_json():
    assert sorted(m["name"] for m in NEW) == sorted(WANT)
    assert {m["source"] for m in NEW} == {"program_span"}
    assert {m["layer"] for m in NEW if m["name"].startswith("link.")} == {
        "host-device link"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_value_of_its_definition(name):
    read = layers.load_reader(name).read
    got = read(ctx_with({"stages": BEFORE}, {"stages": AFTER}))
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_where_no_table_is_served(name):
    read = layers.load_reader(name).read
    # a parent-commit daemon, or SWEED_TRACE=0: /status has no stages
    assert read(ctx_with({}, {})) is None
    assert read(ctx_with({"stages": BEFORE}, {})) is None
    # tracing on, but the stage never ran: an empty table, or no progress
    assert read(ctx_with({"stages": {}}, {"stages": {}})) is None
    assert read(ctx_with({"stages": AFTER}, {"stages": AFTER})) in (None, 0)


def test_delta_counts_a_stage_first_seen_inside_the_window_from_zero():
    ctx = ctx_with({"stages": BEFORE}, {"stages": AFTER})
    assert stages.delta(ctx, "ec.seal.d2h", "bytes") == 2 * 10**9
    assert stages.delta(ctx, "ec.seal.commit", "bytes") == 0  # carries none
    assert stages.delta(ctx, "ec.never", "n") is None
    assert stages.ratio(ctx, ("ec.seal", "n"), ("ec.never", "n")) is None


def listed(cell):
    return [m["name"] for m in NEW if cell in m["workloads"]]


@pytest.fixture(scope="module")
def rehearsed():
    """One traced rehearsal a read cell, shared by the cases that hold it
    to one thing each."""
    runs = {}

    def run(cell):
        if cell not in runs:
            runs[cell] = run_cell(cell, 2_147_483_400 + len(cell), trace=1,
                                  seconds=3)
        return runs[cell]

    return run


@pytest.mark.parametrize("held", ["no_attempt_fails", "the_table_answers"])
@pytest.mark.parametrize("cell", ["warm1.read-degraded", "warm1.read-1lost"])
def test_rehearsed_read_cell_counts_asks_for_shards_nobody_holds(
        cell, held, rehearsed):
    """Since ISSUE 29 an ask for a shard nobody holds is answered "nowhere"
    by the EC volume's shard-location table: counted, never attempted."""
    rc, line, out = rehearsed(cell)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    launches = m["codec.launches_per_read"]
    assert launches > 0
    if held == "no_attempt_fails":
        # a count, so a rehearsal prints it: no attempt is made, none fails
        assert line["metrics"]["store.remote_failed_per_get"] == {
            "value": 0, "unit": "count"}
        for name in listed(cell):
            if name != "store.remote_failed_per_get":
                assert f"[layer] {name}: read" in out, out[-3000:]
                assert name not in line["metrics"]
        return
    # a recovery is one launch: the ask before it and, in read-degraded,
    # the three lost siblings inside it — all "nowhere"
    asks = 4 if cell == "warm1.read-degraded" else 1
    absent = line["metrics"]["store.remote_absent_per_get"]
    assert absent["unit"] == "count"
    assert absent["value"] == pytest.approx(asks * launches)
    for name in ("store.degraded_decode_ms", "codec.launch_ms"):  # numbers
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]  # a rehearsal prints counts only


def test_rehearsed_maintain_cell_reads_the_pipelines_legs_and_the_link():
    rc, line, out = run_cell("warm1.maintain", 2_147_483_414, trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    names = listed("warm1.maintain")
    assert len(names) == 10 and names == listed("mesh4.maintain")
    for name in names:  # read from /status, and kept off a rehearsal's line
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]
    # the client's three: the medians are rates, the stalled operations a
    # count (ISSUE 31); the rates themselves are over all of the window
    for name in ("client.seal_rate_p50", "client.rebuild_rate_p50"):
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]
    assert line["metrics"]["client.stalled_ops"]["unit"] == "count"
    assert "client.seal_rate_total" not in out
    # the spans ISSUE 38 gave their readers: a rebuild at RS(10,4) reads ten
    # shards (a count, so a rehearsal prints it), and its two other legs, its
    # link and the seal's pipeline and commit are read from the same table
    assert line["metrics"]["encoder.rebuild_shards_read"] == {
        "value": 10, "unit": "count"}
    for name in ("encoder.rebuild_stage_busy.dispatch",
                 "encoder.rebuild_stage_busy.fetch", "link.rebuild_h2d_rate",
                 "link.rebuild_d2h_rate", "encoder.seal_pipeline_rate",
                 "store.seal_commit_ms"):
        assert f"[layer] {name}: read" in out, out[-3000:]
        assert name not in line["metrics"]
