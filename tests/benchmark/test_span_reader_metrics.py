"""The readers ISSUE 38 gave the spans four PRs had left unread — a
recovery's fan-out wall and width, a rebuild's read set, dispatch and fetch
legs and link, a seal's pipeline and commit: each reader's arithmetic on one
canned pair of stage tables, nothing to read from a program without the row
or a window in which the stage never ran, and each entry of BENCHMARK.json."""

import pytest

from bench_util import bench, maintain_cells, stage_ctx as ctx_with

from benchmark import layers

BEFORE = {
    "ec.seal.pipeline": {"n": 1, "busy_s": 1.0},
    "ec.seal.read": {"n": 8, "busy_s": 0.5, "bytes": 10**9},
    "ec.seal.commit": {"n": 1, "busy_s": 0.4},
    "ec.rebuild.plan": {"n": 1, "busy_s": 0.001, "width": 6, "local": 1},
    "ec.rebuild.pipeline": {"n": 1, "busy_s": 1.0},
    "ec.rebuild.dispatch": {"n": 8, "busy_s": 0.2, "bytes": 10**9},
    "ec.rebuild.fetch": {"n": 8, "busy_s": 0.5, "bytes": 10**8},
    "ec.rebuild.h2d": {"n": 8, "busy_s": 0.25, "bytes": 10**9},
    "ec.rebuild.d2h": {"n": 8, "busy_s": 0.125, "bytes": 10**8},
    "ec.recover": {"n": 10, "busy_s": 0.3},
    "ec.recover.fanout": {"n": 10, "busy_s": 0.2, "width": 65, "spares": 0},
}
AFTER = {
    "ec.seal.pipeline": {"n": 5, "busy_s": 5.0},
    "ec.seal.read": {"n": 40, "busy_s": 2.5, "bytes": 5 * 10**9},
    "ec.seal.commit": {"n": 5, "busy_s": 1.6},
    "ec.rebuild.plan": {"n": 5, "busy_s": 0.005, "width": 30, "local": 5},
    "ec.rebuild.pipeline": {"n": 5, "busy_s": 4.0},
    "ec.rebuild.dispatch": {"n": 40, "busy_s": 0.8, "bytes": 5 * 10**9},
    "ec.rebuild.fetch": {"n": 40, "busy_s": 2.0, "bytes": 5 * 10**8},
    "ec.rebuild.h2d": {"n": 40, "busy_s": 1.25, "bytes": 5 * 10**9},
    "ec.rebuild.d2h": {"n": 40, "busy_s": 1.125, "bytes": 5 * 10**8},
    "ec.recover": {"n": 60, "busy_s": 1.5},
    "ec.recover.fanout": {"n": 60, "busy_s": 0.95, "width": 400, "spares": 1},
}
# each definition worked by hand from the two tables above, and the row it
# cannot be read without
WANT = {
    "store.recover_fanout_ms": (1000 * 0.75 / 50, "ec.recover.fanout"),
    "store.recover_fanout_width": ((400 - 65) / 50, "ec.recover.fanout"),
    "encoder.rebuild_shards_read": (24 / 4, "ec.rebuild.plan"),
    "encoder.rebuild_stage_busy.dispatch": (100 * 0.6 / 3.0, "ec.rebuild.dispatch"),
    "encoder.rebuild_stage_busy.fetch": (100 * 1.5 / 3.0, "ec.rebuild.fetch"),
    "link.rebuild_h2d_rate": (4.0 / 1.0, "ec.rebuild.h2d"),
    "link.rebuild_d2h_rate": (0.4 / 1.0, "ec.rebuild.d2h"),
    "encoder.seal_pipeline_rate": (4000.0 / 4.0, "ec.seal.pipeline"),
    "store.seal_commit_ms": (1000 * 1.2 / 4, "ec.seal.commit"),
}
# name: unit, better, layer, moves; every one a program_span
ENTRY = {
    "store.recover_fanout_ms": ("ms", "lower", "store / commit", "get_p90_ms"),
    "store.recover_fanout_width": ("count", "higher", "store / commit", "get_p90_ms"),
    "encoder.rebuild_shards_read": ("count", "lower", "encoder pipeline", "rebuild_rate"),
    "encoder.rebuild_stage_busy.dispatch": ("%", "lower", "encoder pipeline", "rebuild_rate"),
    "encoder.rebuild_stage_busy.fetch": ("%", "lower", "encoder pipeline", "rebuild_rate"),
    "link.rebuild_h2d_rate": ("GB/s", "higher", "host-device link", "rebuild_rate"),
    "link.rebuild_d2h_rate": ("GB/s", "higher", "host-device link", "rebuild_rate"),
    "encoder.seal_pipeline_rate": ("MB/s", "higher", "encoder pipeline", "rebuild_rate"),
    "store.seal_commit_ms": ("ms", "lower", "store / commit", "rebuild_rate"),
}


def without(table, row):
    return {name: r for name, r in table.items() if name != row}


# the maintain cells as this PR found them: later ones come after, and join
# the lists below by being appended (no list is held to a written one)
FIVE = ["warm1.maintain", "mesh4.maintain", "geom124.maintain",
        "warm1.maintain-1lost", "lrc1222.maintain-1lost-local"]


def test_the_maintain_cells_are_found_by_their_traffic_files_kind():
    found = maintain_cells()
    assert found[:5] == FIVE
    # the rule, whatever later PRs add: the cells whose generator is a
    # maintain cycle are those that report rebuild_rate, and no read cell;
    # seal_rate those of them whose sets held it (PR 47), the others carry
    # the same number per layer
    listed = {m["name"]: m.get("workloads") for m in bench()["end_to_end"]}
    assert sorted(found) == sorted(listed["rebuild_rate"])
    (beside,) = [m for m in bench()["per_layer"] if m["name"] == "client.seal_rate"]
    assert sorted(found) == sorted(listed["seal_rate"] + beside["workloads"])
    assert not set(found) & set(listed["get_p50_ms"])


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_is_declared_once_for_the_cells_that_run_its_code(name):
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    unit, better, layer, moves = ENTRY[name]
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_span", "layer": layer, "moves": moves}
    if name.startswith("store.recover_fanout"):
        # the cell whose siblings are remote; a later cluster cell may join
        assert "spread4.read-nodeloss" in cells
    else:
        # the five that were there, then any later cell that seals and
        # rebuilds; never a read cell
        assert cells[:5] == FIVE and set(cells) <= set(maintain_cells())
    reader = layers.load_reader(name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        layer, unit, moves, "program_span")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_value_of_its_definition(name):
    read = layers.load_reader(name).read
    got = read(ctx_with({"stages": BEFORE}, {"stages": AFTER}))
    assert got == pytest.approx(WANT[name][0], rel=1e-12)
    # a stage first seen inside the window counts from zero
    row = WANT[name][1]
    from_zero = read(ctx_with({"stages": without(BEFORE, row)}, {"stages": AFTER}))
    assert from_zero is not None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_from_a_program_without_the_row(name):
    read = layers.load_reader(name).read
    row = WANT[name][1]
    # the parent of the PR that brought the span: the table, not the row
    assert read(ctx_with({"stages": without(BEFORE, row)},
                         {"stages": without(AFTER, row)})) is None
    # SWEED_TRACE=0, or a daemon that serves no table at all
    assert read(ctx_with({}, {})) is None
    assert read(ctx_with({"stages": BEFORE}, {})) is None
    assert read(ctx_with({"stages": {}}, {"stages": {}})) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_window_in_which_the_stage_never_ran(name):
    read = layers.load_reader(name).read
    # n did not grow, busy_s did not grow: no share of a peak is 0, and no
    # time or count is made up
    assert read(ctx_with({"stages": AFTER}, {"stages": AFTER})) is None
    assert read(ctx_with({"stages": BEFORE}, {"stages": BEFORE})) is None


def test_the_retired_metric_is_gone_with_its_reader():
    assert "store.degraded_remote_ms" not in [
        m["name"] for m in bench()["per_layer"]]
    with pytest.raises(FileNotFoundError):
        layers.load_reader("store.degraded_remote_ms")
