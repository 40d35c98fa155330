"""The per-layer metrics of the encoder's reader leg (``encoder.read_rate``,
``encoder.rebuild_read_rate``, ``encoder.buffer_reuse_share``): each reader's
arithmetic on canned stage tables, and nothing to read from a program whose
reader takes no buffers from a pool (the parent of the PR that brought it)."""

import pytest

from bench_util import bench, maintain_cells, stage_ctx as ctx_with

from benchmark import layers

BEFORE = {
    "ec.seal.read": {"n": 8, "busy_s": 1.0, "bytes": 10**9},
    "ec.seal.buf.new": {"n": 4, "busy_s": 0.001, "bytes": 4000},
    "ec.seal.buf.wait": {"n": 4, "busy_s": 0.5, "bytes": 4000},
    "ec.rebuild.read": {"n": 8, "busy_s": 2.0, "bytes": 10**9},
}
AFTER = {
    "ec.seal.read": {"n": 24, "busy_s": 3.0, "bytes": 4 * 10**9},
    "ec.seal.buf.new": {"n": 12, "busy_s": 0.003, "bytes": 12000},
    "ec.seal.buf.wait": {"n": 12, "busy_s": 0.9, "bytes": 12000},
    "ec.rebuild.read": {"n": 24, "busy_s": 4.5, "bytes": 4 * 10**9},
    "ec.rebuild.buf.new": {"n": 6, "busy_s": 0.002, "bytes": 6000},
    "ec.rebuild.buf.wait": {"n": 10, "busy_s": 0.0, "bytes": 10000},
}
# a program that spans its legs but has no pool: the parent's table
PARENT = {name: row for name, row in AFTER.items() if ".buf." not in name}
# each definition worked by hand from the two tables above
WANT = {
    "encoder.read_rate": 3.0 / 2.0,
    "encoder.rebuild_read_rate": 3.0 / 2.5,
    "encoder.buffer_reuse_share": 100 * (8 + 10) / (8 + 8 + 6 + 10),
}
# every cell that seals and rebuilds runs the reader leg (ISSUE 38: the
# list is computed from the traffic files, so a later cell joins it)
CELLS = maintain_cells()


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_is_listed_for_every_maintain_cell(name):
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    reader = layers.load_reader(name)
    assert entry["workloads"] == CELLS
    assert entry["layer"] == reader.LAYER == "encoder pipeline"
    assert entry["unit"] == reader.UNIT and entry["moves"] == reader.MOVES
    assert entry["source"] == reader.SOURCE == "program_span"
    assert entry["better"] == "higher"


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_value_of_its_definition(name):
    read = layers.load_reader(name).read
    got = read(ctx_with({"stages": BEFORE}, {"stages": AFTER}))
    assert got == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_where_no_table_is_served(name):
    read = layers.load_reader(name).read
    assert read(ctx_with({}, {})) is None
    assert read(ctx_with({"stages": BEFORE}, {})) is None
    assert read(ctx_with({"stages": {}}, {"stages": {}})) is None
    assert read(ctx_with({"stages": AFTER}, {"stages": AFTER})) is None


def test_the_reuse_share_is_nothing_on_a_program_without_a_pool():
    read = layers.load_reader("encoder.buffer_reuse_share").read
    assert read(ctx_with({"stages": {}}, {"stages": PARENT})) is None
    # while the rates read from the parent's spans as from the change's
    rate = layers.load_reader("encoder.read_rate").read
    assert rate(ctx_with({"stages": {}}, {"stages": PARENT})) == (
        pytest.approx(4.0 / 3.0))


def test_the_reuse_share_counts_a_window_that_only_allocated_as_zero():
    read = layers.load_reader("encoder.buffer_reuse_share").read
    only_new = {"ec.seal.buf.new": {"n": 3, "busy_s": 0.0, "bytes": 3000}}
    assert read(ctx_with({"stages": {}}, {"stages": only_new})) == 0.0
