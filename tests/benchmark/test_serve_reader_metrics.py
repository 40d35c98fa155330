"""The readers ISSUE 39 brought: the serving core's six legs, a holder's
handler whole, and the commit's slow fsyncs — each reader's arithmetic on one
canned pair of stage tables, nothing to read from a program without the row
or a window in which it did not grow, each entry of BENCHMARK.json, and the
two rehearsals that print them."""

import pytest

from bench_util import assert_contract_line, bench, maintain_cells, run_cell
from bench_util import stage_ctx as ctx_with

from benchmark import layers

SHARD_READ = "GET /admin/ec/shard_read"
BEFORE = {
    "serve.proxy": {"n": 10, "busy_s": 0.05, "connect_s": 0.002},
    "serve.proxy.in": {"n": 10, "busy_s": 0.01},
    "serve.native.miss": {"n": 8, "busy_s": 0.002},
    "serve.queue": {"n": 10, "busy_s": 0.004},
    "serve.parse": {"n": 10, "busy_s": 0.003},
    "serve.reply": {"n": 10, "busy_s": 0.005, "bytes": 10**6},
    "GET /": {"n": 8, "busy_s": 0.01},
    SHARD_READ: {"n": 20, "busy_s": 0.02, "failed": 1},
    "ec.seal.commit": {"n": 1, "busy_s": 0.4, "fsyncs": 16, "slow_fsyncs": 1},
}
AFTER = {
    "serve.proxy": {"n": 110, "busy_s": 0.55, "connect_s": 0.022},
    "serve.proxy.in": {"n": 110, "busy_s": 0.16},
    "serve.native.miss": {"n": 88, "busy_s": 0.018},
    "serve.queue": {"n": 110, "busy_s": 0.054},
    "serve.parse": {"n": 110, "busy_s": 0.028},
    "serve.reply": {"n": 110, "busy_s": 0.045, "bytes": 11 * 10**6},
    "GET /": {"n": 88, "busy_s": 0.11},
    SHARD_READ: {"n": 220, "busy_s": 0.17, "failed": 1},
    "ec.seal.commit": {"n": 5, "busy_s": 1.6, "fsyncs": 80, "slow_fsyncs": 4},
}
# each definition worked by hand from the two tables above, and its row
WANT = {
    "serve.proxy_ms": (1000 * 0.5 / 100, "serve.proxy"),
    "serve.proxy_in_ms": (1000 * 0.15 / 100, "serve.proxy.in"),
    "serve.native_miss_ms": (1000 * 0.016 / 80, "serve.native.miss"),
    "serve.queue_ms": (1000 * 0.05 / 100, "serve.queue"),
    "serve.parse_ms": (1000 * 0.025 / 100, "serve.parse"),
    "serve.reply_ms": (1000 * 0.04 / 100, "serve.reply"),
    "serve.shard_read_ms": (1000 * 0.15 / 200, SHARD_READ),
    "store.seal_slow_fsyncs": (3, "ec.seal.commit"),
}
READS = ["warm1.read-degraded", "warm1.read-1lost", "spread4.read-nodeloss"]
# name: unit, layer, moves; every one lower-is-better and a program_span
ENTRY = {name: ("ms", "serving core", "get_p50_ms")
         for name in WANT if name.startswith("serve.")}
ENTRY["serve.shard_read_ms"] = ("ms", "peer", "get_p90_ms")
ENTRY["store.seal_slow_fsyncs"] = ("count", "store / commit", "rebuild_rate")


def without(table, row):
    return {name: r for name, r in table.items() if name != row}


def test_the_eight_stand_together_after_pr_38s_last_wherever_later_entries_stand():
    names = [m["name"] for m in bench()["per_layer"]]
    at = names.index("store.seal_commit_ms")  # PR 38's last, still before
    assert names[at + 1:at + 9] == [
        "serve.proxy_in_ms", "serve.native_miss_ms", "serve.queue_ms",
        "serve.parse_ms", "serve.reply_ms", "serve.proxy_ms",
        "serve.shard_read_ms", "store.seal_slow_fsyncs"]
    assert len(names) == len(set(names))  # and each of them stands once


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_is_declared_once_for_the_cells_that_run_its_code(name):
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    unit, layer, moves = ENTRY[name]
    cells = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves}
    if name == "store.seal_slow_fsyncs":
        assert cells == maintain_cells()  # every cell that seals, no read cell
    elif name == "serve.shard_read_ms":
        assert cells == ["spread4.read-nodeloss"]  # the one with a holder
    else:
        assert cells[:3] == READS
    reader = layers.load_reader(name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        layer, unit, moves, "program_span")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_value_of_its_definition(name):
    read = layers.load_reader(name).read
    got = read(ctx_with({"stages": BEFORE}, {"stages": AFTER}))
    assert got == pytest.approx(WANT[name][0], rel=1e-12)
    # a row first seen inside the window counts from zero
    row = WANT[name][1]
    from_zero = read(ctx_with({"stages": without(BEFORE, row)}, {"stages": AFTER}))
    assert from_zero is not None and from_zero > 0


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_from_a_program_without_the_row(name):
    read = layers.load_reader(name).read
    row = WANT[name][1]
    # the parent of this PR: the table, not the row
    assert read(ctx_with({"stages": without(BEFORE, row)},
                         {"stages": without(AFTER, row)})) is None
    # SWEED_TRACE=0, or a daemon that serves no table at all
    assert read(ctx_with({}, {})) is None
    assert read(ctx_with({"stages": BEFORE}, {})) is None
    assert read(ctx_with({"stages": {}}, {"stages": {}})) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_window_in_which_the_row_did_not_grow(name):
    read = layers.load_reader(name).read
    assert read(ctx_with({"stages": AFTER}, {"stages": AFTER})) is None
    assert read(ctx_with({"stages": BEFORE}, {"stages": BEFORE})) is None


def test_the_slow_fsyncs_are_a_count_of_the_window_and_may_be_none():
    read = layers.load_reader("store.seal_slow_fsyncs").read
    calm = dict(AFTER, **{"ec.seal.commit": {
        "n": 5, "busy_s": 1.6, "fsyncs": 80, "slow_fsyncs": 1}})
    # fsyncs were counted and none of the window's was slow: 0, not nothing
    assert read(ctx_with({"stages": BEFORE}, {"stages": calm})) == 0
    # the parent's row: a commit span that counts no fsyncs is not read as 0
    old = {"ec.seal.commit": {"n": 1, "busy_s": 0.4}}
    new = {"ec.seal.commit": {"n": 5, "busy_s": 1.6}}
    assert read(ctx_with({"stages": old}, {"stages": new})) is None


def test_the_legs_and_the_request_span_fit_inside_the_proxys_wall():
    """The canned tables hold the relation a traced run is read by: way in,
    miss, queue, parse and the request span (the reply is inside it) do not
    pass ``serve.proxy_ms``; what is left is the engine's way out."""
    ctx = ctx_with({"stages": BEFORE}, {"stages": AFTER})
    legs = sum(layers.load_reader(n).read(ctx) for n in (
        "serve.proxy_in_ms", "serve.native_miss_ms", "serve.queue_ms",
        "serve.parse_ms"))
    request = 1000 * (0.11 - 0.01) / 80
    assert legs + request < layers.load_reader("serve.proxy_ms").read(ctx)


# -- the rehearsals ------------------------------------------------------------------
def test_the_maintain_rehearsal_prints_the_windows_slow_fsyncs():
    rc, line, out = run_cell("warm1.maintain", 2_147_483_539, trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    metrics = line["metrics"]
    # the one count among the eight: on a rehearsal's line. A CI disk may
    # stall, so not held to 0 — to the window's fsyncs, sixteen a seal
    assert metrics["store.seal_slow_fsyncs"]["unit"] == "count"
    seals = metrics["rehearsal.seals"]["value"]
    assert 0 <= metrics["store.seal_slow_fsyncs"]["value"] <= 16 * seals
    assert "[layer] store.seal_commit_ms: read" in out


def test_the_read_rehearsal_reads_the_serving_cores_legs():
    rc, line, out = run_cell("warm1.read-1lost", 2_147_483_540, trace=1, seconds=3)
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    from seaweedfs_tpu.native.turbo import turbo_available

    names = ["serve.queue_ms", "serve.parse_ms", "serve.reply_ms"]
    if turbo_available():  # the engine owns the port: every GET is proxied
        names += ["serve.proxy_in_ms", "serve.proxy_ms"]
    names.append("serve.native_miss_ms")
    for name in names:  # times: read, and kept off a rehearsal's line
        assert f"[layer] {name}: read" in out, (name, out[-3000:])
        assert name not in line["metrics"]
