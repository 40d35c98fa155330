"""A maintain window is a schedule (ISSUE 47): the due times and the loop's
bookkeeping under a fake clock, no daemon. Cycle ``i`` is due at
``i * seconds / cycles``; the seconds slept are in neither clock and in
``paused_s``; a late cycle starts the next at once and is counted; the
window makes at most ``cycles`` cycles and ends with its seconds (a
rehearsal's tiny window runs back to back that way); ``client.untimed_share``
leaves the pause out; every maintain traffic file states ``cycles``; and
``seal_rate`` is end to end in the cells whose sets held it and the per-layer
``client.seal_rate``, the same number, in the others."""

import json
import os

import pytest

from bench_util import ROOT, bench, maintain_cells

from benchmark import layers
from benchmark.generators.maintain_cycle import due_s, rates, window

GB = 1_000_000_000


class FakeClock:
    """Time that moves only when a cycle works or the generator sleeps."""

    def __init__(self):
        self.now = 1000.0  # no window starts at nought
        self.slept: list[float] = []

    def __call__(self) -> float:
        return self.now

    def sleep(self, s: float) -> None:
        assert s > 0
        self.slept.append(s)
        self.now += s


def drive(seconds, cycles, seal, rebuild, between=0.1, stop_after=None):
    """A window whose cycle ``i`` seals for ``seal[i]``, rebuilds for
    ``rebuild[i]`` and spends ``between`` on the harness's own steps, each
    clocked as ``maintain_cycle.cycle`` clocks them: around the operation."""
    clock = FakeClock()
    began, seal_s, rebuild_s = [], [], []
    t0 = clock()

    def one_cycle(i):
        began.append(clock() - t0)
        for took, log in ((seal[i], seal_s), (rebuild[i], rebuild_s)):
            t = clock()
            clock.now += took
            log.append(clock() - t)
        clock.now += between
        return stop_after is None or i + 1 < stop_after

    paced = window(seconds, cycles, one_cycle, clock=clock, sleep=clock.sleep)
    client = {"dat_bytes": GB, "seal_s": seal_s, "rebuild_s": rebuild_s, **paced}
    return {"began": began, "clock": clock, "client": client, **paced}


@pytest.mark.parametrize("seconds,cycles", [(51, 16), (51, 12), (10, 4), (3.0, 16)])
def test_cycle_i_is_due_at_i_periods_from_the_windows_start(seconds, cycles):
    assert due_s(0, seconds, cycles) == 0
    assert due_s(1, 51, 16) == pytest.approx(3.1875)  # the cells' 3.19 s
    for i in range(cycles):
        assert due_s(i, seconds, cycles) == pytest.approx(i * seconds / cycles)
        assert due_s(i, seconds, cycles) < seconds  # every one inside the window
    # a window whose cycles are all shorter than the period begins each on time
    short = seconds / cycles / 2
    w = drive(seconds, cycles, [short / 2] * cycles, [short / 4] * cycles,
              between=short / 4)
    assert w["began"] == pytest.approx(
        [due_s(i, seconds, cycles) for i in range(cycles)])
    assert w["begun"] == cycles and w["late_cycles"] == 0


@pytest.mark.parametrize("faster", [1.0, 1.5, 3.0])
def test_a_faster_program_makes_the_same_cycles_and_the_pause_is_in_no_clock(faster):
    seal, rebuild = [1.2 / faster] * 16, [0.4 / faster] * 16
    w = drive(51, 16, seal, rebuild, between=0.3)
    assert w["begun"] == 16 and w["late_cycles"] == 0
    client = w["client"]
    # what the two clocks hold is the operations alone, to the digit
    assert client["seal_s"] == pytest.approx(seal)
    assert client["rebuild_s"] == pytest.approx(rebuild)
    got = rates(GB, client["seal_s"], client["rebuild_s"])
    assert got["seal_rate"] == pytest.approx(1000 * faster / 1.2)
    assert got["rebuild_rate"] == pytest.approx(1000 * faster / 0.4)
    # the slept seconds are paused_s, and with the clocks and the harness's
    # own steps they make up the window: nothing is counted twice or lost
    assert w["paused_s"] == pytest.approx(sum(w["clock"].slept))
    assert len(w["clock"].slept) == 15  # before every cycle but the first
    worked = sum(seal) + sum(rebuild) + 16 * 0.3
    assert w["window_s"] == pytest.approx(worked + w["paused_s"])
    # the last cycle begins at 15 periods and the window ends with it
    assert w["window_s"] == pytest.approx(
        due_s(15, 51, 16) + seal[0] + rebuild[0] + 0.3)


def test_a_late_cycle_starts_the_next_at_once_and_is_counted():
    seal = [1.0] * 16
    seal[3] = 3.0  # one stalled seal: cycle 3 ends after cycle 4 was due
    w = drive(51, 16, seal, [0.5] * 16, between=0.2)
    period = 51 / 16
    ended_3 = 3 * period + 3.0 + 0.5 + 0.2
    assert ended_3 > due_s(4, 51, 16)
    assert w["began"][4] == pytest.approx(ended_3)  # at once: no sleep
    assert w["began"][5] == pytest.approx(due_s(5, 51, 16))  # and back on time
    assert w["late_cycles"] == 1 and w["begun"] == 16
    assert len(w["clock"].slept) == 14  # not before the first, not before the late one
    # the stall is in the rate, as in a window without a schedule
    assert rates(GB, w["client"]["seal_s"], w["client"]["rebuild_s"])[
        "seal_rate"] == pytest.approx(16 * 1000 / 18.0)
    assert layers.load_reader("client.late_cycles").read(w) == 1
    assert layers.load_reader("client.stalled_ops").read(w) == 1


def test_a_window_makes_at_most_its_cycles_and_ends_with_its_seconds():
    # fast cycles: sixteen, and the window closes before its seconds do
    w = drive(51, 16, [0.5] * 40, [0.2] * 40)
    assert w["begun"] == 16 and w["window_s"] < 51
    # slow cycles (4 s for a period of 3.19): every one but the first is
    # late, none begins once the seconds are over, the one begun is finished
    w = drive(51, 16, [3.0] * 40, [0.9] * 40, between=0.1)
    assert w["begun"] == 13 and w["late_cycles"] == 12 and w["paused_s"] == 0
    assert w["began"][-1] < 51 < w["window_s"] == pytest.approx(13 * 4.0)
    # a rehearsal (seconds 1.5, a cycle of 2 s): one cycle, as before PR 47
    w = drive(1.5, 16, [1.5] * 40, [0.5] * 40, between=0.0)
    assert w["begun"] == 1 and w["late_cycles"] == 0
    w = drive(3.0, 16, [1.5] * 40, [0.5] * 40, between=0.0)
    assert w["begun"] == 2 and w["late_cycles"] == 1 and w["paused_s"] == 0
    # a cycle that fails ends the window
    assert drive(51, 16, [0.5] * 40, [0.2] * 40, stop_after=3)["begun"] == 3


@pytest.mark.parametrize("stalled_s,made,late", [(8.0, 16, 4), (20.0, 11, 1)])
def test_a_stall_long_enough_costs_the_window_its_last_cycles(stalled_s, made, late):
    """The window ends with its seconds: behind one seal of 8 s the cycles
    catch up with the schedule (four begin late) and all sixteen are made;
    behind one of 20 s in the tenth cycle the eleventh begins late, ends
    after the 51 s and is the last (``geom124.maintain``, seed 2147547302,
    made fifteen on the chip, twice: PERF.md section 6)."""
    seal = [1.0] * 16
    seal[9] = stalled_s
    w = drive(51, 16, seal, [0.5] * 16, between=0.2)
    assert (w["begun"], w["late_cycles"]) == (made, late)
    assert w["began"][-1] < 51
    # every seal made is in the rate, the stalled one too
    got = rates(GB, w["client"]["seal_s"], w["client"]["rebuild_s"])
    assert got["seal_rate"] == pytest.approx(made * 1000 / (made - 1 + stalled_s))


def test_untimed_share_of_a_window_with_a_pause_is_that_of_the_window_without():
    read = layers.load_reader("client.untimed_share").read
    paced = drive(51, 16, [1.2] * 16, [0.4] * 16, between=0.4)
    # the same cycles back to back: a period of 1 s, so every cycle is late
    raced = drive(16, 16, [1.2] * 16, [0.4] * 16, between=0.4)
    assert raced["begun"] == 8 and raced["late_cycles"] == 7
    assert paced["paused_s"] > 15 and raced["paused_s"] == 0
    assert paced["client"]["seal_s"][:8] == pytest.approx(raced["client"]["seal_s"])
    assert read(paced) == pytest.approx(read(raced))
    assert read(raced) == pytest.approx(100 * 0.4 / 2.0)  # the harness's steps
    # a client that says nothing of a pause (a line from before PR 47)
    old = {"client": {k: v for k, v in raced["client"].items() if k != "paused_s"}}
    assert read(old) == pytest.approx(read(raced))
    assert layers.load_reader("client.late_cycles").read({"client": {}}) is None


def test_every_maintain_traffic_file_states_its_cycles_and_the_reader_is_declared():
    cells = {w["name"]: w for w in bench()["workloads"]}
    for at, name in enumerate(maintain_cells()):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cells[name]["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert isinstance(mix["cycles"], int) and mix["cycles"] > 0, name
        if at < 5:  # the five that were there: sixteen, one every 51 / 16 s
            assert mix["cycles"] == 16, name
            assert "16 a window" in mix["why"] and "3.19 s" in mix["why"]
            assert "16 " in cells[name]["why"], name
            assert bench()["run_seconds"] / mix["cycles"] == pytest.approx(3.1875)
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == "client.late_cycles"]
    assert entry == {"name": "client.late_cycles", "unit": "count",
                     "better": "lower", "source": "host_clock", "layer": "client",
                     "moves": "rebuild_rate", "workloads": maintain_cells()}


def left_cells() -> list[str]:
    """The maintain cells where ``seal_rate`` is no end-to-end metric: those
    the per-layer ``client.seal_rate`` lists (PERF.md section 2)."""
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == "client.seal_rate"]
    return entry["workloads"]


LEFT = left_cells()


def test_seal_rate_is_end_to_end_where_its_sets_held_and_per_layer_in_the_others():
    b = bench()
    listed = {m["name"]: m.get("workloads") for m in b["end_to_end"]}
    (entry,) = [m for m in b["per_layer"] if m["name"] == "client.seal_rate"]
    assert {"warm1.maintain", "geom124.maintain"} <= set(LEFT)  # PR 47's two
    assert entry == {"name": "client.seal_rate", "unit": "MB/s",
                     "better": "higher", "source": "host_clock", "layer": "client",
                     "moves": "rebuild_rate", "workloads": LEFT}
    # every maintain cell reports the seal's rate once: under a bound or beside
    assert sorted(listed["seal_rate"] + entry["workloads"]) == sorted(maintain_cells())
    assert not set(listed["seal_rate"]) & set(entry["workloads"])
    assert listed["rebuild_rate"] == maintain_cells()
    # the seal of each cell that left is sealed under the bound by a cell
    # that stayed: the same configuration, or the same shapes (12 rows, 16 files)
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    shape = lambda cell: _seal_shape(configs[cells[cell]["config"]])  # noqa: E731
    kept = {shape(c) for c in listed["seal_rate"]}
    for cell in LEFT:
        assert shape(cell) in kept, cell
    # both seal shapes stay under the bound, one of them on one chip
    assert len(kept) >= 2 and any(cells[c]["chips"] == 1 for c in listed["seal_rate"])


def _seal_shape(config: dict) -> tuple[int, int]:
    """(rows a seal reads, shard files it writes) of a configuration."""
    with open(os.path.join(ROOT, config["file"])) as f:
        ec = json.load(f)["ec"]
    return ec["data_shards"], ec["data_shards"] + ec["parity_shards"]


@pytest.mark.parametrize("moved", [
    m["name"] for m in bench()["per_layer"]
    if set(m["workloads"]) & set(LEFT) and m["name"] != "client.seal_rate"])
def test_a_reader_of_a_cell_that_left_names_the_rate_every_maintain_cell_reports(moved):
    """A metric may list only cells that report the metric it moves, so the
    readers the five maintain cells share name ``rebuild_rate`` since PR 47,
    as the read cells' name ``get_p50_ms`` since PR 31 — and none of them
    went silent in a cell that left ``seal_rate``."""
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == moved]
    assert entry["moves"] == "rebuild_rate"
    assert entry["workloads"] == maintain_cells()
    assert layers.load_reader(moved).MOVES == "rebuild_rate"


def test_the_seal_rate_beside_is_the_rate_itself_and_on_every_runs_line():
    w = drive(51, 16, [1.0] * 15 + [3.0], [0.5] * 16)
    got = rates(GB, w["client"]["seal_s"], w["client"]["rebuild_s"])
    assert layers.load_reader("client.seal_rate").read(w) == got["seal_rate"]
    assert got["seal_rate"] == pytest.approx(16 * 1000 / 18.0)  # the stall is in it
    assert layers.load_reader("client.seal_rate").read({"client": {}}) is None
    import inspect

    from benchmark.generators import maintain_cycle
    body = inspect.getsource(maintain_cycle.run_cell)
    assert '"client.seal_rate": end_to_end["seal_rate"]' in body
    assert '"client.paused_s"' not in body  # no metric-style name without a reader
