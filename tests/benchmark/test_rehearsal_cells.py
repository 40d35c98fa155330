"""Each cell's command, end to end on the CPU at a tiny size with the
kernel interpreted: the control flow and the bytes, never a speed."""

import pytest

from bench_util import assert_contract_line, bench, copy_benchmark, run_cell

CELLS = [w["name"] for w in bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_prints_the_contract_line(cell):
    rc, line, out = run_cell(cell, seed=2_147_483_000 + CELLS.index(cell))
    assert rc == 0, out[-3000:]
    assert_contract_line(line)
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # and it says so
    assert "[compare] " in out  # each number compared beside its limit


@pytest.mark.parametrize("cell", ["warm1.maintain", "warm1.read-degraded"])
def test_without_a_tpu_and_without_the_rehearsal_flag_no_result(cell):
    rc, line, out = run_cell(cell, seed=77, rehearsal=False)
    assert rc != 0
    assert line is None, out[-2000:]


def test_without_the_program_beside_it_no_result(tmp_path):
    root = copy_benchmark(str(tmp_path), with_program=False)
    rc, line, out = run_cell("warm1.maintain", seed=78, root=root)
    assert rc != 0 and line is None, out[-2000:]
