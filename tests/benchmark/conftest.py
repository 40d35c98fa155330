"""Three rehearsal tests of this directory assert what a degraded read did
BEFORE the EC volume kept its shard-location table (ISSUE 29): three failed
attempts for every ask nobody can answer, a lookup at the master per attempt.
The program no longer does either — that is the change — and a PR that is
not a ``benchmark`` PR may add files here but edit none. So the three are
expected to fail, by name, and ``test_location_table_metrics.py`` holds the
same rehearsals to what the program does now. A ``benchmark`` PR rewrites
the three and deletes this file."""

import pytest

OUTDATED = {
    "test_stage_metrics.py::"
    "test_rehearsed_read_cell_counts_asks_for_shards_nobody_holds[warm1.read-degraded]":
        "asserts store.remote_failed_per_get == 3 x asks x launches; it reads 0",
    "test_stage_metrics.py::"
    "test_rehearsed_read_cell_counts_asks_for_shards_nobody_holds[warm1.read-1lost]":
        "asserts store.remote_failed_per_get == 3 x asks x launches; it reads 0",
    "test_spread4.py::test_traced_rehearsal_reads_the_remote_path":
        "asserts store.remote_failed_per_get > 0 and a lookup per answered "
        "remote read; they read 0 and one per refresh",
}


def pytest_collection_modifyitems(items):
    for item in items:
        why = OUTDATED.get(item.nodeid.rsplit("tests/benchmark/", 1)[-1])
        if why is not None:
            item.add_marker(pytest.mark.xfail(reason=f"ISSUE 29: {why}"))
