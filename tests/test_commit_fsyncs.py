"""The commit counts its fsyncs (ISSUE 39): ``StagedCommit.commit`` adds to
the stage span it runs in how many staged files it fsync'd and how many of
those took ``SLOW_FSYNC_S`` or more; outside a span it records nothing."""

from __future__ import annotations

import os

import pytest

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.trace import STAGES
from seaweedfs_tpu.storage import commit
from seaweedfs_tpu.storage.commit import SLOW_FSYNC_S, StagedCommit


def staged(tmp_path, n):
    sc = StagedCommit(str(tmp_path / "vol_1"), "t")
    for i in range(n):
        with open(sc.stage(str(tmp_path / f"vol_1.f{i:02d}")), "wb") as f:
            f.write(b"x" * (i + 1))
    return sc


def slow_clock(monkeypatch, walls):
    """``fsync_file`` syncs nothing and takes ``walls[i]`` on the commit's
    clock: the i-th staged file's fsync."""
    now = [100.0]
    left = list(walls)
    synced = []

    def fsync_file(path):
        synced.append(os.path.basename(path))
        now[0] += left.pop(0)

    monkeypatch.setattr(commit, "fsync_file", fsync_file)
    monkeypatch.setattr(commit.time, "perf_counter", lambda: now[0])
    return synced


def test_the_threshold_is_one_constant():
    assert SLOW_FSYNC_S == 0.2


@pytest.mark.parametrize("walls,slow", [
    ([0.0] * 16, 0),
    ([0.0, 0.25, 0.0, 0.25, 0.0, 0.0], 2),
    ([0.2, 0.19999], 1),  # the threshold itself is slow
    ([3.0], 1),
])
def test_a_commit_in_a_stage_counts_its_fsyncs_and_the_slow_ones(
        tmp_path, monkeypatch, walls, slow):
    sc = staged(tmp_path, len(walls))
    synced = slow_clock(monkeypatch, walls)
    before = STAGES.snapshot().get("ec.test.commit", {})
    with trace.stage_span("ec.test.commit", quiet=True) as span:
        sc.commit()
    assert span.tags["fsyncs"] == len(walls) == len(synced)
    assert span.tags["slow_fsyncs"] == slow
    row = STAGES.snapshot()["ec.test.commit"]
    assert row["fsyncs"] - before.get("fsyncs", 0) == len(walls)
    assert row["slow_fsyncs"] - before.get("slow_fsyncs", 0) == slow
    # the guarantee untouched: every staged file hardened, then renamed
    assert sorted(synced) == sorted(f"vol_1.f{i:02d}.tmp" for i in range(len(walls)))
    for i in range(len(walls)):
        assert os.path.getsize(tmp_path / f"vol_1.f{i:02d}") == i + 1
    assert not os.path.exists(sc.manifest_path)


def test_a_commit_outside_any_span_records_nothing_and_raises_nothing(
        tmp_path, monkeypatch):
    sc = staged(tmp_path, 3)
    slow_clock(monkeypatch, [0.0, 0.3, 0.0])
    def counted():  # whatever row counts fsyncs (the table is the process's)
        return {name: row["fsyncs"] for name, row in STAGES.snapshot().items()
                if "fsyncs" in row}

    before = counted()
    assert trace.current_span() is None
    sc.commit()
    assert counted() == before
    assert os.path.getsize(tmp_path / "vol_1.f02") == 3


def test_with_tracing_off_a_commit_counts_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("SWEED_TRACE", "0")
    sc = staged(tmp_path, 2)
    before = STAGES.snapshot()
    with trace.stage_span("ec.test.commit", quiet=True) as span:
        sc.commit()
    assert span is None and STAGES.snapshot() == before
    assert os.path.getsize(tmp_path / "vol_1.f01") == 2


def test_a_real_fsync_is_timed_on_the_real_clock(tmp_path):
    sc = staged(tmp_path, 4)
    with trace.stage_span("ec.test.commit", quiet=True) as span:
        sc.commit()
    assert span.tags["fsyncs"] == 4
    assert 0 <= span.tags["slow_fsyncs"] <= 4  # a CI disk may stall
