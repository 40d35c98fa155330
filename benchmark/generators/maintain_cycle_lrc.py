"""Traffic kind ``maintain-cycle-lrc``: ``maintain-cycle``'s whole cycles of
seal + rebuild (its ``run_cell``, and with it its ``cycle``, ``rates`` and
``beside``) on a volume sealed with LRC(12,2,2), every comparison made
against ``benchmark/reference_lrc.py`` — and one more, which holds the
mechanism: the rebuilds of the window read the set of shards the reference
plans (the six others of the lost shard's local group), no other and no
more bytes than those shards hold. A program that rebuilds the right bytes
from twelve shards is RS(12,4) under another name, and not ``correct`` here.
"""

from __future__ import annotations

import os
import time

from .. import reference_lrc, stages
from ..harness import Run, say
from . import maintain_cycle

READ_SET = "rebuilds_that_read_another_set_than_the_reference_plans"


def reference_sums(run: Run) -> dict:
    """``Run.reference_sums`` with this configuration's reference."""
    t = time.monotonic()
    ref = reference_lrc.shard_sums(
        run.kept_dat, run.ec, threads=min(12, os.cpu_count() or 4)
    )
    run.reference_s = time.monotonic() - t
    say(f"[reference] {run.total} shard sums of {ref['shard_bytes']} "
        f"bytes in {run.reference_s:.2f} s (not set-up)")
    return ref


def read_set_faults(status: dict, rebuilds: int, planned: int,
                    shard_bytes: int) -> int:
    """The window's rebuilds that did not read the reference's read set, by
    the program's own stage table (``ec_codec.stages`` of ``/status``, the
    window's two snapshots): one ``ec.rebuild.plan`` a rebuild whose
    ``width`` is the ``planned`` number of shards, and ``ec.rebuild.read``
    moved at most that many shards' bytes a rebuild (holes are not read).
    The table holds sums, so a window that breaks either counts every one of
    its rebuilds; so does a program that serves no such stage."""
    ctx = {"status": status}
    plans = stages.delta(ctx, "ec.rebuild.plan", "n")
    width = stages.delta(ctx, "ec.rebuild.plan", "width")
    read = stages.delta(ctx, "ec.rebuild.read", "bytes")
    as_planned = (
        plans == rebuilds
        and width == planned * rebuilds
        and read is not None
        and read <= planned * shard_bytes * rebuilds
    )
    say(f"[read-set] {rebuilds} rebuilds: ec.rebuild.plan n {plans}, width "
        f"{width} (the reference plans {planned} a rebuild), ec.rebuild.read "
        f"{read} bytes (at most {planned * shard_bytes * rebuilds})")
    return 0 if as_planned else rebuilds


def run_cell(run: Run) -> dict:
    ref: dict = {}

    def sums() -> dict:
        ref.update(reference_sums(run))
        return ref

    run.reference_sums = sums  # the one step of maintain-cycle that names RS
    out = maintain_cycle.run_cell(run)
    run.check.count(READ_SET, read_set_faults(
        out["status"], out["counts"]["rebuilds"],
        len(reference_lrc.read_set(run.mix["lost_shards"])),
        ref["shard_bytes"],
    ))
    return out
