"""The window's fsyncs of staged files that took 0.2 s or more (``slow_fsyncs``
of ``ec.seal.commit``): a stalled disk under a seal, which the mean of
``store.seal_commit_ms`` cannot tell from a dear one. A count over the
window, not a ratio; nothing from a program whose commit counts no fsyncs."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    if not stages.delta(ctx, "ec.seal.commit", "fsyncs"):
        return None
    return stages.delta(ctx, "ec.seal.commit", "slow_fsyncs")
