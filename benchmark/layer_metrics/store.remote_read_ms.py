"""Mean wall of one answered remote shard read at the asker (``ok_s`` over
``ok`` of ``ec.read.remote``): the attempt that was answered alone — the
lookup at the master and the range from the holder — without the failed
attempts and the back-off an ask may have spent before it."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.read.remote", "ok_s"),
                        ("ec.read.remote", "ok"), 1e3)
