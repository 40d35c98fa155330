"""Mean wall of one answered remote shard read at the asker (``ok_s`` over
``ok`` of ``ec.read.remote``): the attempt that was answered alone — the
shard-location table (since ISSUE 29 a lookup at the master only when the
table is stale) and the range from the holder — without any earlier attempt
or sleep."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.read.remote", "ok_s"),
                        ("ec.read.remote", "ok"), 1e3)
