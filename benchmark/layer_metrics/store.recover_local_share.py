"""Share of the window's recoveries whose wanted shard's own local group
sufficed (``local`` over ``n`` of ``ec.recover.plan``, one record a recovery
by the read-set planner): 0 on a Reed-Solomon volume, and on an LRC volume
the recoveries of a shard lost alone among its group's seven."""
LAYER = "store / commit"
UNIT = "%"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover.plan", "local"),
                        ("ec.recover.plan", "n"), 100.0)
