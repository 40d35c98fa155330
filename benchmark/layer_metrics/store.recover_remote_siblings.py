"""Siblings one recovery fetched from other servers (``n`` of
``ec.recover.remote`` over ``n`` of ``ec.recover``): of the ten ranges a
decode needs, those that were not on the recovering server's own disks."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p90_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover.remote", "n"), ("ec.recover", "n"))
