"""Asks one recovery started side by side (``width`` over ``n`` of
``ec.recover.fanout``): equal to ``store.recover_remote_siblings`` while the
store's pool has room for every ask and none fails, under it where a
recovery had to fetch some of its siblings in turn."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p90_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover.fanout", "width"),
                        ("ec.recover.fanout", "n"))
