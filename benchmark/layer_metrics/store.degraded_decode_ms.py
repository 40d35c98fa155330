"""Mean time one recovered interval spends in ``codec.reconstruct``
(``ec.recover.decode``: invert, stack, pad, launch, copy back), per
``ec.recover``."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover.decode", "busy_s"),
                        ("ec.recover", "n"), 1e3)
