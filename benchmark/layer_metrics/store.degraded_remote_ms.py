"""Mean time one recovered interval spends asking other servers for shards
(``ec.read.remote``, back-off sleeps included: the ask before the recovery
and one per missing sibling inside it), per ``ec.recover``."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.read.remote", "busy_s"),
                        ("ec.recover", "n"), 1e3)
