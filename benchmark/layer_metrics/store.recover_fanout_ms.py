"""The wall of a recovery's remote phase (``ec.recover.fanout``: one record
a recovery that fetched remote siblings, from the first ask sent to the last
range in hand), mean over the window's recoveries. The asks' own spans
(``ec.read.remote``) overlap inside it; this is what the GET waited."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p90_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover.fanout", "busy_s"),
                        ("ec.recover.fanout", "n"), 1e3)
