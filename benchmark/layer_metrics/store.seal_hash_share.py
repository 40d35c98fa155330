"""Share of a seal (``ec.seal``) spent re-reading the 14 staged shards and
hashing them (``ec.seal.hash``): what hashing while writing can take out."""
LAYER = "store / commit"
UNIT = "%"
MOVES = "seal_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.hash", "busy_s"),
                        ("ec.seal", "busy_s"), 100.0)
