"""Share of a seal (``ec.seal``) spent in the commit (``ec.seal.commit``: the
``.vif``, fsync of every staged file, manifest, renames): the guarantee
itself, which no change may take out."""
LAYER = "store / commit"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.commit", "busy_s"),
                        ("ec.seal", "busy_s"), 100.0)
