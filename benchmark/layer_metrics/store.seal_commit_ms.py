"""Mean wall of one seal's commit (``ec.seal.commit``: the ``.vif``, fsync of
every staged file, manifest, renames): the guarantee's price on this
machine in this run, in time and not as a share of a seal that itself got
faster or slower."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.commit", "busy_s"),
                        ("ec.seal.commit", "n"), 1e3)
