"""Shards one rebuild reads (``width`` over ``n`` of ``ec.rebuild.plan``, one
record a rebuild by the read-set planner): the code's k for a Reed-Solomon
volume, the lost shard's local group for a local reconstruction code."""
LAYER = "encoder pipeline"
UNIT = "count"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.plan", "width"),
                        ("ec.rebuild.plan", "n"))
