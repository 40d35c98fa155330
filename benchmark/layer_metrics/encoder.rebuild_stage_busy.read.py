"""Share of a rebuild's pipeline wall (``ec.rebuild.pipeline``) that its
``read`` leg was busy (``ec.rebuild.read``: the reader thread's reads of ten
surviving shards): one of the two file legs a stalled rebuild implicates."""
LAYER = "encoder pipeline"
UNIT = "%"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.read", "busy_s"),
                        ("ec.rebuild.pipeline", "busy_s"), 100.0)
