"""Share of a rebuild's pipeline wall (``ec.rebuild.pipeline``) that its
``fetch`` leg was busy (``ec.rebuild.fetch``: the fetch thread's wait for the
result and the copy back of the rebuilt shards as one array). The legs
overlap, so the shares do not sum to 100; the highest bounds a rebuild."""
LAYER = "encoder pipeline"
UNIT = "%"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.fetch", "busy_s"),
                        ("ec.rebuild.pipeline", "busy_s"), 100.0)
