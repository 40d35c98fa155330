"""Rebuilt bytes copied back over the seconds the copy itself took, after the
result was ready (``ec.rebuild.d2h``: the lost shards of a chunk as one
array)."""
LAYER = "host-device link"
UNIT = "GB/s"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.d2h", "bytes"),
                        ("ec.rebuild.d2h", "busy_s"), 1e-9)
