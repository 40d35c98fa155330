"""Bytes of the surviving shards over the seconds a rebuild's reader leg was
busy with them (``ec.rebuild.read``: ten shards a chunk)."""
LAYER = "encoder pipeline"
UNIT = "GB/s"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.read", "bytes"),
                        ("ec.rebuild.read", "busy_s"), 1e-9)
