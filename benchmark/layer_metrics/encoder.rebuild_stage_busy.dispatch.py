"""Share of a rebuild's pipeline wall (``ec.rebuild.pipeline``) that its
``dispatch`` leg was busy (``ec.rebuild.dispatch``: the dispatch thread's
``buf.any()``, ``device_put`` call and kernel launch). The legs overlap, so
the shares do not sum to 100; the highest is the leg that bounds a rebuild."""
LAYER = "encoder pipeline"
UNIT = "%"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.dispatch", "busy_s"),
                        ("ec.rebuild.pipeline", "busy_s"), 100.0)
