"""Bytes staged over the seconds from each chunk's ``device_put`` call until
its staged input was ready (``ec.rebuild.h2d``, timed by a watch thread of
its own: the link's host-to-device rate as a rebuild drives it)."""
LAYER = "host-device link"
UNIT = "GB/s"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.rebuild.h2d", "bytes"),
                        ("ec.rebuild.h2d", "busy_s"), 1e-9)
