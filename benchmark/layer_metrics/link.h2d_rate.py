"""Bytes staged over the seconds from each chunk's ``device_put`` call until
its staged input was ready (``ec.seal.h2d``, timed by a watch thread of its
own: the link's host-to-device rate as a seal drives it)."""
LAYER = "host-device link"
UNIT = "GB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.h2d", "bytes"),
                        ("ec.seal.h2d", "busy_s"), 1e-9)
