"""Parity bytes copied back over the seconds the copy itself took, after the
result was ready (``ec.seal.d2h``)."""
LAYER = "host-device link"
UNIT = "GB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.d2h", "bytes"),
                        ("ec.seal.d2h", "busy_s"), 1e-9)
