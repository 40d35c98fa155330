"""Mean wall of one synchronous device round trip of the codec
(``ec.codec.launch``: stage, launch, copy back): the fixed cost a degraded
read pays per call."""
LAYER = "codec"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.codec.launch", "busy_s"),
                        ("ec.codec.launch", "n"), 1e3)
