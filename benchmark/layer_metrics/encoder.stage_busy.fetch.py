"""Share of a seal's pipeline wall (``ec.seal.pipeline``) that its ``fetch``
leg was busy (``ec.seal.fetch``: the fetch thread's wait for the staged
input and the result, and the copy back). The legs overlap, so the shares do
not sum to 100; the highest is the leg that bounds a seal."""
LAYER = "encoder pipeline"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.fetch", "busy_s"),
                        ("ec.seal.pipeline", "busy_s"), 100.0)
