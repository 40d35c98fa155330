"""The wait for a worker (``serve.queue``): from the loop's hand-off to the
pool to the first line of the request on a worker thread — the pool's queue
and the GIL, behind recoveries and their sibling threads. Mean over the
window's bridged requests."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.queue", "busy_s"),
                        ("serve.queue", "n"), 1e3)
