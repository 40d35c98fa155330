"""The native attempt that handed the request back (``serve.native.miss``):
from ``_maybe_native`` entered to its ``NATIVE_FALLBACK``, only of requests
that reached a native route. Every GET of an EC volume pays it. Mean over
the window's misses."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.native.miss", "busy_s"),
                        ("serve.native.miss", "n"), 1e3)
