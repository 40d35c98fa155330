"""Median wall of one ``Store._recover_interval``: sibling reads, padding,
one synchronous launch, copy back."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stats
    from benchmark.trace_reduce import spans_named

    trace = ctx["trace"]
    walls = [
        (s["end"] - s["start"]) * 1e3
        for s in spans_named(trace, "_recover_interval")
    ] if trace else []
    return stats.median(walls) if walls else None
