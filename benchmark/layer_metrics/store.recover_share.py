"""Share of the traced GETs that recovered at least one interval on the
device."""
LAYER = "store / commit"
UNIT = "%"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark.trace_reduce import inside, spans_named

    trace = ctx["trace"]
    handlers = spans_named(trace, "handler") if trace else []
    if not handlers:
        return None
    recoveries = spans_named(trace, "_recover_interval")
    hit = sum(
        any(r["thread"] == h["thread"] and inside(r, h) for r in recoveries)
        for h in handlers
    )
    return 100.0 * hit / len(handlers)
