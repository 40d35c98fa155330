"""The reply (``serve.reply``): from the handler's return to ``_reply``'s —
the head, the body into the connection's flume and its back-pressure. Mean
over the window's bridged requests."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.reply", "busy_s"),
                        ("serve.reply", "n"), 1e3)
