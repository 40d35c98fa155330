"""The whole of a proxied request inside the daemon (the ``serve.proxy`` row,
the native engine's own sums): from the decision to proxy to the last byte
sent to the client. Minus the five legs and the request span's mean it is
the engine's way out. Mean over the window's proxied requests."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.proxy", "busy_s"),
                        ("serve.proxy", "n"), 1e3)
