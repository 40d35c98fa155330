"""A proxied request's way in (``serve.proxy.in``): from the native engine
having the whole request (its ``X-Sweed-Proxy-T0`` stamp) to the Python
core's loop having the head — the proxy thread's start, the connect, the
accept, the connection's set-up, the send. Mean over the window's proxied
requests."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.proxy.in", "busy_s"),
                        ("serve.proxy.in", "n"), 1e3)
