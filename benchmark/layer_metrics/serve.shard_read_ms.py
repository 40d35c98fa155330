"""A holder's handler for an asked range, whole (the request span
``GET /admin/ec/shard_read``), beside ``peer.shard_serve_ms`` (the read
inside it) and ``store.remote_read_ms`` (the asker's wait). Mean over the
window's ranges served."""
LAYER = "peer"
UNIT = "ms"
MOVES = "get_p90_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    span = "GET /admin/ec/shard_read"
    return stages.ratio(ctx, (span, "busy_s"), (span, "n"), 1e3)
