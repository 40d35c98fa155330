"""Attempts at remote shard reads that raised (``failed`` of
``ec.read.remote``) per GET of the window: asks for shards no server holds."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    failed = stages.delta(ctx, "ec.read.remote", "failed")
    if failed is None or not gets:
        return None
    return failed / gets
