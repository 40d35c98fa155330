"""Attempts at remote shard reads that raised (``failed`` of
``ec.read.remote``) per GET of the window. Since ISSUE 29 an attempt is made
only at a holder the EC volume's shard-location table LISTS (or when the
lookup itself could not be made): an ask for a shard nobody holds is answered
"nowhere" and counted by ``store.remote_absent_per_get``. So this reads 0 in
every cell; above 0 it is a peer's or the master's fault. Before ISSUE 29 it
was three failed attempts for every such ask."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    failed = stages.delta(ctx, "ec.read.remote", "failed")
    if failed is None or not gets:
        return None
    return failed / gets
