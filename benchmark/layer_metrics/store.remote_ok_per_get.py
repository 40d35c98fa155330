"""Remote shard reads that were answered (``ok`` of ``ec.read.remote``) per GET
of the window, summed over the survivors: ranges fetched from the server
that holds the shard, for a healthy read of a shard that is not local and
for the live siblings of a recovery."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    after = (ctx["status"]["after"].get("stages") or {}).get("ec.read.remote", {})
    if "ok" not in after or not gets:  # a program that does not count them
        return None
    return stages.delta(ctx, "ec.read.remote", "ok") / gets
