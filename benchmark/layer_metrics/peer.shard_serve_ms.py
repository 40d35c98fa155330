"""Mean wall of one ``/admin/ec/shard_read`` at the server that holds the
shard (``ec.shard.serve``: the read of the range alone), over the
survivors. Beside ``store.remote_read_ms`` it says how much of a remote
read is the holder's disk and how much the two processes' HTTP work."""
LAYER = "peer"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.shard.serve", "busy_s"),
                        ("ec.shard.serve", "n"), 1e3)
