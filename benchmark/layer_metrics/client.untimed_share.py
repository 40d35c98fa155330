"""Share of a maintain window outside both clocks: the harness's own steps
between a seal and a rebuild (shards deleted, rebuilt shards hashed, the
volume reset, ``os.sync()``). A stall that lands there moves neither rate;
it shows here."""
LAYER = "client"
UNIT = "%"
MOVES = "seal_rate"
SOURCE = "host_clock"


def read(ctx):
    client = ctx["client"]
    window_s = client.get("window_s")
    if not window_s or not client.get("seal_s"):
        return None
    timed = sum(client["seal_s"]) + sum(client["rebuild_s"])
    return 100.0 * (1.0 - timed / window_s)
