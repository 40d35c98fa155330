"""Share of a maintain window outside both clocks: the harness's own steps
between a seal and a rebuild (shards deleted, rebuilt shards hashed, the
volume reset, ``os.sync()``). A stall that lands there moves neither rate;
it shows here. The pause of a scheduled window (``paused_s``, the seconds the
generator slept until a cycle was due) is none of the harness's steps: it is
taken out of the window, so the share of a window with a pause is that of
the same window without one."""
LAYER = "client"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "host_clock"


def read(ctx):
    client = ctx["client"]
    window_s = client.get("window_s")
    if not window_s or not client.get("seal_s"):
        return None
    window_s -= client.get("paused_s") or 0.0
    timed = sum(client["seal_s"]) + sum(client["rebuild_s"])
    return 100.0 * (1.0 - timed / window_s)
