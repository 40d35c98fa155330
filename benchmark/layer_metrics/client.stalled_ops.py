"""The window's seals and rebuilds that took more than twice the median of
their kind: where a stalled run is told from a slow one on its own line.
0 in a calm window. ``seal_rate`` is taken over all of a window's seals, so
one stalled seal of 4.3 s among sixteen of 1.6 s takes 9% off it; this count
beside it says that the loss was one operation's and not every one's."""
LAYER = "client"
UNIT = "count"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "host_clock"


def read(ctx):
    from benchmark.generators.maintain_cycle import stalled_ops

    client = ctx["client"]
    return stalled_ops(client.get("seal_s") or [], client.get("rebuild_s") or [])
