"""Median over the window's seals of ``.dat`` bytes over the wall of that
seal: the steadier statistic beside ``seal_rate``, which is taken over all
seals and so carries every stall. The two apart say a stall was there, and
``client.stalled_ops`` counts them."""
LAYER = "client"
UNIT = "MB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "host_clock"


def read(ctx):
    from benchmark.generators.maintain_cycle import median_rate

    client = ctx["client"]
    return median_rate(client.get("dat_bytes"), client.get("seal_s") or [])
