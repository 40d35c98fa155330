"""Median over the window's rebuilds of ``.dat`` bytes over the wall of that
rebuild: the steadier statistic beside ``rebuild_rate``."""
LAYER = "client"
UNIT = "MB/s"
MOVES = "rebuild_rate"
SOURCE = "host_clock"


def read(ctx):
    from benchmark.generators.maintain_cycle import median_rate

    client = ctx["client"]
    return median_rate(client.get("dat_bytes"), client.get("rebuild_s") or [])
