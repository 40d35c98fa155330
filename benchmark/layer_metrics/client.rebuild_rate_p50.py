"""Median over the window's rebuilds of ``.dat`` bytes over the wall of that
rebuild: the steadier statistic beside ``rebuild_rate``."""
LAYER = "client"
UNIT = "MB/s"
MOVES = "rebuild_rate"
SOURCE = "host_clock"


def read(ctx):
    from benchmark.layers import load_reader

    return load_reader("client.seal_rate_p50").read(ctx, "rebuild_s")
