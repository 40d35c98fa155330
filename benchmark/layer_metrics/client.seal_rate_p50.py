"""Median over the window's seals of ``.dat`` bytes over the wall of that
seal: the steadier statistic beside ``seal_rate``, which is taken over all
seals and so carries every stall. The two apart say a stall was there."""
LAYER = "client"
UNIT = "MB/s"
MOVES = "seal_rate"
SOURCE = "host_clock"
WALLS = "seal_s"


def read(ctx, walls=WALLS):
    from benchmark import stats

    client = ctx["client"]
    seconds = client.get(walls) or []
    if not seconds:
        return None
    return stats.median(client["dat_bytes"] / s / 1e6 for s in seconds)
