"""Median latency, at the client and from when it was due, of the GETs whose
needle has an interval on a lost data shard: the reader who waits for
``_recover_interval``. ``get_p50_ms`` follows the healthy path wherever
fewer than half the GETs recover; this is the median a change to the
recovery moves, and the body of the tail ``get_p90_ms`` reads."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmark import stats

    waits = [
        g["latency_s"] * 1e3 for g in ctx["client"].get("gets", [])
        if g.get("recoveries")
    ]
    return stats.median(waits) if len(waits) >= 20 else None
