"""How late the load generator sent a GET, against when it was due: a
starved generator must not read as a fast server."""
LAYER = "client"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmark import stats

    lags = [g["lag_s"] * 1e3 for g in ctx["client"].get("gets", [])]
    # a diagnostic of the generator, not a claim about the system: the
    # rule of ten samples beyond a percentile is for the end-to-end tail
    return stats.percentile_or_none(lags, 99, min_beyond=1)
