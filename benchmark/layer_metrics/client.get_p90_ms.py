"""The 90th percentile of the window's GET latencies in
``warm1.read-degraded``, where it is no end-to-end metric: the check that
refused PR 31's first tree read it 11.7% and 14.2% wide there over two sets
of runs of one tree, more than half of the widest bound the contract allows,
and fixing the work (one layout, one set of needles and gaps for every seed)
left it as wide (PERF.md section 6). At 0.47 of the saturation rate it is
the quarter of the recovering GETs that queue longest behind other
recoveries in one Python process. ``get_p90_ms`` stays the end-to-end tail
of the read cells where it holds (``warm1.read-1lost``,
``spread4.read-nodeloss``); here the same number is on every traced line,
and on every run's ``[readings]`` line and in its ``readings``."""
LAYER = "client"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmark import stats

    lat_ms = [g["latency_s"] * 1e3 for g in ctx["client"].get("gets", [])]
    return stats.percentile_or_none(lat_ms, 90)
