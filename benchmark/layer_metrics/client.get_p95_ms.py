"""The 95th percentile of the window's GET latencies: the read cells'
end-to-end tail until ISSUE 31, kept so that the ledger's history can be
followed across the change. The stratified request list makes exactly 5% of
a window's GETs 2-4 MiB needles at any rate, so this percentile stands on
the step between two size classes and spreads by 6-22% run to run (PERF.md
section 2); the end-to-end tail is ``get_p90_ms``, inside the mid class."""
LAYER = "client"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmark import stats

    lat_ms = [g["latency_s"] * 1e3 for g in ctx["client"].get("gets", [])]
    return stats.percentile_or_none(lat_ms, 95)
