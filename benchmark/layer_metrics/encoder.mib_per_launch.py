"""``.dat`` MiB a seal's encoder pipeline moves per device launch."""
LAYER = "encoder pipeline"
UNIT = "MiB"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"
OUTER = "write_ec_files"


def read(ctx, outer=OUTER):
    from benchmark.trace_reduce import spans_named

    trace = ctx["trace"]
    outers = spans_named(trace, outer) if trace else []
    if not outers:
        return None
    launches = sum(
        any(o["start"] <= m["start"] < o["end"] for o in outers)
        for m in spans_named(trace, "matmul_device")
    )
    if not launches:
        return None
    return ctx["client"]["dat_bytes"] * len(outers) / launches / (1 << 20)
