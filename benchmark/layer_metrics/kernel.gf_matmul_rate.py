"""Input bytes through the kernel over its device time, per chip."""
LAYER = "kernel"
UNIT = "GB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "device_trace"


def read(ctx):
    from benchmark.layers import load_reader

    got = load_reader("kernel.gf_matmul_roofline").totals(ctx)
    if got is None:
        return None
    cost, kernel_s = got
    return cost["input_bytes"] / kernel_s / 1e9
