"""The kernel's share of its roofline on one chip: the least time the chip
could take for the traced launches' operations and bytes (from their
shapes, ``kernel_model.py``) over the kernel's device time. The bound that
binds is printed on an earlier line."""
LAYER = "kernel"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "device_trace"


def totals(ctx):
    """(summed cost of the traced launches, kernel seconds over all
    devices) or None."""
    from benchmark.kernel_model import gf_matmul_cost
    from benchmark.trace_reduce import spans_named

    trace = ctx["trace"]
    if not trace:
        return None
    cost = {"ops": 0, "bytes": 0, "input_bytes": 0}
    for m in spans_named(trace, "matmul_device"):
        s = m["stats"]
        one = gf_matmul_cost(int(s["rows"]), int(s["k"]), int(s["n"]))
        cost = {key: cost[key] + one[key] for key in cost}
    kernel_s = sum(
        s for name, s in trace["device_op_seconds"].items()
        if name.startswith("gf_matmul_r")
    )
    return (cost, kernel_s) if kernel_s > 0 and cost["ops"] else None


def read(ctx):
    from benchmark.kernel_model import peaks_for, roofline

    got = totals(ctx)
    if got is None:
        return None
    cost, kernel_s = got
    r = roofline(cost, kernel_s, peaks_for(ctx["device_kind"]))
    print(f"[roofline] gf_matmul bound by {r['bound']}: least "
          f"{r['least_s']:.6f} s, took {kernel_s:.6f} s", flush=True)
    return r["share"]
