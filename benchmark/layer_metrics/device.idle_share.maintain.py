"""Share of the traced slice in which no operation ran on the device."""
LAYER = "device"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "device_trace"


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
