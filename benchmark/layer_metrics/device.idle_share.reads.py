"""Share of the traced read window in which no operation ran on the
device."""
LAYER = "device"
UNIT = "%"
MOVES = "get_p50_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmark.layers import load_reader

    return load_reader("device.idle_share.maintain").read(ctx)
