"""Devices that hold a piece of the newest launch's result
(``/status`` ``last_output_devices``): whether a launch spreads over the
mesh."""
LAYER = "codec"
UNIT = "count"
MOVES = "seal_rate"
SOURCE = "program_counter"


def read(ctx):
    devices = ctx["status"]["after"].get("last_output_devices")
    return len(devices) if devices is not None else None
