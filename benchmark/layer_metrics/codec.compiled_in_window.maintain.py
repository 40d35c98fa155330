"""Compile requests the daemon made inside the window (``/status``): a
warm window makes none, whether the compiler or the cache would answer."""
LAYER = "codec"
UNIT = "count"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_counter"


def read(ctx):
    before, after = ctx["status"]["before"], ctx["status"]["after"]
    return after["compiles"]["requests"] - before["compiles"]["requests"]
