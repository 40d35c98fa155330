"""The cycles of a scheduled maintain window that began after they were due:
the one before ended later than ``seconds / cycles`` after it was due itself,
so the next began at once. 0 in a window that kept its pace — every window
whose cycle is shorter than the period (3.19 s at sixteen a window of 51 s).
Above 0 the window was, for those cycles, the race it was until PR 47: the
machine or the program was too slow for the schedule, the window may have
made fewer cycles than the mix states (the window ends with its seconds),
and its rates are read beside ``client.stalled_ops``."""
LAYER = "client"
UNIT = "count"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "host_clock"


def read(ctx):
    return ctx["client"].get("late_cycles")
