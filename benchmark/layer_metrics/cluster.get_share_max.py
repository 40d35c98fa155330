"""The busiest survivor's share of the window's GETs (the client's own
record of where it sent each): a third when the draw is even."""
LAYER = "client"
UNIT = "%"
MOVES = "get_p90_ms"
SOURCE = "host_clock"


def read(ctx):
    gets = [g["server"] for g in ctx["client"].get("gets", []) if "server" in g]
    if not gets:
        return None
    return 100.0 * max(gets.count(s) for s in set(gets)) / len(gets)
