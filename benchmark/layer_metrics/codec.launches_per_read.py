"""Device launches per GET of the window (``/status`` launches over the
client's count)."""
LAYER = "codec"
UNIT = "count"
MOVES = "get_p50_ms"
SOURCE = "program_counter"


def read(ctx):
    gets = len(ctx["client"].get("gets", []))
    if not gets:
        return None
    before, after = ctx["status"]["before"], ctx["status"]["after"]
    launched = sum(after["launches"].values()) - sum(before["launches"].values())
    return launched / gets
