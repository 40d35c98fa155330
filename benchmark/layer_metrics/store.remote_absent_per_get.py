"""Asks for a shard that the EC volume's shard-location table answered
"nowhere" (``absent`` of ``ec.read.remote``: no attempt, no sleep, no
lookup) per GET of the window: how often a degraded read believes the
master instead of asking again. 0 from a program that keeps no table."""
LAYER = "store / commit"
UNIT = "count"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    absent = stages.delta(ctx, "ec.read.remote", "absent")
    if absent is None or not gets:
        return None
    return absent / gets
