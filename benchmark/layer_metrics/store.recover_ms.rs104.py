"""Mean wall of one recovery on an RS(10,4) volume (``ec.recover@10+4``, the
row the stage table keeps for the recoveries whose ``geometry`` tag says
so): sibling reads, plan, one synchronous launch at ``r1_k10``, copy back.
Nothing from a program that keeps no row a code."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover@10+4", "busy_s"),
                        ("ec.recover@10+4", "n"), 1e3)
