"""Mean wall of one recovery on an LRC(12,2,2) volume (``ec.recover@12+2+2``,
the row the stage table keeps for the recoveries whose ``geometry`` tag says
so): six sibling reads and ``r1_k6`` where the local group sufficed, twelve
and ``r1_k12`` where not. Nothing from a program that keeps no row a code."""
LAYER = "store / commit"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.recover@12+2+2", "busy_s"),
                        ("ec.recover@12+2+2", "n"), 1e3)
