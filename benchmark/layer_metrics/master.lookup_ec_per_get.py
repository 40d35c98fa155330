"""``/dir/lookup_ec`` calls at the master (``n`` of ``ec.read.lookup``, one
per attempt of every ask) per GET of the window, summed over the survivors:
the load the read path puts on the one master."""
LAYER = "master"
UNIT = "count"
MOVES = "get_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    lookups = stages.delta(ctx, "ec.read.lookup", "n")
    if lookups is None or not gets:
        return None
    return lookups / gets
