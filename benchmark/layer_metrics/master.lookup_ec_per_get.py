"""``/dir/lookup_ec`` calls at the master (``n`` of ``ec.read.lookup``) per
GET of the window, summed over the survivors: the load the read path puts on
the one master. Since ISSUE 29 it is one call per REFRESH of a volume's
shard-location table (``EcVolume.refresh_locations``: a table gone stale, or
a listed holder that failed), 0.0 in a window whose tables were taken in
warm-up; before, one per attempt of every ask."""
LAYER = "master"
UNIT = "count"
MOVES = "get_p90_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    gets = len(ctx["client"].get("gets", []))
    lookups = stages.delta(ctx, "ec.read.lookup", "n")
    if lookups is None or not gets:
        return None
    return lookups / gets
