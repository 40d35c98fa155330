"""From the first line of the request on its worker to its span opening
(``serve.parse``): the handler built, ``parse_request``, admission. Mean
over the window's bridged requests."""
LAYER = "serving core"
UNIT = "ms"
MOVES = "get_p50_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("serve.parse", "busy_s"),
                        ("serve.parse", "n"), 1e3)
