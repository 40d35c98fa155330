"""Share of the chunks of the window's seals and rebuilds that the reader
read into a recycled buffer (``ec.<op>.buf.wait``) rather than a newly
allocated one (``ec.<op>.buf.new``): whether the pool engages. Nothing to
read from a program whose reader takes no buffers from a pool."""
LAYER = "encoder pipeline"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"

OPS = ("ec.seal", "ec.rebuild")


def read(ctx):
    from benchmark import stages

    def taken(how):
        counts = [stages.delta(ctx, f"{op}.buf.{how}", "n") for op in OPS]
        return sum(n for n in counts if n is not None)

    recycled, made = taken("wait"), taken("new")
    if not recycled + made:
        return None
    return 100.0 * recycled / (recycled + made)
