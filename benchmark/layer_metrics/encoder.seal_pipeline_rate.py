"""Bytes of the ``.dat`` (``bytes`` of ``ec.seal.read``) over the wall of the
seals' pipelines (``ec.seal.pipeline``): a seal without its commit and
without the verb around it. What a reader's, a link's or a writer's gain is
judged on where the machine's fsyncs swing ``seal_rate``."""
LAYER = "encoder pipeline"
UNIT = "MB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.read", "bytes"),
                        ("ec.seal.pipeline", "busy_s"), 1e-6)
