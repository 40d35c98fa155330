"""Bytes of the ``.dat`` over the seconds a seal's reader leg was busy with
them (``ec.seal.read``: one span a chunk around the read alone, the wait for
a buffer or a queue slot outside it): what the reader gets from the file
system, to hold beside the file-read floor of the host."""
LAYER = "encoder pipeline"
UNIT = "GB/s"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.read", "bytes"),
                        ("ec.seal.read", "busy_s"), 1e-9)
