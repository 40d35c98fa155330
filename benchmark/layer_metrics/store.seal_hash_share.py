"""``busy_s`` of ``ec.seal.hash`` over ``busy_s`` of ``ec.seal``. Since PR 27
``ec.seal.hash`` is the seconds inside one shard's running SHA-256 (fourteen
records a seal), spent on the writer's pool WHILE the rows are written: the
threads' seconds summed, overlapped with the pipeline, over the seal's wall.
It is no serial share and can pass 100%: it says the hashing engaged and what
it costs in cores. The serial tail is ``store.seal_tail_share``. (Before
PR 27: the share of a seal spent reading the staged shards back to hash them.)"""
LAYER = "store / commit"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark import stages

    return stages.ratio(ctx, ("ec.seal.hash", "busy_s"),
                        ("ec.seal", "busy_s"), 100.0)
