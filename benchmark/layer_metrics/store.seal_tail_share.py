"""Share of a seal (``Store.ec_encode_volume``) spent after
``write_ec_files`` has returned. Since PR 27 that is the ``.ecx`` and the
commit alone (``.vif``, fsyncs, manifest, renames): the shards are hashed as
they are written, inside the pipeline. Before, it held the read back and
SHA-256 of the 14 shards too."""
LAYER = "store / commit"
UNIT = "%"
MOVES = "rebuild_rate"  # the rate every maintain cell reports (PERF.md section 2)
SOURCE = "program_span"


def read(ctx):
    from benchmark.trace_reduce import inside, spans_named

    trace = ctx["trace"]
    if not trace:
        return None
    shares = []
    for seal in spans_named(trace, "ec_encode_volume"):
        writes = [w for w in spans_named(trace, "write_ec_files") if inside(w, seal)]
        if writes:
            tail = seal["end"] - max(w["end"] for w in writes)
            shares.append(100.0 * tail / (seal["end"] - seal["start"]))
    return sum(shares) / len(shares) if shares else None
