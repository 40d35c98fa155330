"""``.dat`` MiB a rebuild's pipeline moves per device launch."""
LAYER = "encoder pipeline"
UNIT = "MiB"
MOVES = "rebuild_rate"
SOURCE = "program_span"


def read(ctx):
    from benchmark.layers import load_reader

    return load_reader("encoder.mib_per_launch").read(ctx, "rebuild_ec_files")
