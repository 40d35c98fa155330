"""Compile requests the daemon made inside a read window (``/status``): a
warm window makes none, whether the compiler or the cache would answer."""
LAYER = "codec"
UNIT = "count"
MOVES = "get_p50_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmark.layers import load_reader

    return load_reader("codec.compiled_in_window.maintain").read(ctx)
