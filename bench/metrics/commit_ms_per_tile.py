"""commit_ms_per_tile (ms/tile): host time of a tile's facet commit, from
the program's own ``copy_out`` spans of the recorded sweep; moves
``sweep_s``."""
from bench.program_spans import ms_per_tile


def read(ctx):
    return ms_per_tile(ctx, "copy_out")
