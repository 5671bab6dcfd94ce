"""fetch_ms_per_tile (ms/tile): host time of a tile's facet fetch, from the
program's own ``copy_in`` spans of the recorded sweep; moves ``sweep_s``.

A ``copy_in`` span holds the tile's ``halo_resolve``, so the fetch's own
time is this less ``halo_ms_per_tile``."""
from bench.program_spans import ms_per_tile


def read(ctx):
    return ms_per_tile(ctx, "copy_in")
