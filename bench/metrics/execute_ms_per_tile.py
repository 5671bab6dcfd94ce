"""execute_ms_per_tile (ms/tile): host time of the tile executor, from the
program's own ``execute_wave`` spans (one a wave: the batch built, the
kernel or plane recurrence run, the interiors written back) or
``execute_tile`` spans of the recorded sweep, over its tiles; moves
``sweep_s``.

This is the time the host spends enqueueing the work; the kernel's device
time is ``stencil_kernel_roofline``'s."""
from bench.program_spans import ms_per_tile


def read(ctx):
    return ms_per_tile(ctx, "execute_wave", "execute_tile")
