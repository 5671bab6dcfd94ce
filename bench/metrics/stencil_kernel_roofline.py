"""stencil_kernel_roofline (%): the least time the chip needs to move the
tile kernel's bytes, over the kernel's device time in the profiled window;
moves ``sweep_s``.

Bytes (``bench/work.py``): each executed tile reads its (w + t) halo box
and writes its t interior, once.  The kernel is every Mosaic custom call
in the window; on this path that is the stencil tile executor.  The
kernel does a few operations per byte, so the bandwidth bound binds."""


def read(ctx):
    if not ctx.trace.kernel_calls:
        return None
    tiles = ctx.layer["tiles_per_sweep"] * ctx.layer["units"]
    least_s = tiles * ctx.layer["kernel_tile_bytes"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace.kernel_s
