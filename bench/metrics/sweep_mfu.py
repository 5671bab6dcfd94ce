"""sweep_mfu (%): the whole sweep's share of the chip's roofline over the
profiled window; moves ``sweep_s``.

The least time of one sweep is the larger of its compulsory bytes over
the HBM bandwidth (each tile reads its flow-in and writes its facets once)
and its operations over the published peak (``bench/work.py``).  Both
count the work, not what an implementation moves, so the share stays
comparable when the kernel is replaced or the sweep fused."""


def read(ctx):
    layer, peaks = ctx.layer, ctx.peaks
    least_s = max(layer["sweep_bytes"] / peaks["hbm_bytes_per_s"],
                  layer["sweep_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * layer["units"] * least_s / ctx.trace.window_s
