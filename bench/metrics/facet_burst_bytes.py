"""facet_burst_bytes (B/burst): planned bytes per facet burst over the
sweep recorded with ``trace=True`` outside the profiled window, from the
program's own counters: ``4 * (read_elems + write_elems) / (bursts_read +
bursts_write)`` (4 bytes a float32 value); moves ``sweep_s``.

The counters count what each tile's transfer plan moves, in values: a
field program's point is one value per field, so its bursts are longer
where the facet layout keeps a tile's fields together."""

VALUE_BYTES = 4  # float32, the dtype every configuration runs


def read(ctx):
    rec = ctx.layer.get("recorder")
    if rec is None:
        return None
    c = rec.counters
    bursts = c.get("bursts_read") + c.get("bursts_write")
    if not bursts:
        return None
    return VALUE_BYTES * (c.get("read_elems") + c.get("write_elems")) / bursts
