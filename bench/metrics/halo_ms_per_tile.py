"""halo_ms_per_tile (ms/tile): host time the executor spends resolving a
tile's halo gather maps, from the program's own ``halo_resolve`` spans of
one sweep recorded with ``trace=True`` outside the profiled window; moves
``sweep_s``."""


def read(ctx):
    rec = ctx.layer.get("recorder")
    if rec is None:
        return None
    spans = [s for s in rec.spans if s.name == "halo_resolve"]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / ctx.layer["tiles_per_sweep"]
