"""launches_per_tile (launches/tile): device program executions in the
profiled window over the tiles the window's sweeps executed; moves
``sweep_s``.  Each launch is one host dispatch of the executor loop."""


def read(ctx):
    tiles = ctx.layer.get("tiles_per_sweep", 0) * ctx.layer.get("units", 0)
    if not tiles or not ctx.trace.launches:
        return None
    return ctx.trace.launches / tiles
