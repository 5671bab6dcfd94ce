"""Per-tile host time of the program's own spans.

The stencil kind runs one more sweep with ``trace=True`` outside the
profiled window and hands its recorder to the metrics as
``layer["recorder"]``; the program's executor phases (``obs.phase``) are
its spans."""


def ms_per_tile(ctx, *names: str):
    """Milliseconds a tile of the recorded sweep spent in spans named
    ``names``; ``None`` when there is no recorder or no such span."""
    rec = ctx.layer.get("recorder")
    if rec is None:
        return None
    spans = [s for s in rec.spans if s.name in names]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / ctx.layer["tiles_per_sweep"]
