"""fetch_table_bytes (B): bytes the compiled fetch's plan holds on the
device, its gather and scatter tables and each tile's class and shift,
from the program's own ``fetch_table_bytes`` counter of the sweep recorded
with ``trace=True`` outside the profiled window (added once a sweep by
each executor that runs the compiled fetch); moves ``setup_s``, which
pays for building and uploading the plan.

``None`` where the program keeps no such counter."""


def read(ctx):
    rec = ctx.layer.get("recorder")
    if rec is None or "fetch_table_bytes" not in rec.counters:
        return None
    return rec.counters["fetch_table_bytes"]
