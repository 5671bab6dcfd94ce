"""unspanned_idle_share (%): the share of the profiled window in which the
device sat idle while the host ran code that no span inside the
benchmark's ``sweep`` annotation covers; moves ``sweep_s``.

The trace names each idle gap by the innermost host span open
(``bench/trace.py``): gaps under ``sweep`` (one ``CompiledStencil`` call)
or ``window`` (between sweeps) fall outside every program phase and every
JAX dispatch span."""


def read(ctx):
    idle = ctx.trace.idle_by_host
    return 100.0 * (idle.get("sweep", 0.0) + idle.get("window", 0.0)) / ctx.trace.window_s
