"""device_idle_share (%): the share of the profiled window in which no
operation ran on the device; moves ``sweep_s``.

Busy is the union of the device's ``XLA Ops`` and ``Async XLA Ops``
intervals in the window (``bench/trace.py``)."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
