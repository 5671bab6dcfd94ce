"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; ``dryrun.py`` sets the forced host device count
before calling it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "mesh_for_devices"]


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's target mesh: 16x16 (one v5e-class pod, 256 chips) or
    2x16x16 (two pods, 512 chips).  Axes: 'pod' (DCN) x 'data' (DP/FSDP) x
    'model' (TP/EP)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_for_devices(n: int | None = None, model: int = 1):
    """A small mesh over whatever devices exist (tests, examples)."""
    n = n or len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
