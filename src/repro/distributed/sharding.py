"""Sharding rules and constraint helpers (DP / TP / EP / SP / pod).

Axis conventions (launch/mesh.py):

* ``pod``   — outer data-parallel axis across pods (DCN-connected);
* ``data``  — intra-pod data parallelism + FSDP parameter sharding;
* ``model`` — tensor / expert parallelism (ICI-connected).

All model code expresses shardings as logical `PartitionSpec`s built from
the helpers here.  Two robustness rules keep the 40-cell dry-run matrix
green:

1. ``constrain`` / ``sanitize_spec`` silently drop a mesh axis from a dim
   whose size it does not divide (e.g. batch=1 long-context cells cannot
   shard batch; the spec degrades to replication on that dim instead of a
   compile error) — mirroring MaxText's logical-axis fallback.
2. A ``None`` mesh (unit tests, single-device smoke) turns every constraint
   into a no-op, so model code is mesh-agnostic.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "P",
    "set_mesh",
    "get_mesh",
    "use_mesh",
    "constrain",
    "sanitize_spec",
    "sanitize_tree",
    "named",
    "DP_AXES",
    "batch_spec",
    "port_mesh",
    "shard_facets",
]

_STATE = threading.local()

# logical data-parallel axes; ``pod`` is silently absent on single-pod meshes
DP_AXES = ("pod", "data")


def set_mesh(mesh: Mesh | None) -> None:
    _STATE.mesh = mesh


def get_mesh() -> Mesh | None:
    return getattr(_STATE, "mesh", None)


def get_dp_axes() -> tuple:
    return getattr(_STATE, "dp_axes", DP_AXES)


def get_drop_axes() -> frozenset:
    return getattr(_STATE, "drop_axes", frozenset())


class use_mesh:
    """Install the active mesh + parallelism policy for model constraints.

    ``dp_axes``: mesh axes carrying the batch dimension (per-arch policy:
    small models fold 'model' into DP — §Perf H4).
    ``drop_axes``: axes erased from activation constraints (pure-DP mode
    replicates what TP would shard)."""

    def __init__(self, mesh: Mesh | None, *, dp_axes: tuple = DP_AXES,
                 drop_axes=frozenset()):
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.drop_axes = frozenset(drop_axes)

    def __enter__(self):
        self.prev = (get_mesh(), get_dp_axes(), get_drop_axes())
        _STATE.mesh = self.mesh
        _STATE.dp_axes = self.dp_axes
        _STATE.drop_axes = self.drop_axes
        return self.mesh

    def __exit__(self, *exc):
        _STATE.mesh, _STATE.dp_axes, _STATE.drop_axes = self.prev
        return False


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def _present(mesh: Mesh, axes):
    """Drop mesh axes that do not exist in this mesh (e.g. 'pod' single-pod)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if axes in mesh.shape else None
    kept = tuple(a for a in axes if a in mesh.shape)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def sanitize_spec(spec: P, shape: Sequence[int], mesh: Mesh | None) -> P:
    """Adapt a logical spec to a concrete (mesh, shape): drop absent axes;
    for multi-axis dims keep the longest prefix whose product divides the
    dim (e.g. batch=128 over ('data','model')=256 degrades to 'data'=16)."""
    if mesh is None:
        return P()
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim_size, axes in zip(shape, dims):
        axes = _present(mesh, axes)
        if axes is None:
            out.append(None)
            continue
        tup = axes if isinstance(axes, tuple) else (axes,)
        while tup and dim_size % _axis_size(mesh, tup) != 0:
            tup = tup[:-1]
        if not tup:
            out.append(None)
        else:
            out.append(tup if len(tup) > 1 else tup[0])
    return P(*out)


def named(spec: P, shape: Sequence[int], mesh: Mesh | None) -> NamedSharding | None:
    if mesh is None:
        return None
    return NamedSharding(mesh, sanitize_spec(spec, shape, mesh))


def constrain(x: jax.Array, *spec_dims) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op without one).

    Accepts either a ready PartitionSpec (``constrain(x, batch_spec(...))``)
    or bare dims (``constrain(x, 'data', None)``)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    if len(spec_dims) == 1 and isinstance(spec_dims[0], P):
        spec = spec_dims[0]
    else:
        spec = P(*spec_dims)
    drop = get_drop_axes()
    if drop:
        spec = P(*[_drop(a, drop) for a in spec])
    spec = sanitize_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _drop(axes, drop: frozenset):
    if axes is None:
        return None
    tup = axes if isinstance(axes, tuple) else (axes,)
    kept = tuple(a for a in tup if a not in drop)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def sanitize_tree(specs: Any, shapes: Any, mesh: Mesh | None) -> Any:
    """Map sanitize_spec over parallel (spec, shape) pytrees -> NamedShardings."""
    return jax.tree.map(
        lambda s, shp: named(s, shp.shape if hasattr(shp, "shape") else shp, mesh),
        specs,
        shapes,
        is_leaf=lambda s: isinstance(s, P),
    )


def batch_spec(*trailing) -> P:
    """Spec with the batch dim over the policy's data-parallel axes."""
    return P(get_dp_axes(), *trailing)


def translate_specs(tree, *, drop=("model",)):
    """Erase mesh axes from a spec tree (serving weights: no FSDP; pure-DP
    weights: no TP)."""
    dropset = frozenset(drop)
    return jax.tree.map(
        lambda s: P(*[_drop(a, dropset) for a in s]),
        tree, is_leaf=lambda s: isinstance(s, P))


def port_mesh(n_ports: int, axis: str = "port") -> Mesh:
    """1-D mesh standing in for ``n_ports`` memory ports.

    This is the device fabric behind the ``sharded`` backend of
    ``repro.cfa.compile`` (port-count validation against the *platform*
    budget happens there, in the ``Target`` registry; this helper only
    maps ports onto whatever devices exist — pass ``mesh=`` through the
    compiled stencil's call options to supply a custom mesh instead).

    Uses up to ``n_ports`` local devices; with fewer devices than ports the
    mesh folds ports onto the available devices (port p -> device p mod size),
    so the same code runs on a laptop CPU, forced host devices, or a real
    multi-chip slice.
    """
    if n_ports <= 0:
        raise ValueError(f"n_ports must be positive: {n_ports}")
    devs = jax.devices()
    return Mesh(np.asarray(devs[: min(n_ports, len(devs))]), (axis,))


def shard_facets(facets: dict, facet_to_port: dict, mesh: Mesh,
                 axis: str = "port") -> dict:
    """Place each facet array on its assigned port's device.

    The facet array is CFA's unit of contiguity, so a port repartition at
    facet granularity is realised by whole-array placement: facet ``k`` lives
    on the device at mesh coordinate ``facet_to_port[k] mod axis size``.
    Ports beyond the mesh size fold back (see ``port_mesh``).
    """
    n = int(mesh.shape[axis])
    devs = list(mesh.devices.reshape(-1))
    out = {}
    for k, arr in facets.items():
        p = int(facet_to_port.get(k, 0)) % n
        dev = devs[p]
        if getattr(arr, "devices", None) is not None and arr.devices() == {dev}:
            out[k] = arr  # already resident on its port
        else:
            out[k] = jax.device_put(arr, dev)
    return out


def constrain_tree(tree, spec_tree):
    """Constrain every leaf of ``tree`` to the matching spec (active mesh).

    Used to pin gradients to the parameters' FSDP sharding *before* the
    optimizer, which turns the data-parallel gradient sync into a
    reduce-scatter instead of an all-reduce + dynamic-slice (ZeRO; measured
    in EXPERIMENTS.md §Perf H1)."""
    mesh = get_mesh()
    if mesh is None:
        return tree

    def one(x, s):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, sanitize_spec(s, x.shape, mesh)))

    return jax.tree.map(one, tree, spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
