"""Jit'd public wrappers for the CFA stencil tile executor.

``execute_tiles`` / ``execute_tiles_sharded`` are the executor adapters the
``pallas`` and ``sharded`` backends of ``repro.cfa.compile`` drive.
"""
from __future__ import annotations

import jax.numpy as jnp

from .stencil import execute_tiles
from .ref import execute_tiles_ref

__all__ = [
    "execute_tiles",
    "execute_tiles_ref",
    "stencil_tile_op",
    "execute_tiles_sharded",
]


def stencil_tile_op(
    program_name: str,
    halos: jnp.ndarray,
    tile: tuple[int, ...],
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Execute a batch of stencil tiles; kernel path or jnp reference path."""
    if use_kernel:
        return execute_tiles(program_name, halos, tile, interpret=interpret)
    return execute_tiles_ref(program_name, halos, tile)


def execute_tiles_sharded(
    program_name: str,
    halos: jnp.ndarray,  # (B, w0+t0, .., w_{d-1}+t_{d-1}), B % mesh axis size == 0
    tile: tuple[int, ...],
    mesh,
    *,
    axis: str = "port",
    interpret: bool | None = None,
) -> jnp.ndarray:  # (B, t0, .., t_{d-1})
    """Execute a halo batch with its shards on different port-devices.

    The multi-port analogue of ``execute_tiles``: the batch (one wavefront of
    independent tiles) is split over the ``axis`` mesh dimension and each
    shard runs the Pallas tile executor on its own device — tiles on
    different ports genuinely execute concurrently.  The caller pads the
    batch to a multiple of the mesh axis size (the sharded executor's
    ``CFAPipeline._sweep_wavefront_sharded`` does).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = int(mesh.shape[axis])
    if halos.shape[0] % n:
        raise ValueError(
            f"halo batch ({halos.shape[0]}) must be a multiple of the mesh "
            f"axis size ({n}); pad the wavefront first"
        )
    # commit the batch to the mesh (shard_map rejects inputs committed to a
    # different device set, e.g. halos gathered on the default device)
    halos = jax.device_put(halos, NamedSharding(mesh, P(axis)))

    def shard(h):
        return execute_tiles(program_name, h, tile, interpret=interpret)

    # check_vma=False: jax's replication checker cannot see into a
    # pallas_call body
    return jax.shard_map(
        shard, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )(halos)
