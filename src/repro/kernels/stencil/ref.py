"""Pure-jnp oracle for the CFA stencil tile executor.

Given a batch of halo buffers (flow-in gathered from facet arrays, low-side
halo of width ``w`` per axis), compute the tiles' interior planes with the
program's plane recurrence.  This is the reference the Pallas kernel is
validated against; it is also exactly what ``CFAPipeline.execute_tile`` does,
vectorised over a batch of tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cfa.programs import StencilProgram, get_program


def execute_tiles_ref(
    program: StencilProgram | str,
    halos: jnp.ndarray,  # (B, w0+t0, [F,] .., w_{d-1}+t_{d-1})
    tile: tuple[int, ...],
) -> jnp.ndarray:  # (B, t0, [F,] .., t_{d-1})
    if isinstance(program, str):
        program = get_program(program)
    w = program.widths
    d = len(tile)
    spatial = program.with_fields(
        tuple(slice(w[a], None) for a in range(d)), slice(None))[1:]

    def one(H):
        for s in range(tile[0]):
            prev = [H[w[0] + s - m] for m in range(w[0], 0, -1)]
            plane = program.plane_update(prev, w)
            H = H.at[(w[0] + s, *spatial)].set(plane)
        return H[(slice(w[0], None), *spatial)]

    return jax.vmap(one)(halos)
