"""Pallas TPU kernel: CFA stencil tile executor.

TPU adaptation of the paper's "execute" stage (Fig. 13).  One grid step
processes one iteration tile:

* the tile's halo buffer (its flow-in, gathered from facet arrays by
  contiguous block DMAs — see ``repro.core.cfa.transform``) is staged into
  VMEM by the BlockSpec pipeline (Pallas double-buffers grid steps, which is
  the TPU analogue of the paper's read/execute/write DATAFLOW overlap);
* the plane recurrence runs entirely in VMEM: ``t0`` time planes are produced
  with vector shifts on (t1+w1, t2+w2) planes — no HBM traffic between time
  steps (this is the temporal locality tiling bought us);
* the interior volume is emitted; facet extraction (transpose + contiguous
  block store) happens at the XLA level where it fuses with the DMA.

Block shapes: the minor two dims of both the halo buffer and the output are
the spatial dims, which the caller sizes to multiples of (8, 128) for
sublane/lane alignment — the CFA layout guarantees those extents are
contiguous in HBM, which is what makes these DMAs "bursts".  A field
program's blocks carry the field axis after time, ``(w0+t0, F, ..)``, so
the spatial dims stay minor.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cfa.programs import StencilProgram, get_program
from repro.kernels import resolve_interpret


def _tile_kernel(h_ref, o_ref, scratch, *, program: StencilProgram,
                 tile: tuple[int, ...]):
    w = program.widths
    d = len(tile)
    spatial = program.with_fields(
        tuple(slice(w[a], None) for a in range(d)), slice(None))[1:]
    # Stage the halo buffer into the scratch working set once; all further
    # reads/writes are VMEM-local.
    scratch[...] = h_ref[...]
    for s in range(tile[0]):  # t0 is static: fully unrolled time loop
        prev = [scratch[w[0] + s - m] for m in range(w[0], 0, -1)]
        plane = program.plane_update(prev, w)  # static shapes: VMEM values
        scratch[(w[0] + s, *spatial)] = plane
    o_ref[...] = scratch[(slice(w[0], None), *spatial)]


@functools.partial(jax.jit, static_argnames=("program_name", "tile", "interpret"))
def execute_tiles(
    program_name: str,
    halos: jnp.ndarray,  # (B, w0+t0, [F,] .., w_{d-1}+t_{d-1})
    tile: tuple[int, ...],
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:  # (B, t0, [F,] .., t_{d-1})
    """Run the tile executor kernel over a batch of gathered halo buffers.

    Dimension-generic: ``tile`` has one entry per iteration-space axis
    (time first), so 2-D (``heat1d``), 3-D (Table I) and 4-D (``heat3d``)
    programs share this path.  ``interpret`` resolves through
    :func:`repro.kernels.resolve_interpret`.
    """
    program = get_program(program_name)
    w = program.widths
    d = len(tile)
    if program.ndim != d:
        raise ValueError(f"{program_name} is {program.ndim}-D, tile is {d}-D")
    hshape = program.with_fields(tuple(w[a] + tile[a] for a in range(d)),
                                 program.n_fields)
    oshape = program.with_fields(tile, program.n_fields)
    if halos.shape[1:] != hshape:
        raise ValueError(f"halos must be (B, {hshape}), got {halos.shape}")
    B = halos.shape[0]
    zeros = (0,) * len(hshape)
    kernel = functools.partial(_tile_kernel, program=program, tile=tile)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, *hshape), lambda b: (b, *zeros))],
        out_specs=pl.BlockSpec((None, *oshape), lambda b: (b, *zeros)),
        out_shape=jax.ShapeDtypeStruct((B, *oshape), halos.dtype),
        scratch_shapes=[pltpu.VMEM(hshape, halos.dtype)],
        interpret=resolve_interpret(interpret),
        name="cfa_stencil_tile",
    )(halos)
