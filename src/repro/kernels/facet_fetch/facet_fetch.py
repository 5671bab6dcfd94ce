"""Pallas TPU kernel: the CFA read engine (paper Fig. 13/14, 'read' stage).

Assembles a tile's halo buffer from facet arrays where every input is one
facet *block* addressed by a pure BlockSpec index map — demonstrating the
central adaptation claim of DESIGN.md: CFA's full-tile contiguity makes each
flow-in piece exactly one contiguous HBM extent, i.e. one DMA descriptor.

Per interior tile (q0, q1, q2) the seven backward-neighbour pieces map to:

    facet_0 blocks (q0-1; q1|q1-1; q2|q2-1)   — 4 blocks (time halo + corners)
    facet_1 blocks (q0; q1-1; q2|q2-1)        — 2 blocks (x1 halo + extension)
    facet_2 block  (q0; q1; q2-1)             — 1 block  (x2 halo)

(The paper merges pairs of adjacent blocks into single bursts — e.g. the two
facet_1 blocks are contiguous in HBM because the extension direction's tile
coordinate is the last outer dim; Pallas expresses them as two block reads
that the DMA engine coalesces.)

Boundary tiles (any q == 0) take the jnp copy-in path
(``CFAPipeline.copy_in``); this kernel serves the steady-state interior,
which is where the bandwidth is spent.

**Irredundant storage** (``storage="irredundant"``, Ferry 2024): the facet
arrays store every value exactly once, so the slots a facet block shares
with a lower-axis facet are dead and the fetch must take the *owner-facet
indirection*: four extra owner blocks per tile —

    facet_0 blocks (q0; q1-1|q1; q2|q2-1)   — 3 blocks (x0-tails the x1/x2
                                              halo pieces no longer carry)
    facet_1 block  (q0; q1; q2-1)           — 1 block  (the x1-tail rows of
                                              the x2 halo piece)

— are composited over the dead sub-regions, highest-priority owner last.
Every input is still one facet block addressed by a pure BlockSpec index
map: deduplication costs extra DMA descriptors, never gather addressing.
The ``compressed`` discipline has no in-kernel decode stage and is
rejected (see ``ExecutorCaps.storages``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cfa.programs import StencilProgram, get_program
from repro.core.cfa.transform import CFAPipeline
from repro.kernels import resolve_interpret

__all__ = ["fetch_interior_halos"]


def _assemble(h_ref, f0a, f0b, f0c, f0d, f1a, f1b, f2a, *, w, t):
    """Assemble H[(w0+t0), (w1+t1), (w2+t2)] from seven facet blocks.

    Block layouts (inner dim orders from repro.core.cfa.facets):
      facet_0: (t1, t2, w0)   facet_1: (t2, t0, w1)   facet_2: (t0, t1, w2)
    """
    w0, w1, w2 = w
    t0, t1, t2 = t
    h_ref[...] = jnp.zeros_like(h_ref)
    # time halo: full (x1, x2) cross-section of tile (q0-1, q1, q2)
    h_ref[:w0, w1:, w2:] = f0a[...].transpose(2, 0, 1)
    # x1 halo (+ its time corner): facet_1 of (q0, q1-1, q2) spans full t0
    h_ref[w0:, :w1, w2:] = f1a[...].transpose(1, 2, 0)
    # x2 halo: facet_2 of (q0, q1, q2-1) spans full (t0, t1)
    h_ref[w0:, w1:, :w2] = f2a[...]
    # corner (x0-tail, x1-tail): subset of facet_0 block (q0-1, q1-1, q2)
    h_ref[:w0, :w1, w2:] = f0b[...][t1 - w1 :, :, :].transpose(2, 0, 1)
    # corner (x0-tail, x2-tail): subset of facet_0 block (q0-1, q1, q2-1)
    h_ref[:w0, w1:, :w2] = f0c[...][:, t2 - w2 :, :].transpose(2, 0, 1)
    # corner (x1-tail, x2-tail): subset of facet_1 block (q0, q1-1, q2-1)
    h_ref[w0:, :w1, :w2] = f1b[...][t2 - w2 :, :, :].transpose(1, 2, 0)
    # S3 corner: subset of facet_0 block (q0-1, q1-1, q2-1)
    h_ref[:w0, :w1, :w2] = (
        f0d[...][t1 - w1 :, t2 - w2 :, :].transpose(2, 0, 1)
    )


def _kernel(f0a, f0b, f0c, f0d, f1a, f1b, f2a, h_ref, *, w, t):
    _assemble(h_ref, f0a, f0b, f0c, f0d, f1a, f1b, f2a, w=w, t=t)


def _kernel_irredundant(f0a, f0b, f0c, f0d, f1a, f1b, f2a,
                        g0b, g0c, g0d, g1c, h_ref, *, w, t):
    """The owner-facet indirection: composite the dead sub-regions of the
    facet_1/facet_2 pieces from their owner blocks, lowest priority first
    (facet_2 piece < facet_1 overwrite < facet_0 overwrite), so every halo
    value comes from the one facet that stores it."""
    w0, w1, w2 = w
    t0, t1, t2 = t
    _assemble(h_ref, f0a, f0b, f0c, f0d, f1a, f1b, f2a, w=w, t=t)
    # x1 halo piece: its x0-tail rows are owned by facet_0 of (q0, q1-1, q2)
    h_ref[t0:, :w1, w2:] = g0b[...][t1 - w1 :, :, :].transpose(2, 0, 1)
    # x2 halo piece: x1-tail band owned by facet_1 of (q0, q1, q2-1) ...
    h_ref[w0:, t1:, :w2] = g1c[...][t2 - w2 :, :, :].transpose(1, 2, 0)
    # ... then the x0-tail band by facet_0 of (q0, q1, q2-1) (covers the
    # x0-tail ∩ x1-tail sliver facet_1 does not store either)
    h_ref[t0:, w1:, :w2] = g0c[...][:, t2 - w2 :, :].transpose(2, 0, 1)
    # corner (x1-tail, x2-tail): x0-tail rows from facet_0 of (q0, q1-1, q2-1)
    h_ref[t0:, :w1, :w2] = g0d[...][t1 - w1 :, t2 - w2 :, :].transpose(2, 0, 1)


@functools.partial(jax.jit, static_argnames=("program_name", "space", "tile",
                                              "interpret", "storage"))
def fetch_interior_halos(
    program_name: str,
    facets: dict,  # CFAPipeline facet arrays (facet_0 includes virtual row)
    space: tuple[int, int, int],
    tile: tuple[int, int, int],
    *,
    interpret: bool | None = None,
    storage: str = "redundant",
) -> jnp.ndarray:
    """Halo buffers for all interior tiles, gathered block-wise.

    Returns (n0-1, n1-1, n2-1, w0+t0, w1+t1, w2+t2); entry (i, j, k)
    corresponds to tile (i+1, j+1, k+1).  ``storage="irredundant"`` takes
    the owner-facet indirection (four extra owner blocks per tile) over
    deduplicated facet arrays; the result is identical to the redundant
    fetch over redundant arrays.
    """
    prog = get_program(program_name)
    from repro.core.cfa import IterSpace, Tiling, build_facet_specs

    if prog.fields:
        raise ValueError(
            f"the facet_fetch kernel's BlockSpecs address scalar facets; "
            f"{prog.name!r} has {prog.n_fields} fields (CFAPipeline.copy_in "
            "fetches them)")
    if len(space) != 3 or prog.ndim != 3:
        raise ValueError(
            "the facet_fetch kernel's static BlockSpecs address 3-D facet "
            f"layouts only (got a {len(space)}-D space); non-3-D programs "
            "take CFAPipeline.copy_in / kernels.stencil instead"
        )
    if storage not in ("redundant", "irredundant"):
        raise ValueError(
            f"the facet_fetch kernel has no in-kernel decode stage: storage "
            f"must be 'redundant' or 'irredundant', got {storage!r}"
        )
    specs = build_facet_specs(IterSpace(space), prog.deps, Tiling(tile))
    w = tuple(specs[a].width if a in specs else 0 for a in range(3))
    t = tile
    for a in range(3):
        if w[a] and t[a] % w[a]:
            raise ValueError(
                f"kernel fetch requires w | t (axis {a}: t={t[a]}, w={w[a]}); "
                "tile-dependent modulo labelling takes the jnp copy-in path")
    nt = tuple(n // x for n, x in zip(space, tile))
    g = (nt[0] - 1, nt[1] - 1, nt[2] - 1)
    if min(g) < 1:
        raise ValueError("need at least 2 tiles per axis for interior fetch")
    t0, t1, t2 = t
    w0, w1, w2 = w

    # facet_0 array: (nt0+1, nt2, nt1, t1, t2, w0); tile (a,b,c) block is at
    # outer index (a+1, c, b) — the +1 skips the virtual live-in row.  We
    # read tile (q0-1+da, ...) = (i+da, ...) -> outer index i+1+da.
    f0 = lambda da, db, dc: pl.BlockSpec(
        (None, None, None, t1, t2, w0),
        lambda i, j, k, da=da, db=db, dc=dc: (i + 1 + da, k + 1 + dc,
                                              j + 1 + db, 0, 0, 0))
    # facet_1: (nt1, nt0, nt2, t2, t0, w1); tile (a,b,c) at (b, a, c).
    f1 = lambda db, dc: pl.BlockSpec(
        (None, None, None, t2, t0, w1),
        lambda i, j, k, db=db, dc=dc: (j + db, i + 1, k + 1 + dc, 0, 0, 0))
    # facet_2: (nt2, nt1, nt0, t0, t1, w2); tile (a,b,c) at (c, b, a).
    f2 = pl.BlockSpec(
        (None, None, None, t0, t1, w2),
        lambda i, j, k: (k, j + 1, i + 1, 0, 0, 0))

    out_shape = (g[0], g[1], g[2], w0 + t0, w1 + t1, w2 + t2)
    in_specs = [
        f0(0, 0, 0),  # (q0-1, q1, q2): outer idx (q0-1+1, ...) = (i, ...)
        f0(0, -1, 0),
        f0(0, 0, -1),
        f0(0, -1, -1),
        f1(0, 0),
        f1(0, -1),
        f2,
    ]
    operands = [facets[0], facets[0], facets[0], facets[0], facets[1],
                facets[1], facets[2]]
    if storage == "irredundant":
        # the owner blocks: facet_0 of (q0, q1-1, q2), (q0, q1, q2-1) and
        # (q0, q1-1, q2-1) — q0 = i+1, so outer index i+2 past the virtual
        # row — plus facet_1 of (q0, q1, q2-1)
        in_specs += [f0(1, -1, 0), f0(1, 0, -1), f0(1, -1, -1), f1(1, -1)]
        operands += [facets[0], facets[0], facets[0], facets[1]]
        kernel = functools.partial(_kernel_irredundant, w=w, t=t)
    else:
        kernel = functools.partial(_kernel, w=w, t=t)
    return pl.pallas_call(
        kernel,
        grid=g,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (None, None, None, w0 + t0, w1 + t1, w2 + t2),
            lambda i, j, k: (i, j, k, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, facets[0].dtype),
        interpret=resolve_interpret(interpret),
    )(*operands)
