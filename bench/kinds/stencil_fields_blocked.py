"""Kind ``stencil_fields_blocked``: the ``stencil_fields`` kind for spaces
whose whole reference volume does not fit beside the run, checked one
time row of tiles at a time.

The run is ``stencil_fields``' (set-up, window, recorded sweep, the sweeps
kept for the check), and so are the inputs, the compile, the reference's
Yee step and the facet index map; this file imports them.  Only
:func:`check` differs: for each input set it runs the reference ``t_0``
planes at a time (one row of tiles) on the device, carrying the last
plane, gathers the values of that row's facet slots there, and compares
each kept sweep's row of facet values with them.  The reference of one
row, ``(1 + t_0, F, N_1, N_2)`` with the carried plane, and one index
table a facet array, the same for every row, are all it keeps on the
device; the host holds one row's values at a time, never the volume.
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

# a private copy of the stencil_fields kind, whose run looks its check up
# in its own module: this kind's check is put there below
_base = harness.load_module(Path(__file__).with_name("stencil_fields.py"))
input_sets = _base.input_sets
compile_cell = _base.compile_cell
reference_volume = _base.reference_volume
_planes = _base._planes
facet_value_index = _base.facet_value_index


@jax.jit
def _gather(block, index):
    """The reference values of one facet array's row slots, in the
    slots' row-major order (a flat gather: the TPU compiles it at once,
    where a compare in the facet's own tiled shape takes it tens of
    seconds a facet array at this size)."""
    return block[index]


def _row_tables(pipe, block_shape):
    """Per facet array: the outer dim of its time tile coordinate, how
    many time rows a check row takes of it (facet_0 two: the previous
    row's last planes, its virtual live-in row for the first, and its
    own), and the flat index of each of those slots in the row's
    reference block ``(n_live + t_0, F, N_1, N_2)``, the carried planes
    first.  The same table serves every row."""
    tile = pipe.tiling.sizes
    n_live = pipe.specs[0].width
    tables = {}
    for k, spec in pipe.specs.items():
        if tile[k] % spec.width:
            raise ValueError("the blocked check needs each facet's width to "
                             f"divide its tile; facet_{k} has {spec.width} "
                             f"in {tile[k]}")
        dim = spec.outer_axes.index(0)
        span = 2 if k == 0 else 1
        shape = list(pipe.facet_shape(k))
        shape[dim] = span
        idx = facet_value_index(tuple(shape), k, spec.width, tile,
                                spec.outer_axes, spec.inner_axes, n_live,
                                block_shape)
        tables[k] = (dim, span, jnp.asarray(idx.ravel(), jnp.int32))
    return tables


def check(compiled, outputs, sets, n_planes: int, stencil: dict,
          limit: float) -> tuple[float, int]:
    """Largest |facet - reference| over every value of every facet array
    of every sweep, as a share of max|input| of that sweep's input set;
    and how many sweeps read above ``limit``: what ``stencil_fields.check``
    reads, a row of tiles at a time.  The reference and the gather of its
    values run on the device; each row's values come to the host, where
    the difference is taken in float64 as there."""
    if tuple(stencil["fields"]) != ("ey", "ex", "hz") or list(stencil["skew"]) != [1, 1]:
        raise ValueError("the reference is fdtd-2d's (ey, ex, hz) skewed by (1, 1)")
    coeffs = tuple(float(c) for c in stencil["coefficients"])
    pipe = compiled.pipeline
    t0 = pipe.tiling.sizes[0]
    n_live = pipe.specs[0].width
    block_shape = (n_live + t0, len(stencil["fields"]), *pipe.space.sizes[1:])
    tables = _row_tables(pipe, block_shape)

    def worse(a: float, b: float) -> float:
        return max(a, b, key=lambda e: (math.isnan(e), e))

    rels = []
    for j in sorted({j for j, _ in outputs}):
        x = sets[j]
        scale = float(jnp.max(jnp.abs(x)))
        kept = [compiled.rehydrate(f) for i, f in outputs if i == j]
        fits = [all(f[k].shape == pipe.facet_shape(k) for k in tables) for f in kept]
        errs = [0.0 if ok else math.inf for ok in fits]
        carry = x
        for q0 in range(n_planes // t0):
            planes = _planes(carry[-1], n_planes=t0, coeffs=coeffs)
            block = jnp.concatenate([carry, planes]).ravel()
            want = {k: np.asarray(_gather(block, i), np.float64)
                    for k, (_, _, i) in tables.items()}
            for o, facets in enumerate(kept):
                if not fits[o]:
                    continue
                for k, (d, span, _) in tables.items():
                    row = jax.lax.dynamic_slice_in_dim(facets[k], q0, span, axis=d)
                    got = np.asarray(row.astype(jnp.float32), np.float64).ravel()
                    errs[o] = worse(errs[o], float(np.max(np.abs(got - want[k]))))
            carry = planes[-n_live:]
        rels += [e / scale for e in errs]
    failed = sum(not r <= limit for r in rels)  # NaN fails too
    return max(rels, key=lambda r: (math.isnan(r), r)), failed


# the base run looks its check up in its own module
_base.check = check


def run(cell):
    """The ``stencil_fields`` run, checked by :func:`check`, of a program
    that resolves its fetch tables and the recorder's transfer plans once
    a halo class (``CFAPipeline.halo_class``).  One that resolves every
    tile on its own spends tens of seconds on its tables and tens of
    minutes on the recorded sweep's plans at this cell's 2,000 tiles, so
    it is refused at once."""
    from repro.core.cfa.transform import CFAPipeline

    if not hasattr(CFAPipeline, "halo_class"):
        raise RuntimeError("this cell needs a program whose fetch plan and "
                           "transfer plans are resolved once a halo class "
                           "(CFAPipeline.halo_class); this one resolves each tile")
    return _base.run(cell)
