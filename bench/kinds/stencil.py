"""Kind ``stencil``: a time-iterated stencil that ``cfa.compile`` lowers to
facet storage, timed sweep by sweep.

Set-up compiles the configuration's program on its space and tile with the
traffic's backend rule (``"auto"``), makes the traffic's input sets on the
device from the seed and runs one warm-up sweep.  The window then runs
whole sweeps back to back (``CompiledStencil.__call__`` ending in
``block_until_ready``), cycling through the input sets, and closes at the
end of the first sweep that ends after ``--seconds``.  Of the facet dicts
the sweeps return, the last one on each input set and up to
``CHECKED_SWEEPS`` others, drawn from the seed, are kept and, once the
window has closed, compared with :func:`reference_volume` point by point;
the rest are dropped as they come, so memory and check work stay bounded
however many sweeps a window holds.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, work

# sweeps compared besides the last one on each input set
CHECKED_SWEEPS = 8

# -- the plain reference ----------------------------------------------------

def reference_volume(x, n_planes: int, stencil: dict):
    """Every plane of the sweep, computed plane by plane in plain jnp.

    ``x`` holds the live-in plane (time -1) over the spatial extent.  Plane
    ``s`` is ``sum c * plane_{s-1}(x + offset)`` over the stencil's taps, in
    the order the configuration lists them, with the offsets in post-skew
    coordinates and zero outside the space.  Imports nothing of the program
    under test.
    """
    w = work.widths(stencil)
    if w[0] != 1:
        raise ValueError("the reference takes one plane of history (w_0 = 1)")
    offs = tuple(off[1:] for off in work.skewed_offsets(stencil))
    coeffs = tuple(float(c) for _, c in stencil["taps"])
    return _planes(x[-1], n_planes=n_planes, offs=offs, coeffs=coeffs, w=w[1:])


@functools.partial(jax.jit, static_argnames=("n_planes", "offs", "coeffs", "w"))
def _planes(plane, *, n_planes, offs, coeffs, w):
    spatial = plane.shape
    pad = [(wa, 0) for wa in w]

    def step(prev, _):
        p = jnp.pad(prev, pad)
        acc = None
        for off, c in zip(offs, coeffs):
            sl = tuple(slice(wa + o, wa + o + n) for wa, o, n in zip(w, off, spatial))
            v = p[sl] * jnp.asarray(c, plane.dtype)
            acc = v if acc is None else acc + v
        return acc, acc

    return jax.lax.scan(step, plane, None, length=n_planes)[1]


def facet_point_index(shape, axis: int, width: int, tile, outer, inner,
                      n_live: int, ext_shape) -> np.ndarray:
    """Flat index into the extended volume (``n_live`` live-in planes, then
    the sweep's planes) of every element of one facet array.

    ``outer``/``inner`` are the array's axis orders as the returned facet
    storage describes itself: tile coordinates first, then intra-tile
    positions, where axis ``axis`` holds ``x mod width`` over the tile's
    last ``width`` slices.  Facet 0 carries one leading block row of live-in
    planes, so its tile coordinate on axis 0 starts at -1.
    """
    idx = np.indices(shape, dtype=np.int64)
    q = {a: idx[i] - (1 if axis == 0 and a == 0 else 0)
         for i, a in enumerate(outer)}
    x = [None] * len(tile)
    for j, a in enumerate(inner):
        r = idx[len(outer) + j]
        if a == axis:
            base = q[a] * tile[a] + tile[a] - width
            x[a] = base + np.mod(r - base, width)
        else:
            x[a] = q[a] * tile[a] + r
    x[0] = x[0] + n_live
    return np.ravel_multi_index(tuple(x), ext_shape)


# -- the cell ----------------------------------------------------------------

def input_sets(seed: int, cfg: dict, traffic: dict):
    """The traffic's ``input_sets`` live-in inputs, drawn on the device from
    ``seed`` in one jitted call; any whole number (also past 32 bits) is a
    seed."""
    n_sets = int(traffic["input_sets"])
    shape = (work.widths(cfg["stencil"])[0], *cfg["space"][1:])
    seed %= 2**64
    data = jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)
    key = jax.random.wrap_key_data(data, impl="threefry2x32")
    make = jax.jit(lambda k: jax.random.normal(k, (n_sets, *shape), cfg["dtype"]))
    stacked = make(key)
    return [stacked[i] for i in range(n_sets)]


def compile_cell(cfg: dict, traffic: dict):
    """The configuration's program compiled as every run of the cell does."""
    from repro import cfa

    return cfa.compile(cfg["program"], tuple(cfg["space"]),
                       layout=tuple(cfg["tile"]), target=cfg["target"],
                       storage=traffic["storage"], backend=traffic["backend"])


def run(cell):
    cfg, traffic = cell.config, cell.traffic
    space, tile = tuple(cfg["space"]), tuple(cfg["tile"])
    stencil = cfg["stencil"]
    dtype = jnp.dtype(cfg["dtype"])
    w = work.widths(stencil)
    n_sets = int(traffic["input_sets"])

    with cell.span("setup"):
        t = [time.perf_counter()]
        compiled = compile_cell(cfg, traffic)
        pipe = compiled.pipeline
        n_tiles = math.prod(pipe.num_tiles)
        cell.log(f"backend={compiled.backend} storage={compiled.storage} "
                 f"space={space} tile={tile} tiles={n_tiles} "
                 f"waves={len(pipe.wavefronts())}")
        t.append(time.perf_counter())
        sets = input_sets(cell.seed, cfg, traffic)
        jax.block_until_ready(sets)
        t.append(time.perf_counter())
        with harness.jax_programs() as programs:
            jax.block_until_ready(compiled(sets[0], dtype=dtype))
        t.append(time.perf_counter())
    setup_s = t[-1] - cell.t_start
    cell.log(f"[setup] process start to kind: {t[0] - cell.t_start} s, "
             f"compile {t[1] - t[0]} s, inputs {t[2] - t[1]} s, "
             f"warm-up sweep {t[3] - t[2]} s, its programs: {programs}")

    # (sweep, input set, facet dict): the last sweep on each input set, and
    # a sample of the others drawn from the seed
    last = {}
    drawn = harness.Reservoir(CHECKED_SWEEPS, cell.seed)

    def keep(i: int, j: int, facets) -> None:
        prev = last.get(j)
        last[j] = (i, j, facets)
        if prev is not None:
            drawn.offer(prev)

    def sweep(i: int) -> None:
        j = i % n_sets
        with cell.span("sweep"):
            keep(i, j, jax.block_until_ready(compiled(sets[j], dtype=dtype)))

    with cell.window() as win:
        ends = [time.perf_counter()]
        while True:
            sweep(len(ends) - 1)
            ends.append(time.perf_counter())
            if ends[-1] - ends[0] >= cell.seconds:
                break
        n, window_s = len(ends) - 1, ends[-1] - ends[0]
        win.units = n
    cell.log(f"[window] {n} sweeps in {window_s} s; each: "
             f"{[b - a for a, b in zip(ends, ends[1:])]}")

    recorder = None
    if cell.trace:
        # one more sweep with the program's own recorder on, outside the
        # profiled window
        j = n % n_sets
        t0 = time.perf_counter()
        out = compiled(sets[j], dtype=dtype, trace=True)
        keep(n, j, jax.block_until_ready(out))
        recorder = compiled.last_trace()
        cell.log(f"[trace] the sweep with the recorder on: {time.perf_counter() - t0} s")
    peak = cell.memory_peak()
    outputs = sorted([*last.values(), *drawn.items])
    cell.log(f"[check] {len(outputs)} of {n + cell.trace} sweeps: "
             f"{[i for i, _, _ in outputs]}")
    outputs = [(j, facets) for _, j, facets in outputs]

    limit = float(cfg["limits"]["max_rel_err"])
    with cell.span("check"):
        worst, failed = check(compiled, outputs, sets, space[0], stencil, limit)
    return dict(
        end_to_end={"sweep_s": window_s / n, "setup_s": setup_s},
        attempted=n + cell.trace, failed=failed,
        checks=[("max_rel_err", worst, limit)],
        memory_peak_bytes=peak,
        layer=dict(
            units=n, tiles_per_sweep=n_tiles,
            recorder=recorder,
            sweep_bytes=work.sweep_bytes(space, tile, w, dtype.itemsize),
            sweep_flops=work.sweep_flops(space, stencil),
            kernel_tile_bytes=work.kernel_tile_bytes(tile, w, dtype.itemsize),
        ),
    )


def check(compiled, outputs, sets, n_planes: int, stencil: dict,
          limit: float) -> tuple[float, int]:
    """Largest |facet - reference| over every element of every facet array
    of every sweep, as a share of max|input| of that sweep's input set;
    and how many sweeps read above ``limit``."""
    pipe = compiled.pipeline
    tile = pipe.tiling.sizes
    n_live = pipe.specs[0].width
    ext = {}
    for j in sorted({j for j, _ in outputs}):
        x = sets[j]
        vol = np.concatenate([np.asarray(x, np.float64),
                              np.asarray(reference_volume(x, n_planes, stencil),
                                         np.float64)]).ravel()
        ext[j] = (vol, float(jnp.max(jnp.abs(x))))
    ext_shape = (n_live + n_planes, *pipe.space.sizes[1:])
    index = {}
    for k, spec in pipe.specs.items():
        index[k] = facet_point_index(pipe.facet_shape(k), k, spec.width, tile,
                                     spec.outer_axes, spec.inner_axes, n_live,
                                     ext_shape)
    rels = []
    for j, facets in outputs:
        vol, scale = ext[j]
        facets = compiled.rehydrate(facets)
        errs = []
        for k, idx in index.items():
            got = np.asarray(facets[k].astype(jnp.float32), np.float64)
            errs.append(float(np.max(np.abs(got - vol[idx])))
                        if got.shape == idx.shape else math.inf)
        rels.append(max(errs, key=lambda e: (math.isnan(e), e)) / scale)
    failed = sum(not r <= limit for r in rels)  # NaN fails too
    return max(rels, key=lambda r: (math.isnan(r), r)), failed
