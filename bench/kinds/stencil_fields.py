"""Kind ``stencil_fields``: a time-iterated stencil with several coupled
fields per point (PolyBench ``fdtd-2d``'s ey, ex and hz) that
``cfa.compile`` lowers to facet storage, timed sweep by sweep.

The run is the ``stencil`` kind's: set-up compiles the configuration's
program on its space and tile with the traffic's backend rule, makes the
traffic's input sets on the device from the seed and runs one warm-up
sweep; the window runs whole sweeps back to back, cycling through the
input sets, and closes at the end of the first sweep that ends after
``--seconds``.  The last sweep on each input set and up to
``CHECKED_SWEEPS`` others, drawn from the seed, are compared once the
window has closed with :func:`reference_volume`, every field of every
facet array point by point.

A point holds one value per field, so live-in planes are ``(1, F, N_1,
N_2)`` and the facet arrays carry a field axis between their tile
coordinates and intra-tile positions.  The work counts are the scalar
ones in values: bytes at ``F`` times the element size, and the
operations of the configuration's equations, one update per field.
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

# -- the plain reference ------------------------------------------------------


def _cross(stencil: dict) -> dict:
    """The 5-point cross of textbook reads as a ``work`` stencil (one tap
    per read), to count widths and bytes from."""
    return {"skew": stencil["skew"], "taps": [[r, None] for r in stencil["reads"]]}


def reference_volume(x, n_planes: int, stencil: dict):
    """Every plane ``(n_planes, F, N_1, N_2)`` of the sweep, computed plane
    by plane in plain jnp on the skewed rectangle.

    ``x`` holds the live-in plane (time -1) of every field.  One Yee step
    of the textbook grid, fields in the configuration's order, reads the
    previous plane at offset ``(di, dj)``; skewed by (1, 1) that is
    ``(di - 1, dj - 1)``, and zero outside the space.  ``hz`` reads the
    new ``ex[i][j+1]`` and ``ey[i+1][j]``, each the field's own update of
    the previous plane.  Imports nothing of the program under test.
    """
    if tuple(stencil["fields"]) != ("ey", "ex", "hz") or list(stencil["skew"]) != [1, 1]:
        raise ValueError("the reference is fdtd-2d's (ey, ex, hz) skewed by (1, 1)")
    return _planes(x[-1], n_planes=n_planes,
                   coeffs=tuple(float(c) for c in stencil["coefficients"]))


@functools.partial(jax.jit, static_argnames=("n_planes", "coeffs"))
def _planes(plane, *, n_planes, coeffs):
    n1, n2 = plane.shape[1:]
    c_ey, c_ex, c_hz = (jnp.asarray(c, plane.dtype) for c in coeffs)

    def step(prev, _):
        p = jnp.pad(prev, ((0, 0), (2, 0), (2, 0)))

        def at(f, di, dj):  # textbook read (di, dj) of the previous step
            return p[f, 1 + di:1 + di + n1, 1 + dj:1 + dj + n2]

        hz = at(2, 0, 0)
        ey = at(0, 0, 0) - c_ey * (hz - at(2, -1, 0))
        ex = at(1, 0, 0) - c_ex * (hz - at(2, 0, -1))
        ey_below = at(0, 1, 0) - c_ey * (at(2, 1, 0) - hz)
        ex_right = at(1, 0, 1) - c_ex * (at(2, 0, 1) - hz)
        hz = hz - c_hz * (ex_right - ex + ey_below - ey)
        new = jnp.stack([ey, ex, hz])
        return new, new

    return jax.lax.scan(step, plane, None, length=n_planes)[1]


def facet_value_index(shape, axis: int, width: int, tile, outer, inner,
                      n_live: int, ext_shape) -> np.ndarray:
    """Flat index into the extended volume ``(n_live + planes, F, N_1, ..)``
    of every element of one facet array.

    ``outer``/``inner`` are the array's axis orders as the returned facet
    storage describes itself: tile coordinates first, then the field axis,
    then intra-tile positions, where axis ``axis`` holds ``x mod width``
    over the tile's last ``width`` slices.  Facet 0 carries one leading
    block row of live-in planes, so its tile coordinate on axis 0 starts
    at -1.
    """
    idx = np.indices(shape, dtype=np.int64)
    n = len(outer)
    q = {a: idx[i] - (1 if axis == 0 and a == 0 else 0) for i, a in enumerate(outer)}
    x = [None] * len(tile)
    for j, a in enumerate(inner):
        r = idx[n + 1 + j]
        if a == axis:
            base = q[a] * tile[a] + tile[a] - width
            x[a] = base + np.mod(r - base, width)
        else:
            x[a] = q[a] * tile[a] + r
    x[0] = x[0] + n_live
    return np.ravel_multi_index((x[0], idx[n], *x[1:]), ext_shape)


# -- the cell ------------------------------------------------------------------


def input_sets(seed: int, cfg: dict, traffic: dict):
    """The traffic's ``input_sets`` live-in inputs ``(1, F, N_1, N_2)``,
    drawn on the device from ``seed`` in one jitted call; any whole
    number (also past 32 bits) is a seed."""
    n_sets = int(traffic["input_sets"])
    stencil = cfg["stencil"]
    shape = (work.widths(_cross(stencil))[0], len(stencil["fields"]), *cfg["space"][1:])
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
    n_fields = len(stencil["fields"])
    dtype = jnp.dtype(cfg["dtype"])
    w = work.widths(_cross(stencil))
    n_sets = int(traffic["input_sets"])

    with cell.span("setup"):
        t = [time.perf_counter()]
        compiled = compile_cell(cfg, traffic)
        pipe = compiled.pipeline
        n_tiles = math.prod(pipe.num_tiles)
        cell.log(f"backend={compiled.backend} storage={compiled.storage} "
                 f"space={space} fields={n_fields} tile={tile} tiles={n_tiles} "
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
    outputs = sorted([*last.values(), *drawn.items], key=lambda o: o[0])
    cell.log(f"[check] {len(outputs)} of {n + cell.trace} sweeps: "
             f"{[i for i, _, _ in outputs]}")
    outputs = [(j, facets) for _, j, facets in outputs]

    limit = float(cfg["limits"]["max_rel_err"])
    with cell.span("check"):
        worst, failed = check(compiled, outputs, sets, space[0], stencil, limit)
    value_bytes = n_fields * dtype.itemsize
    return dict(
        end_to_end={"sweep_s": window_s / n, "setup_s": setup_s},
        attempted=n + cell.trace, failed=failed,
        checks=[("max_rel_err", worst, limit)],
        memory_peak_bytes=peak,
        layer=dict(
            units=n, tiles_per_sweep=n_tiles,
            recorder=recorder,
            sweep_bytes=work.sweep_bytes(space, tile, w, value_bytes),
            sweep_flops=math.prod(space) * sum(stencil["ops_per_point"].values()),
            kernel_tile_bytes=work.kernel_tile_bytes(tile, w, value_bytes),
        ),
    )


def check(compiled, outputs, sets, n_planes: int, stencil: dict,
          limit: float) -> tuple[float, int]:
    """Largest |facet - reference| over every value of every facet array
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
    ext_shape = (n_live + n_planes, len(stencil["fields"]), *pipe.space.sizes[1:])
    index = {}
    for k, spec in pipe.specs.items():
        index[k] = facet_value_index(pipe.facet_shape(k), k, spec.width, tile,
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
