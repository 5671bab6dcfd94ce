"""The paper's Table I benchmark suite as uniform-dependence program specs.

Each program is given in the *post-skew normal form* the paper assumes
(§IV-E: "we expect such a pre-processing to have been done"): a rectangular
iteration space with all dependence vectors backwards in every dimension.
The skew applied to each classic benchmark is recorded in ``skew`` so that
tests can relate the skewed recurrence back to the textbook stencil.

Iteration semantics: axis 0 is the (skewed) time axis; ``plane_update``
computes the value plane at time ``s`` from the ``depth`` previous planes,
where each previous plane is passed *with its backward halo attached* (halo
width ``w_a`` on the low side of each spatial axis ``a``).  Out-of-space
reads are zero (Dirichlet boundary), making the recurrence total on the
rectangular space.

The suite is dimension-generic: a program's iteration space is d-dimensional
(time + d-1 spatial axes) and planes are (d-1)-dimensional.  Besides the 3-D
Table I benchmarks, the registry carries ``heat1d`` (a 1-D heat equation as
a 2-D tiled space) and ``heat3d`` (a 3-D spatial heat equation as a 4-D
space — the §IV-J regime where some k-th-level neighbours no longer merge
into one burst).

A program may carry several fields per point (``fields``, e.g. PolyBench's
``fdtd-2d`` with ``ey``, ``ex`` and ``hz``; registered in
``FIELD_PROGRAMS``): its planes then have a field axis right after time,
``(F, N_1, ..)``, and ``plane_update`` maps ``(F, ..)`` history planes to
one ``(F, ..)`` plane.  Its ``deps`` carry the count (``Deps.fields``).  A
scalar program has no field axis at all.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .spaces import Deps, IterSpace, Tiling, facet_widths

__all__ = ["StencilProgram", "PROGRAMS", "FIELD_PROGRAMS", "get_program",
           "fdtd2d_textbook"]


@dataclasses.dataclass(frozen=True)
class StencilProgram:
    """A uniform-dependence benchmark in post-skew normal form."""

    name: str
    deps: Deps
    default_tile: tuple[int, ...]
    paper_tiles: tuple[tuple[int, ...], ...]  # Table I tile-size sweep corners
    equivalent_app: str
    skew: tuple[int, ...]  # spatial skew factors applied per spatial axis
    # update: (prev_planes [depth][(F,) spatial+halo], widths) -> new plane
    # [(F,) spatial]
    plane_update: Callable[[Sequence[jnp.ndarray], tuple[int, ...]], jnp.ndarray]
    # names of the values a point holds, in update order; () = one scalar
    fields: tuple[str, ...] = ()

    def __post_init__(self) -> None:
        if len(self.fields) == 1:
            raise ValueError(
                f"{self.name}: a one-field program is a scalar program; "
                "leave fields empty")
        if self.n_fields != max(1, len(self.fields)):
            raise ValueError(
                f"{self.name}: deps carry {self.deps.fields} values a point, "
                f"fields names {len(self.fields)}")

    @property
    def ndim(self) -> int:
        return self.deps.ndim

    @property
    def n_fields(self) -> int:
        """Values a point holds (1 for a scalar program): ``deps.fields``,
        so whatever is built from the deps prices them."""
        return self.deps.fields

    def with_fields(self, dims: Sequence, entry) -> tuple:
        """``dims`` (one entry per axis, time first) with ``entry`` as the
        field axis after time; a scalar program has none, so its ``dims``
        come back unchanged."""
        dims = tuple(dims)
        return (dims[0], entry, *dims[1:]) if self.fields else dims

    @property
    def widths(self) -> tuple[int, ...]:
        return facet_widths(self.deps)

    def space(self, sizes: Sequence[int]) -> IterSpace:
        return IterSpace(tuple(sizes))

    def tiling(self, sizes: Sequence[int] | None = None) -> Tiling:
        return Tiling(tuple(sizes) if sizes is not None else self.default_tile)


def _shiftn(prev: jnp.ndarray, offs: Sequence[int], w: tuple[int, ...]) -> jnp.ndarray:
    """Read ``prev`` (a (d-1)-D plane with low-side halo ``w[1:]``) at the
    spatial offset vector ``offs`` (all components <= 0), returning the
    interior-sized plane.  Dimension-generic ``_shift2``."""
    p = jnp.asarray(prev)
    sl = tuple(
        slice(w[a + 1] + o, w[a + 1] + o + (p.shape[a] - w[a + 1]))
        for a, o in enumerate(offs)
    )
    return p[sl]


def _shift2(prev: jnp.ndarray, di: int, dj: int, w: tuple[int, ...]) -> jnp.ndarray:
    """Read ``prev`` (with low-side halo (w1, w2)) at spatial offset (di, dj),
    di, dj <= 0, returning the interior-sized plane."""
    return _shiftn(prev, (di, dj), w)


def _jacobi_update(offsets: Sequence[tuple[int, ...]], coeffs: Sequence[float]):
    """Depth-1 weighted-sum update over spatial offsets, any dimension."""
    def update(prev_planes: Sequence[jnp.ndarray], w: tuple[int, ...]) -> jnp.ndarray:
        p = prev_planes[-1]  # plane s-1 (depth-1 history used by jacobi family)
        acc = None
        for off, c in zip(offsets, coeffs):
            v = _shiftn(p, off, w) * float(c)  # python float: no promotion
            acc = v if acc is None else acc + v
        return acc

    return update


# --- jacobi2d5p: 5-point Laplace; skew (1,1) -> deps (-1, di-1, dj-1) -------
_J5_OFF = [(-1, -1), (0, -1), (-2, -1), (-1, 0), (-1, -2)]
_J5 = Deps(tuple((-1, a, b) for a, b in _J5_OFF))

# --- jacobi2d9p: 3x3 convolution; skew (1,1) --------------------------------
_J9_OFF = [(a - 1, b - 1) for a in (-1, 0, 1) for b in (-1, 0, 1)]
_J9 = Deps(tuple((-1, a, b) for a, b in _J9_OFF))

# --- gaussian: 5x5 blur; skew (2,2) -> 25 deps ------------------------------
_GA_OFF = [(a - 2, b - 2) for a in range(-2, 3) for b in range(-2, 3)]
_GA = Deps(tuple((-1, a, b) for a, b in _GA_OFF))
_GA_K = np.outer([1, 4, 6, 4, 1], [1, 4, 6, 4, 1]).astype(np.float64)
_GA_K /= _GA_K.sum()

# --- heat1d: 1-D heat equation as a 2-D tiled space; skew (1) ---------------
# textbook: u[t,x] = a*u[t-1,x-1] + (1-2a)*u[t-1,x] + a*u[t-1,x+1]; skewing
# x by t maps the offsets dx in (-1, 0, 1) to backward vectors (-1, dx-1).
_H1_OFF = [(-2,), (-1,), (0,)]
_H1 = Deps(tuple((-1, *o) for o in _H1_OFF))
_H1_A = 0.25  # diffusion number; coeffs (a, 1-2a, a)

# --- heat3d: 3-D spatial heat equation as a 4-D space; skew (1,1,1) ---------
# 7-point stencil: centre + one neighbour per spatial axis and direction;
# skewing each spatial axis by t maps offset d in {-1,0,1} to d-1 on that
# axis.  This is the d >= 4 regime of §IV-J: level-2/3 neighbour pieces
# whose crossed axes miss every candidate facet's extension direction can
# no longer merge into an existing burst.
_H3_OFF = [(0, 0, 0)] + [
    tuple(s if a == ax else 0 for a in range(3))
    for ax in range(3) for s in (-1, 1)
]
_H3 = Deps(tuple((-1, *(c - 1 for c in o)) for o in _H3_OFF))
_H3_A = 0.1  # coeffs: centre 1-6a, each neighbour a


# --- smith-waterman-3seq: 3-sequence alignment; skew s = i+j+k --------------
# original deps: the 7 nonzero corners of {0,-1}^3; skewed by s = i+j+k they
# become (sum, j, k)-space vectors, all strictly backwards on axis 0.
_SW_RAW = [
    (-1, 0, 0), (0, -1, 0), (0, 0, -1),
    (-1, -1, 0), (-1, 0, -1), (0, -1, -1), (-1, -1, -1),
]
_SW = Deps(tuple((a + b + c, b, c) for a, b, c in _SW_RAW))


def _sw_update(prev_planes: Sequence[jnp.ndarray], w: tuple[int, ...]) -> jnp.ndarray:
    """Max-plus alignment recurrence on the skewed lattice (depth 3)."""
    # deps at axis-0 distance 1: (j,k) offsets (0,0),(-1,0),(0,-1)
    # distance 2: (-1,0),(0,-1),(-1,-1);   distance 3: (-1,-1)
    p1, p2, p3 = prev_planes[-1], prev_planes[-2], prev_planes[-3]
    cands = [
        _shift2(p1, 0, 0, w) + 1.0,
        _shift2(p1, -1, 0, w) + 1.0,
        _shift2(p1, 0, -1, w) + 1.0,
        _shift2(p2, -1, 0, w) + 2.0,
        _shift2(p2, 0, -1, w) + 2.0,
        _shift2(p2, -1, -1, w) + 2.0,
        _shift2(p3, -1, -1, w) + 3.0,
    ]
    out = cands[0]
    for c in cands[1:]:
        out = jnp.maximum(out, c)
    return out


def _gol_update(prev_planes: Sequence[jnp.ndarray], w: tuple[int, ...]) -> jnp.ndarray:
    """2nd-order finite difference flavoured 9-point update (jacobi2d9p-gol)."""
    p = prev_planes[-1]
    neigh = None
    for (di, dj) in _J9_OFF:
        v = _shift2(p, di, dj, w)
        neigh = v if neigh is None else neigh + v
    centre = _shift2(p, -1, -1, w)
    return 2.0 * centre - neigh / 9.0


# --- fdtd2d: 2-D FDTD (Yee) with three coupled fields; skew (1,1) -----------
# PolyBench/C 4.2 stencils/fdtd-2d, one time step:
#   ey[i][j] -= 0.5 * (hz[i][j] - hz[i-1][j])
#   ex[i][j] -= 0.5 * (hz[i][j] - hz[i][j-1])
#   hz[i][j] -= 0.7 * (ex[i][j+1] - ex[i][j] + ey[i+1][j] - ey[i][j])
# hz reads the new ex and ey; with their updates inlined, the vector
# (ey, ex, hz) of step t reads step t-1 on the 5-point cross, so skewed by
# (1, 1) it has jacobi2d5p's dependence vectors.
_FD_FIELDS = ("ey", "ex", "hz")
_FD_C = (0.5, 0.5, 0.7)  # ey, ex, hz


def _fdtd2d_update(prev_planes: Sequence[jnp.ndarray], w: tuple[int, ...]) -> jnp.ndarray:
    """One (ey, ex, hz) plane from the last: PolyBench's order, the new
    ``ex``/``ey`` that ``hz`` reads recomputed from the previous plane."""
    p = prev_planes[-1]
    ey, ex, hz = p[0], p[1], p[2]

    def at(f, di, dj):  # textbook offset (di, dj) of step t-1, skewed
        return _shift2(f, di - 1, dj - 1, w)

    ce, cx, ch = _FD_C
    hz_c = at(hz, 0, 0)
    ey_n = at(ey, 0, 0) - ce * (hz_c - at(hz, -1, 0))
    ex_n = at(ex, 0, 0) - cx * (hz_c - at(hz, 0, -1))
    ey_s = at(ey, 1, 0) - ce * (at(hz, 1, 0) - hz_c)  # new ey[i+1][j]
    ex_e = at(ex, 0, 1) - cx * (at(hz, 0, 1) - hz_c)  # new ex[i][j+1]
    hz_n = hz_c - ch * (ex_e - ex_n + ey_s - ey_n)
    return jnp.stack([ey_n, ex_n, hz_n])


def fdtd2d_textbook(ey, ex, hz, steps: int):
    """PolyBench ``fdtd-2d``'s three loops on the unskewed grid, ``steps``
    time steps, in plain jnp: the update in PolyBench's order, zero
    outside the grid instead of its range guards, no ``_fict_`` source
    row.  Returns the ``(steps, 3, NX, NY)`` planes (ey, ex, hz) of every
    step; the oracle the skewed ``fdtd2d`` is related to on the interior."""
    ce, cx, ch = _FD_C

    def step(f, _):
        ey, ex, hz = f
        up = jnp.pad(hz, ((1, 0), (0, 0)))[:-1]  # hz[i-1][j]
        left = jnp.pad(hz, ((0, 0), (1, 0)))[:, :-1]  # hz[i][j-1]
        ey = ey - ce * (hz - up)
        ex = ex - cx * (hz - left)
        ex_e = jnp.pad(ex, ((0, 0), (0, 1)))[:, 1:]  # ex[i][j+1]
        ey_s = jnp.pad(ey, ((0, 1), (0, 0)))[1:]  # ey[i+1][j]
        hz = hz - ch * (ex_e - ex + ey_s - ey)
        f = jnp.stack([ey, ex, hz])
        return f, f

    return jax.lax.scan(step, jnp.stack([ey, ex, hz]), None, length=steps)[1]


PROGRAMS: dict[str, StencilProgram] = {
    "jacobi2d5p": StencilProgram(
        name="jacobi2d5p",
        deps=_J5,
        default_tile=(16, 16, 16),
        paper_tiles=((16, 16, 16), (32, 32, 32), (64, 64, 64), (128, 128, 128)),
        equivalent_app="Laplace equation",
        skew=(1, 1),
        plane_update=_jacobi_update(_J5_OFF, [0.2] * 5),
    ),
    "jacobi2d9p": StencilProgram(
        name="jacobi2d9p",
        deps=_J9,
        default_tile=(16, 16, 16),
        paper_tiles=((16, 16, 16), (32, 32, 32), (64, 64, 64), (128, 128, 128)),
        equivalent_app="3x3 convolution",
        skew=(1, 1),
        plane_update=_jacobi_update(_J9_OFF, [1.0 / 9.0] * 9),
    ),
    "jacobi2d9p-gol": StencilProgram(
        name="jacobi2d9p-gol",
        deps=_J9,
        default_tile=(16, 16, 16),
        paper_tiles=((16, 16, 16), (32, 32, 32), (64, 64, 64), (128, 128, 128)),
        equivalent_app="2nd-order finite difference",
        skew=(1, 1),
        plane_update=_gol_update,
    ),
    "gaussian": StencilProgram(
        name="gaussian",
        deps=_GA,
        default_tile=(4, 16, 16),
        paper_tiles=((4, 16, 16), (4, 32, 32), (4, 64, 64), (4, 128, 128)),
        equivalent_app="5x5 Gaussian Blur",
        skew=(2, 2),
        plane_update=_jacobi_update(_GA_OFF, list(_GA_K.ravel())),
    ),
    "smith-waterman-3seq": StencilProgram(
        name="smith-waterman-3seq",
        deps=_SW,
        default_tile=(16, 16, 16),
        paper_tiles=((16, 16, 16), (32, 32, 32), (64, 64, 64), (128, 128, 128)),
        equivalent_app="Alignment of 3 sequences",
        skew=(0, 0),  # skew folded into axis 0 = i+j+k
        plane_update=_sw_update,
    ),
    # -- beyond Table I: non-3-D workloads (the N-D executor path) ----------
    "heat1d": StencilProgram(
        name="heat1d",
        deps=_H1,
        default_tile=(8, 8),
        paper_tiles=((8, 8), (16, 16), (32, 32), (64, 64)),
        equivalent_app="1-D heat equation (2-D tiled space)",
        skew=(1,),
        plane_update=_jacobi_update(_H1_OFF, [_H1_A, 1 - 2 * _H1_A, _H1_A]),
    ),
    "heat3d": StencilProgram(
        name="heat3d",
        deps=_H3,
        default_tile=(4, 4, 4, 4),
        paper_tiles=((4, 4, 4, 4), (2, 4, 4, 4), (4, 8, 8, 8)),
        equivalent_app="3-D heat equation (4-D tiled space, §IV-J regime)",
        skew=(1, 1, 1),
        plane_update=_jacobi_update(
            [tuple(c - 1 for c in o) for o in _H3_OFF],
            [1 - 6 * _H3_A] + [_H3_A] * 6,
        ),
    ),
}

#: Programs with several coupled values per point.  None is in the paper,
#: so they stay out of ``PROGRAMS``, the suite the figure scripts sweep
#: (their backends and storages are scalar-only in part).
FIELD_PROGRAMS: dict[str, StencilProgram] = {
    "fdtd2d": StencilProgram(
        name="fdtd2d",
        deps=Deps(_J5.vectors, fields=len(_FD_FIELDS)),
        default_tile=(16, 16, 16),
        paper_tiles=(),  # not in the paper's Table I
        equivalent_app="2-D FDTD (Yee) electromagnetics, 3 coupled fields",
        skew=(1, 1),
        plane_update=_fdtd2d_update,
        fields=_FD_FIELDS,
    ),
}


def get_program(name: str) -> StencilProgram:
    """A program of ``PROGRAMS`` or ``FIELD_PROGRAMS`` by name."""
    try:
        return PROGRAMS[name] if name in PROGRAMS else FIELD_PROGRAMS[name]
    except KeyError:
        raise KeyError(f"unknown benchmark {name!r}; have "
                       f"{sorted(PROGRAMS) + sorted(FIELD_PROGRAMS)}") from None
