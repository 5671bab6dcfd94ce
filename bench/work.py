"""The work a tiled stencil sweep needs, counted from its shapes alone.

Everything here is a function of the iteration space, the tile and the
stencil's taps as the configuration states them, never of the program's
layout or executor: a later change that moves fewer bytes does not change
these counts, so a roofline share built on them stays comparable.

Conventions (post-skew normal form, as the configurations state them):
axis 0 is time; a tap ``(dt, dx_1, ..)`` of the textbook stencil reads
the point ``x + (dt, dx_1 - skew_1 * ..)``; every skewed offset is <= 0,
so a tile reads only points below its low corner.
"""
from __future__ import annotations

import itertools
import math
from typing import Sequence

import numpy as np


def skewed_offsets(stencil: dict) -> list[tuple[int, ...]]:
    """The dependence vectors of ``stencil`` in post-skew coordinates.

    ``stencil["taps"]`` lists ``[[dx_1, ..], coefficient]`` pairs of the
    textbook update ``u_t(x) = sum c * u_{t-1}(x + dx)``; skewing spatial
    axis ``a`` by ``skew[a]`` per time step makes the offset
    ``(-1, dx_1 - skew_1, ..)``.
    """
    skew = stencil["skew"]
    return [(-1, *(d - s for d, s in zip(dx, skew, strict=True)))
            for dx, _ in stencil["taps"]]


def widths(stencil: dict) -> tuple[int, ...]:
    """Dependence width per axis: the largest backward reach of any tap."""
    offs = np.asarray(skewed_offsets(stencil))
    if (offs > 0).any():
        raise ValueError(f"skewed offsets must be <= 0: {offs.tolist()}")
    return tuple(int(w) for w in (-offs).max(axis=0))


def flops_per_point(stencil: dict) -> int:
    """One multiply per tap and one add between taps."""
    return 2 * len(stencil["taps"]) - 1


def _shell_points(space: Sequence[int], tile: Sequence[int],
                  w: Sequence[int], q: Sequence[int]) -> int:
    """Points of tile ``q``'s (w + t) halo box below its low corner that
    hold data: inside the space, or live-in planes (time in [-w_0, 0))
    over the spatial extent.  Zero-boundary points hold nothing."""
    d = len(space)
    lo = [qa * ta for qa, ta in zip(q, tile)]
    # per axis: how many of the box's coordinates lie in the data range,
    # and how many of those lie inside the tile itself
    box, inner = 1, 1
    for a in range(d):
        data_lo = -w[0] if a == 0 else 0
        b_lo, b_hi = max(lo[a] - w[a], data_lo), lo[a] + tile[a]
        box *= max(0, b_hi - b_lo)
        inner *= tile[a]
    return box - inner


def facet_points(tile: Sequence[int], w: Sequence[int]) -> int:
    """Points a tile writes to its facets: for each axis with a dependence,
    the tile's last ``w_k`` slices along it."""
    t = list(tile)
    return sum(wk * math.prod(t[:k] + t[k + 1:]) for k, wk in enumerate(w) if wk)


def tile_points(space: Sequence[int], tile: Sequence[int], w: Sequence[int],
                q: Sequence[int]) -> tuple[int, int]:
    """(flow-in points read, facet points written) of tile ``q``."""
    return _shell_points(space, tile, w, q), facet_points(tile, w)


def sweep_bytes(space: Sequence[int], tile: Sequence[int], w: Sequence[int],
                elem_bytes: int) -> int:
    """Compulsory bytes of one tiled sweep: each tile reads its flow-in
    and writes its facets once."""
    nt = [n // t for n, t in zip(space, tile, strict=True)]
    total = 0
    for q in itertools.product(*(range(n) for n in nt)):
        rd, wr = tile_points(space, tile, w, q)
        total += rd + wr
    return total * elem_bytes


def sweep_flops(space: Sequence[int], stencil: dict) -> int:
    """Operations of one sweep: every point of the space is updated once."""
    return math.prod(space) * flops_per_point(stencil)


def kernel_tile_bytes(tile: Sequence[int], w: Sequence[int], elem_bytes: int) -> int:
    """Bytes the tile kernel must move for one tile: read the (w + t) halo
    box, write the t interior."""
    halo = math.prod(ta + wa for ta, wa in zip(tile, w, strict=True))
    return (halo + math.prod(tile)) * elem_bytes
