"""The benchmark's work functions, pinned by hand and by brute force."""
import itertools
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


JACOBI = config("jacobi2d5p-polybench-medium")
HEAT = config("heat3d-polybench-medium")


@pytest.mark.parametrize("cfg, widths, flops", [
    (JACOBI, (1, 2, 2), 9),
    (HEAT, (1, 2, 2, 2), 13),
])
def test_widths_and_flops(cfg, widths, flops):
    assert work.widths(cfg["stencil"]) == widths
    assert work.flops_per_point(cfg["stencil"]) == flops


@pytest.mark.parametrize("cfg, tile, q, reads, writes", [
    # the (w + t) halo box less the tile: 17*34*130 - 16*32*128
    (JACOBI, (16, 32, 128), (1, 4, 1), 9604, 9216),
    (HEAT, (4, 20, 20, 20), (1, 1, 1, 1), 21240, 17600),
])
def test_interior_tile_points(cfg, tile, q, reads, writes):
    w = work.widths(cfg["stencil"])
    assert work.tile_points(cfg["space"], tile, w, q) == (reads, writes)


def test_kernel_tile_bytes():
    w = work.widths(JACOBI["stencil"])
    assert work.kernel_tile_bytes((16, 32, 128), w, 4) == (17 * 34 * 130 + 16 * 32 * 128) * 4


def _brute_sweep_points(space, tile, w):
    """Enumerate every halo-box point of every tile that holds data (in the
    space, or a live-in plane over the spatial extent) and every facet
    point, one by one."""
    d = len(space)
    total = 0
    nt = [n // t for n, t in zip(space, tile)]
    for q in itertools.product(*(range(n) for n in nt)):
        lo = [qa * ta for qa, ta in zip(q, tile)]
        box = [range(lo[a] - w[a], lo[a] + tile[a]) for a in range(d)]
        for p in itertools.product(*box):
            below = any(p[a] < lo[a] for a in range(d))
            data = p[0] >= -w[0] and all(p[a] >= 0 for a in range(1, d))
            total += below and data
        for k in range(d):
            if w[k]:
                total += w[k] * math.prod(tile[a] for a in range(d) if a != k)
    return total


@pytest.mark.parametrize("space, tile, w", [
    ((8, 8, 16), (4, 4, 8), (1, 2, 2)),
    ((4, 6, 6, 6), (2, 3, 3, 3), (1, 2, 2, 2)),
])
def test_sweep_bytes_matches_enumeration(space, tile, w):
    assert work.sweep_bytes(space, tile, w, 4) == 4 * _brute_sweep_points(space, tile, w)


def test_sweep_flops():
    assert work.sweep_flops((96, 256, 256), JACOBI["stencil"]) == 96 * 256 * 256 * 9


@pytest.mark.parametrize("cfg", [JACOBI, HEAT], ids=lambda c: c["name"])
def test_taps_describe_the_program(cfg):
    """The configuration's textbook taps, skewed, are the program's
    dependence vectors, and its coefficients sum to the same total."""
    from repro.core.cfa.programs import get_program

    prog = get_program(cfg["program"])
    assert sorted(work.skewed_offsets(cfg["stencil"])) == sorted(prog.deps.vectors)
    assert work.widths(cfg["stencil"]) == prog.widths
    assert np.isclose(sum(c for _, c in cfg["stencil"]["taps"]), 1.0)


@pytest.mark.parametrize("cfg", [JACOBI, HEAT], ids=lambda c: c["name"])
def test_config_sizes_agree(cfg):
    """The space is 2 * TSTEPS planes of N points a side (PolyBench's two
    half-steps per time step), and divides by the tile."""
    assert cfg["space"][0] == cfg["published"]["planes_per_step"] * cfg["TSTEPS"]
    assert all(n == cfg["N"] for n in cfg["space"][1:])
    assert all(n % t == 0 for n, t in zip(cfg["space"], cfg["tile"], strict=True))
