"""The compiled facet fetch: one jitted gather program per pipeline that
reads every tile's halo through device-resident tables.

It must equal the eager piece-by-piece gather bit for bit, compile once
per pipeline, stay off the one path that keeps the eager gather (facets
over several devices), and keep the int32 offset guard for every tile's
table.
"""
import itertools
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cfa import CFAPipeline, IterSpace, Tiling, get_program, obs
from repro.core.cfa.facets import FacetSpec
from repro.core.cfa.irredundant import IrredundantPipeline
from repro.core.cfa.transform import _fetch_halo

REPO = Path(__file__).resolve().parents[1]

# (program, space, tile): 3-D, 4-D and 2-D, each with boundary tiles and
# a row of live-in tiles
SPACES = {
    "jacobi2d5p": ((8, 12, 8), (4, 4, 4)),
    "heat3d": ((8, 8, 8, 8), (4, 4, 4, 4)),
    "heat1d": ((8, 12), (4, 4)),
    # three fields a point: every table entry spread over the field axis
    "fdtd2d": ((6, 12, 16), (2, 4, 8)),
    # the 9-point stencil's corner pieces
    "jacobi2d9p": ((8, 8, 8), (4, 4, 4)),
    # facets of width 2
    "gaussian": ((4, 16, 16), (2, 8, 8)),
    # a history of depth 3 along time
    "smith-waterman-3seq": ((9, 8, 8), (3, 4, 4)),
    # tiles that width-2 facets do not divide: the modulo labelling of a
    # block depends on the tile
    "jacobi2d9p-gol": ((8, 12, 10), (4, 3, 5)),
}
STORAGES = {"redundant": CFAPipeline, "irredundant": IrredundantPipeline}
CASES = [pytest.param(name, storage, id=f"{name}-{storage}")
         for name in SPACES for storage in STORAGES]


def _pipe(name, storage):
    space, tile = SPACES[name]
    return STORAGES[storage](get_program(name), IterSpace(space), Tiling(tile))


def _inputs(pipe):
    w0 = pipe.specs[0].width
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=pipe.program.with_fields(
        (w0, *pipe.space.sizes[1:]), pipe.fields)))


def _tiles(pipe):
    return list(itertools.product(*(range(n) for n in pipe.num_tiles)))


@pytest.mark.parametrize("name,storage", CASES)
def test_compiled_fetch_equals_eager_gather(name, storage):
    """Every tile, live-in and boundary tiles included, on facets of
    distinct random values (a misplaced element cannot hide behind an
    equal neighbour)."""
    pipe = _pipe(name, storage)
    rng = np.random.default_rng(1)
    facets = {k: jnp.asarray(rng.normal(size=pipe.facet_shape(k)))
              for k in pipe.specs}
    for tile in _tiles(pipe):
        got = pipe.copy_in(facets, tile)
        want = pipe._gather_halo(facets, *pipe._halo_maps(tile))
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(np.asarray(got), np.asarray(want)), tile


@pytest.mark.parametrize("name,storage", CASES)
def test_wavefront_sweep_compiles_the_fetch_once(name, storage):
    """A whole sweep, then a second one on the same pipeline, leave the
    jitted fetch with one compiled entry and the plan built once."""
    pipe = _pipe(name, storage)
    _fetch_halo.clear_cache()
    first = pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    plan = pipe._fetch_plan
    assert _fetch_halo._cache_size() == 1
    again = pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    assert pipe._fetch_plan is plan and _fetch_halo._cache_size() == 1
    for k in first:
        assert np.array_equal(np.asarray(first[k]), np.asarray(again[k]))


@pytest.mark.parametrize("name,storage", CASES)
def test_fetch_path_is_counted(name, storage):
    """One ``fetch_compiled`` a tile from facets on one device, none
    eager, and the recorder still reconciles."""
    pipe = _pipe(name, storage)
    rec = obs.TraceRecorder()
    pipe.recorder = rec
    pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    assert rec.counters.get("fetch_eager") == 0
    assert rec.counters.get("fetch_compiled") == len(_tiles(pipe))
    assert pipe._fetch_plan is not None
    assert rec.reconcile(pipe)["ok"]


_MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, sys.argv[1])
    import test_fetch_plan as t
    from repro.core.cfa import obs

    out = {}
    devices = jax.devices()[:2]
    for name in t.SPACES:
        for storage in t.STORAGES:
            pipe = t._pipe(name, storage)
            rng = np.random.default_rng(1)
            host = {k: rng.normal(size=pipe.facet_shape(k)) for k in pipe.specs}
            # facet k on device k mod 2, as the sharded executor places
            # port-resident facets
            spread = {k: jax.device_put(v, devices[k % 2])
                      for k, v in host.items()}
            one = {k: jax.device_put(v, devices[0]) for k, v in host.items()}
            rec = obs.TraceRecorder()
            pipe.recorder = rec
            got = [pipe.copy_in(spread, tile) for tile in t._tiles(pipe)]
            eager = rec.counters.get("fetch_eager")
            want = [pipe.copy_in(one, tile) for tile in t._tiles(pipe)]
            fetch = dict(
                tiles=len(got), eager=eager,
                compiled=rec.counters.get("fetch_compiled"),
                exact=all(np.array_equal(np.asarray(g), np.asarray(w))
                          for g, w in zip(got, want)))
            # every tile's commit of one random halo buffer, to the spread
            # facets and then to the one-device facets (which it donates)
            rec = obs.TraceRecorder()
            pipe.recorder = rec
            halos = [rng.normal(size=pipe.halo_shape) for _ in t._tiles(pipe)]
            for tile, H in zip(t._tiles(pipe), halos):
                spread = pipe.copy_out(spread, tile, jnp.asarray(H))
            eager = rec.counters.get("commit_eager")
            for tile, H in zip(t._tiles(pipe), halos):
                one = pipe.copy_out(one, tile, jnp.asarray(H))
            commit = dict(
                tiles=len(halos), eager=eager,
                compiled=rec.counters.get("commit_compiled"),
                devices=len(set().union(*(v.devices() for v in spread.values()))),
                exact=all(np.array_equal(np.asarray(spread[k]), np.asarray(one[k]))
                          for k in pipe.specs))
            out[f"{name}-{storage}"] = dict(fetch=fetch, commit=commit)
    print("MULTI_DEVICE " + json.dumps(out))
""")


@pytest.fixture(scope="session")
def multi_device_runs():
    """Every tile's fetch, then every tile's commit, on facets spread over
    two of four forced host devices and on the same facets on one device;
    all cases in one subprocess (the test session has one CPU device).
    ``tests/test_commit_plan.py`` reads the commits."""
    from conftest import multidevice_emulation_reason

    reason = multidevice_emulation_reason()
    if reason is not None:
        pytest.skip(f"multi-device emulation unavailable: {reason}")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT, str(REPO / "tests")],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("MULTI_DEVICE ")]
    assert lines, f"stdout={res.stdout}\nstderr={res.stderr[-3000:]}"
    return json.loads(lines[-1][len("MULTI_DEVICE "):])


@pytest.mark.parametrize("name,storage", CASES)
def test_multi_device_facets_take_the_eager_fetch(name, storage,
                                                  multi_device_runs):
    """Facets over two devices keep the host-buffer gather on every tile,
    and the halos equal the compiled fetch's from one device."""
    got = multi_device_runs[f"{name}-{storage}"]["fetch"]
    n = got["tiles"]
    # the spread fetches all eager; the one-device fetches after them compiled
    assert (got["eager"], got["compiled"]) == (n, n)
    assert got["exact"]


@pytest.mark.parametrize("name,storage", CASES)
def test_table_offset_past_int32_raises_on_first_fetch(name, storage,
                                                       monkeypatch):
    """An offset past int32 in the tables of the last row of time tiles
    (which the first tile, reading only live-in planes, never touches)
    raises OverflowError on the first copy_in, and no plan is kept."""
    real = FacetSpec.point_offsets
    pipe = _pipe(name, storage)
    last_row = pipe.space.sizes[0] - pipe.tiling.sizes[0]

    def point_offsets(spec, pts):
        return real(spec, pts) + np.where(pts[:, 0] >= last_row, 2**31, 0)

    # every offset a table holds, each field's too, derives from these
    monkeypatch.setattr(FacetSpec, "point_offsets", point_offsets)
    with jax.enable_x64(False):
        facets = pipe.init_facets(jnp.float32)
        with pytest.raises(OverflowError, match="int32"):
            pipe.copy_in(facets, (0,) * pipe.space.ndim)
    assert pipe._fetch_plan is None
