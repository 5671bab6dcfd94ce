"""The compiled facet commit: one jitted block-store program per pipeline
that writes every tile's facet blocks through device-resident tables.

It must equal the eager block-by-block store bit for bit under every
storage discipline, compile once per pipeline, upload nothing after its
first call, update the donated facet arrays in place, and stay off the
one path that keeps the eager store (facets over several devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cfa import get_program, obs
from repro.core.cfa.irredundant import CompressedPipeline

from test_fetch_plan import (CASES, SPACES, STORAGES, _inputs, _pipe, _tiles,
                             multi_device_runs)  # noqa: F401  (a fixture)

#: the storage disciplines of the commit, the compressed one with the
#: identity codec and with the lossy default
DISCIPLINES = [*STORAGES, "compressed-raw", "compressed-deltapack16"]
COMMIT_CASES = [pytest.param(name, storage, id=f"{name}-{storage}")
                for name in SPACES for storage in DISCIPLINES]


def _commit_pipe(name, storage):
    if not storage.startswith("compressed-"):
        return _pipe(name, storage)
    from repro.core.cfa import IterSpace, Tiling

    space, tile = SPACES[name]
    return CompressedPipeline(get_program(name), IterSpace(space), Tiling(tile),
                              codec=storage.split("-", 1)[1])


def _random_facets(pipe, rng):
    return {k: rng.normal(size=pipe.facet_shape(k)) for k in pipe.specs}


def _upload(host):
    return {k: jnp.asarray(v) for k, v in host.items()}


@pytest.mark.parametrize("name,storage", COMMIT_CASES)
def test_compiled_commit_equals_eager_stores(name, storage):
    """The live-in row, then every tile, boundary tiles included, from
    facets and halo buffers of distinct random values: the compiled
    ``load_inputs`` and ``copy_out`` leave every facet array equal, bit
    for bit, to the eager ``_store_block`` of each block."""
    pipe = _commit_pipe(name, storage)
    rng = np.random.default_rng(2)
    host = _random_facets(pipe, rng)
    inputs = _inputs(pipe)
    got = pipe.load_inputs(_upload(host), inputs)
    want = _upload(host)
    for q, sl in pipe._live_in_blocks():
        want[0] = pipe._store_block(want[0], pipe.specs[0], (-1, *q), inputs[sl])
    for k in pipe.specs:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), ("live-in", k)
    for tile in _tiles(pipe):
        H = jnp.asarray(rng.normal(size=pipe.halo_shape))
        got = pipe.copy_out(got, tile, H)
        for k, spec in pipe.specs.items():
            want[k] = pipe._store_block(want[k], spec, tile, pipe._commit_slab(H, k))
        for k in pipe.specs:
            assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), (tile, k)


@pytest.mark.parametrize("name,storage", CASES)
def test_wavefront_sweep_compiles_the_commit_once(name, storage):
    """A whole sweep, then a second one on the same pipeline, leave each
    of the plan's programs (the tile commit and the live-in row) with one
    compiled entry and the plan built once."""
    pipe = _pipe(name, storage)
    first = pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    plan = pipe._commit_plan
    assert (plan.commit._cache_size(), plan.load._cache_size()) == (1, 1)
    again = pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    assert pipe._commit_plan is plan
    assert (plan.commit._cache_size(), plan.load._cache_size()) == (1, 1)
    for k in first:
        assert np.array_equal(np.asarray(first[k]), np.asarray(again[k]))


@pytest.mark.parametrize("name,storage", CASES)
def test_commit_uploads_nothing_after_the_first(name, storage):
    """After the first commit, a tile's commit moves nothing from the host:
    the tile's row, the block indices and the permutations are on the
    device, and the owner mask is a constant of the program."""
    pipe = _pipe(name, storage)
    facets = pipe.load_inputs(pipe.init_facets(jnp.float64), _inputs(pipe))
    tiles = _tiles(pipe)
    H = jnp.ones(pipe.halo_shape, jnp.float64)
    facets = pipe.copy_out(facets, tiles[0], H)
    with jax.transfer_guard_host_to_device("disallow"):
        for tile in tiles[1:]:
            facets = pipe.copy_out(facets, tile, H)


@pytest.mark.parametrize("name,storage", CASES)
def test_commit_path_is_counted(name, storage):
    """One ``commit_compiled`` a tile from facets on one device, none
    eager, and the recorder reconciles, the commits included."""
    pipe = _pipe(name, storage)
    rec = obs.TraceRecorder()
    pipe.recorder = rec
    pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    assert rec.counters.get("commit_eager") == 0
    assert rec.counters.get("commit_compiled") == len(_tiles(pipe))
    r = rec.reconcile(pipe)
    assert r["ok"], r["mismatches"]
    assert r["observed"]["commits"] == len(_tiles(pipe))


def test_reconcile_catches_a_missing_commit():
    """A sweep that skipped one tile's commit count no longer reconciles."""
    pipe = _pipe("jacobi2d5p", "redundant")
    rec = obs.TraceRecorder()
    pipe.recorder = rec
    pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    rec.counters.add("commit_compiled", -1)
    r = rec.reconcile(pipe)
    assert not r["ok"] and r["mismatches"] == ["commits"]


@pytest.mark.parametrize("name,storage", CASES)
def test_multi_device_facets_take_the_eager_commit(name, storage,
                                                   multi_device_runs):
    """Facets over two devices keep the eager block stores on every tile,
    stay where they were, and hold what the compiled commit wrote to the
    same facets on one device."""
    got = multi_device_runs[f"{name}-{storage}"]["commit"]
    n = got["tiles"]
    # the spread commits all eager; the one-device commits after them compiled
    assert (got["eager"], got["compiled"]) == (n, n)
    assert got["devices"] == 2
    assert got["exact"]


def test_compiled_commit_donates_the_facets():
    """The facet arrays passed to the compiled commit are donated to it
    (updated in place, not copied a tile): reading one afterwards raises.
    ``load_inputs`` donates facet_0, the one array it writes."""
    pipe = _pipe("jacobi2d5p", "irredundant")
    fresh = pipe.init_facets(jnp.float64)
    loaded = pipe.load_inputs(fresh, _inputs(pipe))
    assert fresh[0].is_deleted()
    assert all(loaded[k] is fresh[k] for k in pipe.specs if k != 0)
    H = jnp.ones(pipe.halo_shape, jnp.float64)
    out = pipe.copy_out(loaded, (0, 0, 0), H)
    for k in pipe.specs:
        assert loaded[k].is_deleted() and not out[k].is_deleted()
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(loaded[k])
