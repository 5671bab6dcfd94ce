"""The compiled facet fetch: one jitted gather program per pipeline that
reads every tile's halo through device-resident tables.

It must equal the eager piece-by-piece gather bit for bit, compile once
per pipeline, stay off the one path that keeps the eager gather (facets
over several devices), and keep the int32 offset guard for every tile's
table.
"""
import dataclasses
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


# -- the class plan: tables resolved once per halo class ------------------------

def _oracle_rows(pipe):
    """Every tile resolved on its own (``fetch_tables``): per tile, per
    facet array, the absolute source offsets and destinations it reads."""
    plan = pipe.fetch_tables()
    out = {}
    for tile, (r, _) in plan.rows.items():
        out[tile] = {k: (plan.src[i][r], plan.dst[i][r]) for i, k in enumerate(plan.keys)}
    return plan, out


@pytest.mark.parametrize("name,storage", CASES)
def test_class_plan_equals_the_per_tile_oracle(name, storage):
    """On every tile: the class row plus the tile's shift is the tile's
    own resolved row, entry for entry (pads included: every row is padded
    alike), its halo class's maps hold the tile's point counts, and the
    halo the compiled fetch gathers equals the oracle plan's and the eager
    gather's bit for bit."""
    pipe = _pipe(name, storage)
    oracle, want = _oracle_rows(pipe)
    plan = pipe.fetch_classes()
    assert plan.keys == oracle.keys and len(plan.maps) <= len(oracle.maps)
    for tile, (r, c) in plan.rows.items():
        maps = pipe._halo_maps(tile)[0]
        assert {k: len(v) for k, v in plan.maps[c].items()} == {
            k: len(v) for k, v in maps.items()}
        for i, k in enumerate(plan.keys):
            src, dst = want[tile][k]
            real = dst < np.prod(plan.shape)
            got_src = plan.src[i][plan.cls[r, i]] + plan.shift[r, i]
            got_dst = plan.dst[i][plan.cls[r, i]]
            assert np.array_equal(got_dst[:len(dst)], dst), (tile, k)
            assert np.array_equal(got_src[:len(src)][real], src[real]), (tile, k)
    rng = np.random.default_rng(1)
    facets = {k: jnp.asarray(rng.normal(size=pipe.facet_shape(k)))
              for k in pipe.specs}
    up = {f: jnp.asarray(getattr(oracle, f)) for f in ("cls", "shift")}
    for tile, (r, _) in oracle.rows.items():
        by_oracle = _fetch_halo(tuple(facets[k] for k in oracle.keys),
                                tuple(map(jnp.asarray, oracle.src)),
                                tuple(map(jnp.asarray, oracle.dst)),
                                up["cls"], up["shift"], r, shape=oracle.shape)
        got = pipe.copy_in(facets, tile)
        eager = pipe._gather_halo(facets, *pipe._halo_maps(tile))
        assert np.array_equal(np.asarray(got), np.asarray(by_oracle)), tile
        assert np.array_equal(np.asarray(got), np.asarray(eager)), tile


@pytest.mark.parametrize("name,storage", CASES)
def test_padded_sources_stay_in_bounds_after_the_shift(name, storage):
    """Every offset the compiled fetch gathers at, pads included, lies in
    its facet array on every tile: the gather promises in-bounds indices."""
    pipe = _pipe(name, storage)
    plan = pipe.fetch_classes()
    for i, k in enumerate(plan.keys):
        idx = plan.src[i][plan.cls[:, i]] + plan.shift[:, i, None]
        assert idx.min() >= 0 and idx.max() < np.prod(pipe.facet_shape(k)), k


@pytest.mark.parametrize("name,storage", CASES)
@pytest.mark.parametrize("axis", [0, 1])
def test_class_tables_do_not_grow_with_the_tile_count(name, storage, axis):
    """Twice the tiles on one axis: the same halo classes and tables of
    the same shapes, so the same bytes on the device bar each tile's
    class and shift."""
    space, tile = SPACES[name]
    wide = list(space)
    wide[axis] *= 2
    a = _pipe(name, storage).fetch_classes()
    b = STORAGES[storage](get_program(name), IterSpace(tuple(wide)),
                          Tiling(tile)).fetch_classes()
    assert a.keys == b.keys and len(a.maps) == len(b.maps)
    assert [s.shape for s in a.src + a.dst] == [s.shape for s in b.src + b.dst]
    assert b.cls.shape == (2 * a.cls.shape[0], len(a.keys))
    for x, y in zip(a.dst, b.dst):
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("name", SPACES)
def test_class_tables_of_twice_the_time_tiles_are_byte_identical(name):
    """Under redundant storage a tile reads each facet one tile back
    across the facet's axis and its extension, at strides that do not
    count the time tiles: a longer run needs the very same tables.
    (Irredundant storage also reads a spatial facet at the tile's own
    block column, a stride away that counts them.)"""
    space, tile = SPACES[name]
    long = (2 * space[0], *space[1:])
    a = _pipe(name, "redundant").fetch_classes()
    b = CFAPipeline(get_program(name), IterSpace(long), Tiling(tile)).fetch_classes()
    assert a.keys == b.keys
    for x, y in zip(a.src + a.dst, b.src + b.dst):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("name,storage", CASES)
def test_int32_guard_covers_source_plus_shift(name, storage, monkeypatch):
    """Shifts that carry the furthest gather offset to 2**31 raise
    OverflowError on the first copy_in, and no plan is kept; one below
    fits.  Where a facet array's rows reach past its anchor, the tables
    and the shifts each fit int32 and only their sum does not."""
    pipe = _pipe(name, storage)
    plan = pipe.fetch_classes()
    top = 2**31 - 1
    reach = [(s.max(axis=1)[plan.cls[:, i]] + plan.shift[:, i]).max()
             for i, s in enumerate(plan.src)]
    # the facet array whose rows reach furthest past its largest shift
    i = max(range(len(reach)), key=lambda i: reach[i] - plan.shift[:, i].max())

    def bumped(to):
        shift = plan.shift.copy()
        shift[:, i] += to - reach[i]
        return lambda: dataclasses.replace(plan, shift=shift)

    monkeypatch.setattr(pipe, "fetch_classes", bumped(top + 1))
    over = pipe.fetch_classes()
    assert over.reach() == top + 1
    assert max(int(s.max()) for s in over.src) <= top
    # every case but irredundant smith-waterman-3seq, whose one facet
    # array is read only behind each tile's own block
    assert (over.shift.max() <= top) == (reach[i] > plan.shift[:, i].max())
    with jax.enable_x64(False):
        with pytest.raises(OverflowError, match="int32"):
            pipe.copy_in(pipe.init_facets(jnp.float32), (0,) * pipe.space.ndim)
        assert pipe._fetch_plan is None
        # the furthest offset, or the largest shift, at 2**31 - 1
        fits = top - max(0, int(plan.shift[:, i].max() - reach[i]))
        monkeypatch.setattr(pipe, "fetch_classes", bumped(fits))
        assert pipe._build_fetch_plan().shift.dtype == jnp.int32


@pytest.mark.parametrize("name,storage", CASES)
def test_a_sweep_counts_the_plan_once(name, storage):
    """Each sweep of an executor that runs the compiled fetch adds the
    plan's halo classes and device bytes once; the plan is built in its
    own ``fetch_plan`` phase, before the sweep's first fetch."""
    pipe = _pipe(name, storage)
    rec = obs.TraceRecorder()
    pipe.recorder = rec
    for backend in ("_sweep", "_sweep_wavefront"):
        getattr(pipe, backend)(_inputs(pipe), dtype=jnp.float64)
    plan = pipe._fetch_plan
    assert rec.counters.get("fetch_classes") == 2 * len(plan.maps)
    assert rec.counters.get("fetch_table_bytes") == 2 * plan.nbytes
    (build,) = rec.find("fetch_plan")
    assert build.t0 + build.dur <= min(s.t0 for s in rec.find("copy_in"))


def test_fdtd2d_large_plan_is_small_and_quick():
    """fdtd-2d LARGE at its cell's tile (bench/configs/): 2,000 tiles, a
    plan of about 1.1 MB of int32 tables, built on the host in well under
    a second."""
    import time

    pipe = CFAPipeline(get_program("fdtd2d"), IterSpace((200, 1000, 1200)),
                       Tiling((20, 50, 120)))
    t0 = time.perf_counter()
    plan = pipe.fetch_classes()
    took = time.perf_counter() - t0
    assert len(plan.rows) == 2000 and [s.shape[0] for s in plan.src] == [4, 3, 2]
    int32_bytes = plan.nbytes // 2  # the host tables are int64
    assert int32_bytes <= 2 * 2**20 and took < 1.0


# fdtd2d over a space whose middle waves hold 11 tiles, past the cells'
# widest MEDIUM wave (8); the tolerance is test_fields.py's
WIDE_SPACE, WIDE_TILE = (4, 24, 48), (2, 4, 8)


@pytest.mark.parametrize("backend", ["pallas", "wavefront"])
def test_fdtd2d_wide_waves_match_the_reference(backend):
    from repro import cfa

    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 3, *WIDE_SPACE[1:])).astype(np.float32))
    want = cfa.compile("fdtd2d", WIDE_SPACE, layout=WIDE_TILE,
                       target="tpu-v5e-hbm", backend="reference")(x)
    c = cfa.compile("fdtd2d", WIDE_SPACE, layout=WIDE_TILE,
                    target="tpu-v5e-hbm", backend=backend)
    assert max(len(w) for w in c.pipeline.wavefronts()) > 8
    got = c(x)
    for k in want:
        assert got[k].shape == want[k].shape
        assert float(jnp.max(jnp.abs(got[k] - want[k]))) <= 1e-5, k


@pytest.mark.parametrize("name,storage", CASES)
def test_every_tile_moves_its_halo_class_plan(name, storage):
    """Every tile's transfer plan, computed on its own, moves the bursts
    of its halo class's first tile: the runs, their hosts and the stored
    values the recorder counts from the plan it keeps once a class."""
    from repro.core.cfa.obs import _pipeline_tile_plan

    pipe = _pipe(name, storage)
    first = {}
    for tile in _tiles(pipe):
        first.setdefault(pipe.halo_class(tile), tile)
    for tile in _tiles(pipe):
        got = _pipeline_tile_plan(pipe, tile)
        want = _pipeline_tile_plan(pipe, first[pipe.halo_class(tile)])
        for field in ("read_runs", "write_runs", "read_run_hosts",
                      "write_run_hosts", "stored_elems"):
            assert getattr(got, field) == getattr(want, field), (tile, field)
