"""Field programs: several coupled values per point through the normal
``cfa.compile`` path (PolyBench ``fdtd-2d`` as ``fdtd2d``).

The skewed program is related to PolyBench's three loops on the unskewed
grid, every backend that takes fields is compared with the untiled
reference through facet storage, irredundant storage rehydrates to the
same payload, the backends that take no fields refuse them at compile
time, and the scalar programs' facet shapes, fetch tables and kernel
blocks are pinned to what they were before fields existed.
"""
import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cfa
from repro.core.cfa import CFAPipeline, IterSpace, Tiling, get_program, obs
from repro.core.cfa.allocation import pack_all
from repro.core.cfa.executors import BackendError
from repro.core.cfa.irredundant import IrredundantPipeline
from repro.core.cfa.plans import (bounding_box_plan, cfa_plan, data_tiling_plan,
                                   original_layout_plan)
from repro.core.cfa.programs import FIELD_PROGRAMS, PROGRAMS, fdtd2d_textbook
from repro.kernels.stencil import execute_tiles

SPACE, TILE = (6, 12, 16), (2, 4, 8)
F = 3


def _inputs(seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(1, F, *SPACE[1:])).astype(dtype))


def _compile(**kw):
    kw.setdefault("layout", TILE)
    kw.setdefault("target", "tpu-v5e-hbm")
    return cfa.compile("fdtd2d", SPACE, **kw)


def test_program_is_registered_with_its_fields():
    prog = get_program("fdtd2d")
    assert prog.fields == ("ey", "ex", "hz") and prog.n_fields == F
    # the 5-point cross skewed by (1, 1): jacobi2d5p's facet geometry,
    # with the value count on the deps that every plan is built from
    assert prog.deps.vectors == get_program("jacobi2d5p").deps.vectors
    assert prog.deps.fields == F
    assert prog.widths == (1, 2, 2)
    assert get_program("jacobi2d5p").n_fields == 1
    # the suite the figure scripts sweep stays the scalar one
    assert FIELD_PROGRAMS["fdtd2d"] is prog and "fdtd2d" not in PROGRAMS
    assert not any(p.fields for p in PROGRAMS.values())


def test_skewed_program_equals_the_textbook_loops_on_the_interior():
    """Plane ``s`` of the skewed program at ``(i + s, j + s)`` is step
    ``s`` of PolyBench's loops at ``(i, j)``, wherever neither run's
    boundary (zero outside, PolyBench's stored new ex/ey at the grid's
    edge) has reached the point yet: one row a step from each side."""
    T, NX, NY = 5, 20, 24
    rng = np.random.default_rng(3)
    u0 = rng.normal(size=(F, NX, NY))
    x = np.zeros((1, F, NX + T, NY + T))
    x[0, :, :NX - 1, :NY - 1] = u0[:, 1:, 1:]  # skewed live-in: (a, b) = (i - 1, j - 1)
    with jax.enable_x64(True):
        pipe = CFAPipeline(get_program("fdtd2d"), IterSpace((T, NX + T, NY + T)),
                           Tiling((T, NX + T, NY + T)))
        skewed = np.asarray(pipe.reference_volume(jnp.asarray(x)))
        text = np.asarray(fdtd2d_textbook(*map(jnp.asarray, u0), steps=T))
    m = T + 2
    for s in range(T):
        got = skewed[s][:, m + s:NX - m + s, m + s:NY - m + s]
        want = text[s][:, m:NX - m, m:NY - m]
        assert got.size and np.allclose(got, want, rtol=0, atol=1e-12), s


def test_textbook_loops_follow_polybench_order():
    """hz reads the ex and ey of the same step (the new values)."""
    rng = np.random.default_rng(4)
    ey, ex, hz = (jnp.asarray(rng.normal(size=(6, 7))) for _ in range(3))
    with jax.enable_x64(True):
        out = np.asarray(fdtd2d_textbook(ey, ex, hz, steps=1))[0]
    ey, ex, hz = (np.asarray(a, np.float64) for a in (ey, ex, hz))
    ey1 = ey.copy()
    ey1[1:] -= 0.5 * (hz[1:] - hz[:-1])
    ey1[0] -= 0.5 * hz[0]
    ex1 = ex.copy()
    ex1[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
    ex1[:, 0] -= 0.5 * hz[:, 0]
    exr = np.pad(ex1, ((0, 0), (0, 1)))[:, 1:]
    eyd = np.pad(ey1, ((0, 1), (0, 0)))[1:]
    hz1 = hz - 0.7 * (exr - ex1 + eyd - ey1)
    assert np.allclose(out, np.stack([ey1, ex1, hz1]), rtol=0, atol=1e-12)


# float32 tolerances: sweep runs the very plane update of the reference
# eagerly, tile by tile, so they agree to the bit; the Pallas kernel
# (interpreted here) and wavefront, which runs a wave's recurrences as one
# compiled program, evaluate the same expression in a fused program, where
# a multiply and an add may become one rounding: a few float32 ulps of
# values of order 1 per plane over the 6 planes, 1e-5 absolute.
BACKENDS = {"reference": 0.0, "sweep": 0.0, "wavefront": 1e-5, "pallas": 1e-5}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_backends_match_the_reference(backend):
    x = _inputs()
    want = _compile(backend="reference")(x)
    got = _compile(backend=backend)(x)
    assert set(got) == set(want)
    for k in want:
        assert got[k].shape == want[k].shape and got[k].dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got[k] - want[k]))) <= BACKENDS[backend], k


def test_auto_picks_pallas_and_returns_the_field_axis():
    c = _compile()
    assert c.backend == "pallas"
    facets = c(_inputs())
    for k, spec in c.pipeline.specs.items():
        n_outer = len(spec.outer_axes)
        assert facets[k].shape == c.pipeline.facet_shape(k)
        # the field axis sits before the inner dims, never minor
        assert facets[k].shape[n_outer] == F and facets[k].ndim == n_outer + 1 + 3
    assert c.pipeline.halo_shape == (1 + 2, F, 2 + 4, 2 + 8)
    assert "3 fields (ey, ex, hz)" in c.describe()


@pytest.mark.parametrize("backend", ["sweep", "pallas"])
def test_irredundant_storage_rehydrates_to_the_redundant_payload(backend):
    x = _inputs(1)
    want = _compile(backend="reference")(x)
    c = _compile(backend=backend, storage="irredundant")
    raw = c(x)
    got = c.rehydrate(raw)
    tol = BACKENDS[backend]
    for k in want:
        assert float(jnp.max(jnp.abs(got[k] - want[k]))) <= tol, k
    # irredundant storage leaves the non-owned slots of every field empty
    smap = c.storage_map
    assert smap.stored_elems < smap.redundant_elems
    assert any(not m.all() for m in smap.owned.values())


@pytest.mark.parametrize("kw,match", [
    (dict(backend="sharded"), "runs scalar programs only"),
    (dict(backend="dataflow"), "runs scalar programs only"),
    (dict(storage="compressed"), 'storage="compressed" stores scalar programs only'),
])
def test_backends_without_fields_refuse_at_compile(kw, match):
    with pytest.raises(ValueError, match=match) as e:
        _compile(**kw)
    if "backend" in kw:
        assert isinstance(e.value, BackendError)


def test_inputs_without_the_field_axis_are_refused():
    with pytest.raises(ValueError, match=r"inputs must be \(1, 3, 12, 16\)"):
        _compile(backend="sweep")(jnp.zeros((1, *SPACE[1:])))


def test_plan_counts_values_and_keeps_each_write_one_burst():
    """The facet write of all fields is one burst, F times the scalar's;
    reads move F times the scalar plan's values.  A scalar run inside one
    block becomes F runs; an extension run across two blocks (§IV-H)
    becomes 2F - 1, the last field's tail joining the next block's first
    field."""
    prog, jacobi = get_program("fdtd2d"), get_program("jacobi2d5p")
    space, tiling = IterSpace((8, 16, 32)), Tiling((4, 8, 16))
    for tile in [(1, 1, 1), (0, 1, 0), (1, 0, 1)]:
        scalar = cfa_plan(space, jacobi.deps, tiling, tile)
        field = cfa_plan(space, prog.deps, tiling, tile)
        assert field.write_runs == tuple(F * r for r in scalar.write_runs)
        assert sum(field.read_runs) == F * sum(scalar.read_runs)
        assert F * scalar.n_read_bursts <= field.n_read_bursts <= (2 * F - 1) * scalar.n_read_bursts
        assert field.read_useful == F * scalar.read_useful
        assert field.stored_elems == F * scalar.stored_elems


@pytest.mark.parametrize("storage", ["redundant", "irredundant"])
def test_recorder_reconciles_and_counts_the_fields(storage):
    c = _compile(backend="wavefront", storage=storage, trace=True)
    c(_inputs())
    rec = c.last_trace()
    assert rec.reconcile(c.pipeline)["ok"]
    assert rec.counters.get("facet_fields") == F
    for name in ("copy_in", "execute_wave", "copy_out"):
        spans = rec.find(name)
        assert spans and all(s.arg("fields") == F for s in spans), name


# -- the scalar programs are unchanged ------------------------------------------

# what the tree before field programs gave, bit for bit: per (program,
# space, tile, storage) the facet array shapes, the halo buffer and a
# digest of the compiled fetch's tables
SCALAR_PINS = {
    ("jacobi2d5p", (8, 12, 8), (4, 4, 4), "redundant"): (
        {0: (3, 2, 3, 4, 4, 1), 1: (3, 2, 2, 4, 4, 2), 2: (2, 3, 2, 4, 4, 2)},
        (5, 6, 6), "af9a112c8bc2639b"),
    ("jacobi2d5p", (8, 12, 8), (4, 4, 4), "irredundant"): (
        {0: (3, 2, 3, 4, 4, 1), 1: (3, 2, 2, 4, 4, 2), 2: (2, 3, 2, 4, 4, 2)},
        (5, 6, 6), "ebe8b84b6012187d"),
    ("heat3d", (8, 8, 8, 8), (4, 4, 4, 4), "redundant"): (
        {0: (3, 2, 2, 2, 4, 4, 4, 1), 1: (2, 2, 2, 2, 4, 4, 4, 2),
         2: (2, 2, 2, 2, 4, 4, 4, 2), 3: (2, 2, 2, 2, 4, 4, 4, 2)},
        (5, 6, 6, 6), "5e6b57fcc6f68762"),
    ("heat3d", (8, 8, 8, 8), (4, 4, 4, 4), "irredundant"): (
        {0: (3, 2, 2, 2, 4, 4, 4, 1), 1: (2, 2, 2, 2, 4, 4, 4, 2),
         2: (2, 2, 2, 2, 4, 4, 4, 2), 3: (2, 2, 2, 2, 4, 4, 4, 2)},
        (5, 6, 6, 6), "f4502828e46c0c20"),
    ("heat1d", (8, 12), (4, 4), "redundant"): (
        {0: (3, 3, 4, 1), 1: (3, 2, 4, 2)}, (5, 6), "ac2e4b583b8c5383"),
    ("heat1d", (8, 12), (4, 4), "irredundant"): (
        {0: (3, 3, 4, 1), 1: (3, 2, 4, 2)}, (5, 6), "81b5ae6a74483bb6"),
    # the jacobi2d5p-medium benchmark cell
    ("jacobi2d5p", (200, 250, 250), (20, 50, 125), "redundant"): (
        {0: (11, 2, 5, 50, 125, 1), 1: (5, 10, 2, 125, 20, 2), 2: (2, 5, 10, 20, 50, 2)},
        (21, 52, 127), "80dba088412018f5"),
}


def _digest(plan) -> str:
    h = hashlib.sha256()
    for a in (*plan.src, *plan.dst):
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
        h.update(str(a.shape).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("key", list(SCALAR_PINS), ids=lambda k: f"{k[0]}-{k[3]}-{'x'.join(map(str, k[1]))}")
def test_scalar_facets_and_fetch_tables_are_unchanged(key):
    name, space, tile, storage = key
    facets, halo, digest = SCALAR_PINS[key]
    cls = CFAPipeline if storage == "redundant" else IrredundantPipeline
    pipe = cls(get_program(name), IterSpace(space), Tiling(tile))
    assert {k: pipe.facet_shape(k) for k in pipe.specs} == facets
    assert pipe.halo_shape == halo
    plan = pipe.fetch_tables()
    assert plan.shape == halo and _digest(plan) == digest


def _block_shapes(program, halo_shape, tile):
    jaxpr = jax.make_jaxpr(
        lambda h: execute_tiles(program, h, tile, interpret=True))(
        jax.ShapeDtypeStruct((2, *halo_shape), jnp.float32))

    def find(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                return e
            for p in e.params.values():
                inner = getattr(p, "jaxpr", None)
                if inner is not None:
                    hit = find(getattr(inner, "jaxpr", inner))
                    if hit is not None:
                        return hit
        return None

    eqn = find(jaxpr.jaxpr)
    return [tuple(getattr(b, "block_size", None) for b in bm.block_shape)
            for bm in eqn.params["grid_mapping"].block_mappings]


@pytest.mark.parametrize("program,halo,tile,blocks", [
    # scalar: (w + t) in, t out, as before fields
    ("jacobi2d5p", (21, 52, 127), (20, 50, 125),
     [(None, 21, 52, 127), (None, 20, 50, 125)]),
    ("heat3d", (5, 22, 22, 22), (4, 20, 20, 20),
     [(None, 5, 22, 22, 22), (None, 4, 20, 20, 20)]),
    # three fields: the field axis after time, spatial dims minor
    ("fdtd2d", (21, 3, 52, 122), (20, 50, 120),
     [(None, 21, 3, 52, 122), (None, 20, 3, 50, 120)]),
])
def test_kernel_blocks(program, halo, tile, blocks):
    assert _block_shapes(program, halo, tile) == blocks


def test_every_tile_reads_f_times_the_scalar_values():
    """The recorder's read and write counters of a field sweep are F times
    the scalar program's with the same geometry, tile for tile."""
    counts = {}
    for name in ("fdtd2d", "jacobi2d5p"):
        pipe = CFAPipeline(get_program(name), IterSpace(SPACE), Tiling(TILE))
        rec = obs.TraceRecorder()
        for tile in itertools.product(*(range(n) for n in pipe.num_tiles)):
            rec.record_read(pipe, tile)
            rec.record_write(pipe, tile)
        counts[name] = rec.counters
    for c in ("read_elems", "write_elems"):
        assert counts["fdtd2d"].get(c) == F * counts["jacobi2d5p"].get(c)
    assert counts["fdtd2d"].get("bursts_write") == counts["jacobi2d5p"].get("bursts_write")


# -- the count travels with the deps ----------------------------------------------

def test_every_plan_prices_the_values_the_deps_carry():
    """No plan takes a field count of its own: built from fdtd2d's deps,
    each scheme moves and stores F times what jacobi2d5p's deps give it."""
    prog, jacobi = get_program("fdtd2d"), get_program("jacobi2d5p")
    space, tiling = IterSpace((8, 16, 32)), Tiling((4, 8, 16))
    plans = [
        lambda d: original_layout_plan(space, d, tiling),
        lambda d: bounding_box_plan(space, d, tiling),
        lambda d: data_tiling_plan(space, d, tiling),
        lambda d: cfa_plan(space, d, tiling),
        lambda d: cfa_plan(space, d, tiling, storage="irredundant"),
    ]
    for make in plans:
        field, scalar = make(prog.deps), make(jacobi.deps)
        assert field.useful == F * scalar.useful, field.scheme
        assert field.transferred == F * scalar.transferred, field.scheme
        assert field.footprint == F * scalar.footprint, field.scheme
    # and the compile's own storage map, built from the deps as well
    c = _compile(backend="sweep", storage="irredundant")
    j = cfa.compile("jacobi2d5p", SPACE, layout=TILE, target="tpu-v5e-hbm",
                    backend="sweep", storage="irredundant")
    assert c.pipeline.storage_map.stored_elems == F * j.pipeline.storage_map.stored_elems


def test_pack_carries_the_field_axis():
    """``pack_all`` of the oracle volume is the sweep's payload, field axis
    included."""
    c = _compile(backend="sweep")
    x = _inputs(seed=4)
    facets = c(x)
    V = c.reference(x)
    assert V.shape == (SPACE[0], F, *SPACE[1:])
    packed = pack_all(V, c.pipeline.specs)
    for k in c.pipeline.specs:
        got = facets[k][1:] if k == 0 else facets[k]
        np.testing.assert_array_equal(np.asarray(packed[k]), np.asarray(got))
