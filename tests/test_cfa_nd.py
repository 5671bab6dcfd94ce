"""N-dimensional CFA: the executor, plans, autotuner and kernels for d != 3.

The paper's construction (§IV-F..J) is dimension-generic; these tests pin it
for a 2-D program (``heat1d``: the 1-D heat equation as a time x space tiled
plane) and a 4-D program (``heat3d``: the 3-D heat equation, the §IV-J
regime where some mid-level neighbour pieces cannot merge into one burst).

Burst-count pins are hand-derived:

* heat1d, tile (t0, t1), widths (1, 2): flow-in is the time-halo row
  (w0*t1 = t1 elements, one facet_0 run) plus the spatial slab w1*t0 with
  the level-2 corner merged into it (one facet_1 run, the corner is hosted
  by facet_1 because its extension axis — time — has the thinnest width,
  §IV-I) -> **2 read bursts**, runs (t1, w1*t0).  Writes: one full block
  per facet -> **2 write bursts**.
* heat3d, widths (1, 2, 2, 2): 4 level-1 reads, 6 level-2 + 4 level-3
  pieces of which 2 find no host whose extension direction is crossed
  (§IV-J, `cfa_piece_census`), plus the level-4 corner ->
  **7 read bursts** = (d + 1) + 2 unmergeable.  Writes: 4 facets -> 4.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.cfa import (
    AXI_ZC706,
    CFAPipeline,
    Deps,
    FacetSpec,
    IterSpace,
    Tiling,
    autotune,
    best_repartition,
    build_facet_specs,
    cfa_plan,
    cfa_piece_census,
    extension_dir,
    facet_widths,
    get_program,
    pack_facet,
    repartition,
)


# ---------------------------------------------------------------------------
# program specs
# ---------------------------------------------------------------------------

def test_nd_facet_widths():
    assert facet_widths(get_program("heat1d").deps) == (1, 2)
    assert facet_widths(get_program("heat3d").deps) == (1, 2, 2, 2)


def test_pipeline_rejects_dimension_mismatch():
    prog = get_program("heat1d")  # 2-D program
    with pytest.raises(ValueError, match="2-D"):
        CFAPipeline(prog, IterSpace((8, 8, 8)), Tiling((4, 4, 4)))
    with pytest.raises(ValueError, match="d >= 2"):
        CFAPipeline(prog, IterSpace((8,)), Tiling((4,)))
    with pytest.raises(ValueError, match="not divisible"):
        CFAPipeline(prog, IterSpace((8, 10)), Tiling((4, 4)))


# ---------------------------------------------------------------------------
# tiled sweep through facets == untiled oracle (2-D and 4-D)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name,space,tile",
    [
        ("heat1d", (16, 16), (4, 4)),
        ("heat1d", (12, 8), (3, 4)),  # non-square, t0 not a multiple of t1
        ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
        ("heat3d", (4, 8, 8, 8), (2, 4, 4, 4)),
    ],
)
def test_nd_sweep_matches_oracle(name, space, tile):
    prog = get_program(name)
    pipe = CFAPipeline(prog, IterSpace(space), Tiling(tile))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(pipe.specs[0].width, *space[1:])))
    facets = pipe._sweep(inputs, dtype=jnp.float64)
    V = pipe.reference_volume(inputs)
    for k, spec in pipe.specs.items():
        got = facets[k]
        if k == 0:
            got = got[1:]  # drop the virtual live-in row
        want = pack_facet(V, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("name,space,tile", [
    ("heat1d", (8, 8), (4, 4)),
    ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
])
def test_nd_wavefront_and_kernel_path(name, space, tile):
    """The wavefront executor and the Pallas tile kernel are N-D too."""
    prog = get_program(name)
    pipe = CFAPipeline(prog, IterSpace(space), Tiling(tile))
    rng = np.random.default_rng(1)
    inputs = jnp.asarray(rng.normal(size=(pipe.specs[0].width, *space[1:])))
    seq = pipe._sweep(inputs, dtype=jnp.float64)
    for kernel in (False, True):
        wav = pipe._sweep_wavefront(inputs, dtype=jnp.float64, use_kernel=kernel)
        for k in seq:
            np.testing.assert_allclose(np.asarray(seq[k]), np.asarray(wav[k]),
                                       rtol=1e-12, atol=1e-12)


def test_2d_sharded_sweep_bit_exact():
    """Multi-port wavefront execution repartitions N-D facets too."""
    prog = get_program("heat1d")
    pipe = CFAPipeline(prog, IterSpace((8, 8)), Tiling((4, 4)))
    rng = np.random.default_rng(2)
    inputs = jnp.asarray(rng.normal(size=(1, 8)))
    ref = pipe._sweep(inputs, dtype=jnp.float64)
    got = pipe._sweep_wavefront_sharded(inputs, dtype=jnp.float64, n_ports=2)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(got[k])).all(), f"facet {k}"


def test_nd_stencil_kernel_matches_ref():
    """The generalized Pallas executor == the jnp reference, both N-D."""
    from repro.kernels.stencil import execute_tiles, execute_tiles_ref

    for name, tile in [("heat1d", (4, 4)), ("heat3d", (2, 2, 2, 2))]:
        prog = get_program(name)
        w = prog.widths
        hshape = tuple(wa + ta for wa, ta in zip(w, tile))
        rng = np.random.default_rng(3)
        halos = jnp.asarray(rng.normal(size=(3, *hshape)))
        got = execute_tiles(name, halos, tile, interpret=True)
        want = execute_tiles_ref(name, halos, tile)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)
    with pytest.raises(ValueError, match="3-D, tile is 2-D"):
        execute_tiles("jacobi2d5p", jnp.zeros((1, 5, 6)), (4, 4))


# ---------------------------------------------------------------------------
# burst counts, pinned (incl. the d >= 4 unmergeable-corner accounting)
# ---------------------------------------------------------------------------

def test_heat1d_burst_counts_pinned():
    prog = get_program("heat1d")
    sp, tl = IterSpace((16, 16)), Tiling((4, 4))
    plan = cfa_plan(sp, prog.deps, tl)
    assert plan.read_runs == (4, 8)  # (w0*t1, w1*t0 incl. merged corner)
    assert plan.n_read_bursts == 2
    assert plan.n_write_bursts == 2
    assert plan.read_transferred == plan.read_useful  # zero redundancy
    census = cfa_piece_census(sp, prog.deps, tl)
    assert census["pieces_by_level"] == {1: 2, 2: 1}
    assert census["unmergeable"] == 0  # d <= 3: everything merges


@pytest.mark.parametrize("space,tile", [
    ((8, 8, 8, 8), (4, 4, 4, 4)),
    ((4, 8, 8, 8), (2, 4, 4, 4)),
])
def test_heat3d_burst_counts_pinned(space, tile):
    """§IV-J: in d = 4 two mid-level pieces find no facet whose extension
    direction is a crossed axis; each starts an extra burst beyond the
    d + 1 = 5 the d <= 3 construction would reach."""
    prog = get_program("heat3d")
    sp, tl = IterSpace(space), Tiling(tile)
    plan = cfa_plan(sp, prog.deps, tl)
    census = cfa_piece_census(sp, prog.deps, tl)
    assert census["pieces_by_level"] == {1: 4, 2: 6, 3: 4, 4: 1}
    assert census["unmergeable"] == 2
    assert plan.n_read_bursts == (4 + 1) + census["unmergeable"]  # == 7
    assert plan.n_write_bursts == 4  # one full block per facet, any d


@pytest.mark.parametrize("name", ["jacobi2d5p", "jacobi2d9p", "gaussian",
                                  "smith-waterman-3seq"])
def test_3d_census_has_no_unmergeable_pieces(name):
    """d = 3 is below the §IV-J regime: every piece merges (4 read bursts)."""
    prog = get_program(name)
    t = prog.default_tile
    sp = IterSpace(tuple(4 * x for x in t))
    census = cfa_piece_census(sp, prog.deps, Tiling(t))
    assert census["unmergeable"] == 0


# ---------------------------------------------------------------------------
# autotune + multiport over N-D spaces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,space", [
    ("heat1d", (16, 16)),
    ("heat3d", (8, 8, 8, 8)),
])
def test_nd_autotune_valid_decision(name, space, tmp_path):
    prog = get_program(name)
    dec = autotune(prog, space, AXI_ZC706, budget=24, seed=0,
                   cache_dir=tmp_path)
    assert dec.evaluated > 0
    best = dec.best_cfa()
    assert best.candidate.scheme == "cfa"
    assert len(best.candidate.tile) == len(space)
    # the decision instantiates and stays exact end-to-end
    from repro import cfa
    pipe = cfa.compile(prog.name, space, layout=dec,
                       backend="sweep").pipeline
    rng = np.random.default_rng(4)
    inputs = jnp.asarray(rng.normal(size=(pipe.specs[0].width, *space[1:])))
    facets = pipe._sweep(inputs, dtype=jnp.float64)
    V = pipe.reference_volume(inputs)
    spec = pipe.specs[0]
    if spec.tile_sizes[0] % spec.width == 0:
        err = float(jnp.abs(facets[0][1:] - pack_facet(V, spec)).max())
        assert err < 1e-12


def test_4d_repartition_conserves_traffic():
    prog = get_program("heat3d")
    sp, tl = IterSpace((12, 12, 12, 12)), Tiling((4, 4, 4, 4))
    plan = cfa_plan(sp, prog.deps, tl)
    pp = repartition(plan, 4, "facet-lpt", model=AXI_ZC706)
    assert pp.transferred == plan.transferred
    assert set(dict(pp.facet_to_port)) == {0, 1, 2, 3}  # all 4 facets placed
    best = best_repartition(plan, 4, AXI_ZC706)
    assert AXI_ZC706.time(best) <= AXI_ZC706.time(plan)


# ---------------------------------------------------------------------------
# extension-direction degenerate/2-D behaviour (explicit, validated)
# ---------------------------------------------------------------------------

def test_extension_dir_degenerate_and_2d():
    # 1-D: c == k is the explicit "no extension direction" marker
    assert extension_dir(0, 1) == 0
    # 2-D: forced to the single other axis
    assert extension_dir(0, 2) == 1
    assert extension_dir(1, 2) == 0
    with pytest.raises(ValueError, match="out of range"):
        extension_dir(3, 2)


def test_build_facet_specs_validates_ext_dirs():
    deps2 = Deps(((-1, -1),))
    sp, tl = IterSpace((8, 8)), Tiling((4, 4))
    # c == k is rejected for d >= 2 ...
    with pytest.raises(ValueError, match="invalid extension direction"):
        build_facet_specs(sp, deps2, tl, ext_dirs={0: 0})
    # ... and is the only legal value for d == 1
    specs1 = build_facet_specs(IterSpace((8,)), Deps(((-2,),)), Tiling((4,)))
    assert specs1[0].ext_dir == 0
    with pytest.raises(ValueError, match="1-D"):
        build_facet_specs(IterSpace((8,)), Deps(((-2,),)), Tiling((4,)),
                          ext_dirs={0: 1})


def test_facet_spec_validates_ext_dir():
    with pytest.raises(ValueError, match="degenerate"):
        FacetSpec(axis=0, width=1, tile_sizes=(4, 4), num_tiles=(2, 2),
                  outer_axes=(0, 1), inner_axes=(0, 1), ext_dir=0)
    with pytest.raises(ValueError, match="out of range"):
        FacetSpec(axis=0, width=1, tile_sizes=(4, 4), num_tiles=(2, 2),
                  outer_axes=(0, 1), inner_axes=(0, 1), ext_dir=5)
