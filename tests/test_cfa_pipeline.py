"""End-to-end CFA pipeline: tiled sweep through facet storage == oracle.

(The hypothesis-based pack/unpack round-trip property lives in
``test_cfa_properties.py`` so this module collects without the optional
``hypothesis`` extra.)
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.cfa import (
    CFAPipeline,
    IterSpace,
    Tiling,
    build_facet_specs,
    get_program,
    pack_all,
    pack_facet,
    unpack_into,
)


def test_pack_rejects_non_dividing_width():
    prog = get_program("smith-waterman-3seq")  # w0 = 3
    space, tiling = IterSpace((16, 16, 16)), Tiling((16, 16, 16))
    specs = build_facet_specs(space, prog.deps, tiling)
    with pytest.raises(ValueError):
        pack_facet(jnp.zeros(space.sizes), specs[0])


# ---------------------------------------------------------------------------
# tiled sweep through facets == untiled oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "name,space,tile",
    [
        ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
        ("jacobi2d5p", (6, 12, 8), (2, 4, 4)),
        ("jacobi2d9p", (8, 8, 8), (4, 4, 4)),
        ("jacobi2d9p-gol", (8, 8, 8), (4, 4, 4)),
        ("gaussian", (4, 16, 16), (2, 8, 8)),
        ("smith-waterman-3seq", (9, 8, 8), (3, 4, 4)),
        # tile-dependent modulo labelling (w does not divide t on axis 0)
        ("smith-waterman-3seq", (8, 8, 8), (4, 4, 4)),
    ],
)
def test_sweep_matches_oracle(name, space, tile):
    prog = get_program(name)
    pipe = CFAPipeline(prog, IterSpace(space), Tiling(tile))
    w0 = pipe.specs[0].width
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(w0, *space[1:])))

    facets = pipe._sweep(inputs, dtype=jnp.float64)
    V = pipe.reference_volume(inputs)

    # Strongest check: every facet block equals the packed oracle volume,
    # i.e. the tiled pipeline stored exactly the right values in the right
    # (burst-contiguous) places.  Covers copy-in, execute and copy-out.
    for k, spec in pipe.specs.items():
        got = facets[k]
        if k == 0:
            got = got[1:]  # drop the virtual live-in row
        if spec.tile_sizes[spec.axis] % spec.width == 0:
            want = pack_facet(V, spec)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-12, atol=1e-12)
        else:
            # general modulo labelling: compare via per-tile gather
            from repro.core.cfa.spaces import facet_points, facet_widths
            import itertools
            wds = facet_widths(prog.deps)
            for q in itertools.product(*map(range, pipe.num_tiles)):
                pts = facet_points(pipe.tiling, wds, k, q)
                offs = spec.offsets(pts)
                if k == 0:
                    offs = offs + spec.block_elems * int(
                        np.prod([spec.num_tiles[a] for a in spec.outer_axes[1:]])
                    )
                vals = np.asarray(facets[k]).ravel()[offs]
                want = np.asarray(V)[tuple(pts.T)]
                np.testing.assert_allclose(vals, want, rtol=1e-12, atol=1e-12)


def test_final_time_plane_recoverable():
    """The application's result (last time plane) lives in facet_0 blocks."""
    prog = get_program("jacobi2d5p")
    space, tile = (8, 8, 8), (4, 4, 4)
    pipe = CFAPipeline(prog, IterSpace(space), Tiling(tile))
    rng = np.random.default_rng(1)
    inputs = jnp.asarray(rng.normal(size=(1, 8, 8)))
    facets = pipe._sweep(inputs, dtype=jnp.float64)
    V = pipe.reference_volume(inputs)

    spec = pipe.specs[0]
    want = pack_facet(V, spec)  # w0 = 1 divides t0
    got = facets[0][1:]
    # last time-tile row holds the final plane
    np.testing.assert_allclose(
        np.asarray(got[-1]), np.asarray(want[-1]), rtol=1e-12, atol=1e-12
    )


def test_gather_offsets_refuse_to_wrap():
    """An offset past JAX's index dtype raises instead of wrapping (with
    x64 off, as on the chip, int64 offsets would narrow to int32)."""
    from repro.core.cfa.transform import device_index

    big = np.array([0, 2**31], np.int64)
    with jax.enable_x64(False):
        with pytest.raises(OverflowError, match="int32"):
            device_index(big)
        fits = device_index(np.array([0, 2**31 - 1], np.int64))
        assert fits.dtype == jnp.int32 and int(fits[1]) == 2**31 - 1
    with jax.enable_x64(True):
        assert int(device_index(big)[1]) == 2**31


def test_copy_in_gathers_through_the_offset_guard(monkeypatch):
    """The fetch plan uploads its tables and row numbers through
    device_index, once, on the first copy_in; later fetches upload
    nothing, not even the tile's row."""
    from repro.core.cfa import transform

    seen = []
    real = transform.device_index
    monkeypatch.setattr(transform, "device_index",
                        lambda offs: seen.append(offs.shape) or real(offs))
    pipe = CFAPipeline(get_program("jacobi2d5p"), IterSpace((8, 8, 8)),
                       Tiling((4, 4, 4)))
    facets = pipe.load_inputs(pipe.init_facets(jnp.float32),
                              jnp.ones((1, 8, 8), jnp.float32))
    # the commit plan's uploads: the row numbers, the block indices and
    # the permutations, one table each per facet array
    commit = pipe._commit_plan
    assert seen == ([(8,)] + [ix.shape for ix in commit.index]
                    + [pm.shape for pm in commit.perm])
    seen.clear()
    pipe.copy_in(facets, (0, 0, 0))  # live-in row only: builds the plan
    plan = pipe._fetch_plan
    assert plan.keys == (0, 1, 2)
    # the row numbers, the source and the destination tables, then each
    # tile's class and shift in every facet array
    assert seen == ([(8,)] + [s.shape for s in plan.src] * 2
                    + [(8, 3), (8, 3)])
    with jax.transfer_guard_host_to_device("disallow"):
        pipe.copy_in(facets, (1, 1, 1))  # one gather per facet array
    assert len(seen) == 9
