"""The irredundant & compressed facet storage subsystem (Ferry 2024).

Acceptance criteria pinned here:

* every Table I program plus ``heat1d``/``heat3d`` runs **bit-exact** under
  ``storage="irredundant"`` vs the redundant layout, on every applicable
  backend, through ``repro.cfa.compile`` (rehydration bridges the payloads);
* the irredundant storage map has ``redundancy == 1.0`` (no duplicate
  storage) and a **strictly smaller footprint** than the redundant layout —
  pinned for ``jacobi2d5p`` and ``heat3d``;
* the fixed-ratio block codec round-trips exactly on data that fits its
  ratio, and the compressed discipline is modeled as reduced bytes/burst;
* the autotuner's storage axis (schema v4) caches, ranks and round-trips;
* ``allocation.pack_all``/``unpack_into`` understand the deduplicated map,
  and the w | t restriction raises the documented ``ValueError`` from every
  public entry point (the tile-dependent case routes to the sweep executor).
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import cfa
from repro.core.cfa import (
    AXI_ZC706,
    CFAPipeline,
    IterSpace,
    Tiling,
    build_facet_specs,
    build_storage_map,
    cfa_plan,
    dedup_facets,
    get_program,
    owner_of,
    rehydrate_facets,
)
from repro.core.cfa.autotune import LayoutDecision, autotune
from repro.core.cfa.compress import CODECS, get_codec
from repro.core.cfa.irredundant import (
    STORAGE_MODES,
    CompressedPipeline,
    IrredundantPipeline,
)
from repro.core.cfa.plans import TransferPlan
from repro.core.cfa.spaces import facet_points

# (program, space, tile): the same test-size corners test_api.py pins.
CASES = [
    ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p-gol", (8, 8, 8), (4, 4, 4)),
    ("gaussian", (4, 16, 16), (2, 8, 8)),
    ("smith-waterman-3seq", (9, 8, 8), (3, 4, 4)),
    ("heat1d", (8, 8), (4, 4)),
    ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
]


def _inputs(space, name, seed=0):
    prog = get_program(name)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(prog.widths[0], *space[1:])))


# ---------------------------------------------------------------------------
# storage map: single assignment + footprint (the acceptance pins)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,space,tile", CASES,
                         ids=[c[0] for c in CASES])
def test_storage_map_single_assignment_and_footprint(name, space, tile):
    prog = get_program(name)
    specs = build_facet_specs(IterSpace(space), prog.deps, Tiling(tile))
    smap = build_storage_map(specs)
    # no duplicate storage: stored slots / distinct values == 1.0, exactly
    assert smap.redundancy == 1.0
    # the owned masks partition each tile's facet union
    pts = np.concatenate([
        facet_points(Tiling(tile), prog.widths, k, (0,) * len(space))
        for k in specs
    ])
    uniq = np.unique(pts, axis=0)
    own = owner_of(specs, uniq)
    assert (own >= 0).all(), "facet-union point with no owner"
    for k in specs:
        assert smap.owned_per_block[k] == int((own == k).sum())
    n_tiles = int(np.prod([n // t for n, t in zip(space, tile)]))
    assert smap.stored_elems == len(uniq) * n_tiles
    # dedup strictly shrinks whenever facets overlap at all
    assert smap.stored_elems <= smap.redundant_elems


@pytest.mark.parametrize("name,space,tile", [
    ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
    ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
])
def test_footprint_strictly_smaller_pinned(name, space, tile):
    """Acceptance pin: irredundant footprint < redundant footprint."""
    prog = get_program(name)
    red = cfa_plan(IterSpace(space), prog.deps, Tiling(tile))
    irr = cfa_plan(IterSpace(space), prog.deps, Tiling(tile),
                   storage="irredundant")
    assert irr.storage == "irredundant" and red.storage == "redundant"
    assert irr.footprint < red.footprint
    assert irr.stored_elems < red.stored_elems
    specs = build_facet_specs(IterSpace(space), prog.deps, Tiling(tile))
    smap = build_storage_map(specs)
    assert irr.footprint == smap.stored_elems
    assert red.footprint == smap.redundant_elems
    assert smap.savings > 0


def test_heat3d_savings_dominates():
    """The d >= 4 regime duplicates the most — dedup recovers the most."""
    prog = get_program("heat3d")
    specs = build_facet_specs(IterSpace((4, 4, 4, 4)), prog.deps,
                              Tiling((2, 2, 2, 2)))
    smap = build_storage_map(specs)
    assert smap.savings > 0.5  # 71.4% at the 2^4 tile


# ---------------------------------------------------------------------------
# TransferPlan storage fields: strict validation (PR 3-style hardening)
# ---------------------------------------------------------------------------

def _plan(**kw):
    return TransferPlan("x", (4,), (4,), 4, 4, **kw)


def test_transfer_plan_storage_validation():
    assert _plan().footprint is None and _plan().stored_elems is None
    assert _plan(storage="irredundant", footprint=8, stored_elems=8,
                 ).footprint == 8
    with pytest.raises(ValueError, match="storage"):
        _plan(storage="deduplicated")
    with pytest.raises(ValueError, match="stored_elems"):
        _plan(stored_elems=0)
    with pytest.raises(ValueError, match="stored_elems"):
        _plan(stored_elems=-3)
    with pytest.raises(ValueError, match="footprint"):
        _plan(footprint=0)
    with pytest.raises(ValueError, match="footprint"):
        _plan(footprint=-1)
    with pytest.raises(ValueError, match="codec_bits"):
        _plan(codec_bits=0)


def test_cfa_plan_rejects_codec_without_compressed():
    prog = get_program("jacobi2d5p")
    with pytest.raises(ValueError, match="compressed"):
        cfa_plan(IterSpace((8, 8, 8)), prog.deps, Tiling((4, 4, 4)),
                 storage="irredundant", codec="deltapack16")
    with pytest.raises(ValueError, match="storage"):
        cfa_plan(IterSpace((8, 8, 8)), prog.deps, Tiling((4, 4, 4)),
                 storage="nope")


def test_baseline_plans_carry_canonical_footprint():
    from repro.core.cfa import bounding_box_plan, original_layout_plan

    prog = get_program("jacobi2d5p")
    sp, til = IterSpace((8, 8, 8)), Tiling((4, 4, 4))
    assert original_layout_plan(sp, prog.deps, til).footprint == 8 ** 3
    assert bounding_box_plan(sp, prog.deps, til).footprint == 8 ** 3


# ---------------------------------------------------------------------------
# bit-exactness: every program x backend, irredundant vs redundant
# ---------------------------------------------------------------------------

def _exact_params():
    out = []
    for name, space, tile in CASES:
        for b in ("sweep", "wavefront", "pallas", "sharded"):
            if b == "pallas" and len(space) != 3:
                continue  # the pallas backend is declared 3-D only
            # repo convention: one fast sharded representative in tier-1,
            # the rest on the CI slow leg
            marks = ([pytest.mark.slow]
                     if b == "sharded" and name != "jacobi2d5p" else [])
            out.append(pytest.param(name, space, tile, b,
                                    marks=marks, id=f"{name}-{b}"))
    return out


@pytest.mark.parametrize("name,space,tile,backend", _exact_params())
def test_irredundant_bit_exact_vs_redundant(name, space, tile, backend):
    """rehydrate(irredundant payload) == redundant payload, same backend."""
    n_ports = 2 if backend == "sharded" else 1
    x = _inputs(space, name)
    red = cfa.compile(name, space, layout=tile, backend=backend,
                      n_ports=n_ports)(x, dtype=jnp.float64)
    c = cfa.compile(name, space, layout=tile, backend=backend,
                    n_ports=n_ports, storage="irredundant")
    assert c.storage == "irredundant" and c.pipeline.storage == "irredundant"
    got = c(x, dtype=jnp.float64)
    # the raw payload is deduplicated: exactly the redundant payload with
    # non-owned slots zeroed
    dd = dedup_facets(red, c.pipeline.storage_map)
    for k in red:
        assert (np.asarray(got[k]) == np.asarray(dd[k])).all(), f"facet {k}"
    # and rehydration reconstructs the redundant payload bit-for-bit
    rh = c.rehydrate(got)
    for k in red:
        assert (np.asarray(rh[k]) == np.asarray(red[k])).all(), f"facet {k}"


@pytest.mark.parametrize("name,space,tile", [CASES[0], CASES[-1]],
                         ids=["jacobi2d5p", "heat3d"])
def test_irredundant_reference_backend_matches_sweep(name, space, tile):
    x = _inputs(space, name)
    ref = cfa.compile(name, space, layout=tile, backend="reference",
                      storage="irredundant")(x, dtype=jnp.float64)
    swp = cfa.compile(name, space, layout=tile, backend="sweep",
                      storage="irredundant")(x, dtype=jnp.float64)
    for k in swp:
        assert (np.asarray(ref[k]) == np.asarray(swp[k])).all(), f"facet {k}"


def test_rehydrate_is_identity_for_redundant():
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep")
    x = _inputs((8, 8, 8), "jacobi2d5p")
    facets = c(x)
    assert c.rehydrate(facets) is facets
    assert c.storage_map is None


# ---------------------------------------------------------------------------
# compressed storage: codec exactness + modeled bytes/burst
# ---------------------------------------------------------------------------

def _truncated(x, bits):
    """Zero the low (width - bits) bits of every word: data the fixed-ratio
    codec preserves exactly."""
    w = 8 * np.dtype(x.dtype).itemsize
    u = {4: jnp.uint32, 8: jnp.uint64}[np.dtype(x.dtype).itemsize]
    raw = jax.lax.bitcast_convert_type(x, u)
    return jax.lax.bitcast_convert_type((raw >> (w - bits)) << (w - bits),
                                        x.dtype)


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_codec_roundtrip(codec):
    c = CODECS[codec]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 3)), jnp.float32)
    rt = c.roundtrip(x)
    assert rt.shape == x.shape and rt.dtype == x.dtype
    if not c.bits:
        assert c.exact(x)  # raw is the identity
    else:
        xt = _truncated(x, min(c.bits, 32))
        assert c.exact(xt), "bit-truncated data must survive the ratio"
        assert c.ratio(x.size, 32) <= 1.0
    # jit-compatible (shape-static encode/decode)
    assert jax.jit(c.roundtrip)(x).shape == x.shape


def test_codec_registry():
    assert get_codec(None).name == "deltapack16"
    assert get_codec("raw").bits == 0
    assert get_codec(CODECS["deltapack8"]) is CODECS["deltapack8"]
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("zstd")
    with pytest.raises(ValueError, match="bits"):
        type(CODECS["raw"])("bad", bits=12)


def test_compressed_raw_codec_bit_exact_vs_irredundant():
    x = _inputs((8, 8, 8), "jacobi2d5p")
    irr = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                      backend="sweep", storage="irredundant")(x, dtype=jnp.float64)
    cmp_ = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                       backend="sweep", storage="compressed", codec="raw")
    got = cmp_(x, dtype=jnp.float64)
    for k in irr:
        assert (np.asarray(got[k]) == np.asarray(irr[k])).all(), f"facet {k}"


def test_compressed_pipeline_quantises_through_codec():
    """With a lossy ratio the payload holds what compression preserved —
    close to, but not necessarily identical to, the irredundant payload."""
    x = _inputs((8, 8, 8), "jacobi2d5p")
    irr = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                      backend="sweep", storage="irredundant")(x, dtype=jnp.float32)
    cp = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                     backend="sweep", storage="compressed",
                     codec="deltapack16")
    got = cp(x, dtype=jnp.float32)
    for k in irr:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(irr[k]),
                                   rtol=2e-2, atol=2e-2)


def test_compressed_bursts_modeled_faster():
    """Same burst structure, fewer bytes: compressed plan time < irredundant
    plan time, and effective bandwidth rises accordingly."""
    prog = get_program("jacobi2d5p")
    sp, til = IterSpace((32, 32, 32)), Tiling((16, 16, 16))
    irr = cfa_plan(sp, prog.deps, til, storage="irredundant")
    cmp_ = cfa_plan(sp, prog.deps, til, storage="compressed",
                    codec="deltapack16")
    assert cmp_.codec_bits == 16 and irr.codec_bits is None
    assert cmp_.read_runs == irr.read_runs  # structure unchanged
    assert cmp_.write_runs == irr.write_runs
    assert AXI_ZC706.time(cmp_) < AXI_ZC706.time(irr)
    from repro.core.cfa import BandwidthReport

    r_i = BandwidthReport.evaluate(irr, AXI_ZC706)
    r_c = BandwidthReport.evaluate(cmp_, AXI_ZC706)
    assert r_c.effective_bw > r_i.effective_bw
    assert r_c.peak_fraction_raw <= 1.0 + 1e-12  # wire bytes never above peak
    assert r_c.storage == "compressed" and r_c.footprint == cmp_.footprint
    # "raw" models as uncompressed
    raw = cfa_plan(sp, prog.deps, til, storage="compressed", codec="raw")
    assert raw.codec_bits is None
    assert AXI_ZC706.time(raw) == AXI_ZC706.time(irr)


def test_compressed_ported_plan_carries_codec():
    from repro.core.cfa import best_repartition

    prog = get_program("jacobi2d5p")
    plan = cfa_plan(IterSpace((32, 32, 32)), prog.deps, Tiling((16, 16, 16)),
                    storage="compressed", codec="deltapack16")
    pp = best_repartition(plan, 4, AXI_ZC706)
    assert pp.codec_bits == 16 and pp.storage == "compressed"
    assert AXI_ZC706.time(pp) <= AXI_ZC706.time(plan)


# ---------------------------------------------------------------------------
# compile() surface: gating, auto-selection, describe
# ---------------------------------------------------------------------------

def test_storage_mode_validation():
    with pytest.raises(ValueError, match="storage"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    storage="dedup")
    with pytest.raises(ValueError, match="compressed"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep", codec="deltapack16")


def test_pallas_rejects_compressed_and_auto_avoids_it():
    with pytest.raises(cfa.BackendError, match="compressed"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="pallas", storage="compressed")
    # auto: 3-D would pick pallas, but compressed falls back to wavefront
    assert cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                       storage="compressed").backend == "wavefront"
    assert cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                       storage="irredundant").backend == "pallas"
    j = get_program("jacobi2d5p")
    assert cfa.select_backend(j, IterSpace((8, 8, 8)),
                              storage="compressed") == "wavefront"


def test_lower_revalidates_storage():
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep", storage="compressed")
    assert c.lower("wavefront").backend == "wavefront"
    with pytest.raises(cfa.BackendError, match="compressed"):
        c.lower("pallas")


def test_available_backends_storage_axis():
    j = get_program("jacobi2d5p")
    have = cfa.available_backends(j, IterSpace((8, 8, 8)),
                                  storage="compressed")
    assert "pallas" not in have and "sweep" in have
    assert "pallas" in cfa.available_backends(j, IterSpace((8, 8, 8)),
                                              storage="irredundant")


def test_describe_and_report_mention_storage():
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep", storage="irredundant")
    assert "irredundant" in c.describe()
    rep = c.report()
    assert rep.storage == "irredundant"
    assert rep.footprint == c.plan.footprint


def test_autotuned_storage_compile_end_to_end(tmp_path):
    c = cfa.compile("jacobi2d5p", (8, 8, 8), backend="sweep",
                    storage="irredundant",
                    autotune_kwargs=dict(budget=16, cache_dir=tmp_path))
    assert c.decision.storage == "irredundant"
    assert c.decision.best_cfa().footprint is not None
    x = _inputs((8, 8, 8), "jacobi2d5p")
    got = c(x, dtype=jnp.float64)
    ref = c.lower("reference")(x, dtype=jnp.float64)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all()


# ---------------------------------------------------------------------------
# autotune: the storage/footprint axis + cache schema v4
# ---------------------------------------------------------------------------

def test_autotune_storage_axis_and_cache(tmp_path):
    dec = autotune("jacobi2d5p", (32, 32, 32), AXI_ZC706, budget=24,
                   storage="irredundant", cache_dir=tmp_path)
    assert dec.storage == "irredundant"
    best = dec.best_cfa()
    assert best.storage == "irredundant"
    assert best.footprint is not None and best.stored_elems is not None
    # cache round-trip preserves the storage axis
    again = autotune("jacobi2d5p", (32, 32, 32), AXI_ZC706, budget=24,
                     storage="irredundant", cache_dir=tmp_path)
    assert again.from_cache and again.storage == "irredundant"
    assert again.best_cfa() == best
    # a different storage mode is a different cache key
    red = autotune("jacobi2d5p", (32, 32, 32), AXI_ZC706, budget=24,
                   cache_dir=tmp_path)
    assert not red.from_cache and red.storage == "redundant"
    # JSON round-trip carries the v4 fields
    rt = LayoutDecision.from_json(dec.to_json())
    assert rt.storage == "irredundant" and rt.ranked[0] == dec.ranked[0]


def test_autotune_footprint_weight_trades_speed_for_size(tmp_path):
    fast = autotune("jacobi2d5p", (32, 32, 32), AXI_ZC706, budget=24,
                    storage="irredundant", cache_dir=tmp_path)
    small = autotune("jacobi2d5p", (32, 32, 32), AXI_ZC706, budget=24,
                     storage="irredundant", footprint_weight=1.0,
                     cache_dir=tmp_path)
    assert small.footprint_weight == 1.0
    assert small.best_cfa().footprint <= fast.best_cfa().footprint
    assert small.best_cfa().effective_bw <= fast.best_cfa().effective_bw


def test_autotune_storage_validation():
    with pytest.raises(ValueError, match="storage"):
        autotune("jacobi2d5p", (8, 8, 8), AXI_ZC706, storage="zip",
                 cache=False)
    with pytest.raises(ValueError, match="compressed"):
        autotune("jacobi2d5p", (8, 8, 8), AXI_ZC706, codec="deltapack8",
                 cache=False)


def test_cache_schema_v3_rejected():
    import json

    from repro.core.cfa.autotune import CacheSchemaError

    dec = autotune("jacobi2d5p", (8, 8, 8), AXI_ZC706, budget=8, cache=False)
    blob = json.loads(dec.to_json())
    blob["version"] = 3
    with pytest.raises(CacheSchemaError, match="v3"):
        LayoutDecision.from_json(json.dumps(blob))


# ---------------------------------------------------------------------------
# allocation: deduplicated pack/unpack + the w | t error-path satellite
# ---------------------------------------------------------------------------

def test_pack_unpack_with_storage_map():
    from repro.core.cfa import pack_all, unpack_into

    prog = get_program("jacobi2d5p")  # w = (1, 2, 2)
    space, tile = (8, 8, 8), (2, 4, 4)  # w | t on every axis
    specs = build_facet_specs(IterSpace(space), prog.deps, Tiling(tile))
    smap = build_storage_map(specs)
    rng = np.random.default_rng(0)
    V = jnp.asarray(rng.normal(size=space))
    facets = pack_all(V, specs, storage_map=smap)
    # dead slots are zeroed by the dedup-aware pack
    for k in specs:
        dead = ~np.broadcast_to(
            smap.owned[k], facets[k].shape)
        assert (np.asarray(facets[k])[dead] == 0).all()
    # masked unpack restores every facet-union point exactly once
    out = jnp.full(space, jnp.nan)
    for k, spec in specs.items():
        out = unpack_into(out, facets[k], spec, owned=smap.owned[k])
    mask = ~jnp.isnan(out)
    assert bool(mask.any())
    np.testing.assert_array_equal(np.asarray(out)[np.asarray(mask)],
                                  np.asarray(V)[np.asarray(mask)])
    # without the owned masks, the dead zeros would clobber owned values
    out2 = jnp.full(space, jnp.nan)
    for k, spec in specs.items():
        out2 = unpack_into(out2, facets[k], spec)
    assert not np.array_equal(np.asarray(out2)[np.asarray(mask)],
                              np.asarray(V)[np.asarray(mask)])


def test_pack_unpack_w_divides_t_error_paths():
    """Satellite: the documented ValueError comes from *every* public entry
    point, up front (not just the _modulo_perm internals mid-computation)."""
    from repro.core.cfa import pack_all, pack_facet, unpack_into

    prog = get_program("jacobi2d5p")  # w = (1, 2, 2)
    space, tile = (9, 9, 9), (3, 3, 3)  # w=2 does not divide t=3
    specs = build_facet_specs(IterSpace(space), prog.deps, Tiling(tile))
    V = jnp.zeros(space)
    with pytest.raises(ValueError, match="sweep executor"):
        pack_facet(V, specs[1])
    with pytest.raises(ValueError, match="sweep executor"):
        pack_all(V, specs)
    with pytest.raises(ValueError, match="sweep executor"):
        unpack_into(V, jnp.zeros(specs[2].shape), specs[2])


@pytest.mark.parametrize("storage", ["redundant", "irredundant"])
def test_tile_dependent_modulo_routes_to_sweep_executor(storage):
    """Regression: a w-does-not-divide-t layout is exactly the case the
    pack/unpack error message routes to the sweep executor — and that
    executor must actually handle it (tile-dependent modulo labelling),
    bit-exact against the oracle-scatter reference backend."""
    name, space, tile = "jacobi2d5p", (9, 9, 9), (3, 3, 3)
    x = _inputs(space, name)
    swp = cfa.compile(name, space, layout=tile, backend="sweep",
                      storage=storage)
    ref = swp.lower("reference")
    got, want = swp(x, dtype=jnp.float64), ref(x, dtype=jnp.float64)
    for k in want:
        assert (np.asarray(got[k]) == np.asarray(want[k])).all(), f"facet {k}"


# ---------------------------------------------------------------------------
# pipelines: construction + payload structure
# ---------------------------------------------------------------------------

def test_storage_modes_constant():
    assert STORAGE_MODES == ("redundant", "irredundant", "compressed")
    assert CFAPipeline.storage == "redundant"
    assert IrredundantPipeline.storage == "irredundant"
    assert CompressedPipeline.storage == "compressed"


def test_compressed_pipeline_resolves_codec():
    prog = get_program("heat1d")
    p = CompressedPipeline(prog, IterSpace((8, 8)), Tiling((4, 4)))
    assert p.codec.name == "deltapack16"  # the default
    p2 = CompressedPipeline(prog, IterSpace((8, 8)), Tiling((4, 4)),
                            codec="raw")
    assert p2.codec.bits == 0


def test_rehydrate_with_virtual_row_untouched():
    """facet_0 (with its virtual live-in row) is fully owned: rehydration
    must never touch it, only refill other facets' dead slots."""
    name, space, tile = "heat1d", (8, 8), (4, 4)
    prog = get_program(name)
    irr = IrredundantPipeline(prog, IterSpace(space), Tiling(tile))
    facets = irr._sweep(_inputs(space, name), jnp.float64)
    rh = rehydrate_facets(facets, irr.storage_map)
    assert rh[0] is facets[0]  # fully owned -> passed through
    assert rh[1].shape == facets[1].shape
