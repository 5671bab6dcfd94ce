"""Multi-port repartition (§VII): scheduling quality, model monotonicity,
the paper-facing speedup claims, the port-aware autotune stage, and the
sharded wavefront executor's exactness against the single-port oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.cfa import (
    AXI_ZC706,
    TPU_V5E_HBM,
    CFAPipeline,
    Deps,
    IterSpace,
    PROGRAMS,
    PortedPlan,
    Tiling,
    assign_ports,
    autotune,
    best_repartition,
    cfa_plan,
    get_program,
    original_layout_plan,
    port_speedup,
    repartition,
)
from repro.core.cfa.autotune import LayoutDecision
from repro.core.cfa.programs import FIELD_PROGRAMS


def _default_setup(name):
    prog = get_program(name)
    tiling = Tiling(prog.default_tile)
    space = IterSpace(tuple(3 * t for t in prog.default_tile))
    return prog, space, tiling


# ---------------------------------------------------------------------------
# scheduling quality: LPT vs round-robin, balance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PROGRAMS) + sorted(FIELD_PROGRAMS))
@pytest.mark.parametrize("n_ports", [2, 3])
def test_facet_lpt_never_worse_than_round_robin(name, n_ports):
    prog, space, tiling = _default_setup(name)
    plan = cfa_plan(space, prog.deps, tiling)
    t_lpt = AXI_ZC706.time(repartition(plan, n_ports, "facet-lpt", model=AXI_ZC706))
    t_rr = AXI_ZC706.time(repartition(plan, n_ports, "facet-rr", model=AXI_ZC706))
    assert t_lpt <= t_rr + 1e-15


def test_balance_is_one_on_symmetric_facet_traffic():
    """A fully symmetric dependence pattern on a cubic tiling gives every
    facet identical traffic, so the 3-facet/3-port LPT split is perfect.
    (Axis-aligned deps: no multi-axis crossings, whose corner points must be
    hosted by a single facet and would skew the loads by one element.)"""
    deps = Deps(((-1, 0, 0), (0, -1, 0), (0, 0, -1)))  # w = (1, 1, 1)
    space, tiling = IterSpace((32, 32, 32)), Tiling((8, 8, 8))
    pa = assign_ports(space, deps, tiling, 3)
    assert pa.balance == pytest.approx(1.0)
    assert sorted(pa.facet_to_port.values()) == [0, 1, 2]  # one facet per port


def test_assign_ports_is_lpt_on_facet_traffic():
    from repro.core.cfa.multiport import _facet_traffic

    prog, space, tiling = _default_setup("jacobi2d5p")
    pa = assign_ports(space, prog.deps, tiling, 2)
    assert pa.n_ports == 2 and set(pa.facet_to_port) == set(range(3))
    traffic = _facet_traffic(space, prog.deps, tiling)
    # nothing lost, and the LPT makespan beats (or ties) round-robin's
    assert sum(pa.port_bytes) == pytest.approx(sum(traffic.values()))
    rr_loads = [0.0, 0.0]
    for i, k in enumerate(sorted(traffic)):
        rr_loads[i % 2] += traffic[k]
    assert max(pa.port_bytes) <= max(rr_loads) + 1e-12
    # and it genuinely split the facets (not everything on one port)
    assert max(pa.port_bytes) < sum(traffic.values())


# ---------------------------------------------------------------------------
# repartition invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["facet-lpt", "facet-rr", "burst-lpt", "stripe"])
def test_repartition_conserves_traffic(strategy):
    prog, space, tiling = _default_setup("jacobi2d9p")
    plan = cfa_plan(space, prog.deps, tiling)
    pp = repartition(plan, 4, strategy, model=AXI_ZC706)
    assert isinstance(pp, PortedPlan) and pp.n_ports == 4
    assert pp.transferred == plan.transferred  # no element lost or duplicated
    assert pp.useful == plan.useful
    if strategy != "stripe":  # stripe splits runs; the others move them whole
        got = sorted(sum(pp.read_runs_by_port, ()) + sum(pp.write_runs_by_port, ()))
        want = sorted(plan.read_runs + plan.write_runs)
        assert got == want


def test_facet_strategy_requires_attribution():
    prog, space, tiling = _default_setup("jacobi2d5p")
    plan = original_layout_plan(space, prog.deps, tiling)  # no facet hosts
    with pytest.raises(ValueError, match="attribution"):
        repartition(plan, 2, "facet-lpt")
    # burst-granular strategies still apply, so best_repartition succeeds
    pp = best_repartition(plan, 2, AXI_ZC706)
    assert AXI_ZC706.time(pp) <= AXI_ZC706.time(plan) + 1e-15
    # facet-only strategies on an attribution-less plan degrade to the
    # trivial single-port schedule instead of aborting the search
    fb = best_repartition(plan, 2, AXI_ZC706, strategies=("facet-lpt", "facet-rr"))
    assert fb.strategy == "single-port" and fb.n_ports == 2
    assert AXI_ZC706.time(fb) == pytest.approx(AXI_ZC706.time(plan))


def test_autotune_with_facet_only_strategies_completes(tmp_path):
    """n_ports > 1 with facet-granular strategies only must not abort on the
    single-array baseline seeds (they carry no facet attribution)."""
    dec = autotune("jacobi2d5p", (48, 48, 48), AXI_ZC706, budget=12,
                   n_ports=2, port_strategies=("facet-lpt", "facet-rr"),
                   cache_dir=tmp_path)
    assert dec.n_ports == 2 and dec.evaluated > 0
    baselines = [s for s in dec.ranked if s.candidate.scheme != "cfa"]
    assert baselines and all(s.port_strategy == "single-port" for s in baselines)


def test_balance_ignores_idle_padded_ports():
    """A repartition that uses fewer ports than available reports the
    balance of the ports it actually loads, not of the idle padding."""
    prog, space, tiling = _default_setup("jacobi2d5p")
    plan = cfa_plan(space, prog.deps, tiling)
    pp = best_repartition(plan, 8, AXI_ZC706, strategies=("facet-lpt",))
    loaded = [l for l in pp.port_elems if l > 0]
    assert len(loaded) <= 3  # only 3 facets exist
    assert pp.balance == pytest.approx(max(loaded) / (sum(loaded) / len(loaded)))


def test_ported_plan_rejects_ragged_port_schedules():
    """Regression: a read/write port-list length mismatch used to be
    silently truncated by the unstrict zip in ``BurstModel.time``, dropping
    ports from the max and under-reporting transfer time.  Construction now
    validates, and the zips are strict."""
    kw = dict(scheme="cfa", n_ports=2, strategy="facet-lpt",
              read_useful=4, write_useful=4)
    with pytest.raises(ValueError, match="read_runs_by_port"):
        PortedPlan(read_runs_by_port=((4,),),  # 1 entry, n_ports=2
                   write_runs_by_port=((4,), (4,)), **kw)
    with pytest.raises(ValueError, match="write_runs_by_port"):
        PortedPlan(read_runs_by_port=((4,), (4,)),
                   write_runs_by_port=((4,), (4,), (4,)), **kw)
    # even a plan corrupted after construction (bypassing __post_init__)
    # must fail loudly in the model, not drop the trailing port
    pp = PortedPlan(read_runs_by_port=((8,), (2,)),
                    write_runs_by_port=((1,), (16,)), **kw)
    object.__setattr__(pp, "read_runs_by_port", ((8,),))
    with pytest.raises(ValueError):
        AXI_ZC706.time(pp)
    with pytest.raises(ValueError):
        pp.port_elems


def test_ported_time_is_max_over_ports():
    prog, space, tiling = _default_setup("jacobi2d5p")
    plan = cfa_plan(space, prog.deps, tiling)
    pp = repartition(plan, 3, "facet-lpt", model=AXI_ZC706)
    per_port = [
        AXI_ZC706.time_s(rr) + AXI_ZC706.time_s(wr)
        for rr, wr in zip(pp.read_runs_by_port, pp.write_runs_by_port)
    ]
    assert AXI_ZC706.time(pp) == pytest.approx(max(per_port))


# ---------------------------------------------------------------------------
# speedup: monotone in n_ports + the §VII headline numbers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", [AXI_ZC706, TPU_V5E_HBM], ids=lambda m: m.name)
def test_port_speedup_monotone_in_n_ports(model):
    prog, space, tiling = _default_setup("jacobi2d5p")
    speedups = [
        port_speedup(space, prog.deps, tiling, n, model)["speedup"]
        for n in range(1, 9)
    ]
    assert speedups[0] == pytest.approx(1.0)
    for a, b in zip(speedups, speedups[1:]):
        assert b >= a - 1e-12, speedups


def test_jacobi2d5p_axi_headline_speedups():
    """The acceptance numbers the benchmark reports (interior-tile plan at
    the default tile under AXI_ZC706): >= 1.7x @ 2 ports, >= 3x @ 4."""
    prog, space, tiling = _default_setup("jacobi2d5p")
    r2 = port_speedup(space, prog.deps, tiling, 2, AXI_ZC706)
    r4 = port_speedup(space, prog.deps, tiling, 4, AXI_ZC706)
    assert r2["speedup"] >= 1.7, r2
    assert r4["speedup"] >= 3.0, r4


# ---------------------------------------------------------------------------
# port-aware autotune stage
# ---------------------------------------------------------------------------

def test_autotune_ports_beats_single_port(tmp_path):
    dec1 = autotune("jacobi2d5p", (64, 64, 64), AXI_ZC706, budget=24,
                    cache_dir=tmp_path)
    dec4 = autotune("jacobi2d5p", (64, 64, 64), AXI_ZC706, budget=24,
                    n_ports=4, cache_dir=tmp_path)
    assert dec1.n_ports == 1 and dec4.n_ports == 4
    assert dec4.best.n_ports == 4 and dec4.best.port_strategy is not None
    assert dec4.best.port_speedup_vs_single >= 1.0
    # co-tuned 4-port effective bandwidth dominates the single-port winner
    assert dec4.best.effective_bw >= dec1.best.effective_bw - 1e-9


def test_autotune_ports_cache_round_trip(tmp_path):
    dec = autotune("jacobi2d9p", (48, 48, 48), AXI_ZC706, budget=16,
                   n_ports=2, cache_dir=tmp_path)
    rt = LayoutDecision.from_json(dec.to_json())
    assert rt.n_ports == dec.n_ports and rt.ranked == dec.ranked
    hit = autotune("jacobi2d9p", (48, 48, 48), AXI_ZC706, budget=16,
                   n_ports=2, cache_dir=tmp_path)
    assert hit.from_cache and hit.ranked == dec.ranked
    # a different port count is a different cache entry, not a stale hit
    other = autotune("jacobi2d9p", (48, 48, 48), AXI_ZC706, budget=16,
                     n_ports=4, cache_dir=tmp_path)
    assert not other.from_cache and other.n_ports == 4


# ---------------------------------------------------------------------------
# sharded wavefront executor == single-port oracle (acceptance criterion)
# ---------------------------------------------------------------------------

def test_sweep_wavefront_sharded_smoke():
    """Fast tier-1 representative of the sharded executor: small problem,
    waves of uneven size (so the padding path runs).  The full program
    matrix below is `slow` and runs on the CI slow leg."""
    prog = get_program("jacobi2d5p")
    pipe = CFAPipeline(prog, IterSpace((4, 4, 4)), Tiling((4, 2, 2)))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(1, 4, 4)))
    ref = pipe._sweep(inputs, dtype=jnp.float64)
    got = pipe._sweep_wavefront_sharded(inputs, dtype=jnp.float64, n_ports=2)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(got[k])).all(), f"facet {k}"


@pytest.mark.slow
@pytest.mark.parametrize(
    "name,space,tile",
    [
        ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
        ("jacobi2d9p", (8, 8, 8), (4, 4, 4)),
        ("jacobi2d9p-gol", (8, 8, 8), (4, 4, 4)),
        ("gaussian", (4, 16, 16), (2, 8, 8)),
        ("smith-waterman-3seq", (9, 8, 8), (3, 4, 4)),
        ("heat1d", (12, 12), (4, 4)),
        ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
    ],
)
def test_sweep_wavefront_sharded_bit_exact(name, space, tile):
    """Every program (Table I + the N-D additions): the multi-port
    executor's facet storage is bit-identical to the single-port ``sweep``'s."""
    prog = get_program(name)
    pipe = CFAPipeline(prog, IterSpace(space), Tiling(tile))
    w0 = pipe.specs[0].width
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(w0, *space[1:])))
    ref = pipe._sweep(inputs, dtype=jnp.float64)
    got = pipe._sweep_wavefront_sharded(inputs, dtype=jnp.float64, n_ports=2)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(got[k])).all(), f"facet {k}"


@pytest.mark.slow
def test_sweep_wavefront_sharded_pads_odd_waves():
    """3 ports over waves whose sizes are not multiples of 3 (padding path)."""
    prog = get_program("jacobi2d5p")
    pipe = CFAPipeline(prog, IterSpace((8, 8, 8)), Tiling((4, 4, 4)))
    rng = np.random.default_rng(1)
    inputs = jnp.asarray(rng.normal(size=(1, 8, 8)))
    ref = pipe._sweep(inputs, dtype=jnp.float64)
    got = pipe._sweep_wavefront_sharded(inputs, dtype=jnp.float64, n_ports=3)
    for k in ref:
        assert (np.asarray(ref[k]) == np.asarray(got[k])).all()


def test_sweep_wavefront_sharded_kernel_path():
    """The Pallas executor path matches to interpreter-rounding tolerance
    (same tolerance class as the existing ``sweep_wavefront(use_kernel)``)."""
    prog = get_program("jacobi2d5p")
    pipe = CFAPipeline(prog, IterSpace((8, 8, 8)), Tiling((4, 4, 4)))
    rng = np.random.default_rng(2)
    inputs = jnp.asarray(rng.normal(size=(1, 8, 8)))
    ref = pipe._sweep(inputs, dtype=jnp.float64)
    got = pipe._sweep_wavefront_sharded(inputs, dtype=jnp.float64, n_ports=2,
                                       use_kernel=True)
    for k in ref:
        np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(got[k]),
                                   rtol=1e-12, atol=1e-12)
