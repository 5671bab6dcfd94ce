"""The ``repro.cfa`` front-end: compile() over every program x backend.

Covers the acceptance criteria of the API redesign:

* ``cfa.compile(...)(inputs)`` is bit-exact against the hand-wired
  ``CFAPipeline`` internals it drives, for every Table I program
  (plus the N-D additions) on every eligible backend;
* backend auto-selection follows the documented rules and the capability
  gate rejects ineligible (backend, program, space, n_ports) combinations
  with a clear error;
* the ``Target`` registry resolves names/models and enforces port budgets;
* the legacy shims (deprecated through PR 4-6) are really gone;
* ``repro.cfa.__all__`` is pinned — accidental public-surface changes fail.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import cfa
from repro.core.cfa import CFAPipeline, IterSpace, Tiling, get_program
from repro.core.cfa.executors import EXECUTORS

# (program, space, tile): the Table I suite at test-size spaces, plus the
# 2-D and 4-D programs (same corners the legacy pipeline tests pin).
CASES = [
    ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p-gol", (8, 8, 8), (4, 4, 4)),
    ("gaussian", (4, 16, 16), (2, 8, 8)),
    ("smith-waterman-3seq", (9, 8, 8), (3, 4, 4)),
    ("heat1d", (8, 8), (4, 4)),
    ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
]

# backend -> the CFAPipeline internal the executor drives
LEGACY = {
    "sweep": lambda p, x: p._sweep(x, dtype=jnp.float64),
    "wavefront": lambda p, x: p._sweep_wavefront(x, dtype=jnp.float64),
    "pallas": lambda p, x: p._sweep_wavefront(x, dtype=jnp.float64,
                                             use_kernel=True),
    "sharded": lambda p, x: p._sweep_wavefront_sharded(x, dtype=jnp.float64,
                                                      n_ports=2),
}


def _inputs(space, tile, name, seed=0):
    prog = get_program(name)
    w0 = prog.widths[0]
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(w0, *space[1:])))


def _exact_params():
    out = []
    for name, space, tile in CASES:
        for b in ("sweep", "wavefront", "pallas", "sharded"):
            if b == "pallas" and len(space) != 3:
                continue  # the pallas backend is declared 3-D only
            # one fast sharded representative stays in tier-1; the rest of
            # the sharded matrix runs on the CI slow leg (repo convention)
            marks = ([pytest.mark.slow]
                     if b == "sharded" and name != "jacobi2d5p" else [])
            out.append(pytest.param(name, space, tile, b,
                                    marks=marks, id=f"{name}-{b}"))
    return out


@pytest.mark.parametrize("name,space,tile,backend", _exact_params())
def test_compile_bit_exact_vs_legacy(name, space, tile, backend):
    """compiled(inputs) == the legacy entry point, facet for facet."""
    n_ports = 2 if backend == "sharded" else 1
    compiled = cfa.compile(name, space, layout=tile, backend=backend,
                           n_ports=n_ports)
    assert compiled.backend == backend
    x = _inputs(space, tile, name)
    got = compiled(x, dtype=jnp.float64)
    legacy_pipe = CFAPipeline(get_program(name), IterSpace(space), Tiling(tile))
    ref = LEGACY[backend](legacy_pipe, x)
    assert set(got) == set(ref)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), f"facet {k}"


@pytest.mark.parametrize("name,space,tile", [CASES[0], CASES[-1]])
def test_reference_backend_matches_sweep(name, space, tile):
    """The oracle-scatter backend lands the same facet storage as sweep."""
    x = _inputs(space, tile, name)
    ref = cfa.compile(name, space, layout=tile, backend="reference")(
        x, dtype=jnp.float64)
    swp = cfa.compile(name, space, layout=tile, backend="sweep")(
        x, dtype=jnp.float64)
    for k in swp:
        assert (np.asarray(ref[k]) == np.asarray(swp[k])).all(), f"facet {k}"


# ---------------------------------------------------------------------------
# backend selection + the capability gate
# ---------------------------------------------------------------------------

def test_auto_backend_selection_rules():
    j, h1, h3 = (get_program(n) for n in ("jacobi2d5p", "heat1d", "heat3d"))
    assert cfa.select_backend(j, IterSpace((8, 8, 8))) == "pallas"
    assert cfa.select_backend(h1, IterSpace((8, 8))) == "wavefront"
    assert cfa.select_backend(h3, IterSpace((4, 4, 4, 4))) == "wavefront"
    assert cfa.select_backend(j, IterSpace((8, 8, 8)), n_ports=2) == "sharded"
    # overlap=True routes to the dataflow backend (any dimensionality);
    # the multiport rule still wins (dataflow is single-port)
    assert cfa.select_backend(j, IterSpace((8, 8, 8)), overlap=True) == "dataflow"
    assert cfa.select_backend(h3, IterSpace((4, 4, 4, 4)),
                              overlap=True) == "dataflow"
    assert cfa.select_backend(j, IterSpace((8, 8, 8)), n_ports=2,
                              overlap=True) == "sharded"
    # compile(backend="auto") applies exactly these rules
    assert cfa.compile(j, (8, 8, 8), layout=(4, 4, 4)).backend == "pallas"
    assert cfa.compile(h1, (8, 8), layout=(4, 4)).backend == "wavefront"
    assert cfa.compile(j, (8, 8, 8), layout=(4, 4, 4),
                       n_ports=2).backend == "sharded"
    assert cfa.compile(j, (8, 8, 8), layout=(4, 4, 4),
                       overlap=True).backend == "dataflow"
    # overlap=True with an explicitly sequential backend is rejected loudly
    with pytest.raises(cfa.BackendError, match="sequentially"):
        cfa.compile(j, (8, 8, 8), layout=(4, 4, 4), backend="sweep",
                    overlap=True)


def test_pallas_backend_is_3d_only():
    with pytest.raises(cfa.BackendError, match="3-D"):
        cfa.compile("heat3d", (4, 4, 4, 4), layout=(2, 2, 2, 2),
                    backend="pallas")
    with pytest.raises(cfa.BackendError, match="3-D"):
        cfa.compile("heat1d", (8, 8), layout=(4, 4), backend="pallas")


def test_single_port_backends_reject_multiport():
    for backend in ("reference", "sweep", "wavefront", "pallas", "dataflow"):
        with pytest.raises(cfa.BackendError, match="single-port"):
            cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                        backend=backend, n_ports=2)


def test_unknown_backend_lists_registered():
    with pytest.raises(cfa.BackendError, match="registered"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="turbo")


def test_capability_gate_error_lists_backends_sorted():
    """check_backend's BackendError spells the eligible alternatives out in
    sorted order — stable regardless of executor registration order (the
    same convention get_executor's unknown-name error already follows)."""
    with pytest.raises(cfa.BackendError) as ei:
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="pallas", n_ports=2)
    msg = str(ei.value)
    eligible = cfa.available_backends(
        get_program("jacobi2d5p"), IterSpace((8, 8, 8)), n_ports=2)
    assert f"eligible backends: {sorted(eligible)}" in msg


def test_available_backends():
    j, h3 = get_program("jacobi2d5p"), get_program("heat3d")
    assert cfa.available_backends(j, IterSpace((8, 8, 8))) == [
        "reference", "sweep", "wavefront", "pallas", "sharded", "dataflow"]
    h3_avail = cfa.available_backends(h3, IterSpace((4, 4, 4, 4)))
    assert "pallas" not in h3_avail
    assert "dataflow" in h3_avail  # the host dataflow path is N-D
    assert cfa.available_backends(j, IterSpace((8, 8, 8)), n_ports=2) == [
        "sharded"]


def test_lower_rebinds_and_revalidates():
    compiled = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                           backend="sweep")
    assert compiled.lower("wavefront").backend == "wavefront"
    assert compiled.backend == "sweep"  # lower() does not mutate
    nd = cfa.compile("heat3d", (4, 4, 4, 4), layout=(2, 2, 2, 2),
                     backend="sweep")
    with pytest.raises(cfa.BackendError):
        nd.lower("pallas")


# ---------------------------------------------------------------------------
# Target registry
# ---------------------------------------------------------------------------

def test_target_resolution():
    t = cfa.get_target("axi-zc706")
    assert t.model == cfa.AXI_ZC706 and t.max_ports == 4
    assert cfa.get_target(cfa.AXI_ZC706) is t  # registered model -> entry
    assert cfa.get_target(t) is t
    custom = cfa.BurstModel(name="lab-bench", peak_bytes_per_s=1e9,
                            setup_s=1e-7, elem_bytes=4)
    wrapped = cfa.get_target(custom)
    assert wrapped.model == custom and wrapped.max_ports is None
    with pytest.raises(ValueError, match="unknown target"):
        cfa.get_target("fpga-9000")


def test_port_budget_enforced():
    with pytest.raises(ValueError, match="port"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    target="axi-zc706", n_ports=8)
    with pytest.raises(ValueError, match="n_ports"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4), n_ports=0)
    # an unvalidated custom model accepts any port count the backend takes
    custom = cfa.BurstModel(name="lab-bench", peak_bytes_per_s=1e9,
                            setup_s=1e-7, elem_bytes=4)
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    target=custom, n_ports=8)
    assert c.n_ports == 8 and c.backend == "sharded"


def test_register_target_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        cfa.register_target(cfa.Target(name="axi-zc706", model=cfa.AXI_ZC706))


def test_recalibrated_model_keeps_platform_port_budget():
    """Tweaking a registered platform's model parameters (a calibration
    workflow) must not silently forfeit the port-budget validation."""
    import dataclasses

    refit = dataclasses.replace(cfa.AXI_ZC706, peak_bytes_per_s=1e9)
    t = cfa.get_target(refit)
    assert t.model == refit and t.max_ports == 4
    with pytest.raises(ValueError, match="port"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    target=refit, n_ports=16)


def test_unknown_call_options_rejected():
    """A typo'd or inapplicable call option fails loudly instead of being
    silently ignored (e.g. interpret= on a kernel-less backend)."""
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep")
    x = _inputs((8, 8, 8), (4, 4, 4), "jacobi2d5p")
    with pytest.raises(TypeError, match="does not accept"):
        c(x, interpret=False)
    p = c.lower("pallas")
    with pytest.raises(TypeError, match="does not accept"):
        p(x, interpert=False)  # typo'd 'interpret'
    assert isinstance(p(x, interpret=True), dict)  # the real knob works


# ---------------------------------------------------------------------------
# layout resolution
# ---------------------------------------------------------------------------

def test_layout_default_uses_program_tile():
    c = cfa.compile("jacobi2d5p", (32, 32, 32), layout="default",
                    backend="sweep")
    assert c.layout.tile == get_program("jacobi2d5p").default_tile


def test_layout_rejects_non_cfa_candidate():
    bad = cfa.LayoutCandidate("bbox", (4, 4, 4))
    with pytest.raises(ValueError, match="cfa"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=bad, backend="sweep")


def test_layout_rejects_unknown_string_and_type():
    with pytest.raises(ValueError, match="layout"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout="best-effort",
                    backend="sweep")
    with pytest.raises(TypeError):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=3.14, backend="sweep")


def test_layout_autotune_and_decision_reuse(tmp_path):
    c = cfa.compile("jacobi2d5p", (8, 8, 8), backend="sweep",
                    autotune_kwargs=dict(budget=16, cache_dir=tmp_path))
    assert c.decision is not None
    assert c.layout == c.decision.best_cfa().candidate
    # a decision object is itself a valid layout= argument
    again = cfa.compile("jacobi2d5p", (8, 8, 8), layout=c.decision,
                        backend="sweep")
    assert again.layout == c.layout
    # ... but only for the (program, space) it was searched for
    with pytest.raises(ValueError, match="decision is for"):
        cfa.compile("jacobi2d9p", (8, 8, 8), layout=c.decision,
                    backend="sweep")
    x = _inputs((8, 8, 8), c.layout.tile, "jacobi2d5p")
    got = c(x, dtype=jnp.float64)
    ref = c.lower("reference")(x, dtype=jnp.float64)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all()


def test_compile_validates_ndim():
    with pytest.raises(ValueError, match="-D"):
        cfa.compile("jacobi2d5p", (8, 8), layout=(4, 4), backend="sweep")


# ---------------------------------------------------------------------------
# the compiled artifact: plan / report / describe
# ---------------------------------------------------------------------------

def test_compiled_plan_and_report():
    c = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep")
    plan = c.plan
    assert isinstance(plan, cfa.TransferPlan) and plan.n_bursts > 0
    rep = c.report()
    assert rep.model == "axi-zc706" and rep.effective_bw > 0
    assert rep.n_ports == 1
    assert "jacobi2d5p" in c.describe()
    # multi-port report: repartitioned, aggregate bandwidth over ports
    c2 = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                     backend="sharded", n_ports=2)
    rep2 = c2.report()
    assert rep2.n_ports == 2
    assert rep2.effective_bw >= rep.effective_bw


# ---------------------------------------------------------------------------
# legacy shims: removed for good (deprecated through PR 4-6, deleted here)
# ---------------------------------------------------------------------------

def test_legacy_shims_are_gone():
    for name in ("sweep", "sweep_wavefront", "sweep_wavefront_sharded",
                 "from_autotuned"):
        assert not hasattr(CFAPipeline, name), (
            f"CFAPipeline.{name} was deleted; use cfa.compile() "
            f"(or the _-prefixed internal from the executors)"
        )
    import repro.kernels.stencil as stencil
    assert not hasattr(stencil, "execute_tiles_from_autotuned")
    # the deprecation machinery itself left with its last clients
    with pytest.raises(ImportError):
        import repro.core.cfa.deprecation  # noqa: F401


# ---------------------------------------------------------------------------
# public-surface snapshot
# ---------------------------------------------------------------------------

# The public API of repro.cfa.  A failure here means the surface changed:
# update this list (and the docs) deliberately, or revert the accident.
PUBLIC_API = [
    "AXI_ZC706",
    "AnalysisReport",
    "BackendError",
    "BandwidthReport",
    "BlockCodec",
    "BurstModel",
    "CFAPipeline",
    "CODECS",
    "CacheSchemaError",
    "CalibratedModel",
    "Calibration",
    "CompileState",
    "CompiledStencil",
    "Counters",
    "DEFAULT_PASSES",
    "Deps",
    "Diagnostic",
    "EXECUTORS",
    "Executor",
    "ExecutorCaps",
    "IterSpace",
    "LayoutCandidate",
    "LayoutDecision",
    "PROGRAMS",
    "Pass",
    "PassPipeline",
    "PassTrace",
    "PipelineError",
    "PortedPlan",
    "RuntimeReport",
    "SCORE_MODES",
    "STORAGE_MODES",
    "ScoredLayout",
    "Span",
    "StencilProgram",
    "StorageMap",
    "TARGETS",
    "TPU_V5E_HBM",
    "Target",
    "Tiling",
    "TraceRecorder",
    "TransferPlan",
    "TransferSample",
    "VerificationError",
    "autotune",
    "available_backends",
    "build_storage_map",
    "calibrate",
    "chrome_trace",
    "compile",
    "dedup_facets",
    "default_pass_fingerprint",
    "default_pipeline",
    "estimate_facet_bytes",
    "fit_burst_model",
    "get_codec",
    "get_executor",
    "get_program",
    "get_target",
    "measure_plan",
    "measure_runs",
    "overlap_speedup",
    "register_executor",
    "register_target",
    "rehydrate_facets",
    "runtime_report",
    "select_backend",
    "validate_chrome_trace",
    "verify",
]


def test_public_api_snapshot():
    assert sorted(cfa.__all__) == sorted(set(cfa.__all__)), "duplicate names"
    assert sorted(cfa.__all__) == PUBLIC_API
    for name in cfa.__all__:
        assert hasattr(cfa, name), f"repro.cfa.__all__ names missing {name}"


def test_builtin_backends_registered():
    assert list(EXECUTORS) == ["reference", "sweep", "wavefront", "pallas",
                               "sharded", "dataflow"]
    # only the dataflow backend declares the Fig. 13 phase overlap
    assert [n for n, ex in EXECUTORS.items() if ex.caps.overlap] == ["dataflow"]
