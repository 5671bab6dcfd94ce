"""``backend="dataflow"``: overlapped fetch/compute/commit, proven harmless.

Differential harness for the software-pipelined executor (Fig. 13 DATAFLOW
made a schedule): every Table I program (plus the 2-D/4-D additions) run
through ``backend="dataflow"`` must land the *exact* facet storage the
sequential ``sweep`` backend lands, on every storage discipline —
prefetching tile j+1 and deferring tile j-1's commit while j executes is a
pure reordering, because all halo reads come from strictly earlier waves.

The host path is pinned bit-exact (``==``, facet for facet); the kernel
path (``use_kernel=True``, the jitted Pallas tile executor) is allowed
float-rounding differences only — the same convention ``test_api.py`` uses
for the pallas backend.
"""
import json
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro import cfa
from repro.core.cfa import get_program
from repro.core.cfa.executors import EXECUTORS, BackendError

# The Table I suite at test-size spaces + the 2-D and 4-D programs — the
# same corners test_api.py pins (kept in sync by the shared shapes).
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
    w0 = prog.widths[0]
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(w0, *space[1:])))


def _run(name, space, tile, backend, storage, **opts):
    compiled = cfa.compile(name, space, layout=tile, backend=backend,
                           storage=storage)
    return compiled(_inputs(space, name), dtype=jnp.float64, **opts)


def _host_params():
    out = []
    for name, space, tile in CASES:
        for storage in ("redundant", "irredundant"):
            out.append(pytest.param(name, space, tile, storage,
                                    id=f"{name}-{storage}"))
    # the compressed discipline is storage-layer-heavy; one 3-D and the
    # 2-D/4-D corners keep tier-1 fast while covering every dimensionality
    for name, space, tile in (CASES[0], CASES[-2], CASES[-1]):
        out.append(pytest.param(name, space, tile, "compressed",
                                id=f"{name}-compressed"))
    return out


@pytest.mark.parametrize("name,space,tile,storage", _host_params())
def test_dataflow_host_path_bit_exact_vs_sweep(name, space, tile, storage):
    """dataflow == sweep, facet for facet, on the eager host path."""
    got = _run(name, space, tile, "dataflow", storage)
    ref = _run(name, space, tile, "sweep", storage)
    assert set(got) == set(ref)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), f"facet {k}"


def _kernel_params():
    out = []
    for name, space, tile in CASES:
        if len(space) != 3:
            continue  # the Pallas tile executor is declared 3-D only
        for storage in (("redundant", "irredundant")
                        if name == "jacobi2d5p" else ("redundant",)):
            out.append(pytest.param(name, space, tile, storage,
                                    id=f"{name}-{storage}"))
    return out


@pytest.mark.parametrize("name,space,tile,storage", _kernel_params())
def test_dataflow_kernel_path_matches_sweep(name, space, tile, storage):
    """dataflow(use_kernel=True) == sweep within float32 kernel rounding."""
    got = _run(name, space, tile, "dataflow", storage, use_kernel=True)
    ref = _run(name, space, tile, "sweep", storage)
    assert set(got) == set(ref)
    for k in ref:
        assert np.allclose(np.asarray(got[k]), np.asarray(ref[k]),
                           rtol=1e-5, atol=1e-5), f"facet {k}"


def test_dataflow_matches_wavefront_and_reference():
    """Three-way agreement: dataflow == reference oracle bit for bit, and
    wavefront, whose wave runs as one compiled program (a multiply and an
    add may fuse into one rounding), with both at the fused-program
    tolerance of the kernel path."""
    name, space, tile = CASES[0]
    df = _run(name, space, tile, "dataflow", "redundant")
    wf = _run(name, space, tile, "wavefront", "redundant")
    ref = _run(name, space, tile, "reference", "redundant")
    for k in ref:
        assert (np.asarray(df[k]) == np.asarray(ref[k])).all(), f"facet {k}"
        for other in (df, ref):
            assert np.allclose(np.asarray(wf[k]), np.asarray(other[k]),
                               rtol=1e-5, atol=1e-5), f"facet {k}"


# --------------------------------------------------------------------------
# Capability gating
# --------------------------------------------------------------------------


def test_dataflow_declares_overlap_cap():
    caps = EXECUTORS["dataflow"].caps
    assert caps.overlap
    assert caps.kernels
    assert not caps.multiport
    # the only backend whose modeled time composes with overlap=True
    assert [n for n, ex in EXECUTORS.items() if ex.caps.overlap] == ["dataflow"]


def test_dataflow_kernel_path_rejects_non_3d():
    name, space, tile = ("heat1d", (8, 8), (4, 4))
    compiled = cfa.compile(name, space, layout=tile, backend="dataflow")
    with pytest.raises(BackendError, match=r"3-D.*2-D"):
        compiled(_inputs(space, name), dtype=jnp.float64, use_kernel=True)


def test_dataflow_kernel_path_rejects_compressed():
    name, space, tile = CASES[0]
    compiled = cfa.compile(name, space, layout=tile, backend="dataflow",
                           storage="compressed")
    with pytest.raises(BackendError, match="decode"):
        compiled(_inputs(space, name), dtype=jnp.float64, use_kernel=True)


def test_dataflow_rejects_unknown_options():
    name, space, tile = CASES[0]
    compiled = cfa.compile(name, space, layout=tile, backend="dataflow")
    with pytest.raises(TypeError, match="does not accept"):
        compiled(_inputs(space, name), dtype=jnp.float64, mesh=None)


# --------------------------------------------------------------------------
# The modeled counterpart rides along
# --------------------------------------------------------------------------


def test_dataflow_report_defaults_to_overlap():
    """report() on a dataflow-bound stencil models the pipelined schedule."""
    name, space, tile = CASES[0]
    compiled = cfa.compile(name, space, layout=tile, backend="dataflow")
    c = 1e-4
    ovl = compiled.report(compute_s=c)            # overlap defaults to caps
    seq = compiled.report(compute_s=c, overlap=False)
    assert ovl.overlap and not seq.overlap
    assert ovl.compute_s == seq.compute_s == c
    # the report's bandwidths divide by the composed time, so the
    # overlapped report can only look faster, never slower
    assert ovl.raw_bw >= seq.raw_bw
    assert ovl.effective_bw >= seq.effective_bw
    model = compiled.target.model
    t_ovl = model.time(compiled.plan, compute_s=c, overlap=True)
    t_seq = model.time(compiled.plan, compute_s=c, overlap=False)
    t = model.transfer_time_s(compiled.plan)
    assert max(t, c) <= t_ovl <= t_seq == t + c
    # a sequential backend's default report stays sequential
    assert not cfa.compile(name, space, layout=tile,
                           backend="sweep").report().overlap


# --------------------------------------------------------------------------
# The committed benchmark record stays honest
# --------------------------------------------------------------------------

RESULTS = Path(__file__).resolve().parent.parent / "benchmarks" / "results" / "dataflow"


@pytest.mark.parametrize("model", ["axi-zc706", "tpu-v5e-hbm"])
def test_committed_suite_record_demonstrates_overlap(model):
    """The shipped suite artifact records a real measured overlap win.

    Regenerate with ``PYTHONPATH=src python benchmarks/dataflow_bench.py``;
    this test fails if a regeneration ships a record where no transfer-bound
    program measured faster overlapped than sequential.
    """
    record = json.loads((RESULTS / f"suite_{model}.json").read_text())
    head = record["headline"]
    assert head["transfer_bound_overlap_demonstrated"] is True
    assert head["best_transfer_bound"]["measured_speedup"] > 1.0
    assert {r["program"] for r in record["rows"]} == {c[0] for c in CASES}
    for row in record["rows"]:
        assert row["wave_factor"] >= 1
        for reg in row["regimes"]:
            assert reg["rel_err_modeled_overlap"] >= 0.0
            assert reg["rel_err_fitted_overlap"] >= 0.0
            assert reg["modeled"]["speedup"] <= reg["modeled"]["bound"] + 1e-9
