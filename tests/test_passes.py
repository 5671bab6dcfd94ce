"""The staged lowering (repro.core.cfa.passes).

Covers the pass-pipeline acceptance bar:

* *differential equivalence* — an explicitly assembled ``default_pipeline()``
  run over a ``CompileState`` produces facets bit-exact against
  ``cfa.compile()`` for every Table I program (plus heat1d/heat3d) across
  the storage x backend matrix (comparisons are same-backend: the pallas
  interpret kernel is not bit-exact against sweep in float64, and that
  pre-dates the pipeline);
* *pass-order validation* — a missing, duplicated or mis-ordered stage is
  rejected loudly at pipeline assembly, never mid-lowering;
* *trace* — every compile records a per-pass artifact diff retrievable as
  ``CompiledStencil.trace()``;
* *distribute* — a space exceeding ``host_budget`` lowers to sharded
  execution bit-exact against the single-host sweep, and a budget even the
  target's full port complement cannot satisfy raises.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import cfa
from repro.core.cfa import get_program
from repro.core.cfa.passes import (
    DEFAULT_PASSES,
    CompileState,
    PassPipeline,
    PassTrace,
    PipelineError,
    compiler_pass,
    default_pass_fingerprint,
    default_pipeline,
    estimate_facet_bytes,
)
from repro.core.cfa.spaces import IterSpace

# (program, space, tile): the Table I suite at test-size spaces + the N-D
# additions (pinned tiles keep the matrix out of the autotuner)
CASES = [
    ("jacobi2d5p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p", (8, 8, 8), (4, 4, 4)),
    ("jacobi2d9p-gol", (8, 8, 8), (4, 4, 4)),
    ("gaussian", (4, 16, 16), (2, 8, 8)),
    ("smith-waterman-3seq", (9, 8, 8), (3, 4, 4)),
    ("heat1d", (8, 8), (4, 4)),
    ("heat3d", (4, 4, 4, 4), (2, 2, 2, 2)),
]
STORAGES = ("redundant", "irredundant", "compressed")
BACKENDS = ("sweep", "wavefront", "pallas", "sharded", "dataflow")


def _inputs(name, space, seed=0):
    w0 = get_program(name).widths[0]
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(w0, *space[1:])))


def _eligible(name, space, storage, backend):
    if backend == "pallas":
        return len(space) == 3 and storage != "compressed"
    return True


def _matrix_params():
    out = []
    for name, space, tile in CASES:
        for storage in STORAGES:
            for backend in BACKENDS:
                if not _eligible(name, space, storage, backend):
                    continue
                # fast subset: the full matrix on jacobi2d5p, plus every
                # program's redundant sweep; the rest rides the CI slow leg
                fast = (name == "jacobi2d5p"
                        or (storage == "redundant" and backend == "sweep"))
                out.append(pytest.param(
                    name, space, tile, storage, backend,
                    marks=[] if fast else [pytest.mark.slow],
                    id=f"{name}-{storage}-{backend}"))
    return out


@pytest.mark.parametrize("name,space,tile,storage,backend", _matrix_params())
def test_pipeline_differential_bit_exact(name, space, tile, storage, backend):
    """compile() and a hand-assembled default pipeline agree, facet for
    facet, across the program x storage x backend matrix."""
    n_ports = 2 if backend == "sharded" else 1
    compiled = cfa.compile(name, space, layout=tile, backend=backend,
                           storage=storage, n_ports=n_ports)
    state = CompileState(program=name, space=space, layout=tile,
                         backend=backend, storage=storage, n_ports=n_ports)
    final = default_pipeline().run(state)
    manual = final.compiled
    assert manual.backend == compiled.backend == backend
    assert manual.layout.key == compiled.layout.key
    x = _inputs(name, space)
    got = compiled(x, dtype=jnp.float64)
    ref = manual(x, dtype=jnp.float64)
    assert set(got) == set(ref)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), f"facet {k}"


def test_explicit_passes_kwarg_is_the_same_lowering():
    pipe = default_pipeline()
    a = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep")
    b = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                    backend="sweep", passes=pipe)
    x = _inputs("jacobi2d5p", (8, 8, 8))
    ga, gb = a(x, dtype=jnp.float64), b(x, dtype=jnp.float64)
    for k in ga:
        assert (np.asarray(ga[k]) == np.asarray(gb[k])).all()
    # and the explicit pipeline retains its own trace
    assert tuple(t.name for t in pipe.trace()) == pipe.names


# ---------------------------------------------------------------------------
# pass-order validation: assembly-time, loud
# ---------------------------------------------------------------------------

def test_missing_stage_rejected_at_assembly():
    with pytest.raises(PipelineError, match="requires"):
        default_pipeline().without("layout_search")  # lower_backend starves
    with pytest.raises(PipelineError, match="requires"):
        default_pipeline().without("resolve_program")


def test_missing_lower_backend_rejected():
    with pytest.raises(PipelineError, match="compiled"):
        default_pipeline().without("lower_backend")


def test_duplicated_stage_rejected():
    with pytest.raises(PipelineError, match="duplicate"):
        PassPipeline(DEFAULT_PASSES + (DEFAULT_PASSES[0],))


def test_misordered_stage_rejected():
    shuffled = (DEFAULT_PASSES[1],) + (DEFAULT_PASSES[0],) + DEFAULT_PASSES[2:]
    with pytest.raises(PipelineError, match="mis-ordered|requires"):
        PassPipeline(shuffled)


def test_without_unknown_stage_rejected():
    with pytest.raises(PipelineError, match="no pass named"):
        default_pipeline().without("not_a_stage")


def test_replaced_swaps_a_stage():
    @compiler_pass("select_backend", version="2",
                   requires=("program", "target"), provides=("backend",))
    def always_sweep(state):
        import dataclasses

        from repro.core.cfa.executors import get_executor
        return dataclasses.replace(state, executor=get_executor("sweep"))

    pipe = default_pipeline().replaced("select_backend", always_sweep)
    assert pipe.names == default_pipeline().names
    assert ("select_backend", "2") in pipe.fingerprint()
    compiled = cfa.compile("heat3d", (4, 4, 4, 4), layout=(2, 2, 2, 2),
                           passes=pipe)
    assert compiled.backend == "sweep"  # auto would have picked wavefront


def test_fingerprint_is_ordered_names_and_versions():
    fp = default_pipeline().fingerprint()
    assert fp == default_pass_fingerprint()
    assert [n for n, _ in fp] == list(default_pipeline().names)
    assert all(isinstance(n, str) and isinstance(v, str) for n, v in fp)


# ---------------------------------------------------------------------------
# the trace artifact
# ---------------------------------------------------------------------------

def test_trace_shape_and_artifact_diffs():
    compiled = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                           backend="sweep", storage="irredundant")
    tr = compiled.trace()
    assert tuple(t.name for t in tr) == default_pipeline().names
    assert all(isinstance(t, PassTrace) for t in tr)
    assert all(t.wall_s >= 0 for t in tr)
    by_name = {t.name: t for t in tr}
    assert dict(by_name["resolve_program"].changed).keys() >= {"program",
                                                               "space"}
    assert "candidate" in dict(by_name["layout_search"].changed)
    assert "storage_map" in dict(by_name["storage_map"].changed)
    assert "compiled" in dict(by_name["lower_backend"].changed)
    d = tr[0].to_dict()
    assert set(d) == {"pass", "version", "wall_s", "changed"}
    assert d["pass"] == "resolve_program"


def test_noop_passes_trace_empty_diffs():
    compiled = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                           backend="sweep")
    by_name = {t.name: t for t in compiled.trace()}
    # single-port, redundant, no budget: these stages have nothing to do
    assert by_name["distribute"].changed == ()
    assert by_name["storage_map"].changed == ()
    assert by_name["port_repartition"].changed == ()


# ---------------------------------------------------------------------------
# the distribute pass
# ---------------------------------------------------------------------------

def _budget_for_shards(name, space, shards):
    """A per-host byte budget that forces exactly ``shards`` shards."""
    target = cfa.get_target("axi-zc706")
    prog = get_program(name)
    est = estimate_facet_bytes(prog, IterSpace(space),
                               elem_bytes=target.model.elem_bytes)
    return -(-est // shards)


def test_distribute_lowers_to_sharded_bit_exact():
    name, space = "jacobi2d5p", (8, 8, 8)
    budget = _budget_for_shards(name, space, 2)
    dist = cfa.compile(name, space, layout=(4, 4, 4), host_budget=budget)
    assert dist.distributed
    assert dist.backend == "sharded"
    single = cfa.compile(name, space, layout=(4, 4, 4), backend="sweep")
    assert not single.distributed
    x = _inputs(name, space)
    got = dist(x, dtype=jnp.float64)
    ref = single(x, dtype=jnp.float64)
    for k in ref:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), f"facet {k}"
    # the decision shows up in the trace
    by_name = {t.name: t for t in dist.trace()}
    changed = dict(by_name["distribute"].changed)
    assert changed.keys() >= {"n_ports", "distributed"}


def test_distribute_noop_when_space_fits():
    compiled = cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4),
                           backend="sweep", host_budget=10**12)
    assert not compiled.distributed
    assert compiled.backend == "sweep"


def test_distribute_budget_beyond_port_complement_raises():
    with pytest.raises(ValueError, match="host_budget|port"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4), host_budget=8)


def test_distribute_budget_must_be_positive():
    with pytest.raises(ValueError, match="positive"):
        cfa.compile("jacobi2d5p", (8, 8, 8), layout=(4, 4, 4), host_budget=0)


def test_estimate_facet_bytes_scales_with_space_and_width():
    prog = get_program("jacobi2d5p")
    small = estimate_facet_bytes(prog, IterSpace((8, 8, 8)))
    big = estimate_facet_bytes(prog, IterSpace((8, 32, 32)))
    assert 0 < small < big
    assert estimate_facet_bytes(prog, IterSpace((8, 8, 8)),
                                elem_bytes=8) == 2 * small
