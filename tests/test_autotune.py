"""Tests for the layout autotuner (repro.core.cfa.autotune).

Covers the ISSUE-1 acceptance bar: search determinism under a fixed seed,
cache hit/miss round-trips, the chosen layout never losing to the hand-coded
plans (cfa/original/bbox/data-tiling), and the autotuned pipeline staying
bit-exact against the untiled oracle.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cfa import (
    AXI_ZC706,
    IterSpace,
    LayoutDecision,
    PROGRAMS,
    autotune,
    candidate_tilings,
    hand_coded_baselines,
    pack_facet,
)
from repro.core.cfa.plans import original_layout_plan, interior_tile
from repro.core.cfa.programs import FIELD_PROGRAMS, get_program
from repro.core.cfa.spaces import Tiling


def _small_space(prog):
    """2 tiles per axis at the default tile — every hand-coded seed is legal."""
    return tuple(2 * t for t in prog.default_tile)


# ---------------------------------------------------------------------------
# determinism + cache
# ---------------------------------------------------------------------------

def test_search_deterministic_given_seed(tmp_path):
    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=40, cache_dir=tmp_path)
    a = autotune(prog, (32, 32, 32), AXI_ZC706, seed=7, cache=False, **kw)
    b = autotune(prog, (32, 32, 32), AXI_ZC706, seed=7, cache=False, **kw)
    assert a.ranked == b.ranked
    assert a.evaluated == b.evaluated


def test_cache_roundtrip_hit_and_miss(tmp_path):
    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=24, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert not first.from_cache
    again = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert again.from_cache
    assert again.ranked == first.ranked
    # a different key (other seed) is a miss
    other = autotune(prog, (32, 32, 32), AXI_ZC706, budget=24, seed=1,
                     cache_dir=tmp_path)
    assert not other.from_cache


def test_decision_json_roundtrip(tmp_path):
    prog = PROGRAMS["gaussian"]
    d = autotune(prog, _small_space(prog), AXI_ZC706, budget=16, seed=0,
                 cache=False, cache_dir=tmp_path)
    back = LayoutDecision.from_json(d.to_json())
    assert back == d
    assert back.best.candidate == d.best.candidate


def test_corrupt_cache_entry_recomputed(tmp_path):
    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=16, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    for f in tmp_path.glob("*.json"):
        f.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        redo = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert not redo.from_cache
    assert redo.ranked == first.ranked


def test_old_cache_schema_rejected_loudly(tmp_path):
    """Schema v3: an old-version decision under the current key warns and
    re-searches instead of silently deserializing (or silently vanishing)."""
    import json

    from repro.core.cfa import CacheSchemaError

    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=16, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    (entry,) = tmp_path.glob("*.json")
    blob = json.loads(entry.read_text())
    blob["version"] = 2
    entry.write_text(json.dumps(blob))
    with pytest.raises(CacheSchemaError, match="schema v2"):
        LayoutDecision.from_json(entry.read_text())
    with pytest.warns(RuntimeWarning, match="schema v2"):
        redo = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert not redo.from_cache
    assert redo.ranked == first.ranked
    # the re-search overwrote the stale entry: next call is a clean hit
    hit = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert hit.from_cache


def test_cache_key_records_backend_capability_set(tmp_path):
    """Schema v3: the key folds the executor capability fingerprint in, so
    a decision is not silently reused after the backend envelope changes."""
    from repro.core.cfa.executors import (EXECUTORS, ExecutorCaps,
                                          register_executor)

    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=16, seed=0, cache_dir=tmp_path)
    autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert autotune(prog, (32, 32, 32), AXI_ZC706, **kw).from_cache

    class _Dummy:
        name = "test-dummy"
        caps = ExecutorCaps(ndims=(3,), description="cache-key probe")

        def execute(self, pipeline, inputs, **kw):  # pragma: no cover
            raise NotImplementedError

    register_executor(_Dummy())
    try:
        assert not autotune(prog, (32, 32, 32), AXI_ZC706, **kw).from_cache
    finally:
        del EXECUTORS["test-dummy"]
    assert autotune(prog, (32, 32, 32), AXI_ZC706, **kw).from_cache


# a cheap measured pass for the cache-split tests: tiny program, one repeat
_MEASURED_KW = dict(score="measured", measure_top=2,
                    measure_kwargs=dict(warmup=0, repeats=1))


def test_measured_and_modeled_cache_keys_are_disjoint(tmp_path):
    """Schema v5: the score axis (plus host fingerprint) is folded into the
    cache key, so a modeled decision can never be served for a measured
    query (or vice versa) — each query is a miss in the other's cache."""
    prog = PROGRAMS["heat1d"]
    kw = dict(budget=8, seed=0, cache_dir=tmp_path)
    modeled = autotune(prog, (8, 64), AXI_ZC706, **kw)
    assert not modeled.from_cache
    measured = autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW)
    assert not measured.from_cache  # distinct key: no crosstalk
    assert measured.score == "measured"
    # both populated their own keys: each repeat query is now a clean hit
    assert autotune(prog, (8, 64), AXI_ZC706, **kw).from_cache
    assert autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW).from_cache


def test_modeled_entry_at_measured_key_rejected_loudly(tmp_path):
    """Schema v5: an entry whose recorded score disagrees with the query
    (e.g. written by a buggy tool under the wrong key) warns and re-searches
    instead of silently serving the wrong ranking objective."""
    import json

    from repro.core.cfa.autotune import _cache_load

    prog = PROGRAMS["heat1d"]
    kw = dict(budget=8, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW)
    (entry,) = tmp_path.glob("*.json")
    blob = json.loads(entry.read_text())
    assert blob["score"] == "measured"
    blob["score"] = "modeled"  # forge a modeled decision under the measured key
    entry.write_text(json.dumps(blob))
    assert _cache_load(entry, "modeled") is not None  # the forgery is valid JSON
    with pytest.warns(RuntimeWarning, match="score='modeled'.*score='measured'"):
        redo = autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW)
    assert not redo.from_cache
    assert redo.best.candidate == first.best.candidate
    # the re-search overwrote the forged entry: next call is a clean hit
    assert autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW).from_cache


def test_decision_records_score_and_roundtrips(tmp_path):
    """The decision carries its scoring mode: 'modeled' by default, and the
    mode survives the JSON round-trip either way."""
    prog = PROGRAMS["heat1d"]
    kw = dict(budget=8, seed=0, cache=False, cache_dir=tmp_path)
    modeled = autotune(prog, (8, 64), AXI_ZC706, **kw)
    assert modeled.score == "modeled"
    assert LayoutDecision.from_json(modeled.to_json()).score == "modeled"
    measured = autotune(prog, (8, 64), AXI_ZC706, **kw, **_MEASURED_KW)
    assert measured.score == "measured"
    assert LayoutDecision.from_json(measured.to_json()).score == "measured"
    assert any(s.measured_time_s is not None for s in measured.ranked)


# ---------------------------------------------------------------------------
# quality: never worse than the hand-coded plans (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PROGRAMS) + sorted(FIELD_PROGRAMS))
def test_decision_beats_every_hand_coded_plan(name, tmp_path):
    prog = get_program(name)
    space = _small_space(prog)
    decision = autotune(prog, space, AXI_ZC706, budget=48, seed=0,
                        cache_dir=tmp_path)
    base = hand_coded_baselines(prog, IterSpace(space), AXI_ZC706)
    for bname, s in base.items():
        assert decision.best.effective_bw >= s.effective_bw - 1e-9, (
            f"{name}: autotuned {decision.best.effective_bw:.3e} lost to "
            f"hand-coded {bname} {s.effective_bw:.3e}"
        )
    # and the best CFA-family candidate also beats the hand-coded CFA plan
    assert decision.best_cfa().effective_bw >= base["cfa"].effective_bw - 1e-9


@pytest.mark.parametrize("name", sorted(PROGRAMS) + sorted(FIELD_PROGRAMS))
def test_chosen_plan_not_worse_than_original_layout(name, tmp_path):
    """The winner's modeled bursts and transfer time never exceed the
    original-layout baseline (which moves the minimum possible bytes)."""
    prog = get_program(name)
    space = _small_space(prog)
    decision = autotune(prog, space, AXI_ZC706, budget=48, seed=0,
                        cache_dir=tmp_path)
    sp, tiling = IterSpace(space), Tiling(prog.default_tile)
    orig = original_layout_plan(sp, prog.deps, tiling,
                                interior_tile(sp, tiling))
    t_orig = (AXI_ZC706.time_s(orig.read_runs)
              + AXI_ZC706.time_s(orig.write_runs))
    assert decision.best.n_bursts <= orig.n_bursts
    assert decision.best.time_s <= t_orig + 1e-12


# ---------------------------------------------------------------------------
# the search space itself
# ---------------------------------------------------------------------------

def test_candidate_tilings_legal_and_bounded():
    prog = PROGRAMS["gaussian"]  # widths (1, 4, 4)
    tilings = candidate_tilings(prog.widths, (8, 32, 32), max_halo_elems=4096)
    assert tilings, "search space must be non-empty"
    for t in tilings:
        for n, tk, w in zip((8, 32, 32), t, prog.widths):
            assert n % tk == 0 and tk >= max(1, w)
        halo = np.prod([tk + w for tk, w in zip(t, prog.widths)])
        assert halo <= 4096


def test_ranking_is_sorted_by_effective_bw(tmp_path):
    prog = PROGRAMS["jacobi2d9p"]
    d = autotune(prog, _small_space(prog), AXI_ZC706, budget=32, seed=0,
                 cache=False, cache_dir=tmp_path)
    bws = [s.effective_bw for s in d.ranked]
    assert bws == sorted(bws, reverse=True)
    assert d.evaluated == len(d.ranked)


# ---------------------------------------------------------------------------
# schema v7: the pass-pipeline fingerprint
# ---------------------------------------------------------------------------

def test_cache_key_records_pass_pipeline(tmp_path):
    """Schema v7: the lowering pipeline's (name, version) fingerprint is
    folded into the cache key, so a reordered/edited pipeline searches
    fresh instead of silently reusing the old pipeline's decision."""
    from repro.core.cfa.passes import default_pass_fingerprint

    prog = PROGRAMS["jacobi2d5p"]
    kw = dict(budget=16, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (32, 32, 32), AXI_ZC706, **kw)
    assert first.pass_pipeline == default_pass_fingerprint()
    assert autotune(prog, (32, 32, 32), AXI_ZC706, **kw).from_cache
    # an edited pipeline (bumped pass version) keys differently: a miss
    edited = tuple((n, "99") if n == "layout_search" else (n, v)
                   for n, v in default_pass_fingerprint())
    other = autotune(prog, (32, 32, 32), AXI_ZC706, **kw,
                     pass_fingerprint=edited)
    assert not other.from_cache
    assert other.pass_pipeline == edited
    # both keys now populated: each repeat query is a clean hit
    assert autotune(prog, (32, 32, 32), AXI_ZC706, **kw).from_cache
    assert autotune(prog, (32, 32, 32), AXI_ZC706, **kw,
                    pass_fingerprint=edited).from_cache


def test_foreign_pass_pipeline_entry_rejected_loudly(tmp_path):
    """Schema v7: an entry recording a different pass pipeline than the
    query's (e.g. written by a buggy tool under the wrong key) warns and
    re-searches instead of silently serving a stale lowering's decision."""
    import json

    from repro.core.cfa.autotune import _cache_load
    from repro.core.cfa.passes import default_pass_fingerprint

    prog = PROGRAMS["heat1d"]
    kw = dict(budget=8, seed=0, cache_dir=tmp_path)
    first = autotune(prog, (8, 64), AXI_ZC706, **kw)
    (entry,) = tmp_path.glob("*.json")
    blob = json.loads(entry.read_text())
    blob["pass_pipeline"] = [["bogus_pass", "1"]]  # forge a foreign lowering
    entry.write_text(json.dumps(blob))
    # the forgery is valid JSON — only the fingerprint check rejects it
    assert _cache_load(entry, "modeled") is not None
    with pytest.warns(RuntimeWarning, match="pass pipeline"):
        redo = autotune(prog, (8, 64), AXI_ZC706, **kw)
    assert not redo.from_cache
    assert redo.best.candidate == first.best.candidate
    # the re-search overwrote the forged entry: next call is a clean hit
    assert autotune(prog, (8, 64), AXI_ZC706, **kw).from_cache


def test_decision_pass_pipeline_roundtrips(tmp_path):
    prog = PROGRAMS["heat1d"]
    d = autotune(prog, (8, 64), AXI_ZC706, budget=8, seed=0, cache=False,
                 cache_dir=tmp_path)
    back = LayoutDecision.from_json(d.to_json())
    assert back.pass_pipeline == d.pass_pipeline is not None


# ---------------------------------------------------------------------------
# end-to-end: the autotuned pipeline is still exact
# ---------------------------------------------------------------------------

def test_autotuned_compile_matches_oracle(tmp_path):
    from repro import cfa

    prog = PROGRAMS["jacobi2d5p"]
    space = (16, 16, 16)
    compiled = cfa.compile(prog.name, space, layout="autotune",
                           backend="sweep",
                           autotune_kwargs=dict(budget=24, seed=0,
                                                cache_dir=tmp_path))
    pipe = compiled.pipeline
    assert pipe.decision is not None
    assert pipe.tiling.sizes == pipe.decision.best_cfa().candidate.tile
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(pipe.specs[0].width, *space[1:])),
                         jnp.float32)
    facets = compiled(inputs, dtype=jnp.float32)
    V = pipe.reference_volume(inputs)
    spec = pipe.specs[0]
    if spec.tile_sizes[0] % spec.width:
        pytest.skip("winning tile not a multiple of w0; pack_facet n/a")
    err = float(jnp.abs(facets[0][1:] - pack_facet(V.astype(jnp.float32),
                                                   spec)).max())
    assert err < 1e-4
