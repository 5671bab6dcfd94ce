"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the repo convention, then a
readable report.  Roofline terms come from the dry-run records
(benchmarks/results/dryrun) when present.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

RESULTS = Path(__file__).parent / "results"


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}")


def table1_suite() -> None:
    """Table I: the benchmark suite, and the field programs beside it, run
    end-to-end through facet storage."""
    import jax.numpy as jnp
    import numpy as np
    from repro import cfa
    from repro.core.cfa.programs import FIELD_PROGRAMS

    for name, prog in {**cfa.PROGRAMS, **FIELD_PROGRAMS}.items():
        t = tuple(min(x, 4) for x in prog.default_tile)
        space = tuple(2 * x for x in t)
        compiled = cfa.compile(prog, space, layout=t, backend="sweep")
        rng = np.random.default_rng(0)
        spec = compiled.pipeline.specs[0]
        inputs = jnp.asarray(rng.normal(
            size=prog.with_fields((spec.width, *space[1:]), prog.n_fields)))
        t0 = time.perf_counter()
        facets = compiled(inputs)
        us = 1e6 * (time.perf_counter() - t0)
        V = compiled.reference(inputs)
        from repro.core.cfa import pack_facet
        ok = "n/a"
        if spec.tile_sizes[0] % spec.width == 0:
            want = pack_facet(V.astype(jnp.float32), spec)
            err = float(jnp.abs(facets[0][1:] - want).max())
            ok = f"max_err={err:.2e}"
        _csv(f"table1/{name}", us, f"deps={len(prog.deps.vectors)};{ok}")


def fig15_bandwidth() -> None:
    from benchmarks.bandwidth_fig15 import run_fig15

    rows = run_fig15()
    (RESULTS / "fig15.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        if r["model"] == "axi-zc706":
            _csv(
                f"fig15/{r['benchmark']}/{r['tile']}/{r['scheme']}",
                r["time_us"],
                f"raw={r['raw_frac']:.3f};eff={r['eff_frac']:.3f};"
                f"bursts={r['n_bursts']}",
            )


def fig16_area() -> None:
    from benchmarks.area_fig16 import run_fig16

    rows = run_fig16()
    (RESULTS / "fig16.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        _csv(f"fig16/{r['benchmark']}/{r['scheme']}", 0.0,
             f"layout_ops={r['layout_ops']};descriptors={r['descriptors_per_tile']}")


def fig17_vmem() -> None:
    from benchmarks.vmem_fig17 import run_fig17

    rows = run_fig17()
    (RESULTS / "fig17.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        _csv(f"fig17/{r['benchmark']}/{r['tile']}", 0.0,
             f"cfa={r['cfa_vmem_frac']:.4f};bbox={r['bbox_vmem_frac']:.4f};"
             f"dt={r['data_tiling_vmem_frac']:.4f}")


def kvcache() -> None:
    from benchmarks.kvcache_bench import run_kvcache_bench, run_kvcache_walltime

    rows = run_kvcache_bench()
    (RESULTS / "kvcache.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        _csv(f"kvcache/{r['shape']}", 0.0,
             f"block_eff={r['block_eff_frac']:.3f};"
             f"canon_eff={r['canonical_eff_frac']:.3f};speedup={r['speedup']:.1f}x")
    wt = run_kvcache_walltime()
    _csv("kvcache/walltime_block", wt["block_us"], "jnp-cpu-sanity")
    _csv("kvcache/walltime_canonical", wt["canonical_us"], "jnp-cpu-sanity")


def multiport() -> None:
    """Paper §VII future work: facet distribution over HBM ports."""
    from repro.core.cfa import AXI_ZC706, TPU_V5E_HBM, IterSpace, Tiling, get_program
    from repro.core.cfa.multiport import port_speedup

    rows = []
    prog = get_program("jacobi2d5p")
    space, tiling = IterSpace((64, 64, 64)), Tiling((16, 16, 16))
    for model in (AXI_ZC706, TPU_V5E_HBM):
        for n in (1, 2, 3):
            r = port_speedup(space, prog.deps, tiling, n, model)
            rows.append(dict(r, model=model.name))
            _csv(f"multiport/{model.name}/{n}ports", r["t_multi_us"],
                 f"speedup={r['speedup']:.2f};balance={r['balance']:.2f}")
    out = RESULTS / "multiport" / "quick.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))


def autotune_table() -> None:
    """Layout autotuner: winning layout per benchmark vs the hand-coded plans."""
    from repro import cfa
    from repro.core.cfa import IterSpace, hand_coded_baselines

    rows = []
    for name, prog in cfa.PROGRAMS.items():
        space = tuple(2 * t for t in prog.default_tile)
        # decision-only: the front door's cfa.autotune, no executor needed
        d = cfa.autotune(prog, space, cfa.AXI_ZC706, seed=0, budget=64)
        base = hand_coded_baselines(prog, IterSpace(space), cfa.AXI_ZC706)
        gain = d.best.effective_bw / max(s.effective_bw for s in base.values())
        rows.append({
            "benchmark": name,
            "space": list(space),
            "winner": d.best.candidate.key,
            "eff_frac": d.best.peak_fraction_effective,
            "gain_vs_hand_coded": gain,
            "evaluated": d.evaluated,
            "from_cache": d.from_cache,
        })
        _csv(f"autotune/{name}", 0.0,
             f"winner={d.best.candidate.key};"
             f"eff={d.best.peak_fraction_effective:.3f};gain={gain:.2f}x")
    out = RESULTS / "autotune" / "quick.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))


def roofline_table() -> None:
    from benchmarks.roofline import build_table

    rows = build_table("single")
    if not rows:
        print("# roofline: no dry-run records found (run repro.launch.dryrun)")
        return
    (RESULTS / "roofline_single.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        _csv(
            f"roofline/{r['arch']}/{r['cell']}", 0.0,
            f"dominant={r['dominant']};frac={r['roofline_fraction']:.4f};"
            f"useful={r['useful_ratio']:.2f}",
        )


def main() -> None:
    RESULTS.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    table1_suite()
    fig15_bandwidth()
    fig16_area()
    fig17_vmem()
    kvcache()
    multiport()
    autotune_table()
    roofline_table()


if __name__ == "__main__":
    main()
