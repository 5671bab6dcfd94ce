"""Quickstart: Canonical Facet Allocation in five minutes.

One call — ``cfa.compile`` — picks a burst-friendly layout for the paper's
running example (a 3-D skewed jacobi iteration space), builds the
read->execute->write schedule and binds an execution backend.  The compiled
stencil then runs the tiled computation entirely through facet storage,
verifies against the untiled oracle, and prints the burst statistics that
are the paper's whole point.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro import cfa

prog = cfa.get_program("jacobi2d5p")
space = (16, 32, 32)

# 1. compile: layout search + planning + backend selection in one call ------
compiled = cfa.compile(prog, space, target="axi-zc706",
                       autotune_kwargs=dict(seed=0, budget=64))
print(f"dependence pattern ({len(prog.deps.vectors)} vectors): "
      f"{prog.deps.vectors}")
print(f"facet widths w_k = {prog.widths}")
for k, s in compiled.pipeline.specs.items():
    print(f"  facet_{k}: shape {s.shape}  outer={s.outer_axes} inner={s.inner_axes}")
print(f"autotuned layout: {compiled.layout.key}  "
      f"({compiled.decision.evaluated} candidates scored"
      f"{', cached' if compiled.decision.from_cache else ''})")
print(f"backend: {compiled.backend}  (auto rule: sharded if n_ports > 1, "
      f"pallas on 3-D, wavefront otherwise)")

# 2. the compiled plan: burst statistics vs the paper's baselines -----------
from repro.core.cfa import (IterSpace, Tiling, bounding_box_plan,
                            original_layout_plan)

tiling = Tiling(compiled.layout.tile)
rep = compiled.report()
print(f"\n{'CFA (compiled)':>14}: {compiled.plan.n_bursts:5d} bursts/tile, "
      f"redundancy {compiled.plan.redundancy:5.1%}, "
      f"effective bw {rep.peak_fraction_effective:6.1%} (AXI) "
      f"{compiled.report(cfa.TPU_V5E_HBM).peak_fraction_effective:6.1%} (TPU DMA)")
for name, plan in [
    ("original", original_layout_plan(IterSpace(space), prog.deps, tiling)),
    ("bounding-box", bounding_box_plan(IterSpace(space), prog.deps, tiling)),
]:
    axi = cfa.BandwidthReport.evaluate(plan, cfa.AXI_ZC706)
    tpu = cfa.BandwidthReport.evaluate(plan, cfa.TPU_V5E_HBM)
    print(f"{name:>14}: {plan.n_bursts:5d} bursts/tile, "
          f"redundancy {plan.redundancy:5.1%}, "
          f"effective bw {axi.peak_fraction_effective:6.1%} (AXI) "
          f"{tpu.peak_fraction_effective:6.1%} (TPU DMA)")

# 3. run it: the whole computation through facet storage --------------------
rng = np.random.default_rng(0)
inputs = jnp.asarray(rng.normal(size=(1, 32, 32)), jnp.float32)
facets = compiled(inputs)

V = compiled.reference(inputs)  # the untiled oracle
from repro.core.cfa import pack_facet
spec = compiled.pipeline.specs[0]
err = float(jnp.abs(facets[0][1:] - pack_facet(V, spec)).max())
print(f"\ncompiled stencil == untiled oracle: max err {err:.2e}")
assert err < 1e-5

# 4. rebind backends: same layout, different executors ----------------------
# (sweep runs the plane recurrence eagerly; the Pallas kernel backend above
# and wavefront run it in compiled programs, so they agree to float
# rounding, not bitwise)
sweep = compiled.lower("sweep")(inputs)
wave = compiled.lower("wavefront")(inputs)
for k in facets:
    for other in (facets, wave):
        np.testing.assert_allclose(np.asarray(other[k]), np.asarray(sweep[k]),
                                   rtol=1e-5, atol=1e-5)
print("backends pallas == sweep == wavefront (to rounding)")
print("OK")
