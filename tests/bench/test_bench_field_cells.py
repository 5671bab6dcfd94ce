"""The cells of field programs and of irredundant storage, run at a tiny
size on the CPU through the harness's internals (the harness's look for a
chip is skipped): a sound run is correct, and the bfloat16 control, a
sweep left unchanged, a tile left unexecuted and a perturbed value of any
one field make ``correct`` come out false."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402

# each cell cut to a few tiles of a smaller tile shape
TINY = {
    "fdtd2d-medium": dict(space=[8, 20, 48], tile=[4, 10, 24]),
    "heat3d-medium-irredundant": dict(space=[8, 8, 8, 8], tile=[4, 4, 4, 4]),
}
SEED = 2**34 + 4321  # seeds past 32 bits are whole numbers like any other


def tiny_spec(cell):
    spec = harness.cell_spec(harness.load_manifest(ROOT), cell)
    spec.config.update(TINY[cell])
    spec.traffic.update(input_sets=2)
    return spec


def run(spec):
    return harness.run_cell(spec, seed=SEED, seconds=0.0, trace=False,
                            t_start=time.perf_counter(), devices=jax.devices()[:1])


def test_cells_run_what_they_name():
    fdtd = tiny_spec("fdtd2d-medium")
    irr = tiny_spec("heat3d-medium-irredundant")
    assert fdtd.config["kind"] == "stencil_fields" and fdtd.traffic["storage"] == "redundant"
    assert irr.config["kind"] == "stencil" and irr.traffic["storage"] == "irredundant"
    compiled = fdtd.kind.compile_cell(fdtd.config, fdtd.traffic)
    assert compiled.backend == "pallas" and compiled.pipeline.fields == 3
    compiled = irr.kind.compile_cell(irr.config, irr.traffic)
    assert compiled.backend == "wavefront" and compiled.storage == "irredundant"


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    res = run(tiny_spec(cell))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 1
    assert set(res["metrics"]) == {"sweep_s", "setup_s"}
    (name, check), = res["checks"].items()
    assert name == "max_rel_err" and 0 <= check["value"] <= check["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_fails_the_limit(cell):
    """The program's own bfloat16 path, the step below float32."""
    spec = tiny_spec(cell)
    limit = spec.config["limits"]["max_rel_err"]
    sound = control.readings(spec, [SEED], spec.config["dtype"], log=lambda m: None)
    low = control.readings(spec, [SEED], "bfloat16", log=lambda m: None)
    assert sound[SEED] <= limit < low[SEED]


def _unchanged(monkeypatch):
    """The sweep returns its state unchanged: live-in loaded, nothing run."""
    from repro.core.cfa.transform import CFAPipeline

    def sweep(self, inputs, dtype=jnp.float32, **kw):
        return self.load_inputs(self.init_facets(dtype), inputs.astype(dtype))

    monkeypatch.setattr(CFAPipeline, "_sweep_wavefront", sweep)


def _tile_fault(monkeypatch, fault, field=None):
    """Break the tile executor of either backend: ``half`` leaves every
    other tile of a wave unexecuted, ``altered`` moves one value of each
    call's first tile where it is produced, of field ``field`` when the
    program has fields (the tile's last point lies on every one of its
    facets)."""
    import repro.kernels.stencil as kernels
    from repro.core.cfa.transform import CFAPipeline

    real_kernel, real_tile = kernels.execute_tiles, CFAPipeline.execute_tile
    calls = []

    def point(ndim):
        last = [-1] * ndim
        if field is not None:
            last[1] = field
        return tuple(last)

    def kernel(name, halos, tile, *, interpret=None):
        out = real_kernel(name, halos, tile, interpret=interpret)
        if fault == "half":
            return out.at[1::2].set(0.0)
        return out.at[(0, *point(out.ndim - 1))].add(1.0)

    def execute_tile(self, H):
        calls.append(1)
        if fault == "half":
            return real_tile(self, H) if len(calls) % 2 else H
        return real_tile(self, H).at[point(H.ndim)].add(1.0)

    monkeypatch.setattr(kernels, "execute_tiles", kernel)
    monkeypatch.setattr(CFAPipeline, "execute_tile", execute_tile)


FAULTS = [("heat3d-medium-irredundant", f, None) for f in ("unchanged", "half", "altered")]
FAULTS += [("fdtd2d-medium", f, None) for f in ("unchanged", "half")]
FAULTS += [("fdtd2d-medium", "altered", field) for field in range(3)]


@pytest.mark.parametrize("cell,fault,field", FAULTS)
def test_fault_is_not_correct(cell, fault, field, monkeypatch):
    if fault == "unchanged":
        _unchanged(monkeypatch)
    else:
        _tile_fault(monkeypatch, fault, field)
    res = run(tiny_spec(cell))
    assert not res["correct"] and res["failed"] == res["attempted"] == 1
    assert res["checks"]["max_rel_err"]["value"] > res["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_feeds_the_layer_metrics(cell, tmp_path):
    """With ``--trace 1`` one more sweep runs with the program's recorder;
    the CPU has no device plane to reduce, so this reads only what the
    kind hands the metrics."""
    spec = tiny_spec(cell)
    c = harness.Cell(config=spec.config, traffic=spec.traffic,
                     seed=SEED, seconds=0.0, trace=True,
                     t_start=time.perf_counter(), devices=jax.devices()[:1],
                     trace_dir=tmp_path / "trace")
    out = spec.kind.run(c)
    assert c.windows[0].compiles == 0
    assert out["attempted"] == 2 and out["failed"] == 0
    layer = out["layer"]
    ctx = harness.MetricContext(trace=None, layer=layer, peaks={})
    names = {m["name"] for m in spec.per_layer}
    for name in ("halo_ms_per_tile", "fetch_ms_per_tile", "commit_ms_per_tile",
                 "execute_ms_per_tile", "facet_burst_bytes"):
        if name in names:
            metric = harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")
            assert metric.read(ctx) > 0, name
    assert layer["recorder"].counters.get("facet_fields") == (
        3 if cell == "fdtd2d-medium" else 1)


def test_field_work_counts_values():
    """Bytes at three values a point, 11 operations a point (3 for ey, 3
    for ex, 5 for hz), and the kernel's three-field halo box plus
    interior."""
    from bench import work

    spec = tiny_spec("fdtd2d-medium")
    c = harness.Cell(config=spec.config, traffic=spec.traffic, seed=SEED,
                     seconds=0.0, trace=False, t_start=time.perf_counter(),
                     devices=jax.devices()[:1], trace_dir=ROOT / ".bench_trace" / "x")
    layer = spec.kind.run(c)["layer"]
    space, tile = TINY["fdtd2d-medium"]["space"], TINY["fdtd2d-medium"]["tile"]
    assert layer["sweep_flops"] == 11 * space[0] * space[1] * space[2]
    assert layer["sweep_bytes"] == 3 * work.sweep_bytes(space, tile, (1, 2, 2), 4)
    assert layer["kernel_tile_bytes"] == 12 * (5 * 12 * 26 + 4 * 10 * 24)
