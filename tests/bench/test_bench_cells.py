"""Each cell's kind, run at a tiny size on the CPU through the harness's
internals (the harness's look for a chip is skipped): a sound run is
correct, and the control and every fault a stencil cell can have make
``correct`` come out false."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402

# each cell cut to a few tiles of its own tile shape or a smaller one
TINY = {
    "jacobi2d5p-medium": dict(space=[8, 20, 50], tile=[4, 10, 25]),
    "heat3d-medium": dict(space=[8, 8, 8, 8], tile=[4, 4, 4, 4]),
}
SEED = 2**33 + 12345  # seeds past 32 bits are whole numbers like any other


def tiny_spec(cell):
    spec = harness.cell_spec(harness.load_manifest(ROOT), cell)
    spec.config.update(TINY[cell])
    spec.traffic.update(input_sets=2)
    return spec


def run(spec):
    return harness.run_cell(spec, seed=SEED, seconds=0.0, trace=False,
                            t_start=time.perf_counter(), devices=jax.devices()[:1])


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    res = run(tiny_spec(cell))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 1
    assert set(res["metrics"]) == {"sweep_s", "setup_s"}
    assert res["device"]["platform"] == jax.devices()[0].platform
    (name, check), = res["checks"].items()
    assert name == "max_rel_err" and 0 <= check["value"] <= check["limit"]
    assert list(res)[-1] == "checks"


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


def _tile_fault(monkeypatch, fault):
    """Break the tile executor of either backend: ``half`` leaves every
    other tile of a wave unexecuted, ``altered`` moves one value of each
    call's first tile where it is produced (the warm-up sweep calls it
    too, so every sweep must carry the fault)."""
    import repro.kernels.stencil as kernels
    from repro.core.cfa.transform import CFAPipeline

    real_kernel, real_tile = kernels.execute_tiles, CFAPipeline.execute_tile
    calls = []

    def kernel(name, halos, tile, *, interpret=None):
        out = real_kernel(name, halos, tile, interpret=interpret)
        if fault == "half":
            return out.at[1::2].set(0.0)
        # the tile's last point lies on every one of its facets
        return out.at[(0, *(-1,) * (out.ndim - 1))].add(1.0)

    def execute_tile(self, H):
        calls.append(1)
        if fault == "half":
            return real_tile(self, H) if len(calls) % 2 else H
        return real_tile(self, H).at[(-1,) * H.ndim].add(1.0)

    monkeypatch.setattr(kernels, "execute_tiles", kernel)
    monkeypatch.setattr(CFAPipeline, "execute_tile", execute_tile)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    if fault == "unchanged":
        _unchanged(monkeypatch)
    else:
        _tile_fault(monkeypatch, fault)
    res = run(tiny_spec(cell))
    assert not res["correct"] and res["failed"] == res["attempted"] == 1
    assert res["checks"]["max_rel_err"]["value"] > res["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_feeds_the_layer_metrics(cell, tmp_path):
    """With ``--trace 1`` the window is profiled and one more sweep runs
    with the program's recorder; the CPU has no device plane to reduce, so
    this reads only what the kind hands the metrics."""
    spec = tiny_spec(cell)
    c = harness.Cell(config=spec.config, traffic=spec.traffic,
                     seed=SEED, seconds=0.0, trace=True,
                     t_start=time.perf_counter(), devices=jax.devices()[:1],
                     trace_dir=tmp_path / "trace")
    out = spec.kind.run(c)
    assert c.windows[0].xplane is not None and c.windows[0].xplane.is_file()
    assert c.windows[0].compiles == 0
    assert out["attempted"] == 2 and out["failed"] == 0
    layer = out["layer"]
    assert layer["units"] == 1 and layer["tiles_per_sweep"] > 1
    halo = harness.load_module(ROOT / "bench" / "metrics" / "halo_ms_per_tile.py")
    ctx = harness.MetricContext(trace=None, layer=layer, peaks={})
    assert halo.read(ctx) > 0


def test_reservoir_is_bounded_and_drawn_from_the_seed():
    def draw(seed):
        r = harness.Reservoir(8, seed)
        for i in range(10_000):
            r.offer(i)
        return r.items

    picks = draw(SEED)
    assert len(picks) == 8 and len(set(picks)) == 8 and picks == draw(SEED)
    assert picks != draw(SEED + 1)
    # the sample spans the stream, not only its head
    assert max(picks) >= 8


def test_many_sweeps_keep_memory_and_check_bounded(monkeypatch):
    """However many sweeps a window holds, the kind keeps and compares the
    last sweep on each input set and at most ``CHECKED_SWEEPS`` others.
    Each sweep here returns a fresh copy of its input set's answer, so a
    short window holds many of them."""
    spec = tiny_spec("jacobi2d5p-medium")
    kind, n_sets = spec.kind, spec.traffic["input_sets"]
    k = kind.CHECKED_SWEEPS
    real_compile, real_check = kind.compile_cell, kind.check
    seen = {}

    class Memo:
        def __init__(self, compiled):
            self.compiled, self.answers = compiled, {}

        def __getattr__(self, name):
            return getattr(self.compiled, name)

        def __call__(self, x, **kw):
            key = id(x)
            if key not in self.answers:
                self.answers[key] = self.compiled(x, **kw)
            return {f: jnp.copy(a) for f, a in self.answers[key].items()}

    def check(compiled, outputs, *args):
        shape = compiled.pipeline.facet_shape(0)
        seen["outputs"] = len(outputs)
        seen["live"] = sum(a.shape == shape for a in jax.live_arrays())
        return real_check(compiled, outputs, *args)

    monkeypatch.setattr(kind, "compile_cell", lambda *a: Memo(real_compile(*a)))
    monkeypatch.setattr(kind, "check", check)
    res = harness.run_cell(spec, seed=SEED, seconds=1.0, trace=False,
                           t_start=time.perf_counter(), devices=jax.devices()[:1])
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > 2 * (k + n_sets)
    assert seen["outputs"] == k + n_sets
    # the kept answers and the memo's own: nothing else of the window lives
    assert seen["live"] <= k + 2 * n_sets
