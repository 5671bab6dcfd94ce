"""The ``fdtd2d-large`` cell (kind ``stencil_fields_blocked``) run at a
tiny size on the CPU through the harness's internals (the harness's look
for a chip is skipped): a sound run is correct, the bfloat16 control and
a value perturbed in the last row of tiles make ``correct`` false, the
blocked check reads what ``stencil_fields``' whole-volume check reads on
the same sweeps, and a traced run feeds every metric of the cell."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness, trace  # noqa: E402

CELL = "fdtd2d-large"
# three rows of tiles of a smaller tile shape, 18 tiles
TINY = dict(space=[12, 20, 48], tile=[4, 10, 16])
SEED = 2**35 + 2024  # seeds past 32 bits are whole numbers like any other
FIXTURE = Path(__file__).parent / "data" / "jacobi2d5p-tiny.xplane.pb.gz"


def tiny_spec():
    spec = harness.cell_spec(harness.load_manifest(ROOT), CELL)
    spec.config.update(TINY)
    spec.traffic.update(input_sets=2)
    return spec


def _sweeps(spec, dtype):
    """One sweep on each input set, as the check receives them."""
    kind, cfg = spec.kind, spec.config
    compiled = kind.compile_cell(cfg, spec.traffic)
    sets = kind.input_sets(SEED, cfg, spec.traffic)
    outs = [(j, jax.block_until_ready(compiled(x, dtype=jnp.dtype(dtype))))
            for j, x in enumerate(sets)]
    return compiled, outs, sets


def _check(kind, spec, compiled, outs, sets):
    cfg = spec.config
    return kind.check(compiled, outs, sets, cfg["space"][0], cfg["stencil"],
                      cfg["limits"]["max_rel_err"])


def test_cell_runs_what_it_names():
    spec = tiny_spec()
    assert spec.config["kind"] == "stencil_fields_blocked"
    assert spec.traffic["storage"] == "redundant" and spec.traffic["backend"] == "auto"
    compiled = spec.kind.compile_cell(spec.config, spec.traffic)
    assert compiled.backend == "pallas" and compiled.pipeline.fields == 3


def test_sound_run_is_correct():
    res = harness.run_cell(tiny_spec(), seed=SEED, seconds=0.0, trace=False,
                           t_start=time.perf_counter(), devices=jax.devices()[:1])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 1
    assert set(res["metrics"]) == {"sweep_s", "setup_s"}
    (name, check), = res["checks"].items()
    assert name == "max_rel_err" and 0 <= check["value"] <= check["limit"]


def test_control_fails_the_limit():
    """The program's own bfloat16 path, the step below float32."""
    spec = tiny_spec()
    limit = spec.config["limits"]["max_rel_err"]
    sound = control.readings(spec, [SEED], spec.config["dtype"], log=lambda m: None)
    low = control.readings(spec, [SEED], "bfloat16", log=lambda m: None)
    assert sound[SEED] <= limit < low[SEED]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_blocked_check_reads_the_whole_volume_check(dtype):
    """On the same sweeps the two checks read the same, to the bit: 0.0
    on the sound path, and the bfloat16 path's error."""
    spec = tiny_spec()
    whole = harness.load_module(ROOT / "bench" / "kinds" / "stencil_fields.py")
    compiled, outs, sets = _sweeps(spec, dtype)
    got = _check(spec.kind, spec, compiled, outs, sets)
    assert got == _check(whole, spec, compiled, outs, sets)
    assert (got == (0.0, 0)) == (dtype == "float32")


@pytest.mark.parametrize("k", [0, 1, 2])
def test_value_perturbed_in_the_last_row_fails(k):
    """One value of facet ``k``, in the last row of time tiles and of
    the last tile (its last point lies on every facet), moved by a tenth
    of the input's scale; the other input set's sweep stays sound."""
    spec = tiny_spec()
    compiled, outs, sets = _sweeps(spec, "float32")
    pipe = compiled.pipeline
    dim = pipe.specs[k].outer_axes.index(0)
    facets = dict(outs[0][1])
    assert facets[k].shape[dim] == pipe.num_tiles[0] + (k == 0)
    facets[k] = facets[k].at[(-1,) * facets[k].ndim].add(
        0.1 * float(jnp.max(jnp.abs(sets[0]))))
    worst, failed = _check(spec.kind, spec, compiled, [(0, facets), outs[1]], sets)
    assert failed == 1 and worst == pytest.approx(0.1, rel=1e-3)


def test_traced_run_feeds_every_metric(tmp_path):
    """With ``--trace 1`` one more sweep runs with the program's recorder;
    the CPU has no device plane, so the device-trace readers read the
    recorded window of a tiny ``jacobi2d5p`` cell beside this cell's own
    layer facts.  Every metric the manifest lists for the cell reads a
    number, ``fetch_table_bytes`` the plan's device bytes."""
    spec = tiny_spec()
    c = harness.Cell(config=spec.config, traffic=spec.traffic, seed=SEED,
                     seconds=0.0, trace=True, t_start=time.perf_counter(),
                     devices=jax.devices()[:1],
                     trace_dir=tmp_path / "trace")
    out = spec.kind.run(c)
    assert c.windows[0].compiles == 0
    assert out["attempted"] == 2 and out["failed"] == 0
    layer = out["layer"]
    rec = layer["recorder"]
    ctx = harness.MetricContext(trace=trace.reduce(FIXTURE, [0]), layer=layer,
                                peaks=harness.peaks("TPU v5 lite"))
    names = {m["name"] for m in spec.per_layer}
    assert "fetch_table_bytes" in names and len(names) == 11
    for name in sorted(names):
        metric = harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")
        value = metric.read(ctx)
        assert value is not None and value >= 0, name
    pipe = spec.kind.compile_cell(spec.config, spec.traffic).pipeline
    plan = pipe._build_fetch_plan()
    assert rec.counters["fetch_table_bytes"] == plan.nbytes > 0
    assert rec.counters["fetch_classes"] == len(plan.maps)
