"""Runtime burst telemetry (core/cfa/obs.py).

Spans from every executor, counters reconciling exactly against the plan
accounting, Chrome trace export + schema validation, the dataflow
backend's overlapped lanes, tracing off (no recorder; each phase only a
profiler annotation), and the measured-vs-modeled RuntimeReport with its
CFA3xx fixit vocabulary.
"""
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro import cfa
from repro.core.cfa import AXI_ZC706, IterSpace, Tiling, get_program
from repro.core.cfa.obs import (
    Counters,
    RuntimeReport,
    Span,
    TraceRecorder,
    now,
    phase,
    runtime_report,
    trace_enabled_by_env,
    validate_chrome_trace,
)
from repro.core.cfa.plans import original_layout_plan

SPACE, TILE = (8, 8, 8), (4, 4, 4)
N_TILES = 8  # (8/4)^3


def _inputs(space, name="jacobi2d5p", seed=0):
    w0 = get_program(name).widths[0]
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(w0, *space[1:])))


def _traced(backend, *, name="jacobi2d5p", space=SPACE, tile=TILE, **kw):
    c = cfa.compile(name, space, layout=tile, backend=backend, trace=True,
                    **kw)
    c(_inputs(space, name), dtype=jnp.float64)
    return c, c.last_trace()


# ---------------------------------------------------------------------------
# span emission per executor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["sweep", "wavefront", "dataflow"])
def test_per_tile_spans(backend):
    _, rec = _traced(backend)
    assert len(rec.find("copy_in")) == N_TILES
    assert len(rec.find("copy_out")) == N_TILES
    assert len(rec.find("halo_resolve")) == N_TILES
    # every runtime span carries its tile's wave id
    waves = sorted({s.arg("wave") for s in rec.find("copy_in")})
    assert waves == [0, 1, 2, 3]


def test_sweep_executes_per_tile():
    _, rec = _traced("sweep")
    ex = rec.find("execute_tile")
    assert len(ex) == N_TILES
    assert rec.counters["waves"] == 4
    assert all(s.track == "port0/compute" for s in ex)


def test_wavefront_executes_per_wave():
    _, rec = _traced("wavefront")
    ex = rec.find("execute_wave")
    assert len(ex) == 4  # one batched span per wave
    assert [s.arg("n_tiles") for s in ex] == [1, 3, 3, 1]
    assert sum(s.arg("n_tiles") for s in ex) == N_TILES
    assert not rec.find("execute_tile")


@pytest.mark.parametrize("backend,counter", [
    ("wavefront", "execute_compiled"),
    ("sweep", "execute_eager"),
    ("dataflow", "execute_eager"),
])
def test_execute_path_is_counted(backend, counter):
    """One ``execute_compiled`` a tile where a wave's recurrences run as
    one compiled program, one ``execute_eager`` a tile where the eager
    recurrence runs; the recorder still reconciles."""
    c, rec = _traced(backend)
    other = ({"execute_compiled", "execute_eager"} - {counter}).pop()
    assert rec.counters.get(counter) == N_TILES
    assert rec.counters.get(other) == 0
    assert rec.reconcile(c.pipeline)["ok"]


def test_sharded_attributes_ports():
    pipe = cfa.compile("jacobi2d5p", SPACE, layout=TILE,
                       backend="sharded", n_ports=2, trace=True)
    pipe(_inputs(SPACE), dtype=jnp.float64)
    rec = pipe.last_trace()
    assert rec.reconcile(pipe.pipeline)["ok"]
    # the mesh folds ports onto however many devices exist (1 on a
    # laptop CPU), so derive the expected shard set from the trace itself
    waves = rec.find("execute_wave")
    assert len(waves) == 4
    n_shards = {s.arg("n_ports") for s in waves}.pop()
    ports = {s.arg("port") for s in rec.find("copy_in")}
    assert ports == set(range(n_shards))
    assert ({s.track for s in rec.find("copy_in")}
            == {f"port{p}/fetch" for p in range(n_shards)})


def test_halo_indirections_only_when_not_redundant():
    _, rec_red = _traced("sweep")
    assert rec_red.counters["halo_indirections"] == 0
    _, rec_irr = _traced("sweep", storage="irredundant")
    assert rec_irr.counters["halo_indirections"] > 0
    assert rec_irr.counters["halo_indirections"] <= rec_irr.counters["halo_points"]


# ---------------------------------------------------------------------------
# reconciliation (the acceptance criterion: exact, not approximate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,storage", [
    ("sweep", "redundant"),
    ("wavefront", "redundant"),
    ("dataflow", "redundant"),
    ("sweep", "irredundant"),
])
def test_reconcile_exact(backend, storage):
    c, rec = _traced(backend, storage=storage)
    r = rec.reconcile(c.pipeline)
    assert r["ok"], r["mismatches"]
    # counters' total wire bytes == BurstModel.plan_bytes over all tiles
    wire = rec.counters["wire_bytes_read"] + rec.counters["wire_bytes_write"]
    assert wire == r["expected"]["plan_bytes"]
    assert r["observed"]["tiles"] == N_TILES


@pytest.mark.parametrize("backend,storage", [
    ("wavefront", "redundant"),
    ("pallas", "redundant"),
    ("sweep", "irredundant"),
])
def test_reconcile_exact_for_field_programs(backend, storage):
    """fdtd2d's three fields a point: the counters reconcile exactly, and
    read and write ``F`` times the values of jacobi2d5p, the scalar
    program with the same dependences, at the same tile."""
    prog = get_program("fdtd2d")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, prog.n_fields, *SPACE[1:])))
    c = cfa.compile("fdtd2d", SPACE, layout=TILE, backend=backend,
                    storage=storage, trace=True)
    c(x, dtype=jnp.float64)
    rec = c.last_trace()
    r = rec.reconcile(c.pipeline)
    assert r["ok"], r["mismatches"]
    _, scalar = _traced("sweep", storage=storage)
    for k in ("read_elems", "write_elems"):
        assert rec.counters[k] == prog.n_fields * scalar.counters[k], k
    assert rec.counters["facet_fields"] == prog.n_fields
    assert scalar.counters["facet_fields"] == 1


def test_facet_burst_bytes_reads_the_recorded_counters():
    """The benchmark's ``facet_burst_bytes`` reader: 4 bytes a value over
    the planned bursts of the recorded sweep, and nothing without a
    recorder or bursts."""
    import importlib.util
    from pathlib import Path
    from types import SimpleNamespace

    path = Path(__file__).resolve().parents[1] / "bench" / "metrics" / "facet_burst_bytes.py"
    spec = importlib.util.spec_from_file_location("facet_burst_bytes", path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)

    _, rec = _traced("wavefront")
    c = rec.counters
    want = 4 * (c["read_elems"] + c["write_elems"]) / (c["bursts_read"] + c["bursts_write"])
    assert metric.read(SimpleNamespace(layer={"recorder": rec})) == want > 0
    assert metric.read(SimpleNamespace(layer={"recorder": None})) is None
    assert metric.read(SimpleNamespace(layer={"recorder": TraceRecorder()})) is None


def test_reconcile_catches_skipped_tile():
    c, rec = _traced("sweep")
    # forge a recorder that "missed" one tile's commit
    rec.counters.add("tiles", -1)
    rec.counters.add("bursts_write", -1)
    r = rec.reconcile(c.pipeline)
    assert not r["ok"]
    assert "tiles" in r["mismatches"] and "bursts_write" in r["mismatches"]


def test_reconcile_catches_missing_span():
    c, rec = _traced("sweep")
    victim = rec.find("copy_out")[0]
    rec.spans.remove(victim)
    r = rec.reconcile(c.pipeline)
    assert any(m.startswith("spans:copy_out@wave") for m in r["mismatches"])


# ---------------------------------------------------------------------------
# dataflow overlap (acceptance: prefetch/compute/commit as concurrent lanes)
# ---------------------------------------------------------------------------


def test_dataflow_overlapping_lanes():
    _, rec = _traced("dataflow")
    compute = rec.find("execute_tile")
    assert len(compute) == N_TILES
    fetch = rec.find("copy_in")
    commit = rec.find("copy_out")
    # lanes are distinct tracks
    assert {s.track for s in compute} == {"port0/compute"}
    assert {s.track for s in fetch} == {"port0/fetch"}
    assert {s.track for s in commit} == {"port0/commit"}

    def inside(inner, outer):
        return (outer.t0 <= inner.t0 and
                inner.t0 + inner.dur <= outer.t0 + outer.dur)

    # while tile j is in flight, j+1's prefetch and j-1's commit land
    # inside its compute span on their own lanes — the Fig. 13 overlap.
    # The pipeline drains at wave boundaries, so the structural floor is
    # (wave length - 1) overlapped neighbors per wave: 0+2+2+0 = 4 here.
    expected = sum(len(w) - 1
                   for w in cfa.compile("jacobi2d5p", SPACE, layout=TILE,
                                        backend="dataflow")
                   .pipeline.wavefronts())
    assert expected == 4
    fetched_inside = sum(
        any(inside(f, c) for c in compute) for f in fetch)
    committed_inside = sum(
        any(inside(w, c) for c in compute) for w in commit)
    assert fetched_inside >= expected
    assert committed_inside >= expected


def test_dataflow_matches_sweep_while_traced():
    """Tracing must not perturb results: dataflow traced == sweep untraced."""
    c_df = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="dataflow",
                       trace=True)
    c_sw = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep")
    x = _inputs(SPACE)
    got = c_df(x, dtype=jnp.float64)
    want = c_sw(x, dtype=jnp.float64)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------


def test_tracing_off_allocates_nothing():
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep")
    assert not c.trace_enabled
    c(_inputs(SPACE))
    assert c.last_trace() is None
    assert c.pipeline.recorder is None


def test_per_call_trace_override():
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep")
    c(_inputs(SPACE), trace=True)
    rec1 = c.last_trace()
    assert rec1 is not None and rec1.counters["tiles"] == N_TILES
    # trace=False leaves the previous recorder in place, records nothing new
    c(_inputs(SPACE), trace=False)
    assert c.last_trace() is rec1
    assert c.pipeline.recorder is None


# ---------------------------------------------------------------------------
# compile-span folding + env knobs
# ---------------------------------------------------------------------------


def test_pass_traces_fold_before_runtime():
    _, rec = _traced("sweep")
    passes = rec.find(cat="compile")
    assert {s.track for s in passes} == {"compile"}
    names = [s.name for s in passes]
    assert "pass:resolve_program" in names and "pass:lower_backend" in names
    # compile spans sit before the runtime epoch, runtime spans after
    assert all(s.t0 < 0 or math.isclose(s.t0 + s.dur, 0.0, abs_tol=1e-9)
               for s in passes)
    assert all(s.t0 >= 0 for s in rec.find(cat="runtime"))


def test_repro_trace_env_enables(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep")
    assert c.trace_enabled
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert not trace_enabled_by_env()
    # an explicit trace= beats the env
    c2 = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep",
                     trace=False)
    assert not c2.trace_enabled


def test_repro_trace_dir_autosaves(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep",
                    trace=True)
    c(_inputs(SPACE))
    files = list(tmp_path.glob("*.json"))
    assert len(files) == 1
    assert not validate_chrome_trace(json.loads(files[0].read_text()))


# ---------------------------------------------------------------------------
# chrome export + schema validation
# ---------------------------------------------------------------------------


def test_chrome_trace_valid_and_lanes_named():
    _, rec = _traced("dataflow")
    obj = rec.to_chrome()
    assert validate_chrome_trace(obj) == []
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"compile", "port0/fetch", "port0/compute",
            "port0/commit"} <= names
    # counters travel with the trace
    assert obj["otherData"]["counters"]["tiles"] == N_TILES
    assert obj["otherData"]["backend"] == "dataflow"
    # timestamps are non-negative microseconds (compile spans shifted in)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    # round-trips through JSON text
    assert validate_chrome_trace(json.loads(json.dumps(obj))) == []


def test_validate_rejects_malformed():
    _, rec = _traced("sweep")
    good = rec.to_chrome()
    assert validate_chrome_trace({"traceEvents": []})
    bad_ph = json.loads(json.dumps(good))
    bad_ph["traceEvents"][-1]["ph"] = "Q"
    assert any("unknown ph" in p for p in validate_chrome_trace(bad_ph))
    orphan = json.loads(json.dumps(good))
    for e in orphan["traceEvents"]:
        if e["ph"] == "X":
            e["tid"] = 999
    assert any("thread_name" in p for p in validate_chrome_trace(orphan))
    no_counters = json.loads(json.dumps(good))
    del no_counters["otherData"]["counters"]
    assert any("counters" in p for p in validate_chrome_trace(no_counters))


def test_span_and_counters_validation():
    with pytest.raises(ValueError):
        Span(name="x", cat="nope", track="t", t0=0.0, dur=0.0, args=())
    with pytest.raises(ValueError):
        Span(name="x", cat="runtime", track="t", t0=0.0, dur=-1.0, args=())
    c = Counters()
    c.add("a", 2)
    c.add("a", 3)
    assert c["a"] == 5 and "a" in c and c.get("missing") == 0
    assert c.as_dict() == {"a": 5}


# ---------------------------------------------------------------------------
# measurement spans through the shared recorder
# ---------------------------------------------------------------------------


def test_measure_runs_emits_spans():
    from repro.core.cfa.calibrate import measure_runs

    rec = TraceRecorder(model=AXI_ZC706, label="measure-test")
    t = measure_runs((64, 64), 8, warmup=0, repeats=3, recorder=rec,
                     label="grid")
    assert t > 0.0
    passes = rec.find("measure_pass", cat="measure")
    assert len(passes) == 3
    assert {s.track for s in passes} == {"measure/grid"}
    summary, = rec.find("measure", cat="measure")
    assert summary.arg("median_s") == t
    assert rec.counters["measure_passes"] == 3
    assert rec.counters["measure_schedules"] == 1
    assert validate_chrome_trace(rec.to_chrome()) == []


# ---------------------------------------------------------------------------
# measured-vs-modeled attribution
# ---------------------------------------------------------------------------


def test_runtime_report_original_baseline_fixit(monkeypatch):
    """Acceptance: the burst-hostile original layout ranks >= 1 deviation
    with a fixit hint (contiguity — its runs sit below the burst knee)."""
    monkeypatch.setenv("REPRO_MEASURE_WARMUP", "0")
    monkeypatch.setenv("REPRO_MEASURE_REPEATS", "1")
    prog = get_program("jacobi2d5p")
    plan = original_layout_plan(IterSpace(SPACE), prog.deps, Tiling(TILE))
    rep = runtime_report(plan, AXI_ZC706)
    assert isinstance(rep, RuntimeReport) and rep.rows
    assert rep.worst.fixit == "contiguity"
    assert rep.worst.observed_s > 0 and rep.worst.modeled_s > 0
    assert "fixit" in rep.summary()
    d = rep.to_dict()
    assert d["rows"][0]["fixit"] == "contiguity"


def test_runtime_report_facet_rows(monkeypatch):
    monkeypatch.setenv("REPRO_MEASURE_WARMUP", "0")
    monkeypatch.setenv("REPRO_MEASURE_REPEATS", "1")
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="sweep")
    rec = TraceRecorder(model=AXI_ZC706)
    rep = c.runtime_report(recorder=rec)
    keys = [r.key for r in rep.rows]
    assert any(k.startswith("plan:") for k in keys)
    assert any(k.startswith("facet:") for k in keys)
    # rows rank worst deviation first
    devs = [abs(r.deviation) for r in rep.rows]
    assert devs == sorted(devs, reverse=True)
    # the samples were routed through the shared recorder
    assert rec.find("measure_pass", cat="measure")


# ---------------------------------------------------------------------------
# executor phases on the profiler's clock (obs.phase)
# ---------------------------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    """The names of the profiler annotations opened, in order."""
    import jax

    opened = []
    real = jax.profiler.TraceAnnotation

    def annotate(name, **kwargs):
        opened.append(name)
        return real(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotate)
    return opened


def test_phase_without_recorder_only_annotates(annotations):
    with phase(None, "copy_in", "fetch", after=lambda: pytest.fail("called"),
               tile=[0, 0, 0]):
        pass
    assert annotations == ["copy_in"]


def test_phase_records_its_span_and_accounts_after_it(annotations):
    rec = TraceRecorder()
    rec.port = 1
    closed = {}

    def after():
        closed["t"] = now() - rec.epoch
        return {"n_read_bursts": 3}

    with phase(rec, "copy_in", "fetch", after=after, tile=[0, 1, 0]):
        pass
    (span,) = rec.spans
    assert annotations == ["copy_in"]  # the bare name, no per-tile args
    assert (span.name, span.cat, span.track) == ("copy_in", "runtime", "port1/fetch")
    assert dict(span.args) == {"tile": [0, 1, 0], "n_read_bursts": 3}
    assert span.t0 + span.dur <= closed["t"]


def test_untraced_sweep_annotates_every_phase(annotations):
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend="wavefront")
    c(_inputs(SPACE))
    phases = [n for n in annotations if n in (
        "load_inputs", "copy_in", "halo_resolve", "execute_wave", "copy_out",
        "execute_tile")]
    assert phases[0] == "load_inputs"
    assert {n: phases.count(n) for n in set(phases)} == {
        "load_inputs": 1, "copy_in": N_TILES, "halo_resolve": N_TILES,
        "execute_wave": 4, "copy_out": N_TILES}
    # copy_in opens before its halo_resolve
    assert all(phases[i - 1] == "copy_in"
               for i, n in enumerate(phases) if n == "halo_resolve")


@pytest.mark.parametrize("backend", ["sweep", "wavefront", "dataflow", "sharded"])
def test_every_executor_loads_inputs_once(backend):
    kw = {"n_ports": 2} if backend == "sharded" else {}
    _, rec = _traced(backend, **kw)
    (load,) = rec.find("load_inputs")
    assert load.track == "port0/commit"
    assert load.t0 <= min(s.t0 for s in rec.find("copy_in"))


@pytest.mark.parametrize("backend", ["sweep", "wavefront", "pallas"])
def test_untraced_facets_match_traced_bit_for_bit(backend):
    """No profiler and no recorder: the same facets, bit for bit, as a run
    with the recorder on."""
    c = cfa.compile("jacobi2d5p", SPACE, layout=TILE, backend=backend)
    x = _inputs(SPACE)
    off = c(x, dtype=jnp.float32)
    assert c.pipeline.recorder is None and c.last_trace() is None
    on = c(x, dtype=jnp.float32, trace=True)
    assert c.last_trace().counters["tiles"] == N_TILES
    assert set(on) == set(off)
    for k in off:
        np.testing.assert_array_equal(np.asarray(on[k]), np.asarray(off[k]))
