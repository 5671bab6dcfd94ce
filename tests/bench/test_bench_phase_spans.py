"""The program's executor phases on the profiler's clock, and the per-layer
metrics that read them.

A tiny ``jacobi2d5p`` cell runs through the kind with ``--trace 1`` on the
CPU on the ``wavefront`` and ``pallas`` (interpreted) backends: its window
is profiled (one sweep) and one more sweep runs with the program's
recorder.  The CPU trace has no device plane to reduce, so the host line
is read directly.
"""
import sys
import time
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

SEED = 2**33 + 54321
TINY = dict(space=[8, 20, 50], tile=[4, 10, 25])  # 8 tiles in 4 waves
PHASES = ("load_inputs", "copy_in", "halo_resolve", "execute_wave", "copy_out")
SPAN_METRICS = ("commit_ms_per_tile", "execute_ms_per_tile", "fetch_ms_per_tile")
FIXTURE = Path(__file__).parent / "data" / "jacobi2d5p-tiny.xplane.pb.gz"
# the same tiny window (space (32, 32, 256), tile (16, 32, 128): 4 tiles in
# 3 waves, pallas, one sweep) profiled on one TPU v5e with the phase spans
PHASED = Path(__file__).parent / "data" / "jacobi2d5p-tiny-phases.xplane.pb.gz"


def metric(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def window_events(xplane):
    """The host events of the profiled window's thread that lie in it."""
    for plane in trace.load(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            win = [ev for ev in evs if ev[2] == trace.WINDOW]
            if win:
                lo, hi = win[0][:2]
                return [ev for ev in evs if lo <= ev[0] and ev[1] <= hi]
    raise AssertionError("no host line holds the window")


@pytest.fixture(scope="module", params=["wavefront", "pallas"])
def traced(request, tmp_path_factory):
    spec = harness.cell_spec(harness.load_manifest(ROOT), "jacobi2d5p-medium")
    spec.config.update(TINY)
    spec.traffic.update(input_sets=2, backend=request.param)
    cell = harness.Cell(config=spec.config, traffic=spec.traffic, seed=SEED,
                        seconds=0.0, trace=True, t_start=time.perf_counter(),
                        devices=jax.devices()[:1],
                        trace_dir=tmp_path_factory.mktemp("trace"))
    out = spec.kind.run(cell)
    assert out["failed"] == 0 and cell.windows[0].units == 1
    return SimpleNamespace(out=out, events=window_events(cell.windows[0].xplane))


def test_window_holds_each_phase_once_per_unit(traced):
    """One sweep in the window: ``load_inputs`` once, ``copy_in``,
    ``halo_resolve`` and ``copy_out`` once a tile, ``execute_wave`` once a
    wave."""
    names = Counter(name for _, _, name in traced.events)
    assert names["sweep"] == 1
    assert {p: names[p] for p in PHASES} == {
        "load_inputs": 1, "copy_in": 8, "halo_resolve": 8,
        "execute_wave": 4, "copy_out": 8}
    assert names["execute_tile"] == 0


def test_phases_nest(traced):
    """``halo_resolve`` lies in a ``copy_in``, and every phase in the
    sweep; the phases themselves never overlap one another."""
    evs = traced.events

    def inside(ev, outer):
        return any(s <= ev[0] and ev[1] <= e for s, e, n in evs if n == outer)

    for ev in evs:
        if ev[2] == "halo_resolve":
            assert inside(ev, "copy_in")
        if ev[2] in PHASES:
            assert inside(ev, "sweep")
    top = sorted(ev[:2] for ev in evs if ev[2] in PHASES and ev[2] != "halo_resolve")
    assert all(a[1] <= b[0] for a, b in zip(top, top[1:]))


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_read_the_recorded_sweep(traced, name):
    ctx = harness.MetricContext(trace=None, layer=traced.out["layer"], peaks={})
    assert metric(name).read(ctx) > 0


def test_recorder_sweep_carries_the_same_phases(traced):
    rec = traced.out["layer"]["recorder"]
    names = Counter(s.name for s in rec.spans if s.cat == "runtime")
    assert names == {"load_inputs": 1, "copy_in": 8, "halo_resolve": 8,
                     "execute_wave": 4, "copy_out": 8}


# -- the readers on synthetic input -------------------------------------------

def _span(name, dur):
    return SimpleNamespace(name=name, dur=dur)


RECORDED = [_span("load_inputs", 0.5), _span("copy_in", 0.010),
            _span("halo_resolve", 0.004), _span("copy_in", 0.030),
            _span("halo_resolve", 0.006), _span("execute_wave", 0.002),
            _span("copy_out", 0.007), _span("copy_out", 0.009)]


@pytest.mark.parametrize("name,expected", [
    ("fetch_ms_per_tile", 20.0),      # (10 + 30) ms over 2 tiles
    ("commit_ms_per_tile", 8.0),      # (7 + 9) ms over 2 tiles
    ("execute_ms_per_tile", 1.0),     # 2 ms over 2 tiles
])
def test_span_metrics_exact(name, expected):
    rec = SimpleNamespace(spans=RECORDED)
    ctx = harness.MetricContext(trace=None, peaks={},
                                layer={"recorder": rec, "tiles_per_sweep": 2})
    assert metric(name).read(ctx) == pytest.approx(expected, rel=1e-12)


def test_execute_metric_reads_per_tile_spans():
    """The ``sweep`` backend times each tile's execute on its own."""
    rec = SimpleNamespace(spans=[_span("execute_tile", 0.003)] * 4)
    ctx = harness.MetricContext(trace=None, peaks={},
                                layer={"recorder": rec, "tiles_per_sweep": 4})
    assert metric("execute_ms_per_tile").read(ctx) == pytest.approx(3.0, rel=1e-12)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_are_silent_without_their_spans(name):
    layer = {"tiles_per_sweep": 2}
    assert metric(name).read(harness.MetricContext(None, dict(layer, recorder=None), {})) is None
    empty = SimpleNamespace(spans=[_span("load_inputs", 0.5)])
    assert metric(name).read(harness.MetricContext(None, dict(layer, recorder=empty), {})) is None


def test_unspanned_idle_share_exact():
    summary = SimpleNamespace(window_s=4.0, idle_by_host={
        "sweep": 0.3, "window": 0.1, "copy_in": 2.0, "DevicePut": 1.0})
    ctx = harness.MetricContext(trace=summary, layer={}, peaks={})
    assert metric("unspanned_idle_share").read(ctx) == pytest.approx(10.0, rel=1e-12)
    summary.idle_by_host = {"copy_in": 3.0}
    assert metric("unspanned_idle_share").read(ctx) == 0.0


def test_unspanned_idle_share_on_a_recorded_chip_trace():
    """The recorded tiny window predates the program's phase spans, so
    all its Python idle time falls under ``sweep``."""
    summary = trace.reduce(FIXTURE, [0])
    ctx = harness.MetricContext(trace=summary, layer={}, peaks={})
    idle = summary.idle_by_host
    share = metric("unspanned_idle_share").read(ctx)
    assert share == pytest.approx(
        100 * (idle.get("sweep", 0) + idle.get("window", 0)) / summary.window_s)
    assert 0 < share < 100 * summary.idle_share


def test_recorded_chip_window_names_its_idle_by_phase():
    """On the chip the idle gaps fall under the program's phases: none of
    the window's Python runs outside one but the loop between them."""
    summary = trace.reduce(PHASED, [0])
    assert summary.kernel_calls == 3 and summary.launches == 398
    assert set(PHASES) <= set(summary.idle_by_host)
    top3 = [name for name, _ in summary.breakdown()["idle_gaps"][:3]]
    assert "sweep" not in top3 and "copy_in" in top3
    ctx = harness.MetricContext(trace=summary, layer={}, peaks={})
    assert metric("unspanned_idle_share").read(ctx) == pytest.approx(
        0.4420095105228279, rel=1e-9)
