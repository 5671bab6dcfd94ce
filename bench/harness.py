"""The benchmark's plumbing, driven by ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, kind or
per-layer metric lives in a file of its own, found by the name the
manifest gives it:

* ``configs[].file``             the configuration (sizes, source, limits);
* ``bench/traffic/<traffic>.json`` the traffic mix a cell runs;
* ``bench/kinds/<kind>.py``      how a kind of configuration is built, run
                                 and checked (``run(cell) -> dict``);
* ``bench/metrics/<metric>.py``  one per-layer metric (``read(ctx)``,
                                 ``None`` when there is nothing to read).

So a later change adds a cell, a configuration, a metric or a kind as new
files and manifest entries, without editing a file that is here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import random
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent
# jax.monitoring duration events of JAX's way to a program: tracing,
# lowering, a backend compile, a load from the persistent cache
PROGRAM_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/cache_retrieval")
# those that mean a program was compiled or loaded
COMPILE_EVENTS = ("backend_compile_duration", "cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the manifest and the files it names ---------------------------------------

def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have {sorted(e['name'] for e in entries)}")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path (kinds and metrics are
    files, not a package, so that adding one edits nothing)."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellSpec:
    """One cell with everything the manifest and its files say about it."""

    workload: dict
    config: dict
    traffic: dict
    kind: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    bench: Path = BENCH

    @property
    def name(self) -> str:
        return self.workload["name"]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(manifest: dict, name: str, bench: Path = BENCH) -> CellSpec:
    root = bench.parent
    wl = find(manifest["workloads"], name, "workload")
    cfg_entry = find(manifest["configs"], wl["config"], "configuration")
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(bench / "traffic" / f"{wl['traffic']}.json")
    kind = load_module(bench / "kinds" / f"{config['kind']}.py")
    return CellSpec(
        workload=wl, config=config, traffic=traffic, kind=kind,
        end_to_end=[m for m in manifest["end_to_end"] if applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if applies(m, name)],
        bench=bench,
    )


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


# -- the device -----------------------------------------------------------------

def accelerator(chips: int):
    """The devices a cell runs on, or a reason why there are none: the
    benchmark measures a TPU and never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, (f"needs a TPU; JAX found platform {devices[0].platform!r} "
                      f"({devices[0].device_kind}, {len(devices)} device(s))")
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips; JAX found {len(devices)}"
    return devices[:chips], None


# -- what a kind sees of a run --------------------------------------------------

class Reservoir:
    """A sample of at most ``k`` items drawn uniformly from a stream of
    unknown length (Algorithm R), the draws fixed by ``seed``: what a kind
    keeps of a window's answers to check once it has closed, so memory and
    check work stay bounded however many answers a window holds."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen = int(k), 0
        self.items: list = []
        self._rng = random.Random(seed)

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            r = self._rng.randrange(self.seen + 1)
            if r < self.k:
                self.items[r] = item
        self.seen += 1


@dataclasses.dataclass
class Window:
    """The measured window: ``units`` is set by the kind (sweeps, steps);
    ``xplane`` is the profile of the window when the run traces."""

    xplane: Path | None = None
    units: int = 0
    compiles: int = 0


@contextlib.contextmanager
def jax_programs():
    """Tally the programs JAX traces, lowers, compiles or loads from its
    persistent cache while the block runs: ``{event: [count, seconds]}``
    of its ``jax.monitoring`` duration events."""
    import jax

    tally: dict[str, list] = {}

    def count(event: str, duration: float, **kwargs) -> None:
        if event.startswith(PROGRAM_EVENTS):
            t = tally.setdefault(event.rsplit("/", 1)[-1], [0, 0.0])
            t[0] += 1
            t[1] += duration

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        yield tally
    finally:
        jax.monitoring.unregister_event_duration_listener(count)


@dataclasses.dataclass
class Cell:
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    trace_dir: Path
    log: Any = log
    windows: list = dataclasses.field(default_factory=list)

    def span(self, name: str):
        """A host span in the profiler's own trace (free when it is off)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """Bracket the measured window and count the programs JAX compiles
        or loads from its cache inside it (there should be none).  With
        ``--trace 1`` the profiler records the window (host tracer on,
        Python tracer off) into ``trace_dir``."""
        import jax

        win = Window()
        self.windows.append(win)
        with jax_programs() as tally:
            if not self.trace:
                yield win
            else:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
                try:
                    with self.span("window"):
                        yield win
                finally:
                    jax.profiler.stop_trace()
                found = sorted(self.trace_dir.rglob("*.xplane.pb"))
                win.xplane = found[-1] if found else None
        win.compiles = sum(tally.get(e, [0])[0] for e in COMPILE_EVENTS)
        self.log(f"[window] programs compiled or loaded from the compile "
                 f"cache inside the window: {win.compiles}")

    def memory_peak(self) -> int | None:
        peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in self.devices]
        peaks_ = [p for p in peaks_ if p is not None]
        return max(peaks_) if peaks_ else None


# -- one run --------------------------------------------------------------------

@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reads: the reduced device trace of the
    window, the kind's own layer facts (work counts, the program's
    recorder) and the device's published peaks."""

    trace: Any
    layer: dict
    peaks: dict


def run_cell(spec: CellSpec, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list, root: Path = ROOT) -> dict:
    """Run one cell once; returns the result line as a dict."""
    from bench import trace as trace_mod

    cell = Cell(config=spec.config, traffic=spec.traffic,
                seed=seed, seconds=seconds, trace=trace, t_start=t_start,
                devices=devices, trace_dir=root / ".bench_trace" / spec.name)
    out = spec.kind.run(cell)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics: dict[str, dict] = {}
    breakdown = None
    if not trace:
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        win = cell.windows[0]
        if win.xplane is None:
            raise RuntimeError("the profiler wrote no trace of the window")
        t0 = time.perf_counter()
        summary = trace_mod.reduce(win.xplane, [d.id for d in devices])
        log(f"[trace] {win.xplane.stat().st_size} bytes of trace reduced in "
            f"{time.perf_counter() - t0} s")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = MetricContext(trace=summary, layer=out["layer"],
                            peaks=peaks(dev.device_kind, spec.bench))
        for m in spec.per_layer:
            value = load_module(spec.bench / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = summary.breakdown()
    checks = out["checks"]
    result = {
        "correct": all(v <= lim for _, v, lim in checks) and out["failed"] == 0,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # JSON has no NaN or infinity: a reading that is not finite is printed
    # as the largest float, which fails any limit as it did here
    result["checks"] = {name: {"value": v if math.isfinite(v) else sys.float_info.max,
                               "limit": lim} for name, v, lim in checks}
    return result


def print_result(result: dict) -> None:
    """The result as the last line of standard output, and each number
    compared beside its limit as the last lines of standard error."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

