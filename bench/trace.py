"""Reduce a profiler trace of the measured window to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (or a gzipped copy)
with nothing but JAX.  On a TPU the trace has one plane per chip,
``/device:TPU:<n>``, whose lines are ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (the operations inside them) and
``Async XLA Ops`` (DMAs in flight), and a ``/host:CPU`` plane whose
``python`` line carries the benchmark's own ``TraceAnnotation`` spans and
JAX's dispatch spans.  Device and host events share one clock.

* busy      union of the intervals of ``XLA Ops`` and ``Async XLA Ops``
            events, clipped to the window, averaged over the chips;
* window    the host span of the benchmark's ``window`` annotation;
* launches  ``XLA Modules`` events that start inside the window;
* kernels   ``XLA Ops`` events of Mosaic custom calls (Pallas kernels).
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
BUSY_LINES = ("XLA Ops", "Async XLA Ops")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
WINDOW = "window"


def load(path: Path | str):
    """The ``ProfileData`` of an ``.xplane.pb`` file, gzipped or not."""
    import jax

    path = Path(path)
    if path.suffix == ".gz":
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(str(path))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi) around disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(events: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """Flatten nested host spans into segments named by the innermost
    span open in each (spans on one thread nest or are disjoint)."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name)
    t = None

    def emit(upto: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end
        if stack and upto > t:
            segs.append((t, upto, stack[-1][1]))
        t = upto

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        if t is None:
            t = s
        emit(s)
        stack.append((e, name))
    if stack:
        emit(max(end for end, _ in stack))
    return segs


def _op_head(text: str) -> str:
    """``%fusion.3 = f32[4096]{...} fusion(...)`` -> ``%fusion.3 = f32[4096]``."""
    head = text.split("{", 1)[0].split(" ", 3)
    return " ".join(head[:3]) if len(head) >= 3 else text[:80]


def _module_base(name: str) -> str:
    """``jit_gather(1234)`` -> ``jit_gather``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                     # averaged over the chips
    launches: int                     # program executions, all chips
    kernel_s: float                   # Mosaic kernel time, all chips
    kernel_calls: int
    op_s: dict[str, float]            # device time by module:op, all chips
    idle_by_host: dict[str, float]    # idle seconds by innermost host span
    n_chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(path_or_data, chips: list[int] | None = None) -> TraceSummary:
    """Reduce the trace of one window.  ``chips`` are the device ids the
    cell ran on (all TPU planes when ``None``)."""
    pd = path_or_data if hasattr(path_or_data, "planes") else load(path_or_data)
    host_events: list[tuple[float, float, str]] = []
    window = None
    devices = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and (chips is None or int(m.group(1)) in chips):
            devices[int(m.group(1))] = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                win = [ev for ev in evs if ev[2] == WINDOW]
                if win:
                    window = (win[0][0], win[0][1])
                    host_events = evs
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = window
    busy_total, launches, kernel_ns, kernel_calls = 0.0, 0, 0.0, 0
    op_ns: dict[str, float] = defaultdict(float)
    idle_ns: dict[str, float] = defaultdict(float)
    segs = innermost([ev for ev in host_events if ev[0] < hi and ev[1] > lo])
    for plane in devices.values():
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.end_ns, e.name)
                         for e in lines.get("XLA Modules", ()) if lo <= e.start_ns < hi)
        launches += len(modules)
        busy_iv = []
        for name in BUSY_LINES:
            busy_iv += [(e.start_ns, e.end_ns) for e in lines.get(name, ())]
        busy = clip(union(busy_iv), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        # device time by enclosing module and op
        mi = 0
        for e in sorted(lines.get("XLA Ops", ()), key=lambda ev: ev.start_ns):
            if not lo <= e.start_ns < hi:
                continue
            while mi < len(modules) and modules[mi][1] < e.start_ns:
                mi += 1
            mod = (_module_base(modules[mi][2])
                   if mi < len(modules) and modules[mi][0] <= e.start_ns else "?")
            op_ns[f"{mod}:{_op_head(e.name)}"] += e.duration_ns
            if KERNEL_MARK in e.name:
                kernel_ns += e.duration_ns
                kernel_calls += 1
        # idle time by what the host was doing
        si = 0
        for g0, g1 in gaps(busy, lo, hi):
            while si < len(segs) and segs[si][1] <= g0:
                si += 1
            j = si
            while j < len(segs) and segs[j][0] < g1:
                s, e, name = segs[j]
                idle_ns[name] += min(e, g1) - max(s, g0)
                j += 1
    n = len(devices)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        launches=launches, kernel_s=kernel_ns / 1e9, kernel_calls=kernel_calls,
        op_s={k: v / 1e9 for k, v in op_ns.items()},
        idle_by_host={k: v / n / 1e9 for k, v in idle_ns.items()}, n_chips=n,
    )
