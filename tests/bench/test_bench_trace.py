"""The reduction from a profiler trace to device metrics.

The recorded trace is the window of a tiny ``jacobi2d5p`` cell (space
(32, 32, 256), tile (16, 32, 128): 4 tiles in 3 waves, ``pallas`` backend,
one sweep) that the harness profiled on one TPU v5e; its numbers are
pinned here as read from that file.
"""
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "jacobi2d5p-tiny.xplane.pb.gz"


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert busy == [(0, 3), (5, 8), (10, 11)]
    assert trace.clip(busy, 1, 10.5) == [(1, 3), (5, 8), (10, 10.5)]
    assert trace.gaps(trace.clip(busy, 1, 12), 1, 12) == [(3, 5), (8, 10), (11, 12)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_innermost_names_the_deepest_open_span():
    events = [(0, 10, "window"), (1, 4, "sweep"), (2, 3, "gather"),
              (5, 9, "sweep"), (6, 7, "put")]
    assert trace.innermost(events) == [
        (0, 1, "window"), (1, 2, "sweep"), (2, 3, "gather"), (3, 4, "sweep"),
        (4, 5, "window"), (5, 6, "sweep"), (6, 7, "put"), (7, 9, "sweep"),
        (9, 10, "window")]


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(FIXTURE, [0])


def test_recorded_window_adds_up(summary):
    assert summary.n_chips == 1
    assert 0 < summary.busy_s < summary.window_s
    # every idle nanosecond of the window is attributed to a host span
    idle = summary.window_s - summary.busy_s
    assert math.isclose(sum(summary.idle_by_host.values()), idle, rel_tol=1e-9)
    assert 0 < summary.kernel_s < summary.busy_s
    assert summary.kernel_s <= sum(summary.op_s.values())
    bd = summary.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"] == sorted(bd["device_ops"], key=lambda kv: -kv[1])


def test_recorded_window_counts(summary):
    # the pallas backend runs one kernel per wave
    assert summary.kernel_calls == 3
    assert summary.launches == 396
    assert summary.window_s == 0.175368775
    assert summary.busy_s == 0.000627145
    assert summary.kernel_s == 1.448e-05


def test_no_window_or_device_is_an_error(tmp_path):
    class Plane:
        def __init__(self, name, lines=()):
            self.name, self.lines = name, list(lines)

    class Data:
        planes = [Plane("/host:CPU")]

    with pytest.raises(ValueError, match="window"):
        trace.reduce(Data(), [0])
