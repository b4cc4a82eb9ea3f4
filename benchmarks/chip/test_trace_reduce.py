"""The trace reduction, on a small trace recorded on a TPU v5e.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/test_trace_reduce.py

``testdata/tiny_v5e.xplane.pb`` is the traced window of a tiny configuration
served through the harness on one TPU v5e chip (``calibrate.py
record-trace``): decode steps of 2 layers, d 64, batches of up to 4. The
source paths in its metadata were rewritten to ``<checkout>/``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE), str(HERE / "family")]

import jax  # noqa: E402

import harness  # noqa: E402
import trace_reduce as T  # noqa: E402

TRACE = HERE / "testdata" / "tiny_v5e.xplane.pb"


def test_union_and_clip():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_self_times_take_nested_ops_out():
    # a loop of 10 ns with two 3 ns ops inside, then a lone 2 ns op
    events = [(0, 10, "loop"), (1, 3, "a"), (5, 3, "b"), (12, 2, "c")]
    assert T.self_times(events) == {"loop": 4, "a": 3, "b": 3, "c": 2}


@pytest.fixture(scope="module")
def recorded():
    profile = jax.profiler.ProfileData.from_file(str(TRACE))
    return profile, T.reduce(profile, harness.SPANS)


def _raw(profile, line_name):
    plane = profile.find_plane_with_name("/device:TPU:0")
    line = next(l for l in plane.lines if l.name == line_name)
    return [(e.start_ns, e.duration_ns, e.name) for e in line.events]


def test_busy_is_the_union_of_ops_in_the_window(recorded):
    profile, s = recorded
    lo, hi = s.window
    # brute force: mark every nanosecond covered by an operation
    covered = set()
    for start, dur, _ in _raw(profile, "XLA Ops"):
        covered.update(range(max(int(start), int(lo)), min(int(start + dur), int(hi))))
    assert s.devices == 1
    assert abs(s.busy_ns - len(covered)) <= len(_raw(profile, "XLA Ops"))
    assert 0 < s.busy_ns < s.window_ns
    assert sum(ns for _, ns in s.gaps) == pytest.approx(s.window_ns - s.busy_ns)


def test_steps_fall_inside_engine_spans(recorded):
    _, s = recorded
    steps = s.executions(harness.STEP_PROGRAM)
    engine = [sp for sp in s.spans if sp[0] == "engine"]
    assert steps and engine
    inside = sum(any(a <= m.start and m.end <= b for _, a, b in engine)
                 for m in steps)
    assert inside == len(steps)


def test_gaps_are_named_by_host_spans(recorded):
    _, s = recorded
    names = {n for n, _ in s.gaps}
    assert names <= set(harness.SPANS) | {"outside_harness_spans"}
    assert "engine" in names
    out = T.breakdown(s)
    assert len(out["device_ops"]) <= T.TOP and len(out["idle_gaps"]) <= T.TOP
    assert all(v > 0 for _, v in out["device_ops"])
