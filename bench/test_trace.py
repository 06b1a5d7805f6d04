"""Self-check of the trace reduction (``bench/trace.py``) on a small trace
recorded on one TPU v5e and kept in ``bench/testdata/``.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_trace.py

The reduction's numbers are checked against a second, independent
computation over the same file (a sweep over +1/-1 interval boundaries)
and against the numbers read off the trace by hand when it was recorded.
"""

from __future__ import annotations

import glob
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
TRACE = BENCH / "testdata" / "fleet-sliding.xplane.pb"
_spec = importlib.util.spec_from_file_location("bench_trace",
                                               BENCH / "trace.py")
trace = importlib.util.module_from_spec(_spec)
sys.modules["bench_trace"] = trace
_spec.loader.exec_module(trace)

#: read off the recorded trace by hand (raw event durations summed per
#: name with ProfileData): a traced window of a one-job sliding sum over
#: 100,000 keys (an 8 x 131,072 carry) in which the Pallas fold ran 11
#: times
HAND = {"n_devices": 1, "fold_module": "jit_step", "fold_runs": 11,
        "fold_module_s": 6.122823, "fold_op": "%fused_streaming_fold.1",
        "fold_op_s": 6.105532, "window_s": 14.056363}


@pytest.fixture(scope="module")
def planes():
    return list(trace.load_planes(str(TRACE)))


@pytest.fixture(scope="module")
def summary(planes):
    return trace.reduce_planes(planes)


def covered_ns(intervals, lo, hi) -> float:
    """Length covered by at least one interval, by a boundary sweep."""
    pts = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort()
    depth, last, total = 0, None, 0.0
    for x, d in pts:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


def test_busy_matches_a_boundary_sweep(planes, summary):
    mark = [(e.start_ns, e.start_ns + e.duration_ns)
            for p in planes if p.name == trace.HOST_PLANE
            for ln in p.lines for e in ln.events
            if e.name == trace.WINDOW_MARK]
    assert len(mark) == 1
    lo, hi = mark[0]
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in planes if p.name.startswith(trace.DEVICE_PLANE)
           for ln in p.lines if ln.name == trace.OPS_LINE
           for e in ln.events]
    assert summary.window_s == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    assert summary.busy_s == pytest.approx(covered_ns(ops, lo, hi) * 1e-9,
                                           rel=1e-9)
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.n_devices == HAND["n_devices"]


def test_gaps_and_busy_fill_the_window(summary):
    idle = sum(s for _n, s in summary.gaps)
    assert idle + summary.busy_s == pytest.approx(summary.window_s,
                                                  rel=1e-9)
    assert all(s > 0 for _n, s in summary.gaps)
    assert all(isinstance(n, str) and n for n, _s in summary.gaps)


def test_fold_module_and_ops(summary):
    assert summary.module_runs[HAND["fold_module"]] == HAND["fold_runs"]
    assert summary.modules[HAND["fold_module"]] == pytest.approx(
        HAND["fold_module_s"], abs=1e-5)
    assert summary.ops[HAND["fold_op"]] == pytest.approx(HAND["fold_op_s"],
                                                         abs=1e-5)
    assert summary.window_s == pytest.approx(HAND["window_s"], abs=1e-5)
    assert sum(summary.ops.values()) >= summary.busy_s * (1 - 1e-9)
    bd = summary.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [s for _n, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert np.isclose(covered_ns([(0, 2), (1, 3), (5, 8)], 1, 6), 3)


def test_no_window_mark_is_an_error():
    class Plane:
        name = trace.HOST_PLANE
        lines = ()
    with pytest.raises(ValueError):
        trace.reduce_planes([Plane()])


def test_recorded_trace_is_small():
    assert TRACE.stat().st_size < 4 << 20
    assert glob.glob(str(BENCH / "testdata" / "*.xplane.pb")) == [str(TRACE)]


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, \
            duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_runs_cut_by_the_window_count_by_their_share_inside():
    """A program run that straddles an edge of the window counts by the
    share of its duration inside it, in runs and in seconds alike."""
    host = _Plane(trace.HOST_PLANE, [_Line("t", [
        _Ev(trace.WINDOW_MARK, 1000, 1000)])])
    runs = [_Ev("jit_step", 500, 1000),       # 50% inside (left edge)
            _Ev("jit_step", 1200, 200),       # whole
            _Ev("jit_step", 1800, 800),       # 25% inside (right edge)
            _Ev("jit_step", 2500, 100)]       # outside
    dev = _Plane(trace.DEVICE_PLANE + "0", [
        _Line(trace.MODULES_LINE, runs),
        _Line(trace.OPS_LINE, [_Ev("%fusion", e.start_ns, e.duration_ns)
                               for e in runs])])
    s = trace.reduce_planes([host, dev])
    assert s.module_runs["jit_step"] == pytest.approx(0.5 + 1 + 0.25)
    assert s.modules["jit_step"] == pytest.approx((500 + 200 + 200) * 1e-9)
    assert s.busy_s == pytest.approx(700e-9)     # union of the ops
    assert s.window_s == pytest.approx(1000e-9)
