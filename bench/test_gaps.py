"""The naming of idle gaps by the program's own spans (``bench/gaps.py``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_gaps.py

Synthetic planes cover each branch of the rule: a span on the driving
thread, the ``server.lane_wait`` suffix from another thread, and the
fallback to the harness's ``bench:`` rule.  A trace with no program span
(the committed ``fleet-sliding`` trace) reads exactly as ``trace.py``
reads it.  A trace of ``lr-count.steady`` with program spans, recorded on
one TPU v5e and cut down by ``bench/shrink_trace.py``, is named below the
step.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import gaps  # noqa: E402

trace = gaps.trace


class _Ev:
    def __init__(self, name, start_ns, end_ns):
        self.name, self.start_ns = name, start_ns
        self.duration_ns = end_ns - start_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes():
    driver = _Line("driver", [
        _Ev(trace.WINDOW_MARK, 0, 1000),
        _Ev("bench:JobServer.step", 0, 900),
        _Ev("repro:server.step", 0, 900),
        _Ev("bench:SharedIngest.pump", 50, 250),
        _Ev("repro:ingest.pump", 50, 250),
        _Ev("repro:ingest.decode", 60, 100),
        _Ev("repro:server.lane_wait", 400, 600),
        _Ev("bench:StreamingCoordinator._process_prepared", 650, 800),
        _Ev("repro:coord.fold_drain", 650, 800),
        _Ev("repro:server.lane_wait", 882, 894),
    ])
    prefetch = _Line("prefetch", [_Ev("repro:topic.read", 420, 580),
                                  _Ev("repro:coord.prepare", 300, 350)])
    host = _Plane(trace.HOST_PLANE, [driver, prefetch])
    ops = [(0, 10), (260, 290), (390, 400), (610, 640), (820, 830),
           (870, 880), (896, 897), (990, 1000)]
    dev = _Plane(trace.DEVICE_PLANE + "0", [
        _Line(trace.OPS_LINE, [_Ev("%fusion", a, b) for a, b in ops])])
    return [host, dev]


def test_gaps_are_named_by_the_driving_thread():
    got = [(g.name, g.bench_name, round(g.seconds * 1e9), g.by_program)
           for g in gaps.named_gaps(_planes())]
    assert got == [
        ("ingest.pump", "SharedIngest.pump", 250, True),
        # the prefetch thread's prepare is open too, but the driver is in
        # its own step work: no suffix
        ("server.step", "JobServer.step", 100, True),
        ("server.lane_wait/topic.read", "JobServer.step", 210, True),
        ("coord.fold_drain", "StreamingCoordinator._process_prepared", 180,
         True),
        ("server.step", "JobServer.step", 40, True),
        # waiting, and nothing open on another thread: no suffix
        ("server.lane_wait", "JobServer.step", 16, True),
        # the driver's step has closed: the harness's rule names it
        ("outside JobServer.step", "outside JobServer.step", 93, False),
    ]


def test_coverage_of_the_idle_time_inside_the_step():
    got = gaps.named_gaps(_planes())
    assert gaps.step_coverage(got) == pytest.approx(1.0)
    half = [gaps.Gap("x", "JobServer.step", 1.0, True),
            gaps.Gap("JobServer.step", "JobServer.step", 3.0, False),
            gaps.Gap("y", gaps.OUTSIDE, 5.0, True)]
    assert gaps.step_coverage(half) == pytest.approx(0.25)
    assert gaps.step_coverage([]) is None


def test_gaps_match_the_harness_rule_and_busy_time():
    """The same gaps (edges, lengths, harness names) as trace.py finds."""
    planes = _planes()
    summary = trace.reduce_planes(planes)
    got = gaps.named_gaps(planes)
    assert [(g.bench_name, g.seconds) for g in got] == summary.gaps


def test_a_trace_without_program_spans_reads_as_before():
    path = BENCH / "testdata" / "fleet-sliding.xplane.pb"
    planes = list(trace.load_planes(str(path)))
    got = gaps.named_gaps(planes)
    assert [(g.name, g.seconds) for g in got] == \
        trace.reduce_planes(planes).gaps
    assert not any(g.by_program for g in got)


#: read off the recorded steady trace when it was recorded: a traced window
#: of 32 partition jobs in which the harness's rule named every long gap
#: ``JobServer.step`` (no harness span wraps the pump in this cell)
STEADY = BENCH / "testdata" / "program" / "lr-count-steady.xplane.pb"
STEADY_HAND = {"window_s": 4.09809053, "busy_s": 0.04937137, "gaps": 5338,
               "top_publish": 9, "top_decode": 1, "longest": 0.265925379}


def test_the_recorded_steady_trace_is_named_below_the_step():
    assert STEADY.stat().st_size < 1 << 20
    planes = list(trace.load_planes(str(STEADY)))
    summary = trace.reduce_planes(planes)
    assert summary.window_s == pytest.approx(STEADY_HAND["window_s"],
                                             abs=1e-6)
    assert summary.busy_s == pytest.approx(STEADY_HAND["busy_s"], abs=1e-6)
    got = gaps.named_gaps(planes)
    assert len(got) == STEADY_HAND["gaps"]
    top = sorted(got, key=lambda g: -g.seconds)[:trace.TOP]
    assert all(g.bench_name == "JobServer.step" for g in top)
    assert not any(g.name == "JobServer.step" for g in top)
    names = [g.name for g in top]
    assert names.count("ingest.publish") == STEADY_HAND["top_publish"]
    assert names.count("ingest.decode") == STEADY_HAND["top_decode"]
    assert top[0].seconds == pytest.approx(STEADY_HAND["longest"], abs=1e-9)
    assert gaps.step_coverage(got) >= 0.9
