"""Idle gaps named by the program's own spans (``repro:<name>``).

The device's idle gaps in a traced window, as ``trace.py`` finds them (on
the first chip, between the union of its op intervals), each named by what
the program was doing at the gap's midpoint: the innermost ``repro:`` span
open then on a thread that holds ``repro:server.step`` (the thread that
drives the server).  Where that span is ``server.lane_wait``, the driver
is waiting on a prefetch thread, so the innermost ``repro:`` span open
then on any other thread is appended: ``server.lane_wait/topic.read``.
Where no program span is open on the driving thread (a program without
the recorder among them), ``trace.py``'s own rule names the gap from the
harness's ``bench:`` spans, as the result line's ``idle_gaps`` does.

    python3 bench/gaps.py <file.xplane.pb>

prints the ten longest gaps and the share of the idle time inside
``JobServer.step`` that a program span names.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402

trace = run.load_file(BENCH / "trace.py", "bench_trace")

PROGRAM_PREFIX = "repro:"
DRIVER_SPAN = "server.step"
WAIT_SPAN = "server.lane_wait"
OUTSIDE = "outside JobServer.step"     # trace.name_gap's name for no span


@dataclass(frozen=True)
class Gap:
    name: str           # by the program's spans, else by trace.py's rule
    bench_name: str     # by trace.py's rule alone (the result line's name)
    seconds: float
    by_program: bool


def program_lines(planes) -> list[list[tuple[int, int, str]]]:
    """The ``repro:`` spans of each host thread, as ``(start, end, name)``
    in nanoseconds."""
    out = []
    for plane in planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                      ev.name[len(PROGRAM_PREFIX):])
                     for ev in line.events
                     if ev.name.startswith(PROGRAM_PREFIX)]
            if spans:
                out.append(spans)
    return out


def innermost(mid: float, lines) -> tuple[int, str] | None:
    """``(start, name)`` of the latest-starting span open at ``mid`` on
    any of ``lines``."""
    best = None
    for spans in lines:
        for a, b, name in spans:
            if a <= mid <= b and (best is None or a > best[0]):
                best = (a, name)
    return best


def name_gap(mid: float, driving, others) -> str | None:
    """The program's name for a gap at ``mid``; None when no program span
    is open on a driving thread."""
    found = innermost(mid, driving)
    if found is None:
        return None
    name = found[1]
    if name == WAIT_SPAN:
        other = innermost(mid, others)
        if other is not None:
            name = f"{name}/{other[1]}"
    return name


def busy_union(planes, lo: float, hi: float):
    """The union of the first chip's op intervals inside ``[lo, hi]`` (the
    chip whose gaps ``trace.reduce_planes`` names)."""
    for plane in planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        busy = [c for line in plane.lines if line.name == trace.OPS_LINE
                for ev in line.events
                if (c := trace.clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                    lo, hi)) is not None]
        if busy:
            return trace.union(busy)
    raise ValueError("no device op ran inside the traced window")


def named_gaps(planes) -> list[Gap]:
    """Every idle gap of the traced window, named both ways."""
    planes = list(planes)
    mark, bench_spans = trace.host_spans(planes)
    if mark is None:
        raise ValueError(f"no {trace.WINDOW_MARK!r} span in the trace")
    lo, hi = mark
    lines = program_lines(planes)
    driving = [ln for ln in lines if any(n == DRIVER_SPAN for *_, n in ln)]
    others = [ln for ln in lines if not any(n == DRIVER_SPAN
                                            for *_, n in ln)]
    edges = [lo] + [x for ab in busy_union(planes, lo, hi) for x in ab] \
        + [hi]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        bench_name = trace.name_gap(mid, bench_spans)
        name = name_gap(mid, driving, others)
        gaps.append(Gap(name or bench_name, bench_name, (b - a) * 1e-9,
                        name is not None))
    return gaps


def step_coverage(gaps: list[Gap]) -> float | None:
    """Share of the idle time inside ``JobServer.step`` (any gap the
    harness's rule places inside it) that a program span names."""
    inside = [g for g in gaps if g.bench_name != OUTSIDE]
    total = sum(g.seconds for g in inside)
    if total <= 0:
        return None
    return sum(g.seconds for g in inside if g.by_program) / total


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    gaps = named_gaps(trace.load_planes(path))
    for g in sorted(gaps, key=lambda g: -g.seconds)[:trace.TOP]:
        print(f"{g.seconds:.6f} s  {g.name}  (harness: {g.bench_name})")
    idle = sum(g.seconds for g in gaps)
    cov = step_coverage(gaps)
    print(f"{len(gaps)} gaps, {idle:.6f} s idle; inside JobServer.step, "
          f"named by a program span: "
          + ("n/a" if cov is None else f"{100 * cov:.2f}%"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
