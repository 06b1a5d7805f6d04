"""Profiler trace → device busy and idle time, op and module times, and the
idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` (JAX alone).  In a TPU trace each
chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per HLO op (a Pallas kernel is one op, named after the kernel) and
its ``XLA Modules`` line one event per program run (``jit_<function>``).
The host plane ``/host:CPU`` has one line per thread, holding the
``TraceAnnotation`` spans the harness opens: ``WINDOW_MARK`` brackets the
traced window and ``bench:<Class>.<method>`` spans the program's entry
points.  All of them share one clock in the file.

Busy time is the union of the op intervals inside the window, averaged
over the chips that ran anything; idle is the window less busy time.  A
gap between busy intervals is named by the innermost harness span open on
the host at its midpoint.
"""

from __future__ import annotations

import glob
from dataclasses import dataclass, field

WINDOW_MARK = "bench:trace_window"
SPAN_PREFIX = "bench:"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: dict[str, float] = field(default_factory=dict)       # seconds
    modules: dict[str, float] = field(default_factory=dict)   # seconds
    #: program runs inside the window, each counted by the share of its
    #: duration that falls inside (a run cut by an edge counts in part)
    module_runs: dict[str, float] = field(default_factory=dict)
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        """The ``breakdown`` of a result line: the ops that took most
        device time and the longest idle gaps, at most ten of each."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short(name: str) -> str:
    """An op's HLO instruction name (``%fusion.3``), a module's function
    name (``jit_step``): the trace holds the whole HLO text and a hash."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def host_spans(planes) -> tuple[tuple[float, float] | None, list]:
    """The window mark and every harness span on the host plane."""
    mark = None
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_MARK:
                    mark = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len(SPAN_PREFIX):]))
    return mark, spans


def name_gap(mid: float, spans: list) -> str:
    """The innermost (latest-starting) harness span open at ``mid``."""
    best = None
    for a, b, name in spans:
        if a <= mid <= b and (best is None or a > best[0]):
            best = (a, name)
    return best[1] if best else "outside JobServer.step"


def reduce_planes(planes) -> TraceSummary:
    planes = list(planes)
    mark, spans = host_spans(planes)
    if mark is None:
        raise ValueError(f"no {WINDOW_MARK!r} span in the trace")
    lo, hi = mark
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    runs: dict[str, float] = {}
    per_device = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        busy = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                c = clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if c is None:
                    continue
                secs = (c[1] - c[0]) * 1e-9
                name = short(ev.name)
                if line.name == OPS_LINE:
                    busy.append(c)
                    ops[name] = ops.get(name, 0.0) + secs
                else:
                    modules[name] = modules.get(name, 0.0) + secs
                    runs[name] = runs.get(name, 0.0) + (
                        (c[1] - c[0]) / ev.duration_ns)
        if busy:
            per_device.append(union(busy))
    if not per_device:
        raise ValueError("no device op ran inside the traced window")
    busy_s = sum(b - a for u in per_device for a, b in u) * 1e-9 \
        / len(per_device)
    gaps = []
    for u in per_device[:1]:           # gaps of the first chip
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((name_gap((a + b) / 2, spans), (b - a) * 1e-9))
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                        n_devices=len(per_device), ops=ops, modules=modules,
                        module_runs=runs, gaps=gaps)


def load_planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def reduce_dir(trace_dir: str) -> TraceSummary:
    """Reduce the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {len(found)}")
    return reduce_planes(load_planes(found[0]))
