"""Open-loop load generator: Linear Road position reports, written as JSON-lines
segments into a directory that a ``FileStore`` reads as an event log.

It runs in a process of its own and imports nothing but the standard
library and NumPy (never JAX), so the process that holds the chip keeps it.
The parent starts it, then talks to it over stdin:

    go <t0>     start the schedule at monotonic time t0 (seconds)
    stop        publish nothing more, print a summary line, exit

The stream is Linear Road's position reports (Arasu et al., VLDB 2004) at
its peak load: ``expressways`` expressways, each ``2 x segments`` one-mile
segments (both directions) driven by ``vehicles_per_expressway`` vehicles,
every vehicle reporting once every ``report_period_s`` seconds of event
time.  A vehicle has a fixed phase in the period, expressway, direction,
integer speed in [0, ``speed_max_mph``] and starting position, all drawn
from ``SeedSequence([seed, 2**32])``; it drives at that speed and wraps at
the expressway's end.  A report is ``[event_time, "seg<id>", speed]`` with
``id = (expressway * 2 + direction) * segments + segment``: the program's
log holds (time, key, value), so the report's other fields are not carried.

Event ``i`` is vehicle ``order[i % V]``'s report in period ``i // V``
(``order`` sorts the vehicles by phase), so events are in event-time order
and the event-time rate is exactly ``V / report_period_s``.  Its scheduled
creation time is ``t0 + event_time * event_time_rate / offered_rate``:
event time runs ``offered_rate / event_time_rate`` times faster than wall
time.  Segment ``k`` (``segment_records`` events) depends only on ``k`` and
the vehicles, so the reference regenerates any segment alone, and it is
published atomically (write, then rename) when its last event is due,
whether or not the system keeps up.

    python bench/generator.py --config C.json --traffic T.json --seed N \
        --root DIR --prefix streams/linear-road
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np


class Stream:
    """The stream a configuration and a traffic mix define, segment by
    segment — shared by the generator and the reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        s = cfg["stream"]
        self.expressways = int(s["expressways"])
        self.directions = int(s["directions"])
        self.segments = int(s["segments"])
        self.segment_ft = float(s["segment_ft"])
        self.period = float(s["report_period_s"])
        self.vehicles = self.expressways * int(s["vehicles_per_expressway"])
        self.event_time_rate = self.vehicles / self.period
        self.keys = self.expressways * self.directions * self.segments
        self.key_prefix = s["key_prefix"]
        self.offered_rate = float(traffic["offered_rate"])
        self.segment_records = int(traffic["segment_records"])
        self.seed = int(seed) % (1 << 64)
        rng = np.random.default_rng(np.random.SeedSequence([self.seed,
                                                             1 << 32]))
        V = self.vehicles
        phase = rng.random(V) * self.period
        self.order = np.argsort(phase, kind="stable")
        self.phase = phase[self.order]
        self.lane = (rng.integers(0, self.expressways, V) * self.directions
                     + rng.integers(0, self.directions, V))[self.order]
        self.speed = rng.integers(0, int(s["speed_max_mph"]) + 1,
                                  V)[self.order]
        self.length_ft = self.segments * self.segment_ft
        self.pos0 = (rng.random(V) * self.length_ft)[self.order]

    @property
    def speedup(self) -> float:
        """Event-time seconds per wall second."""
        return self.offered_rate / self.event_time_rate

    def segment(self, k: int):
        """Segment ``k``: ``(event_time, segment key id, speed)`` arrays."""
        n = self.segment_records
        cycle, r = np.divmod(np.arange(k * n, (k + 1) * n), self.vehicles)
        t = cycle * self.period + self.phase[r]
        ft = self.speed[r] * t * (5280.0 / 3600.0)
        way = self.lane[r]
        sign = np.where(way % self.directions == 0, 1.0, -1.0)
        pos = np.mod(self.pos0[r] + sign * ft, self.length_ft)
        seg = np.minimum((pos // self.segment_ft).astype(np.int64),
                         self.segments - 1)
        return t, way * self.segments + seg, self.speed[r].astype(np.float64)

    def due(self, k: int) -> float:
        """Seconds after t0 at which segment ``k``'s last event is created."""
        n = self.segment_records
        return ((k + 1) * n) / self.offered_rate

    def encode(self, k: int) -> bytes:
        t, veh, val = self.segment(k)
        p = self.key_prefix
        return ("\n".join(f'[{a!r},"{p}{b}",{c!r}]' for a, b, c in
                          zip(t.tolist(), veh.tolist(), val.tolist()))
                + "\n").encode()


def segment_key(prefix: str, k: int, n: int) -> str:
    """The log's segment key: zero-padded index, record count in the key."""
    return f"{prefix.rstrip('/')}/segment-{k:06d}-n{n}"


def publish(root: str, key: str, blob: bytes) -> None:
    """Write then rename, so a reader never sees half a segment."""
    path = os.path.join(root, key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def run(stream: Stream, root: str, prefix: str, t0: float,
        stop: threading.Event) -> dict:
    """Publish segments on schedule until ``stop`` is set."""
    late: list[float] = []
    k = 0
    while not stop.is_set():
        blob = stream.encode(k)
        due = t0 + stream.due(k)
        wait = due - time.monotonic()
        if wait > 0 and stop.wait(wait):
            break
        publish(root, segment_key(prefix, k, stream.segment_records), blob)
        late.append(time.monotonic() - due)
        k += 1
    lat = np.asarray(late) if late else np.zeros(1)
    return {"segments": k, "events": k * stream.segment_records,
            "late_p50_s": float(np.median(lat)),
            "late_p99_s": float(np.quantile(lat, 0.99)),
            "late_max_s": float(lat.max()),
            "late_over_100ms": int((lat > 0.1).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", required=True)
    ap.add_argument("--prefix", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    stream = Stream(cfg, traffic, args.seed)

    stop = threading.Event()
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "go":
        return 1                       # parent gone or never ready

    def watch_stdin() -> None:
        sys.stdin.readline()           # "stop", or EOF when the parent dies
        stop.set()

    threading.Thread(target=watch_stdin, daemon=True).start()
    summary = run(stream, args.root, args.prefix, float(line[1]), stop)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
