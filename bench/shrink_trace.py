"""Cut a profiler trace down to what the trace readers read, for a test
fixture.

    python3 bench/shrink_trace.py <in.xplane.pb> <out.xplane.pb>

Keeps the host plane's ``bench:`` and ``repro:`` spans (the window mark
among them) with their threads, and each TPU plane's ``XLA Ops`` line
with op names cut to their HLO instruction names (``trace.short``); drops
every other plane, line, event and stat.  ``trace.reduce_planes`` and
``gaps.named_gaps`` read the same numbers from the cut file as from the
whole one.  Needs TensorFlow's ``xplane_pb2`` (the trace's protobuf
schema), which the benchmark itself does not.
"""

from __future__ import annotations

import sys

HOST_PLANE = "/host:CPU"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
KEEP_PREFIXES = ("bench:", "repro:")


def shrink(data: bytes) -> bytes:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(data)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        host = plane.name == HOST_PLANE
        if not host and not plane.name.startswith(DEVICE_PLANE):
            continue
        kept = out.planes.add()
        kept.id, kept.name = plane.id, plane.name
        used = set()
        for line in plane.lines:
            if not host and line.name != OPS_LINE:
                continue
            events = [ev for ev in line.events
                      if not host or plane.event_metadata[ev.metadata_id]
                      .name.startswith(KEEP_PREFIXES)]
            if not events:
                continue
            new = kept.lines.add()
            new.id, new.display_id = line.id, line.display_id
            new.name, new.display_name = line.name, line.display_name
            new.timestamp_ns, new.duration_ps = line.timestamp_ns, \
                line.duration_ps
            for ev in events:
                e = new.events.add()
                e.metadata_id, e.offset_ps, e.duration_ps = \
                    ev.metadata_id, ev.offset_ps, ev.duration_ps
                used.add(ev.metadata_id)
        for mid in used:
            name = plane.event_metadata[mid].name
            meta = kept.event_metadata[mid]
            meta.id = mid
            meta.name = name if host else \
                name.split(" = ", 1)[0].split("(", 1)[0]
    return out.SerializeToString()


def main(argv=None) -> int:
    src, dst = (argv or sys.argv[1:])[:2]
    with open(src, "rb") as f:
        data = shrink(f.read())
    with open(dst, "wb") as f:
        f.write(data)
    return 0


if __name__ == "__main__":
    sys.exit(main())
