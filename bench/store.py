"""The substrates the benchmark hands the system under test.

``RoutedStore`` is one ``ObjectStore`` over two: keys under the log prefix go
to the generator's ``FileStore`` directory, everything the jobs write goes to
a ``MemoryStore``.  It stamps the monotonic time at which each sink window
object lands and counts the writes of each key (exactly-once shows as one).

``RecordingMeta`` is a ``MetadataStore`` that stamps each streaming
checkpoint's record offset (with ``checkpoint_interval`` 1, the end of
every micro-batch), and ``RecordingBus`` an ``EventBus`` that stamps the
trigger poll each micro-batch opens with.  Together they give each batch
its records and its span, so the records folded by any time are known to
within the fraction of one batch, counted pro rata over its span.
"""

from __future__ import annotations

import bisect
import time
from collections import defaultdict

from repro.core import EventBus, MemoryStore, MetadataStore
from repro.core.storage import FileStore, ObjectStore

#: the coordinator's per-job consumer group on its batch-trigger topic
GROUP_PREFIX = "streaming-coordinator:"


class RoutedStore(ObjectStore):
    """Log keys to a directory, every other key to memory; sink windows
    (keys holding ``/window-``) are stamped when they land."""

    def __init__(self, log_root: str, log_prefix: str) -> None:
        self.log = FileStore(log_root)
        self.mem = MemoryStore()
        self.log_prefix = log_prefix.rstrip("/") + "/"
        self.landed: dict[str, float] = {}
        self.writes: dict[str, int] = defaultdict(int)

    def _route(self, key: str) -> ObjectStore:
        return self.log if key.startswith(self.log_prefix) else self.mem

    def put(self, key: str, data: bytes) -> None:
        self._route(key).put(key, data)
        if "/window-" in key:
            self.writes[key] += 1
            self.landed.setdefault(key, time.monotonic())

    def get(self, key: str, byte_range=None) -> bytes:
        return self._route(key).get(key, byte_range)

    def head(self, key: str):
        return self._route(key).head(key)

    def delete(self, key: str) -> None:
        self._route(key).delete(key)

    def list_objects(self, prefix: str = ""):
        if prefix.startswith(self.log_prefix):
            return self.log.list_objects(prefix)
        out = self.mem.list_objects(prefix)
        if self.log_prefix.startswith(prefix):
            out = sorted(out + self.log.list_objects(prefix),
                         key=lambda m: m.key)
        return out


class RecordingMeta(MetadataStore):
    """Stamps ``(monotonic time, offset)`` of every streaming checkpoint,
    per job."""

    def __init__(self) -> None:
        super().__init__()
        self.offsets: dict[str, list[tuple[float, int]]] = defaultdict(list)

    def set(self, key: str, value, ttl=None) -> None:
        super().set(key, value, ttl)
        if key.startswith("stream/") and key.endswith("/state"):
            self.offsets[key[len("stream/"):-len("/state")]].append(
                (time.monotonic(), int(value["offset"])))

    def final_offsets(self) -> dict[str, int]:
        return {job: marks[-1][1] for job, marks in self.offsets.items()}


class RecordingBus(EventBus):
    """Stamps each job's batch-trigger polls: one opens every batch."""

    def __init__(self) -> None:
        super().__init__()
        self.polls: dict[str, list[float]] = defaultdict(list)

    def poll(self, group: str, topic: str, timeout: float = 1.0,
             max_records: int = 64):
        if group.startswith(GROUP_PREFIX):
            self.polls[group[len(GROUP_PREFIX):]].append(time.monotonic())
        return super().poll(group, topic, timeout, max_records)


def batches(meta: RecordingMeta, bus: RecordingBus
            ) -> list[tuple[float, float, int]]:
    """``(start, end, records)`` of every batch any job folded: a
    checkpoint that advanced the offset ends a batch, which began at the
    job's last poll before it."""
    out = []
    for job, marks in meta.offsets.items():
        polls = bus.polls.get(job, [])
        prev = 0
        for t_end, off in marks:
            if off > prev:
                i = bisect.bisect_right(polls, t_end) - 1
                start = polls[i] if i >= 0 else t_end
                out.append((start, t_end, off - prev))
                prev = off
    return out


def folded_between(spans: list[tuple[float, float, int]], lo: float,
                   hi: float) -> float:
    """Records folded in ``[lo, hi]``: each batch counted by the share of
    its span inside the interval."""
    total = 0.0
    for a, b, n in spans:
        if b <= a:
            total += n if lo <= b <= hi else 0
        else:
            total += n * max(0.0, min(b, hi) - max(a, lo)) / (b - a)
    return total
