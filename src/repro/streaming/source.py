"""Replayable event-log source — the Kafka-topic stand-in for streams.

A stream is a sequence of ``(event_time, key, value)`` records persisted as
JSON-lines *segment* objects under an object-store prefix (append-only, like
a Kafka partition's segment files).  ``StreamSource`` reads the log in key
order and chunks it into bounded micro-batches; because segments are
immutable, iteration is replayable from the start — the property worker
restarts and exactly-once-ish reprocessing rely on, same as the batch
engine's idempotent spills.

Producers call ``write_event_log`` (or ``StreamSource.from_records`` for
in-memory tests/benchmarks, which skips storage entirely).
"""

from __future__ import annotations

import io
import itertools
import json
from dataclasses import dataclass
from typing import Any, Iterable, Iterator

from ..core import tracing
from ..core.storage import ObjectStore


def write_event_log(store: ObjectStore, prefix: str,
                    events: Iterable[tuple[float, Any, float]],
                    segment_records: int = 4096) -> int:
    """Append events to the log as numbered JSON-lines segment objects.
    Returns the number of records written."""
    existing = len(store.list_objects(prefix.rstrip("/") + "/segment-"))
    buf = io.BytesIO()
    n_seg, n_rec, in_seg = existing, 0, 0

    def flush() -> None:
        nonlocal n_seg, in_seg
        if in_seg:
            # record count travels in the key (-nNNN) so readers can skip
            # or size segments without downloading them
            key = f"{prefix.rstrip('/')}/segment-{n_seg:06d}-n{in_seg}"
            store.put(key, buf.getvalue())
            n_seg += 1
            in_seg = 0
            buf.seek(0)
            buf.truncate()

    for ts, key, value in events:
        buf.write(json.dumps([ts, key, value],
                             separators=(",", ":")).encode())
        buf.write(b"\n")
        n_rec += 1
        in_seg += 1
        if in_seg >= segment_records:
            flush()
    flush()
    return n_rec


@dataclass
class MicroBatch:
    """A bounded chunk of the stream: the unit one incremental round folds."""

    index: int
    records: list  # of (event_time, key, value)

    @property
    def max_event_time(self) -> float:
        return max(r[0] for r in self.records)

    @property
    def min_event_time(self) -> float:
        return min(r[0] for r in self.records)

    def __len__(self) -> int:
        return len(self.records)


class StreamSource:
    """Chunk a persisted (or in-memory) event log into micro-batches."""

    #: the span timing each micro-batch's read (None: the reads of the
    #: log's segments are spans of their own)
    read_span: str | None = None

    def __init__(self, store: ObjectStore | None = None, prefix: str = "",
                 records: Iterable[tuple[float, Any, float]] | None = None,
                 batch_records: int = 1024) -> None:
        if (store is None) == (records is None):
            raise ValueError("pass exactly one of (store+prefix, records)")
        if batch_records < 1:
            raise ValueError("batch_records must be >= 1")
        self.store = store
        self.prefix = prefix
        self._records = list(records) if records is not None else None
        self.batch_records = batch_records

    @classmethod
    def from_records(cls, records: Iterable[tuple[float, Any, float]],
                     batch_records: int = 1024) -> "StreamSource":
        return cls(records=records, batch_records=batch_records)

    # -- reading ---------------------------------------------------------------
    def segments(self) -> list[str]:
        assert self.store is not None
        prefix = self.prefix.rstrip("/") + "/segment-"
        return sorted(m.key for m in self.store.list_objects(prefix))

    @staticmethod
    def _segment_count(key: str) -> int | None:
        """Record count embedded in the segment key (-nNNN suffix), or None
        for legacy keys that need a download to count."""
        tail = key.rsplit("-", 1)[-1]
        if tail.startswith("n") and tail[1:].isdigit():
            return int(tail[1:])
        return None

    def segments_from(self, skip: int) -> Iterator[tuple[str | None, list]]:
        """The log from record ``skip`` on as ``(segment key, records)``,
        one pair per segment (one keyless pair for an in-memory log) — the
        shared-ingest pump's tail read.  Store-backed logs drop whole
        already-consumed segments by their key-embedded record counts,
        without downloading them; a segment is decoded whole before it is
        handed on (the ``ingest.fetch`` and ``ingest.decode`` spans)."""
        if self._records is not None:
            if skip < len(self._records):
                yield None, self._records[skip:]
            return
        with tracing.span("ingest.fetch", key=self.prefix):
            segments = self.segments()
        for seg in segments:
            count = self._segment_count(seg)
            if count is not None and skip >= count:
                skip -= count
                continue
            with tracing.span("ingest.fetch", key=seg) as fetch:
                blob = self.store.get(seg)
                fetch.n = len(blob)
            with tracing.span("ingest.decode", key=seg) as decode:
                lines = [ln for ln in blob.splitlines() if ln]
                records = [(float(ts), key, float(value)) for ts, key, value
                           in map(json.loads, lines[skip:])]
                decode.n = len(records)
            skip = max(0, skip - len(lines))
            if records:
                yield seg, records

    def _events_from(self, skip: int) -> Iterator[tuple[float, Any, float]]:
        """Records in log order, skipping the first ``skip`` cheaply."""
        for _seg, records in self.segments_from(skip):
            yield from records

    def events(self) -> Iterator[tuple[float, Any, float]]:
        """Every record in log order — a fresh, replayable pass."""
        return self._events_from(0)

    def batch_sizes(self, start_record: int = 0) -> list[int]:
        """Per-batch record counts from metadata alone — key-embedded
        segment counts when available, a line count otherwise.  Lets a
        producer announce batch triggers without parsing (or, for counted
        segments, even downloading) the payloads a second time."""
        if self._records is not None:
            total = len(self._records)
        else:
            total = 0
            for seg in self.segments():
                count = self._segment_count(seg)
                if count is None:
                    count = len([ln for ln in self.store.get(seg).splitlines()
                                 if ln])
                total += count
        total = max(0, total - start_record)
        sizes = []
        while total > 0:
            sizes.append(min(total, self.batch_records))
            total -= sizes[-1]
        return sizes

    def batches(self, start_record: int = 0) -> Iterator[MicroBatch]:
        """Chunk the log from record ``start_record`` onward into
        micro-batches of ``batch_records``.

        Resume is record-addressed, not batch-addressed: a restarted
        StreamingCoordinator passes its checkpointed *record* offset, so
        chunk boundaries cannot drift when the log has grown past a
        previously-partial final batch.  Batch indices restart at 0 for each
        iteration — they identify batches within one run.  Each batch's
        read is one ``read_span`` (if the class names one), closed before
        the batch is handed on.
        """
        records = self._events_from(start_record)
        for index in itertools.count():
            with tracing.span(self.read_span, key=index) as read:
                chunk = list(itertools.islice(records, self.batch_records))
                read.n = len(chunk)
            if not chunk:
                return
            yield MicroBatch(index, chunk)
