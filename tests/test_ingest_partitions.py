"""Partitioned shared ingest (``repro.service.ingest_share``).

The claims under test:

* **Determinism across widths** — an N-partition materialization yields
  the same merged record sequence as the single-partition topic (global
  ``seq`` order == physical log order), so partitioning never changes a
  subscriber's bytes.
* **Per-(subscriber, partition) cursors** — a subscriber's scalar
  cursor dissects into per-partition replay cursors that sum to it,
  grow monotonically, and are stable across crash/re-materialization —
  exactly-once per partition.
* **Edge cases from the issue** — late subscriber replaying from 0
  across partitions, partition-skewed traffic (every record one key),
  and crash re-attach with per-partition cursors mid-segment.
* **Partition subsets** — parallel subscribers can split one source by
  partition and together see every record exactly once.
* **Batched materialization** — the pump appends each segment as one
  batch per partition, yet leaves exactly the layout the per-record rule
  gives (FNV-1a route, dense per-partition offsets, ``seq``), routes each
  distinct key once through a bounded memo, and makes no envelope, id or
  clock read per record.
"""

import time
import uuid
from collections import Counter

import numpy as np
import pytest

from repro.core import EventBus, MemoryStore, MetadataStore
from repro.core.events import NO_HEADERS, CloudEvent, Topic
from repro.pipeline import Pipeline, Windowing
from repro.service import JobServer, JobStatus, ParkPolicy, SharedIngest
from repro.service.ingest_share import ROUTE_MEMO_MAX
from repro.streaming import (StreamSource, StreamingCoordinator,
                             write_event_log)

W = 4


def _events(n=400, n_keys=6, span=100.0, seed=0, t0=0.0):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.uniform(t0, t0 + span, n))
    keys = rng.integers(0, n_keys, n)
    vals = rng.integers(0, 9, n).astype(float)
    return [(float(t), f"k{k}", float(v)) for t, k, v in zip(ts, keys, vals)]


def _program(job_id, *, agg="sum", batch_records=100):
    return (Pipeline.from_source(batch_records=batch_records).key_by()
            .window(Windowing.tumbling(25.0)).reduce(agg)
            .sink("stream-output/")
            .build(num_buckets=16, n_workers=W, batch_records=batch_records,
                   job_id=job_id))


def _standalone(events, job_id, *, agg="sum", batch_records=100):
    built = _program(job_id, agg=agg, batch_records=batch_records)
    store = MemoryStore()
    coord = StreamingCoordinator(store, MetadataStore(), program=built)
    coord.run_stream(
        StreamSource.from_records(events, batch_records=batch_records))
    return {m.key: store.get(m.key)
            for m in store.list_objects(f"stream-output/{job_id}/")}


def _sink_bytes(store, tenant, job_id):
    ns = f"tenants/{tenant}/"
    return {m.key[len(ns):]: store.get(m.key)
            for m in store.list_objects(f"{ns}stream-output/{job_id}/")}


def _ingest(events, n_partitions, *, prefix="part/", seg=64):
    store = MemoryStore()
    write_event_log(store, prefix, events, segment_records=seg)
    ing = SharedIngest(EventBus(), store, prefix, n_partitions=n_partitions)
    ing.pump()
    return ing


# ---------------------------------------------------------------------------
# Merged-view determinism + cursor dissection
# ---------------------------------------------------------------------------

def test_partitioned_merge_equals_single_partition_order():
    events = _events(n=300, seed=1)
    one = _ingest(events, 1)
    four = _ingest(events, 4)
    assert one.end_offset() == four.end_offset() == len(events)
    assert list(four.records_from(0)) == list(one.records_from(0)) == events
    # records actually spread over multiple partitions
    widths = [four.bus.end_offset(four.topic, p) for p in range(4)]
    assert sum(widths) == len(events) and sum(1 for w in widths if w) > 1
    # offsets address the merged view at any position
    for off in (0, 1, 99, 250, len(events)):
        assert list(four.records_from(off)) == events[off:]


def test_partition_cursors_dissect_scalar_cursor_exactly():
    events = _events(n=257, seed=2)
    ing = _ingest(events, 3)
    prev = {p: 0 for p in range(3)}
    for cursor in (0, 1, 64, 200, 257):
        cur = ing.partition_cursors(cursor)
        assert sum(cur.values()) == cursor
        assert all(cur[p] >= prev[p] for p in cur)     # monotone
        prev = cur
    # replaying each partition from its cursor covers exactly the
    # merged-view tail: together the partitions hold each record once
    cursor = 100
    cur = ing.partition_cursors(cursor)
    tail = []
    for p in range(3):
        for rec in ing.bus.fetch(ing.topic, p, cur[p]):
            tail.append(rec.value[:3])
    assert sorted(tail) == sorted(tuple(e) for e in events[cursor:])


def test_subscriber_partition_subsets_split_the_source():
    events = _events(n=300, seed=3)
    ing = _ingest(events, 4)
    left = ing.subscribe("left", partitions=[0, 1])
    right = ing.subscribe("right", partitions=[2, 3])
    got_left = list(left._events_from(0))
    got_right = list(right._events_from(0))
    assert len(got_left) == left.ingest.end_offset(left.partitions)
    assert sorted(got_left + got_right) == sorted(events)
    assert left.lag(0) + right.lag(0) == len(events)
    # subset views stay in global order too
    seqs = {tuple(e): i for i, e in enumerate(events)}
    assert [seqs[tuple(e)] for e in got_left] == \
        sorted(seqs[tuple(e)] for e in got_left)
    with pytest.raises(ValueError, match="out of range"):
        ing.subscribe("bad", partitions=[7])
    with pytest.raises(ValueError, match="non-empty"):
        ing.subscribe("empty", partitions=[])


# ---------------------------------------------------------------------------
# Issue edge cases, end to end through the JobServer
# ---------------------------------------------------------------------------

def test_late_subscriber_replays_from_zero_across_partitions():
    events = _events(n=400, seed=4)
    store = MemoryStore()
    write_event_log(store, "gps/", events, segment_records=64)
    server = JobServer(store, MetadataStore(), ingest_partitions=3)
    server.add_tenant("alice")
    server.add_tenant("bob")
    server.submit("alice", _program("early-p"), source_prefix="gps/")
    server.step()                       # fully materialized, alice ahead
    assert server.ingests["gps"].n_partitions == 3
    assert server.ingests["gps"].pumped == len(events)
    late = server.submit("bob", _program("late-p", agg="count"),
                         source_prefix="gps/")
    assert server.jobs[late].cursor == 0
    server.run_until_complete()
    assert _sink_bytes(store, "alice", "early-p") == \
        _standalone(events, "early-p")
    assert _sink_bytes(store, "bob", "late-p") == \
        _standalone(events, "late-p", agg="count")


def test_partition_skewed_traffic_single_hot_key():
    """Every record carries one key → every record lands one partition;
    the merged view, cursors, and job bytes must not care."""
    events = [(float(t), "hot", float(v % 9))
              for t, v in zip(np.linspace(0, 100, 300), range(300))]
    ing = _ingest(events, 4)
    widths = [ing.bus.end_offset(ing.topic, p) for p in range(4)]
    assert sorted(widths)[-1] == len(events)        # all on the hot partition
    assert list(ing.records_from(0)) == events
    cur = ing.partition_cursors(123)
    assert sum(cur.values()) == 123 and max(cur.values()) == 123

    store = MemoryStore()
    write_event_log(store, "gps/", events, segment_records=64)
    server = JobServer(store, MetadataStore(), ingest_partitions=4)
    server.add_tenant("alice")
    jid = server.submit("alice", _program("skew-p"), source_prefix="gps/")
    states = server.run_until_complete()
    assert states[jid] == JobStatus.DONE
    assert _sink_bytes(store, "alice", "skew-p") == \
        _standalone(events, "skew-p")


def test_crash_reattach_mid_segment_keeps_partition_cursors():
    """Park at a checkpoint that falls mid-segment (290 records, 64 per
    segment), crash the server, re-materialize on a fresh bus: the
    partition layout and the checkpoint's per-partition cursor dissection
    must come back identical (stable FNV-1a routing + seq merge), and the
    resumed job must finish with standalone byte parity — exactly-once
    per partition across the crash."""
    events = _events(n=400, seed=5)
    first, second = events[:290], events[290:]
    store = MemoryStore()
    meta = MetadataStore()
    write_event_log(store, "gps/", first, segment_records=64)
    server = JobServer(store, meta, ingest_partitions=3,
                       park_policy=ParkPolicy(idle_seconds=0.0))
    server.add_tenant("alice")
    jid = server.submit("alice", _program("crashp-1"), source_prefix="gps/")
    while server.step():
        pass
    assert server.jobs[jid].state == JobStatus.PARKED
    ckpt = server.status(jid)["checkpointed_offset"]
    assert ckpt == 290
    cursors_before = server.jobs[jid].sub.partition_cursors(ckpt)
    del server                          # crash: bus + topics gone with it

    write_event_log(store, "gps/", second, segment_records=64)
    server2 = JobServer(store, meta, ingest_partitions=3)
    server2.add_tenant("alice")
    server2.submit("alice", _program("crashp-1"), source_prefix="gps/",
                   resume=True)
    server2.ingests["gps"].pump()       # re-materialize from the log
    cursors_after = server2.jobs[jid].sub.partition_cursors(ckpt)
    assert cursors_after == cursors_before
    assert sum(cursors_after.values()) == ckpt
    states = server2.run_until_complete()
    assert states[jid] == JobStatus.DONE
    assert _sink_bytes(store, "alice", "crashp-1") == \
        _standalone(events, "crashp-1")


# ---------------------------------------------------------------------------
# Batched materialization: the per-record layout, a segment at a time
# ---------------------------------------------------------------------------

def _fnv1a(key: str) -> int:
    h = 0xCBF29CE484222325
    for b in key.encode():
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def _expected_layout(events, n_partitions):
    """The topic the per-record rule builds: each record routed by FNV-1a
    of its string key, numbered by its partition's running count."""
    logs = [[] for _ in range(n_partitions)]
    for seq, (ts, key, value) in enumerate(events):
        p = _fnv1a(str(key)) % n_partitions
        logs[p].append((len(logs[p]), p, str(key), (ts, key, value, seq)))
    return logs


@pytest.mark.parametrize("n_partitions", [1, 4, 32])
def test_batched_pump_matches_per_record_layout(n_partitions):
    rng = np.random.default_rng(6)
    # string and integer keys: the route hashes ``str(key)``, the record
    # keeps the key as decoded
    events = [(float(t), int(k) if k % 3 else f"k{k}", float(v))
              for t, k, v in zip(np.sort(rng.uniform(0, 100, 700)),
                                 rng.integers(0, 40, 700),
                                 rng.integers(0, 9, 700))]
    chunks = [events[:130], events[130:131], [], events[131:500],
              events[500:]]
    store = MemoryStore()
    ing = SharedIngest(EventBus(), store, "lay/", n_partitions=n_partitions)
    seen = 0
    for chunk in chunks:                 # segments land between pumps
        write_event_log(store, "lay/", chunk, segment_records=64)
        assert ing.pump() == len(chunk)
        seen += len(chunk)
        log = events[:seen]
        expected = _expected_layout(log, n_partitions)
        for p in range(n_partitions):
            got = [(r.offset, r.partition, r.key, r.value)
                   for r in ing.bus.fetch(ing.topic, p)]
            assert got == expected[p]
        where = {seq: p for p, part in enumerate(expected)
                 for *_, (_, _, _, seq) in part}
        for parts in (None, tuple(range(0, n_partitions, 2))):
            members = set(range(n_partitions) if parts is None else parts)
            view = [seq for seq in range(seen) if where[seq] in members]
            assert ing.end_offset(parts) == len(view)
            for cursor in sorted({0, 1, len(view) // 2, len(view)}):
                assert list(ing.records_from(cursor, parts)) == \
                    [log[seq] for seq in view[cursor:]]
                want = Counter(where[seq] for seq in view[:cursor])
                assert ing.partition_cursors(cursor, parts) == \
                    {p: want[p] for p in sorted(members)}
                assert ing.lag(cursor, parts) == len(view) - cursor
    assert ing.pumped == len(events)
    assert ing.pump() == 0


def test_route_memo_hashes_each_key_once_and_stays_bounded(monkeypatch):
    calls = Counter()
    fnv = Topic.partition_for

    def counted(self, key):
        calls[key] += 1
        return fnv(self, key)

    monkeypatch.setattr(Topic, "partition_for", counted)
    events = [(float(i), f"k{i % 7}", 1.0) for i in range(500)]
    store = MemoryStore()
    write_event_log(store, "memo/", events[:200], segment_records=64)
    ing = SharedIngest(EventBus(), store, "memo/", n_partitions=4)
    ing.pump()
    write_event_log(store, "memo/", events[200:], segment_records=64)
    ing.pump()
    assert ing.pumped == 500 and ing.pumps == 2
    assert calls == Counter({f"k{i}": 1 for i in range(7)})
    assert ing.route_misses == 7                 # hit share 1 - 7/500

    # an open key space: more distinct keys than the memo holds
    wide = [(float(i), f"v{i}", 1.0) for i in range(ROUTE_MEMO_MAX + 300)]
    store = MemoryStore()
    write_event_log(store, "wide/", wide, segment_records=8192)
    ing = SharedIngest(EventBus(), store, "wide/", n_partitions=4)
    ing.pump()
    assert ing.route_misses == len(wide)
    assert 0 < len(ing._routes) <= ROUTE_MEMO_MAX
    for p in range(4):
        assert all(_fnv1a(r.key) % 4 == p
                   for r in ing.bus.fetch(ing.topic, p))
    assert ing.end_offset() == len(wide)


def test_produce_batch_dense_offsets_one_wakeup_per_call():
    bus = EventBus()
    topic = bus.create_topic("t", n_partitions=2)
    cond = topic.partitions[1].cond
    wakeups = []
    notify_all = cond.notify_all

    def counted():
        wakeups.append(len(topic.partitions[1].log))
        notify_all()

    cond.notify_all = counted
    key = next(k for k in map(str, range(100))
               if topic.partition_for(k) == 1)
    assert bus.produce_batch("t", 1, ["a", "b", "c"], [10, 11, 12], 5.0) == 0
    assert bus.produce_batch("t", 1, ["d"], [13], 6.0) == 3
    assert bus.produce("t", CloudEvent(type="x", source="s", data={}),
                       key=key).offset == 4
    assert bus.produce_batch("t", 1, ["e", "f"], [14, 15], 7.0) == 5
    assert wakeups == [3, 4, 5, 7]               # one per call, after it
    log = bus.fetch("t", 1)
    assert [r.offset for r in log] == list(range(7))
    assert {r.partition for r in log} == {1}
    assert [r.key for r in log] == ["a", "b", "c", "d", key, "e", "f"]
    assert [r.timestamp for r in log[:4]] == [5.0, 5.0, 5.0, 6.0]
    assert all(r.headers is NO_HEADERS for r in log if r.key != key)
    assert bus.produced == 7 and bus.end_offset("t", 0) == 0
    with pytest.raises(ValueError, match="one key per value"):
        bus.produce_batch("t", 1, ["x"], [], 8.0)
    assert bus.end_offset("t", 1) == 7


def test_pump_makes_no_envelope_or_clock_read_per_record(monkeypatch):
    events = _events(n=300, seed=8)                     # 5 segments of <= 64
    store = MemoryStore()
    write_event_log(store, "env/", events, segment_records=64)
    ing = SharedIngest(EventBus(), store, "env/", n_partitions=4)
    clock = []
    now = time.time

    def counted():
        clock.append(1)
        return now()

    def no_ids():
        raise AssertionError("a materialized record drew an id")

    monkeypatch.setattr(time, "time", counted)
    monkeypatch.setattr(uuid, "uuid4", no_ids)
    assert ing.pump() == len(events)
    monkeypatch.undo()
    assert len(clock) == 5                              # one per segment
    stamps = {}
    for p in range(4):
        for rec in ing.bus.fetch(ing.topic, p):
            assert type(rec.value) is tuple and len(rec.value) == 4
            assert rec.headers is NO_HEADERS
            stamps.setdefault(rec.value[3] // 64, set()).add(rec.timestamp)
    assert len(stamps) == 5 and all(len(s) == 1 for s in stamps.values())
