"""The program's span recorder (``repro.core.tracing``).

A two-job ``JobServer`` over a small log — one shared ingest, the
overlapped drive, a park and a cold restore — is run with the recorder
off and on.  Off, nothing is recorded and every span is one shared no-op;
on, every declared span the run reaches is recorded, the counts carried
in ``n`` add up to what the server moved, spans nest on every thread, the
park and restore spans match the registry's counts, and the sink bytes
are the same as with the recorder off.
"""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemoryStore, MetadataStore, tracing
from repro.core.events import EventBus
from repro.pipeline import Pipeline, Windowing
from repro.service import JobServer, ParkPolicy
from repro.service.ingest_share import SharedIngest
from repro.streaming import StreamSource, write_event_log

PREFIX = "gps/"
JOBS = (("trace-sum", "sum"), ("trace-count", "count"))


@pytest.fixture(autouse=True)
def _recorder_off():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


def _events(n=400, seed=3):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.uniform(0, 100.0, n))
    keys = rng.integers(0, 5, n)
    vals = rng.integers(0, 9, n).astype(float)
    return [(float(t), f"k{k}", float(v)) for t, k, v in zip(ts, keys, vals)]


def _program(job_id, agg):
    return (Pipeline.from_source(batch_records=100).key_by()
            .window(Windowing.tumbling(25.0)).reduce(agg)
            .sink("stream-output/")
            .build(num_buckets=16, n_workers=4, batch_records=100,
                   job_id=job_id))


def _serve(record: bool):
    """Both jobs drain half the log and park; the second half wakes them
    (cold restores, overlapped drive); then both finish."""
    events = _events()
    store = MemoryStore()
    write_event_log(store, PREFIX, events[:250], segment_records=64)
    server = JobServer(store, MetadataStore(),
                       park_policy=ParkPolicy(idle_seconds=0.0))
    server.add_tenant("t")
    for job_id, agg in JOBS:
        server.submit("t", _program(job_id, agg), source_prefix=PREFIX)
    if record:
        tracing.enable()
    while server.step():
        pass
    write_event_log(store, PREFIX, events[250:], segment_records=64)
    server.run_until_complete()
    drained = tracing.drain()
    tracing.disable()
    sinks = {m.key: store.get(m.key)
             for m in store.list_objects("tenants/t/stream-output/")}
    return server, store, sinks, drained


@pytest.fixture(scope="module")
def served_off():
    return _serve(record=False)


@pytest.fixture(scope="module")
def served_on():
    return _serve(record=True)


def test_off_by_default_records_nothing(served_off):
    _server, _store, sinks, drained = served_off
    assert sinks
    assert drained == tracing.Drained([], 0)
    assert not tracing.enabled()
    off = tracing.span("ingest.pump")
    assert off is tracing.span("coord.fold", n=3, key="x")
    with off as opened:
        opened.n = 5                   # ignored, no state
    assert tracing.drain().spans == []


def test_every_reached_span_is_recorded(served_on):
    _server, _store, _sinks, drained = served_on
    names = {s.name for s in drained.spans}
    # the run reaches every span of the program but perhaps a compile (the
    # programs may be compiled already in this process: see the compile
    # test)
    assert set(tracing.SPANS) - {"jax.compile"} <= names <= set(tracing.SPANS)
    assert drained.dropped == 0


def test_counts_add_up_to_what_moved(served_on):
    server, store, _sinks, drained = served_on
    by = {}
    for s in drained.spans:
        by.setdefault(s.name, []).append(s)
    pumped = server.stats()["ingests"][PREFIX.rstrip("/")]["pumped"]
    assert pumped == 400
    assert sum(s.n for s in by["ingest.decode"]) == pumped
    assert sum(s.n for s in by["ingest.publish"]) == pumped
    assert sum(s.n for s in by["ingest.pump"]) == pumped
    folded = sum(job.report.records_in for job in server.jobs.values())
    assert folded == 2 * pumped
    assert sum(s.n for s in by["coord.fold_drain"]) == folded
    assert sum(s.n for s in by["coord.prepare"]) == folded
    assert sum(s.n for s in by["topic.read"]) == folded
    assert len(by["coord.fold_drain"]) == sum(
        job.report.batches for job in server.jobs.values())
    segs = {m.key: m.size for m in store.list_objects(PREFIX)}
    gets = [s for s in by["ingest.fetch"] if s.key in segs]
    assert sum(s.n for s in gets) == sum(segs.values())
    assert len(gets) == len(by["ingest.decode"]) == len(segs)
    emitted = sum(job.report.windows_emitted
                  for job in server.jobs.values())
    assert sum(s.n for s in by["coord.finalize"]) == emitted


def _assert_nested(spans):
    """On each thread every span lies inside the span open when it opened
    (its parent) or after it closed: none partly overlaps another."""
    ids = {s.id: s for s in spans}
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for recs in by_thread.values():
        stack = []
        for s in sorted(recs, key=lambda r: (r.start, -r.end, r.id)):
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack:
                assert s.end <= stack[-1].end, (s, stack[-1])
                assert s.parent == stack[-1].id, (s, stack[-1])
            else:
                assert s.parent not in ids, s
            stack.append(s)


def test_spans_nest_on_every_thread(served_on):
    spans = [s for s in served_on[3].spans if s.name != "jax.compile"]
    assert len({s.thread for s in spans}) > 1     # prefetch threads too
    _assert_nested(spans)
    ids = {s.id: s for s in spans}
    for s in spans:
        parent = ids.get(s.parent)
        if parent is not None:
            assert parent.thread == s.thread
            assert parent.start <= s.start and s.end <= parent.end


def test_lifecycle_counters_match_the_registry(served_on):
    server, _store, _sinks, drained = served_on
    recs = [server.registry.record(jid) for jid in server.jobs]
    parks = sum(r["parks"] for r in recs)
    restores = sum(r["restores"] for r in recs)
    assert parks >= 2 and restores >= 2
    names = [s.name for s in drained.spans]
    assert names.count("server.park") == parks
    # each job's first restore is a fresh start, the rest are cold
    assert names.count("server.restore") == restores + len(server.jobs)


def test_sink_bytes_do_not_depend_on_the_recorder(served_off, served_on):
    assert served_on[2] == served_off[2]


def test_overflow_drops_the_oldest_and_counts():
    tracing.enable(capacity=3)
    for i in range(5):
        with tracing.span("coord.fold", n=i, key=f"k{i}"):
            pass
    drained = tracing.drain()
    assert [s.key for s in drained.spans] == ["k2", "k3", "k4"]
    assert [s.n for s in drained.spans] == [2, 3, 4]
    assert drained.dropped == 2
    assert tracing.drain() == tracing.Drained([], 0)


def test_undeclared_names_raise_and_none_times_nothing():
    tracing.enable()
    with pytest.raises(KeyError):
        tracing.span("coord.nothing")
    with tracing.span(None, n=3) as nothing:
        nothing.n = 4                  # ignored, no state
        with tracing.span("coord.fold", n=7):
            pass
    spans = tracing.drain().spans
    assert [(s.name, s.n, s.parent) for s in spans] == [("coord.fold", 7,
                                                         None)]


def test_one_chunker_for_logs_and_subscribers():
    """A log read directly and the same log read off a shared ingest give
    the same micro-batches; only the subscriber's reads are spans, one per
    batch plus the empty read that ends the stream, each closed before its
    batch is handed on."""
    events = _events(n=230)
    store = MemoryStore()
    write_event_log(store, PREFIX, events, segment_records=64)
    ingest = SharedIngest(EventBus(), store, PREFIX)
    ingest.pump()
    sub = ingest.subscribe("j", batch_records=50)
    tracing.enable()
    direct = [(b.index, b.records) for b in
              StreamSource(store, PREFIX, batch_records=50).batches(17)]
    assert not [s for s in tracing.drain().spans if s.name == "topic.read"]
    shared = []
    for b in sub.batches(17):
        reads = [s for s in tracing.drain().spans if s.name == "topic.read"]
        assert [(s.key, s.n) for s in reads] == [(b.index, len(b.records))]
        shared.append((b.index, b.records))
    assert [s.n for s in tracing.drain().spans] == [0]
    assert shared == direct
    assert [len(r) for _i, r in direct] == [50, 50, 50, 50, 13]
    assert [r for _i, b in direct for r in b] == events[17:]


def test_compiles_are_spans_inside_the_open_span():
    tracing.enable()
    with tracing.span("coord.fold"):
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(13.0)).block_until_ready()
    drained = tracing.drain()
    outer = [s for s in drained.spans if s.name == "coord.fold"]
    compiles = [s for s in drained.spans if s.name == "jax.compile"]
    assert len(outer) == 1 and compiles
    assert {s.key for s in compiles} >= {"trace", "lower", "compile"}
    assert all(s.parent == outer[0].id for s in compiles)
    assert all(outer[0].start <= s.start <= s.end <= outer[0].end
               for s in compiles)


def test_threads_record_at_once():
    """Many threads open nested spans under a tiny switch interval: no
    record is lost, and spans still nest."""
    n_threads, rounds = 16, 100
    tracing.enable(capacity=1 << 16)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for i in range(rounds):
            with tracing.span("coord.fold_drain", key=i):
                with tracing.span("coord.fold", n=i):
                    pass

    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    drained = tracing.drain()
    assert len(drained.spans) == 2 * n_threads * rounds
    assert sorted(s.n for s in drained.spans if s.name == "coord.fold") \
        == sorted(list(range(rounds)) * n_threads)
    assert drained.dropped == 0
    _assert_nested(drained.spans)
