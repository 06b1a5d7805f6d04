"""The multi-tenant job service (``repro.service``).

The platform claims under test, each asserted against ground truth:

* **Physical-once ingest** — N tenants on one source read every log
  segment exactly once (a counting store proves it), yet each tenant's
  sink is byte-identical to a standalone single-pipeline run.
* **Scale-to-zero round trip** — an idle job parks (pool at zero
  replicas), the next matching event cold-restores it (latency
  recorded), and the final bytes are still exactly-once.
* **Crash re-attach** — a fresh ``JobServer`` over the same store+meta
  resumes a checkpointed job with ``resume=True`` and finishes with
  byte parity.
* **Late registration** — a job submitted after the ingest has already
  materialized replays from cursor 0 and catches up.
* **Tenancy** — quota breaches fail only the offending job; cross-job
  sink-prefix collisions are rejected at submit.
* **Control plane** — pause/resume/cancel/status through the
  ``JobRPC`` skeleton and the metadata-only ``JobServiceClient``.
"""

from collections import Counter

import numpy as np
import pytest

from repro.core import (JobServiceClient, MemoryStore, MetadataStore,
                        QuotaExceeded)
from repro.launch.serve import JobRPC
from repro.pipeline import Pipeline, PipelineError, Windowing
from repro.service import JobServer, JobStatus, ParkPolicy
from repro.streaming import (StreamSource, StreamingCoordinator,
                             write_event_log)

W = 4


class CountingStore(MemoryStore):
    """MemoryStore that counts get() calls per key — the analogue of the
    paper's per-request S3 billing line."""

    def __init__(self):
        super().__init__()
        self.gets = Counter()

    def get(self, key):
        self.gets[key] += 1
        return super().get(key)


def _events(n=600, n_keys=5, span=120.0, seed=0):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.uniform(0, span, n))       # in-order: no late drops
    keys = rng.integers(0, n_keys, n)
    vals = rng.integers(0, 9, n).astype(float)  # ints exact in fp32
    return [(float(t), f"k{k}", float(v)) for t, k, v in zip(ts, keys, vals)]


def _program(job_id, *, agg="sum", batch_records=100):
    return (Pipeline.from_source(batch_records=batch_records).key_by()
            .window(Windowing.tumbling(25.0)).reduce(agg)
            .sink("stream-output/")
            .build(num_buckets=16, n_workers=W, batch_records=batch_records,
                   job_id=job_id))


def _standalone(events, job_id, *, agg="sum", batch_records=100):
    """Ground truth: the same program driven alone on a private store."""
    built = _program(job_id, agg=agg, batch_records=batch_records)
    store = MemoryStore()
    coord = StreamingCoordinator(store, MetadataStore(), program=built)
    coord.run_stream(
        StreamSource.from_records(events, batch_records=batch_records))
    return {m.key: store.get(m.key)
            for m in store.list_objects(f"stream-output/{job_id}/")}


def _sink_bytes(store, tenant, job_id):
    """A tenant's sink on the shared store, keyed namespace-relative so it
    compares directly against a standalone run."""
    ns = f"tenants/{tenant}/"
    return {m.key[len(ns):]: store.get(m.key)
            for m in store.list_objects(f"{ns}stream-output/{job_id}/")}


# ---------------------------------------------------------------------------
# Shared ingest: physical-once + byte parity
# ---------------------------------------------------------------------------

def test_two_tenants_one_physical_ingest_byte_identical_sinks():
    events = _events(n=600, seed=1)
    store = CountingStore()
    write_event_log(store, "gps/", events, segment_records=128)
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    server.add_tenant("bob")
    a = server.submit("alice", _program("shared-a", agg="sum"),
                      source_prefix="gps/")
    b = server.submit("bob", _program("shared-b", agg="count"),
                      source_prefix="gps/")
    states = server.run_until_complete()
    assert states == {a: JobStatus.DONE, b: JobStatus.DONE}

    # one SharedIngest, two subscribers, every segment fetched exactly once
    seg_reads = {k: c for k, c in store.gets.items()
                 if k.startswith("gps/segment-")}
    assert seg_reads, "the physical log was never read"
    assert all(c == 1 for c in seg_reads.values()), seg_reads
    ing = server.stats()["ingests"]["gps"]
    assert ing["pumped"] == len(events) and ing["subscribers"] == 2

    # each sink byte-identical to the tenant running alone
    assert _sink_bytes(store, "alice", "shared-a") == \
        _standalone(events, "shared-a", agg="sum")
    assert _sink_bytes(store, "bob", "shared-b") == \
        _standalone(events, "shared-b", agg="count")


def test_late_registering_job_replays_from_log_start():
    events = _events(n=400, seed=4)
    store = MemoryStore()
    write_event_log(store, "gps/", events, segment_records=64)
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    server.add_tenant("bob")
    server.submit("alice", _program("early-1"), source_prefix="gps/")
    server.step()                       # ingest fully materialized, alice ahead
    assert server.ingests["gps"].pumped == len(events)
    late = server.submit("bob", _program("late-1", agg="count"),
                         source_prefix="gps/")
    assert server.jobs[late].cursor == 0        # private cursor from the top
    server.run_until_complete()
    assert _sink_bytes(store, "alice", "early-1") == \
        _standalone(events, "early-1")
    assert _sink_bytes(store, "bob", "late-1") == \
        _standalone(events, "late-1", agg="count")


# ---------------------------------------------------------------------------
# Scale-to-zero lifecycle
# ---------------------------------------------------------------------------

def test_park_scales_to_zero_and_cold_restore_is_exactly_once():
    events = _events(n=400, seed=2, span=100.0)
    first, second = events[:250], events[250:]
    store = MemoryStore()
    write_event_log(store, "gps/", first, segment_records=64)
    server = JobServer(store, MetadataStore(),
                       park_policy=ParkPolicy(idle_seconds=0.0))
    server.add_tenant("alice")
    jid = server.submit("alice", _program("cold-1"), source_prefix="gps/")
    while server.step():
        pass
    job = server.jobs[jid]
    assert job.state == JobStatus.PARKED
    assert job.coord is None                    # carries freed
    assert server.pool.stats()["replicas"] == 0
    assert server.pool.stats()["scale_downs"] >= 1

    # the next matching events wake it: a timed cold restore
    write_event_log(store, "gps/", second, segment_records=64)
    states = server.run_until_complete()
    assert states[jid] == JobStatus.DONE
    rec = server.registry.record(jid)
    assert rec["parks"] >= 1 and rec["restores"] >= 1
    assert rec["cold_start_seconds"] > 0
    st = server.status(jid)
    assert st["restores"] == rec["restores"]
    assert st["cold_start_seconds"] == rec["cold_start_seconds"]

    # exactly-once across the park/unpark round trip
    assert _sink_bytes(store, "alice", "cold-1") == \
        _standalone(events, "cold-1")


def test_crashed_server_reattaches_and_finishes_exactly_once():
    events = _events(n=500, seed=3)
    store = MemoryStore()
    meta = MetadataStore()
    write_event_log(store, "gps/", events[:300], segment_records=64)
    server = JobServer(store, meta,
                       park_policy=ParkPolicy(idle_seconds=0.0))
    server.add_tenant("alice")
    server.submit("alice", _program("crash-1"), source_prefix="gps/")
    while server.step():
        pass                # folds the available tail, parks with checkpoint
    assert server.jobs["crash-1"].state == JobStatus.PARKED
    del server              # the crash: all live state gone

    write_event_log(store, "gps/", events[300:], segment_records=64)
    server2 = JobServer(store, meta)    # fresh bus + pool, same store+meta
    server2.add_tenant("alice")
    server2.submit("alice", _program("crash-1"), source_prefix="gps/",
                   resume=True)
    states = server2.run_until_complete()
    assert states["crash-1"] == JobStatus.DONE
    assert _sink_bytes(store, "alice", "crash-1") == \
        _standalone(events, "crash-1")


# ---------------------------------------------------------------------------
# Tenancy: quotas and cross-job prefix claims
# ---------------------------------------------------------------------------

def test_quota_breach_fails_only_the_offending_tenant():
    events = _events(n=300, seed=5)
    store = MemoryStore()
    write_event_log(store, "gps/", events, segment_records=64)
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    server.add_tenant("cheap", quota_bytes=64)  # too small for any state
    a = server.submit("alice", _program("q-ok"), source_prefix="gps/")
    c = server.submit("cheap", _program("q-poor"), source_prefix="gps/")
    states = server.run_until_complete()
    assert states[a] == JobStatus.DONE
    assert states[c] == JobStatus.FAILED
    assert "QuotaExceeded" in server.jobs[c].error
    assert "QuotaExceeded" in server.status(c)["error"]
    # the neighbor is untouched
    assert _sink_bytes(store, "alice", "q-ok") == _standalone(events, "q-ok")


def test_quota_counts_replaced_objects_once():
    store = MemoryStore()
    server = JobServer(store, MetadataStore())
    t = server.add_tenant("tiny", quota_bytes=10)
    view = t.store_view(store)
    view.put("x", b"12345678")          # 8 of 10 bytes
    view.put("x", b"87654321")          # replacement frees the old 8 first
    with pytest.raises(QuotaExceeded):
        view.put("y", b"123")           # 8 + 3 > 10
    assert view.used_bytes() == 8


def test_cross_job_prefix_collision_rejected_at_submit():
    store = MemoryStore()
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    write_event_log(store, "gps/", _events(n=10), segment_records=8)
    server.submit("alice", _program("dup-1"), source_prefix="gps/")
    # same job id: globally unique, even per-tenant
    with pytest.raises(ValueError, match="already registered"):
        server.submit("alice", _program("dup-1"), source_prefix="gps/")
    # a sink nesting under an existing claim: prefix-listing overlap
    nested = (Pipeline.from_source(batch_records=100).key_by()
              .window(Windowing.tumbling(25.0)).reduce("sum")
              .sink("stream-output/dup-1/")
              .build(num_buckets=16, n_workers=W, batch_records=100,
                     job_id="dup-2"))
    with pytest.raises(PipelineError, match="collides"):
        server.submit("alice", nested, source_prefix="gps/")
    # distinct tenants namespace apart: same relative sink is fine
    server.add_tenant("bob")
    server.submit("bob", _program("dup-3"), source_prefix="gps/")


# ---------------------------------------------------------------------------
# Control plane: RPC skeleton + metadata-only client
# ---------------------------------------------------------------------------

def test_lifecycle_verbs_via_rpc_and_client():
    events = _events(n=300, seed=6)
    store = MemoryStore()
    write_event_log(store, "gps/", events[:150], segment_records=64)
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    rpc = JobRPC(server)
    client = JobServiceClient(server)

    assert rpc.handle({"method": "register", "name": "rollup",
                       "program": _program("life-1")})["ok"]
    resp = rpc.handle({"method": "submit", "tenant": "alice",
                       "program": "rollup", "source_prefix": "gps/"})
    assert resp["ok"]
    jid = resp["result"]
    assert jid == "life-1"
    assert client.status(jid)["state"] == JobStatus.PENDING

    server.step()
    assert client.status(jid)["state"] == JobStatus.RUNNING
    assert rpc.handle({"method": "pause", "job_id": jid})["result"] == \
        JobStatus.PAUSED

    # paused jobs do NOT wake on arriving events — only resume() does
    write_event_log(store, "gps/", events[150:], segment_records=64)
    while server.step():
        pass
    assert client.status(jid)["state"] == JobStatus.PAUSED
    assert server.status(jid)["lag"] > 0    # live field: server-side status

    assert rpc.handle({"method": "resume", "job_id": jid})["result"] == \
        JobStatus.RUNNING
    states = server.run_until_complete()
    assert states[jid] == JobStatus.DONE
    assert server.status(jid)["windows_emitted"] > 0
    assert client.jobs() == [jid]
    assert _sink_bytes(store, "alice", "life-1") == \
        _standalone(events, "life-1")

    # RPC edge: errors answer, they don't raise
    assert not rpc.handle({"method": "nope"})["ok"]
    bad = rpc.handle({"method": "status", "job_id": "ghost"})
    assert not bad["ok"] and "KeyError" in bad["error"]


def test_cancel_abandons_without_flush_and_keeps_claims():
    events = _events(n=200, seed=7)
    store = MemoryStore()
    write_event_log(store, "gps/", events, segment_records=64)
    server = JobServer(store, MetadataStore())
    server.add_tenant("alice")
    jid = server.submit("alice", _program("gone-1"), source_prefix="gps/")
    server.step()
    server.cancel(jid)
    states = server.run_until_complete()
    assert states[jid] == JobStatus.CANCELLED
    with pytest.raises(ValueError, match="already CANCELLED"):
        server.cancel(jid)
    # the cancelled job's prefix claim survives (its objects may too)
    with pytest.raises(PipelineError, match="collides"):
        server.submit("alice", (Pipeline.from_source(batch_records=100)
                                .key_by().window(Windowing.tumbling(25.0))
                                .reduce("sum").sink("stream-output/gone-1/")
                                .build(num_buckets=16, n_workers=W,
                                       batch_records=100, job_id="gone-2")),
                      source_prefix="gps/")
