"""Chip smoke test: the streaming path on one TPU, end to end, at a fleet's
real key count.

A fleet-telemetry deployment: GPS events from 100,000 vehicles land in a
JSON event log; a sliding-window sum per vehicle runs over it through the
normal entry points (``Pipeline.build().run()`` and the multi-tenant
``JobServer``), with the window carry (8 slots x 131,072 buckets x 2
channels) on the device.  Every phase is checked against a NumPy float64
reference that shares no code with ``repro``; event values are multiples
of 1/8, so every f32 sum is exact and any rounding shows as a mismatch.

    python chip_smoke.py              # phases (a) vmap, (b) pallas, (c) JobServer
    python chip_smoke.py --chips 4    # shard_map over a 4-chip mesh only

Phase (b) asserts that Mosaic compiled the fused fold (no interpret mode).
Earlier lines print observations (wall times, events/s); the last line is
one JSON object naming the device.  Exits non-zero, with no such line,
when JAX finds no TPU or when any phase fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_EVENTS = 1_000_000
N_VEHICLES = 100_000
SPAN = 300.0                      # seconds of event time
SIZE, SLIDE = 60.0, 15.0          # sliding window: fan-out SIZE / SLIDE = 4
TUMBLE = 60.0                     # the second tenant's tumbling count
BATCH = 16384
NUM_BUCKETS = 131072
N_SLOTS = 8
LOG = "streams/fleet"


def make_events(seed: int, n: int):
    """``n`` in-order GPS events ``(t, "veh<id>", value)``: uniform
    vehicles, values multiples of 1/8 in [0, 200)."""
    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0.0, SPAN, n))
    veh = rng.integers(0, N_VEHICLES, n)
    val = rng.integers(0, 1600, n) / 8.0
    return t, veh, val


def window_key(start: float, end: float) -> str:
    return f"window-{start:.3f}-{end:.3f}"


def reference(t, veh, val, size: float, slide: float, kind: str):
    """Per-(window, vehicle) sums or counts in float64: window k covers
    ``[k * slide, k * slide + size)``.  Returns ``{window key: {vehicle:
    value}}`` over the (window, vehicle) pairs that hold an event."""
    fan = int(round(size / slide))
    last = np.floor(t / slide).astype(np.int64)
    lo = int(last.min()) - fan + 1
    n_win = int(last.max()) - lo + 1
    sums = np.zeros(n_win * N_VEHICLES)
    counts = np.zeros(n_win * N_VEHICLES)
    for j in range(fan):
        flat = (last - j - lo) * N_VEHICLES + veh
        sums += np.bincount(flat, weights=val, minlength=sums.size)
        counts += np.bincount(flat, minlength=counts.size)
    out = {}
    for w in range(n_win):
        row = slice(w * N_VEHICLES, (w + 1) * N_VEHICLES)
        hit = np.nonzero(counts[row])[0]
        if hit.size:
            got = (sums if kind == "sum" else counts)[row][hit]
            start = (w + lo) * slide
            out[window_key(start, start + size)] = {
                f"veh{v}": float(x) for v, x in zip(hit, got)}
    return out


def parse_sink(blobs: dict[str, bytes], prefix: str):
    """Sink objects under ``prefix`` → ``{window key: {vehicle: value}}``."""
    out = {}
    for key, blob in blobs.items():
        rows = (json.loads(line) for line in blob.decode().splitlines())
        out[key[len(prefix):]] = {k: float(v) for k, v in rows}
    return out


def check(name: str, got, want) -> None:
    if got.keys() != want.keys():
        raise AssertionError(
            f"{name}: windows differ: {sorted(got.keys() ^ want.keys())[:4]}")
    for w, cells in want.items():
        if got[w] != cells:
            bad = [k for k in cells.keys() | got[w].keys()
                   if got[w].get(k) != cells.get(k)]
            raise AssertionError(
                f"{name}: {len(bad)} cells of {w} differ from the reference, "
                f"e.g. {bad[0]}: {got[w].get(bad[0])} != {cells.get(bad[0])}")


def vehicle(record):
    return record[1]


def sliding_sum(prefix: str | None = None):
    from repro.pipeline import Pipeline, Windowing
    return (Pipeline.from_source(prefix=prefix, batch_records=BATCH)
            .key_by(vehicle).window(Windowing.sliding(SIZE, SLIDE))
            .reduce("sum").sink("stream-output/"))


def tumbling_count():
    from repro.pipeline import Pipeline, Windowing
    return (Pipeline.from_source(batch_records=BATCH)
            .key_by(vehicle).window(Windowing.tumbling(TUMBLE))
            .reduce("count").sink("stream-output/"))


def run_pipeline(store, job_id: str, **build):
    """One streaming run of the sliding-window query over the log;
    returns its sink objects and wall seconds."""
    built = sliding_sum(LOG).build(num_buckets=NUM_BUCKETS, n_slots=N_SLOTS,
                                   job_id=job_id, **build)
    t0 = time.perf_counter()
    report = built.run(store=store)
    wall = time.perf_counter() - t0
    if report.error is not None:
        raise RuntimeError(f"{job_id}: {report.error}")
    prefix = f"stream-output/{job_id}/"
    blobs = {m.key: store.get(m.key) for m in store.list_objects(prefix)}
    return built, blobs, prefix, wall


def assert_mosaic_step(built) -> float:
    """The pallas step is compiled by Mosaic: interpret mode is off and
    the step's compiled HLO holds a ``tpu_custom_call``.  Returns the
    compile seconds of that step."""
    import jax
    import jax.numpy as jnp
    from repro.engine.compile import default_pallas_interpret
    if default_pallas_interpret():
        raise AssertionError("pallas would run in interpret mode")
    agg = built.stages[0].sides[0].compiled
    rows = jax.ShapeDtypeStruct((BATCH, 5), jnp.float32)
    t0 = time.perf_counter()
    hlo = agg._step.lower(rows, agg.init_carry(), jnp.int32(0)) \
        .compile().as_text()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in hlo:
        raise AssertionError("pallas step HLO holds no tpu_custom_call")
    return secs


def phase_one_chip(store, events, log) -> None:
    t, veh, val = events
    want = reference(t, veh, val, SIZE, SLIDE, "sum")

    _, blobs_a, prefix, wall = run_pipeline(store, "fleet-vmap")
    check("(a) vmap", parse_sink(blobs_a, prefix), want)
    log(f"(a) vmap: {len(want)} windows match the reference; "
        f"observed {wall:.2f} s wall, {len(t) / wall:,.0f} events/s")

    built, blobs_b, prefix_b, wall = run_pipeline(store, "fleet-pallas",
                                                  backend="pallas")
    compile_s = assert_mosaic_step(built)
    check("(b) pallas", parse_sink(blobs_b, prefix_b), want)
    renamed = {k.replace(prefix_b, prefix): v for k, v in blobs_b.items()}
    if renamed != blobs_a:
        raise AssertionError("(b) pallas sink bytes differ from (a) vmap")
    log(f"(b) pallas (Mosaic, tpu_custom_call): byte-identical to (a); "
        f"observed {wall:.2f} s wall, {len(t) / wall:,.0f} events/s, "
        f"step compile {compile_s:.2f} s")


def phase_job_server(store, events, log) -> None:
    from repro.core import MetadataStore
    from repro.service import JobServer, JobStatus
    t, veh, val = events
    server = JobServer(store, MetadataStore())
    server.add_tenant("fleet-ops")
    server.add_tenant("billing")
    build = dict(num_buckets=NUM_BUCKETS, n_slots=N_SLOTS,
                 batch_records=BATCH)
    a = server.submit("fleet-ops",
                      sliding_sum().build(job_id="js-sum", **build),
                      source_prefix=LOG)
    b = server.submit("billing",
                      tumbling_count().build(job_id="js-count", **build),
                      source_prefix=LOG)
    t0 = time.perf_counter()
    states = server.run_until_complete()
    wall = time.perf_counter() - t0
    if states != {a: JobStatus.DONE, b: JobStatus.DONE}:
        raise AssertionError(f"(c) job states {states}")
    for tenant, job, want in (
            ("fleet-ops", "js-sum", reference(t, veh, val, SIZE, SLIDE, "sum")),
            ("billing", "js-count",
             reference(t, veh, val, TUMBLE, TUMBLE, "count"))):
        prefix = f"tenants/{tenant}/stream-output/{job}/"
        blobs = {m.key: store.get(m.key) for m in store.list_objects(prefix)}
        check(f"(c) {job}", parse_sink(blobs, prefix), want)
    log(f"(c) JobServer, 2 tenants on one shared ingest: both DONE, both "
        f"sinks match; observed {wall:.2f} s wall")


def phase_four_chips(store, events, log) -> None:
    """shard_map over a 4-device ``workers`` mesh vs one-device vmap with
    the same 4 workers vs the reference; the carry and the wire rows must
    be split over the mesh."""
    import jax
    import jax.numpy as jnp
    t, veh, val = events
    want = reference(t, veh, val, SIZE, SLIDE, "sum")
    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = jax.sharding.Mesh(np.array(devices[:4]), ("workers",))

    _, blobs_v, prefix_v, wall_v = run_pipeline(store, "fleet-vmap4",
                                                n_workers=4)
    check("vmap x4 workers", parse_sink(blobs_v, prefix_v), want)

    built, blobs_s, prefix_s, wall_s = run_pipeline(
        store, "fleet-shard", backend="shard_map", n_workers=4, mesh=mesh)
    check("shard_map", parse_sink(blobs_s, prefix_s), want)
    renamed = {k.replace(prefix_s, prefix_v): v for k, v in blobs_s.items()}
    if renamed != blobs_v:
        raise AssertionError("shard_map sink bytes differ from vmap")

    agg = built.stages[0].sides[0].compiled
    carry = agg.init_carry()
    rows = jax.ShapeDtypeStruct((BATCH, 5), jnp.float32)
    in_rows = agg._step.lower(rows, carry, jnp.int32(0)).compile() \
        .input_shardings[0][0]
    for name, sh in (("carry", carry.sharding), ("rows", in_rows)):
        if len(sh.device_set) != 4 or sh.is_fully_replicated:
            raise AssertionError(f"{name} is not split over the mesh: {sh}")
    log(f"shard_map on {len(mesh.devices)} chips: byte-identical to vmap "
        f"(4 workers, one chip) and to the reference; carry and rows split "
        f"over the mesh; observed {wall_s:.2f} s wall "
        f"(vmap {wall_v:.2f} s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.core import MemoryStore
    from repro.engine.compile import enable_compile_cache
    from repro.streaming import write_event_log

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()

    def log(msg: str) -> None:
        print(msg, flush=True)

    log(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}")
    t0 = time.perf_counter()
    events = make_events(args.seed, N_EVENTS)
    store = MemoryStore()
    t, veh, val = events
    write_event_log(store, LOG, zip(t.tolist(), (f"veh{v}" for v in veh),
                                    val.tolist()))
    log(f"log: {N_EVENTS:,} events, {N_VEHICLES:,} vehicles, "
        f"{SPAN:.0f} s of event time; set-up {time.perf_counter() - t0:.2f} s")

    phases = ([("four_chips", phase_four_chips)] if args.chips == 4 else
              [("one_chip", phase_one_chip), ("job_server", phase_job_server)])
    failed = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for name, phase in phases:
            try:
                phase(store, events, log)
            except Exception:
                traceback.print_exc()
                log(f"phase {name} FAILED")
                failed.append(name)
    donation = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    if donation:
        log(f"carry donation refused: {donation[0]}")
        failed.append("donation")
    if failed:
        log(f"chip_smoke: failed phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
