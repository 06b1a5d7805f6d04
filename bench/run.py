"""Run one benchmark cell once, on the chip, and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/``), its traffic mix
(``bench/traffic/<traffic>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``).

The driven path is the same in every cell.  Before JAX is imported the
generator (``bench/generator.py``) starts in a child process; it publishes
the stream as JSON-lines segments into a directory on an open-loop
schedule.  The system under test is a ``JobServer`` whose jobs read that
log through one shared ingest and write their windows to memory
(``bench/store.py``).  Set-up builds and submits the jobs, runs each job's
program once on a few records (so every shape the window uses is compiled
or loaded from the cache at ``<checkout>/.jax_cache``), starts the
generator and drives ``JobServer.step()`` through the traffic's warm-up.
The measured window then drives ``step()`` for ``--seconds`` seconds,
sleeping the configuration's poll interval whenever a step moves nothing.

Afterwards the generator stops, the program's state is freed, and every
window each job emitted is compared with ``bench/reference.py``.  The
numbers compared and their limits are the last lines on stderr and the
``checks`` key, last in the result line.  Without a TPU (or with fewer
chips than the cell asks for) the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402


BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH / "metrics"), str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

LOG_PREFIX = "streams/linear-road"
TENANT = "linear-road"
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def load_spec(workload: str) -> dict:
    """The cell, its configuration, traffic mix and metrics, by name."""
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    traffic_file = BENCH / "traffic" / f"{cell['traffic']}.json"
    with open(traffic_file) as f:
        traffic = json.load(f)

    def mine(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "cfg": cfg, "cfg_file": str(ROOT / conf["file"]),
            "traffic": traffic, "traffic_file": str(traffic_file),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_file(path: Path, name: str):
    """Import a file of the benchmark under a name of its own (``trace``
    would otherwise be the standard library's)."""
    if name in sys.modules:
        return sys.modules[name]
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``bench/metrics/<name>.py``: ``WRAPS`` and ``read(ctx)``."""
    return load_file(BENCH / "metrics" / f"{name}.py",
                     f"bench_metric_{name.replace('.', '_')}")


class Generator:
    """The load generator's child process (never imports JAX)."""

    def __init__(self, spec: dict, seed: int, root: str) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "generator.py"),
             "--config", spec["cfg_file"], "--traffic", spec["traffic_file"],
             "--seed", str(seed), "--root", root, "--prefix", LOG_PREFIX],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def go(self, t0: float) -> None:
        self.proc.stdin.write(f"go {t0!r}\n")
        self.proc.stdin.flush()

    def stop(self) -> dict:
        """Stop publishing; returns the generator's summary."""
        try:
            self.proc.stdin.write("stop\n")
            self.proc.stdin.flush()
        except BrokenPipeError:
            pass
        out, _ = self.proc.communicate(timeout=60)
        if self.proc.returncode != 0 or not out.strip():
            raise RuntimeError(f"generator exited {self.proc.returncode}")
        return json.loads(out.strip().splitlines()[-1])

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)


def record_key(record):
    return record[1]


def build_programs(cfg: dict):
    """One built pipeline per job, as the configuration states."""
    from repro.pipeline import Pipeline, Windowing
    q = cfg["query"]
    size = float(q["window_s"])
    slide = float(q.get("slide_s", size))
    windowing = (Windowing.tumbling(size) if slide == size
                 else Windowing.sliding(size, slide))
    c = cfg["carry"]
    programs = []
    for j in range(int(cfg["jobs"])):
        pipe = (Pipeline.from_source(batch_records=cfg["batch_records"])
                .key_by(record_key).window(windowing).reduce(q["aggregate"])
                .sink("stream-output/"))
        programs.append(pipe.build(
            num_buckets=c["buckets"], n_slots=c["slots"],
            n_workers=cfg["n_workers"], backend=cfg["backend"],
            checkpoint_interval=cfg["checkpoint_interval"],
            batch_records=cfg["batch_records"],
            job_id=f"{cfg['name']}-{j:02d}"))
    return programs


def warm_programs(cfg: dict, programs) -> None:
    """Drive each job's own program once over a few records that close
    windows, on a private store: the fold step (donating), the slot
    gather and clear, and the checkpoint all run at the window's shapes."""
    from repro.core import MemoryStore, MetadataStore
    from repro.streaming import RunOptions, StreamSource
    q = cfg["query"]
    span = 3 * float(q["window_s"])
    recs = [(span * i / 64, f"{cfg['stream']['key_prefix']}{i % 7}",
             1.0) for i in range(64)]
    for built in programs:
        src = StreamSource.from_records(recs,
                                        batch_records=cfg["batch_records"])
        report = built.run(src, store=MemoryStore(), meta=MetadataStore(),
                           options=RunOptions(overlap=cfg["overlap"]))
        if report.error is not None:
            raise RuntimeError(f"warm-up of {built.job_id}: {report.error}")


@dataclass
class CompileCounter:
    """Counts JAX lowerings and backend compiles while ``armed``."""

    armed: bool = False
    counts: dict = field(default_factory=dict)

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if self.armed and (event.endswith("jaxpr_to_mlir_module_duration")
                           or event.endswith("backend_compile_duration")):
            name = event.rsplit("/", 1)[-1]
            self.counts[name] = self.counts.get(name, 0) + 1


class Spans:
    """Host-clock spans around the program's entry points, installed by
    wrapping class attributes for the traced run (and removed after)."""

    def __init__(self) -> None:
        self.spans: dict[str, list[tuple[float, float, int]]] = {}
        self._undo: list[tuple[type, str, object]] = []

    def wrap(self, target: str) -> None:
        """``target`` is ``module:Class.attr``; one that is gone is left
        out, so its reader finds nothing to read."""
        import importlib
        import jax
        mod_name, qual = target.split(":")
        cls_name, attr = qual.split(".")
        try:
            cls = getattr(importlib.import_module(mod_name), cls_name)
            orig = cls.__dict__[attr]
        except (ImportError, AttributeError, KeyError):
            return
        if target in self.spans:
            return
        rec = self.spans.setdefault(target, [])
        label = f"bench:{cls_name}.{attr}"

        def wrapped(*args, **kwargs):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(label):
                out = orig(*args, **kwargs)
            n = getattr(args[1], "n_records", 0) if len(args) > 1 else 0
            rec.append((t0, time.monotonic(), int(n)))
            return out

        setattr(cls, attr, wrapped)
        self._undo.append((cls, attr, orig))

    def unwrap(self) -> None:
        for cls, attr, orig in reversed(self._undo):
            setattr(cls, attr, orig)
        self._undo.clear()


@dataclass
class Context:
    """What a per-layer reader may read."""

    cfg: dict
    peaks: dict
    device_kind: str
    window: tuple[float, float]
    folded: float                     # records folded in the window
    spans: dict
    trace: object = None              # trace.TraceSummary of the traced part
    trace_window: tuple | None = None  # monotonic bounds of the traced part
    close_to_emit: list = field(default_factory=list)  # program's, seconds
    event_to_emit: list = field(default_factory=list)  # harness's, seconds


def offered_events(traffic: dict, t0: float, t: float) -> int:
    """Events the open-loop schedule has published by time ``t``."""
    seg = int(traffic["segment_records"])
    return seg * int(max(0.0, t - t0) * float(traffic["offered_rate"])
                     // seg)


def drive(server, until: float, poll_s: float) -> None:
    while time.monotonic() < until:
        if server.step() == 0:
            time.sleep(poll_s)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             gen: Generator, gen_root: str, *, require_tpu: bool = True,
             controls: tuple[str, ...] = ()) -> tuple[int, dict | None]:
    """Set up, measure, check.  Returns (exit code, result line).
    ``controls`` (``reference.CONTROLS``) are also put in the program's
    place and compared; their readings go under the result's
    ``controls`` key and decide nothing."""
    import jax
    cfg, traffic, cell = spec["cfg"], spec["traffic"], spec["cell"]
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s); nothing run",
              file=sys.stderr)
        return EXIT_NO_CHIP, None
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)

    from repro.service import JobServer, ParkPolicy
    from repro.streaming import RunOptions
    from _spans import nearest_rank
    from store import (RecordingBus, RecordingMeta, RoutedStore, batches,
                       folded_between)
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    if require_tpu and dev.device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       f"bench/peaks.json")
    t_jax = time.monotonic()

    store = RoutedStore(gen_root, LOG_PREFIX)
    meta = RecordingMeta()
    bus = RecordingBus()
    server = JobServer(store, meta, bus,
                       ingest_partitions=int(cfg["partitions"]),
                       park_policy=ParkPolicy(**cfg["park_policy"]),
                       overlap=cfg["overlap"])
    server.add_tenant(TENANT)
    programs = build_programs(cfg)
    parts = int(cfg["partitions"])
    for j, built in enumerate(programs):
        server.submit(TENANT, built, source_prefix=LOG_PREFIX,
                      options=RunOptions(overlap=cfg["overlap"]),
                      partitions=[j] if parts > 1 else None)
    t_build = time.monotonic()
    warm_programs(cfg, programs)
    t_warm = time.monotonic()

    poll_s = float(cfg["poll_interval_s"])
    t0 = time.monotonic()
    gen.go(t0)
    drive(server, t0 + float(traffic["warmup_s"]), poll_s)

    # -- the measured window ---------------------------------------------
    readers = {}
    spans = Spans()
    if trace:
        for m in spec["per_layer"]:
            readers[m["name"]] = load_reader(m["name"])
        spans.wrap("repro.service.server:JobServer.step")
        for r in readers.values():
            for target in getattr(r, "WRAPS", ()):
                spans.wrap(target)
    marks = {jid: len(job.report.emit_latencies)
             for jid, job in server.jobs.items()}
    t_start = time.monotonic()
    setup_s = t_start - T_PROCESS
    counter.armed = True
    t_end = t_start + seconds
    trace_dir = None
    trace_window = None
    if trace:
        trace_mod = load_file(BENCH / "trace.py", "bench_trace")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        trace_s = min(float(traffic.get("trace_s", 4.0)), seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK):
            t_trace = time.monotonic()
            drive(server, t_trace + trace_s, poll_s)
            trace_window = (t_trace, time.monotonic())
        jax.profiler.stop_trace()
    drive(server, t_end, poll_s)
    counter.armed = False
    t_done = time.monotonic()
    spans_b = batches(meta, bus)
    backlog_start = offered_events(traffic, t0, t_start) \
        - folded_between(spans_b, 0.0, t_start)
    backlog_end = offered_events(traffic, t0, t_end) \
        - folded_between(spans_b, 0.0, t_end)
    folded = folded_between(spans_b, t_start, t_end)
    folded_ckpt = checkpointed(meta, t_end) - checkpointed(meta, t_start)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    gen_summary = gen.stop()
    spans.unwrap()

    log(f"setup: jax {t_jax - T_PROCESS:.3f} s, build+submit "
        f"{t_build - t_jax:.3f} s, warm {t_warm - t_build:.3f} s, "
        f"warm-up traffic {t_start - t0:.3f} s; setup_s {setup_s:.4f}")
    log(f"window: {seconds} s measured, last step ended "
        f"{t_done - t_end:.3f} s past its close; folded {folded:.1f} "
        f"records in {len(spans_b)} batches of the run; checkpointed "
        f"offsets advanced {folded_ckpt} records in the window")
    log(f"generator: {gen_summary['segments']} segments "
        f"({gen_summary['events']} events); late p50 "
        f"{gen_summary['late_p50_s']:.6f} s, p99 "
        f"{gen_summary['late_p99_s']:.6f} s, max "
        f"{gen_summary['late_max_s']:.6f} s, "
        f"{gen_summary['late_over_100ms']} segments >100 ms late")
    growth = (backlog_end - backlog_start) / seconds
    log(f"backlog: {backlog_start:.0f} events at window start, "
        f"{backlog_end:.0f} at its end ({growth:.1f} events/s growth "
        f"against {traffic['offered_rate']} offered)")
    log(f"compiles in window: {counter.counts or 'none'}")

    close_to_emit = [x for jid, job in server.jobs.items()
                     for x in job.report.emit_latencies[marks[jid]:]]
    summary = None
    if trace:
        summary = trace_mod.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- free the program's state, then the reference -------------------
    sinks: dict[str, dict[str, bytes]] = {}
    for jid in server.jobs:
        prefix = f"tenants/{TENANT}/stream-output/{jid}/"
        sinks[jid] = {m.key[len(prefix):]: store.mem.get(m.key)
                      for m in store.mem.list_objects(prefix)}
    landed = dict(store.landed)
    writes = dict(store.writes)
    offsets = meta.final_offsets()
    job_ids = list(server.jobs)
    del server, store, programs
    gc.collect()

    import reference as ref_mod
    ref = ref_mod.Reference(cfg, traffic, seed, gen_summary["segments"])
    args = (ref, seed, sinks, landed, writes, offsets, job_ids, t0,
            (t_start, t_end))
    checks, attempted, failed, latencies = verify(*args)
    control_checks = {c: verify(*args, control=c)[0] for c in controls}
    log(f"latency samples: {len(latencies)} windows landed in the window"
        + (f"; event->emit p50 {nearest_rank(latencies, 0.5):.4f} s, p90 "
           f"{nearest_rank(latencies, 0.9):.4f} s" if latencies else ""))

    metrics = {}
    breakdown = None
    device_extra = {}
    if trace:
        ctx = Context(cfg=cfg, peaks=peaks, device_kind=dev.device_kind,
                      window=(t_start, t_end), folded=folded,
                      spans=spans.spans, trace=summary,
                      trace_window=trace_window,
                      close_to_emit=close_to_emit, event_to_emit=latencies)
        device_extra = {"busy_s": summary.busy_s,
                        "window_s": summary.window_s}
        breakdown = summary.breakdown()
        for m in spec["per_layer"]:
            value = readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            name = m["name"]
            if name == "setup_s":
                value = setup_s
            elif name == "events_per_s":
                value = folded / seconds
            else:
                raise KeyError(f"no measurement for end-to-end metric "
                               f"{name!r}")
            if value is not None:
                metrics[name] = {"value": value, "unit": m["unit"]}
    correct = passes(checks)
    for name, v in checks.items():
        rel = ">=" if name == "windows_checked" else "<="
        print(f"check {name} = {v['value']} (limit {rel} {v['limit']})",
              file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak, **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {"offered_rate": float(traffic["offered_rate"]),
                     "folded": folded, "folded_by_checkpoints": folded_ckpt,
                     "backlog_start": backlog_start,
                     "backlog_end": backlog_end,
                     "generator_late_max_s": gen_summary["late_max_s"],
                     "latency_samples": len(latencies),
                     "compiles_in_window": sum(counter.counts.values())}
    if controls:
        result["controls"] = {c: {"correct": passes(v), "checks": v}
                              for c, v in control_checks.items()}
    result["checks"] = checks
    return 0, result


def passes(checks: dict) -> bool:
    """Every number within its limit: at most, or for ``windows_checked``
    at least."""
    return all(v["value"] >= v["limit"] if k == "windows_checked"
               else v["value"] <= v["limit"] for k, v in checks.items())


def verify(ref, seed, sinks, landed, writes, offsets, job_ids, t0, window,
           *, control=None):
    """Every window each job emitted against the reference; and the
    event→emit latency of the windows that landed inside ``window``.
    With ``control`` the control's windows stand in the program's place."""
    import reference as ref_mod
    parts = ref.n_partitions
    kp = ref.key_prefix
    scale = ref.stream.event_time_rate / ref.stream.offered_rate
    totals = {"wrong_cells": 0, "missing_windows": 0, "extra_windows": 0,
              "windows_checked": 0}
    failed = 0
    latencies = []
    for j, jid in enumerate(job_ids):
        part = j if parts > 1 else 0
        want = ref.windows(part, offsets.get(jid, 0))
        if control is None:
            got = {k: ref_mod.parse_sink(b, kp)
                   for k, b in sinks[jid].items()}
        else:
            got = ref.windows(part, offsets.get(jid, 0), control=control,
                              control_seed=seed + j)
        res = ref_mod.compare(got, want)
        for k in totals:
            totals[k] += res[k]
        failed += res["missing_windows"] + res["extra_windows"] \
            + res["windows_wrong"]
        prefix = f"tenants/{TENANT}/stream-output/{jid}/"
        for wkey in sinks[jid]:
            t_land = landed.get(prefix + wkey)
            if t_land is None or not window[0] <= t_land <= window[1]:
                continue
            end = float(wkey.rsplit("-", 1)[1])
            created = t0 + ref.last_event_time(part, end) * scale
            latencies.append(t_land - created)
    values = dict(totals, rewrites=0 if control else
                  sum(n - 1 for n in writes.values()))
    checks = {name: {"value": values[name], "limit": limit}
              for name, limit in ref_mod.LIMITS.items()}
    checks["windows_checked"] = {"value": totals["windows_checked"],
                                 "limit": 1}
    return checks, totals["windows_checked"] + totals["missing_windows"] \
        + totals["extra_windows"], failed, latencies


def checkpointed(meta, t: float) -> int:
    """Records all jobs had checkpointed by time ``t``: the sum of each
    job's last checkpoint offset stamped at or before ``t``."""
    return sum(max((off for tm, off in marks if tm <= t), default=0)
               for marks in meta.offsets.values())


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (a
    fixed path: it is part of the cache key), every program in it.

    The program has a setter of its own (``engine.compile.
    enable_compile_cache``), which follows ``JAX_COMPILATION_CACHE_DIR``.
    The benchmark keeps its own so that where each run caches is fixed by
    the yardstick, not by the environment or by a later change to the
    program."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    gen_root = tempfile.mkdtemp(prefix="bench-log-")
    gen = Generator(spec, args.seed, gen_root)     # before JAX is imported
    try:
        enable_compile_cache()
        code, result = run_cell(spec, args.seed, args.seconds,
                                bool(args.trace), gen, gen_root)
    finally:
        gen.kill()
        shutil.rmtree(gen_root, ignore_errors=True)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
