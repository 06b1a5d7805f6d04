"""Tests of the harness at a size a CPU holds: a sound run is correct, the
controls and the planted faults are not.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench.py

Each test drives the whole run of a cell — generator child process, the
``JobServer`` over the routed store, warm-up, measured window, reference —
skipping only the harness's look for a chip, at a few hundred vehicles on
short expressways.  The fault tests break the timed path underneath the run
and must see ``correct`` come out false.
"""

from __future__ import annotations

import copy
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "metrics"),
                str(BENCH.parent / "src")]

import run  # noqa: E402


def small_spec(cell: str, tmp: Path) -> dict:
    """The cell's spec with its scale cut to a CPU's (widths of the query
    and the guarantees unchanged)."""
    spec = run.load_spec(cell)
    cfg = copy.deepcopy(spec["cfg"])
    cfg["stream"]["vehicles_per_expressway"] = 300     # 20 events/s
    cfg["stream"]["segments"] = 20                     # 80 segment keys
    cfg["batch_records"] = 512
    if cfg["jobs"] > 1:
        cfg["jobs"] = cfg["partitions"] = 4
        cfg["carry"]["buckets"] = 32
    traffic = dict(spec["traffic"], offered_rate=1000.0,
                   segment_records=128, warmup_s=3.0)
    spec = dict(spec, cfg=cfg, traffic=traffic)
    for key, obj in (("cfg_file", cfg), ("traffic_file", traffic)):
        path = tmp / f"{key}.json"
        path.write_text(__import__("json").dumps(obj))
        spec[key] = str(path)
    return spec


def run_small(cell: str, tmp: Path, seed: int, controls=()):
    spec = small_spec(cell, tmp)
    root = tempfile.mkdtemp(dir=tmp)
    gen = run.Generator(spec, seed, root)
    try:
        code, result = run.run_cell(spec, seed, 3.0, False, gen, root,
                                    require_tpu=False, controls=controls)
    finally:
        gen.kill()
        shutil.rmtree(root, ignore_errors=True)
    assert code == 0
    return result


CELLS = ["lr-lav.drain", "lr-count.steady"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    result = run_small(cell, tmp_path, seed=2**31 + 11)
    checks = result["checks"]
    assert result["correct"], checks
    assert checks["windows_checked"]["value"] >= 4
    assert result["metrics"]["events_per_s"]["value"] > 0


@pytest.mark.parametrize("cell,control", [
    ("lr-lav.drain", "bf16"),
    ("lr-lav.drain", "replay"),
    ("lr-count.steady", "replay"),
])
def test_control_is_not_correct(cell, control, tmp_path):
    result = run_small(cell, tmp_path, seed=7, controls=(control,))
    assert result["correct"], result["checks"]
    read = result["controls"][control]
    assert not read["correct"]
    assert read["checks"]["wrong_cells"]["value"] > 0


def _state_unchanged(monkeypatch):
    """The fold step returns the carry it was given."""
    from repro.engine.plan import CompiledStreamAggregate
    orig = CompiledStreamAggregate.step

    def step(self, rows, carry, min_window=None, *, donate=False):
        new, stats = orig(self, rows, carry + 0, min_window, donate=False)
        return carry, stats

    monkeypatch.setattr(CompiledStreamAggregate, "step", step)


def _half_batch(monkeypatch):
    """Half of every micro-batch is left out of the fold."""
    from repro.streaming.coordinator import StreamingCoordinator
    orig = StreamingCoordinator._ingest_device

    def ingest(self, si, recs, report, via=None):
        return orig(self, si, recs[: (len(recs) + 1) // 2], report, via=via)

    monkeypatch.setattr(StreamingCoordinator, "_ingest_device", ingest)


def _answer_altered(monkeypatch):
    """One row of every window is altered where the window is produced."""
    from repro.streaming.coordinator import StreamingCoordinator
    orig = StreamingCoordinator._window_records

    def records(self, si, slot):
        out = orig(self, si, slot)
        if out:
            k, v = out[0]
            out[0] = (k, v + 1)
        return out

    monkeypatch.setattr(StreamingCoordinator, "_window_records", records)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_is_not_correct(cell, fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    result = run_small(cell, tmp_path, seed=5)
    assert not result["correct"], result["checks"]


def test_reference_partitions_match_the_log_rule():
    """The reference's FNV-1a partition rule, checked on known values."""
    import reference
    # 64-bit FNV-1a of b"" is the offset basis; of b"a" a published value
    assert reference.fnv1a_partition("", 1 << 62) == \
        0xCBF29CE484222325 % (1 << 62)
    assert reference.fnv1a_partition("a", 1 << 62) == \
        0xAF63DC4C8601EC8C % (1 << 62)
    parts = [reference.fnv1a_partition(f"seg{v}", 32) for v in range(3200)]
    counts = np.bincount(parts, minlength=32)
    assert counts.min() > 50 and counts.max() < 150


def _context(**kw):
    trace = run.load_file(BENCH / "trace.py", "bench_trace")
    base = dict(cfg=run.load_spec("lr-lav.drain")["cfg"],
                peaks={"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 1e9}}},
                device_kind="TPU v5 lite", window=(10.0, 20.0),
                folded=2e6, spans={}, trace_window=(11.5, 14.5),
                trace=trace.TraceSummary(
                    window_s=4.0, busy_s=1.0, n_devices=1,
                    modules={"jit_step": 2.0},
                    module_runs={"jit_step": 3.5}))
    base.update(kw)
    return run.Context(**base)


def test_readers_compute_their_numbers():
    pump = "repro.service.ingest_share:SharedIngest.pump"
    proc = ("repro.streaming.coordinator:"
            "StreamingCoordinator._process_prepared")
    ctx = _context(spans={pump: [(9.0, 11.0, 0), (12.0, 13.0, 0)],
                          proc: [(11.0, 12.0, 100), (13.0, 14.0, 300),
                                 (15.0, 16.0, 5000)]},
                   close_to_emit=[0.1, 0.3, 0.2],
                   event_to_emit=[float(i) for i in range(1, 101)])
    read = {n: run.load_reader(n).read for n in (
        "device_idle_pct", "ingest_s_per_mev", "fold_drain_s_per_mev",
        "fold_roofline_pct", "close_to_emit_p50_s", "emit_p50_s",
        "emit_p90_s", "prepare_s_per_mev")}
    assert read["device_idle_pct"](ctx) == pytest.approx(75.0)
    assert read["ingest_s_per_mev"](ctx) == pytest.approx(2.0 / 2)
    assert read["fold_drain_s_per_mev"](ctx) == pytest.approx(3.0 / 2)
    # per fold, over the batches that overlap the traced window (100 and
    # 300 records, not the 5000 after it): wire n*20 B plus carry cells
    # min(n*5, 8*512) * 2 channels * 4 B read and written; 3.5 runs (one
    # cut by an edge counts by its share inside)
    per_fold = (100 * 20 + 500 * 16 + 300 * 20 + 1500 * 16) / 2
    assert read["fold_roofline_pct"](ctx) == pytest.approx(
        100 * per_fold * 3.5 / 1e9 / 2.0)
    assert read["close_to_emit_p50_s"](ctx) == pytest.approx(0.2)
    assert read["emit_p50_s"](ctx) == 50.0
    assert read["emit_p90_s"](ctx) == 90.0
    assert read["prepare_s_per_mev"](ctx) is None      # nothing wrapped
    empty = _context(trace=None, folded=0.0, event_to_emit=[1.0] * 99)
    for name in ("device_idle_pct", "fold_roofline_pct", "ingest_s_per_mev",
                 "close_to_emit_p50_s", "emit_p90_s"):
        assert read[name](empty) is None, name
