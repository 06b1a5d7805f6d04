"""fold_roofline_pct: the fold step's share of its memory roofline.

The least time the fold can take is its necessary bytes over the chip's
HBM bandwidth (``bench/peaks.json``); the bound is memory, as the fold
does a few operations per byte.  Necessary bytes per micro-batch of ``n``
records: the wire rows in (``n`` x 5 float32 columns) plus the carry cells
the batch touches, read and written (``min(n x fan-out, slots x buckets) x
channels x 4 B x 2``).  They are counted the same way whatever implements
the fold.  The fold's device time is the run time of its program in the
trace: ``jit_step`` (the Pallas fused fold, ``kernels/fused_fold/ops.py``)
or ``jit__stream_agg_device_body`` (the XLA fold, ``engine/plan.py``).
Both sides are taken over the traced window alone: a program run cut by
its edge counts by the share of it inside (``trace.TraceSummary``), and the
batch sizes are those of the fold/drain spans that overlap it.
"""

WRAPS = ("repro.streaming.coordinator:StreamingCoordinator._process_prepared",)
FOLD_MODULES = ("jit_step", "jit__stream_agg_device_body")


def fold_bytes(n, cfg):
    q = cfg["query"]
    fan = round(q["window_s"] / q.get("slide_s", q["window_s"]))
    c = cfg["carry"]
    cells = min(n * fan, c["slots"] * c["buckets"])
    return n * 5 * 4 + cells * c["channels"] * 4 * 2


def is_fold(module_name):
    return module_name in FOLD_MODULES


def read(ctx):
    if ctx.trace is None or ctx.trace_window is None:
        return None
    lo, hi = ctx.trace_window
    batches = [n for a, b, n in ctx.spans.get(WRAPS[0], ())
               if n > 0 and a < hi and b > lo]
    runs = sum(r for m, r in ctx.trace.module_runs.items() if is_fold(m))
    secs = sum(s for m, s in ctx.trace.modules.items() if is_fold(m))
    if not batches or not runs or secs <= 0:
        return None
    per_fold = sum(fold_bytes(n, ctx.cfg) for n in batches) / len(batches)
    peak = ctx.peaks["devices"][ctx.device_kind]["hbm_bytes_per_s"]
    return 100.0 * (per_fold * runs / peak) / secs
