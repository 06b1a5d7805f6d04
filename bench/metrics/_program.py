"""The program's own spans (``repro.core.tracing``), as the per-layer
readers of a ``--trace 1`` run read them.

The harness imports a traced run's readers just before its measured
window opens, so each reader of a program span calls :func:`start` as it
is imported: that turns the program's recorder on for the window.  The
first reader to read drains it once, for all of them, and logs each
span's count and the sum of its ``n`` on a line of its own (batches are
``coord.fold_drain``, parks ``server.park``, records pumped the ``n`` of
``ingest.pump``).  A program without the recorder (no
``repro.core.tracing``) leaves every such reader with nothing to read: it
returns None.

This rests on the harness importing readers only in traced runs and only
just before the window: one that imported them earlier would record its
measured runs too.  Turning the recorder on and draining it belong in
the harness itself, once a benchmark change may edit it.
"""

try:
    from repro.core import tracing
except ImportError:           # a program from before the recorder
    tracing = None

#: span records kept for a window (the drain logs any dropped past it)
CAPACITY = 1 << 18

_drained = None


def start():
    """Turn the program's recorder on, once per process."""
    if tracing is not None and not tracing.enabled() and _drained is None:
        tracing.enable(capacity=CAPACITY)


def drained():
    """Everything the recorder holds, drained once (and the recorder
    turned off); None without a recorder."""
    global _drained
    if tracing is None:
        return None
    if _drained is None:
        _drained = tracing.drain()
        tracing.disable()
        print(f"program spans (count, sum of n): {span_totals(_drained.spans)};"
              f" {len(_drained.spans)} spans, {_drained.dropped} dropped",
              flush=True)
    return _drained


def span_totals(spans):
    """``{name: (spans closed, sum of their n)}``, by name."""
    out = {}
    for s in spans:
        k, n = out.get(s.name, (0, 0))
        out[s.name] = (k + 1, n + s.n)
    return dict(sorted(out.items()))


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _covered(intervals, lo, hi):
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in _union(intervals))


def _per_mev(ctx, name, seconds_of):
    rec = drained()
    if rec is None or name not in tracing.SPANS or ctx.folded <= 0:
        return None
    spans = [s for s in rec.spans if s.name == name]
    return seconds_of(spans, rec.spans, *ctx.window) / (ctx.folded / 1e6)


def seconds_per_mev(ctx, name):
    """Seconds inside span ``name`` during the window per million events
    folded in it, each thread's spans counted once where they nest (so
    spans on concurrent threads add up).  None when the program does not
    declare ``name`` or nothing was folded; 0.0 when it never opened."""
    def seconds(spans, _all, lo, hi):
        by_thread = {}
        for s in spans:
            by_thread.setdefault(s.thread, []).append((s.start, s.end))
        return sum(_covered(iv, lo, hi) for iv in by_thread.values())
    return _per_mev(ctx, name, seconds)


def self_seconds_per_mev(ctx, name):
    """As :func:`seconds_per_mev`, for the time in span ``name`` that no
    span nested in it (its children, on its own thread) accounts for."""
    def seconds(spans, every, lo, hi):
        children = {}
        for s in every:
            children.setdefault(s.parent, []).append((s.start, s.end))
        total = 0.0
        for s in spans:
            a, b = max(s.start, lo), min(s.end, hi)
            if b > a:
                total += b - a - _covered(children.get(s.id, ()), a, b)
        return total
    return _per_mev(ctx, name, seconds)
