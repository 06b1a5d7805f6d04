"""Shared arithmetic of the per-layer readers."""

import math


def seconds_per_mev(ctx, target):
    """Seconds spent in ``target`` during the window per million events
    folded in it; None when the entry point was not found or nothing was
    folded."""
    spans = ctx.spans.get(target)
    if not spans or ctx.folded <= 0:
        return None
    lo, hi = ctx.window
    secs = sum(max(0.0, min(b, hi) - max(a, lo)) for a, b, _n in spans)
    return secs / (ctx.folded / 1e6)


def nearest_rank(values, q):
    """The nearest-rank ``q`` quantile of ``values``."""
    s = sorted(values)
    return float(s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))])
