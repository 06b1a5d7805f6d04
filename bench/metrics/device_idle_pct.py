"""device_idle_pct: share of the traced window in which no operation ran on
the device, from the profiler trace (``bench/trace.py``)."""

WRAPS = ()


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
