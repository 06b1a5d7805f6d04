"""close_to_emit_p50_s: median over all jobs of the program's own
``StreamReport.emit_latencies`` recorded during the window: from the
watermark passing a window's end to its bytes in the store."""

import statistics

WRAPS = ()


def read(ctx):
    lat = ctx.close_to_emit
    return statistics.median(lat) if lat else None
