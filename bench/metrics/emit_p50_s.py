"""emit_p50_s: median over every window any job emitted in the window of
the wall time its sink object landed minus the scheduled creation time of
the last event of its partition that feeds it (``bench/run.py``'s
``verify``)."""

from _spans import nearest_rank

WRAPS = ()


def read(ctx):
    lat = ctx.event_to_emit
    return nearest_rank(lat, 0.5) if lat else None
