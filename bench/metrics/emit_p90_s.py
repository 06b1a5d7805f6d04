"""emit_p90_s: the 90th percentile (nearest rank) of the same population
as ``emit_p50_s``; read only with 100 samples or more, so that at least
ten lie beyond it."""

from _spans import nearest_rank

WRAPS = ()


def read(ctx):
    lat = ctx.event_to_emit
    return nearest_rank(lat, 0.9) if len(lat) >= 100 else None
