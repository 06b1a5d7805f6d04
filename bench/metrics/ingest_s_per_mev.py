"""ingest_s_per_mev: host seconds inside the shared ingest's pump (the one
physical log read: list, GET, JSON decode, materialize onto the topic) per
million events folded in the window."""

from _spans import seconds_per_mev

WRAPS = ("repro.service.ingest_share:SharedIngest.pump",)


def read(ctx):
    return seconds_per_mev(ctx, WRAPS[0])
