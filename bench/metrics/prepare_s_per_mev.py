"""prepare_s_per_mev: host seconds in the coordinator's prepare half
(``_prepare_batch``: routing and the fused map chains, on the prefetch
threads when the drive overlaps) per million events folded in the window."""

from _spans import seconds_per_mev

WRAPS = ("repro.streaming.coordinator:StreamingCoordinator._prepare_batch",)


def read(ctx):
    return seconds_per_mev(ctx, WRAPS[0])
