"""fold_drain_s_per_mev: host seconds in the coordinator's fold/drain half
(``_process_prepared``: key tables, wire, fold dispatch, watermark,
finalize, sink put and checkpoint) per million events folded in the
window."""

from _spans import seconds_per_mev

WRAPS = ("repro.streaming.coordinator:StreamingCoordinator._process_prepared",)


def read(ctx):
    return seconds_per_mev(ctx, WRAPS[0])
