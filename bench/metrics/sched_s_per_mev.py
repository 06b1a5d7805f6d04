"""sched_s_per_mev: host seconds of ``JobServer.step`` that
no span nested in it accounts for (``server.step`` less its children on
the same thread: lag scans, the wake and park checks, starting the
prefetch threads) per million events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.self_seconds_per_mev(ctx, "server.step")
