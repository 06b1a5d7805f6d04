"""lane_wait_s_per_mev: host seconds the overlapped drive waits
on a job's prefetch thread for its next prepared batch
(``server.lane_wait``) per million events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "server.lane_wait")
