"""restore_s_per_mev: host seconds building or cold-restoring
jobs' coordinators (``server.restore``: pool activation, carry download,
tracker and dictionary rebuild) per million events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "server.restore")
