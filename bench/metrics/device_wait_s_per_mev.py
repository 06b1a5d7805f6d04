"""device_wait_s_per_mev: host seconds blocked on reads that
wait for the device (``coord.device_wait``: the deferred fold stats at
each batch barrier and the carry read of each checkpoint) per million
events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "coord.device_wait")
