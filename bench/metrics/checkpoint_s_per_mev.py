"""checkpoint_s_per_mev: host seconds in the coordinators'
checkpoints (``coord.checkpoint``: carry read back, ``np.savez``, the
store PUT and the metadata write) per million events folded in the
window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "coord.checkpoint")
