"""topic_read_s_per_mev: host seconds the jobs spend reading their
micro-batches off the ingest topic (``topic.read``, on the driver or the
prefetch threads, each thread counted on its own) per million events
folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "topic.read")
