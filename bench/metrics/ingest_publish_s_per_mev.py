"""ingest_publish_s_per_mev: host seconds materializing decoded
records onto the ingest topic (``ingest.publish``: the envelope, the key
partitioning and ``bus.produce`` of each record of a segment) per million
events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "ingest.publish")
