"""ingest_fetch_s_per_mev: host seconds in the shared ingest's log
reads (``ingest.fetch``: the segment listing of each pump, and one GET per
segment) per million events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "ingest.fetch")
