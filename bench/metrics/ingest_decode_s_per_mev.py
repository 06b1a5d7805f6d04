"""ingest_decode_s_per_mev: host seconds decoding log segments
(``ingest.decode``: split into lines and ``json.loads`` each, one segment
at a time) per million events folded in the window."""

import _program

WRAPS = ()
_program.start()


def read(ctx):
    return _program.seconds_per_mev(ctx, "ingest.decode")
