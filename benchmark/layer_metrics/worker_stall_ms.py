"""Stall time of single workers: the instants of the rings that are no
freeze of the sandbox (``sandbox_freeze_ms``).  The program's own pauses.
0 is a value."""
import spans

LAYER = "event loop"
UNIT = "ms"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    split = spans.stall_split(ctx)
    return None if split is None else split[1]
