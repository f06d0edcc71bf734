"""Stall time the workers shared: instants of the loop-lag watchdogs' rings
that three quarters of the workers have within 150 ms of each other, each
counted once with the worst worker's lag.  A freeze of the whole sandbox.
0 is a value."""
import spans

LAYER = "event loop"
UNIT = "ms"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    split = spans.stall_split(ctx)
    return None if split is None else split[0]
