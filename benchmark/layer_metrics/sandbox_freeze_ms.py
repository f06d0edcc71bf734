"""Stall time the workers shared: instants of the loop-lag watchdogs' rings
that three quarters of the workers have within 150 ms of each other, each
counted once with the worst worker's lag.  A freeze of the whole sandbox.
0 is a value.  Left out only of a window as short as a CPU rehearsal's
(``spans.REHEARSAL_WINDOW_S``), because the accepted rehearsal test wants
every non-% metric above 0; a ``benchmark`` PR lifts that."""
import spans

LAYER = "event loop"
UNIT = "ms"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    split = spans.stall_split(ctx)
    return None if split is None else split[0]
