"""Stall time of single workers: the instants of the rings that are no
freeze of the sandbox (``sandbox_freeze_ms``).  The program's own pauses.
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
    return None if split is None else split[1]
