"""The program's own busy share: the part of the workers' wall time between
the scrapes that their loops did not spend inside ``select()``
(``loop-idle``), to stand beside ``/proc``'s CPU share."""
import spans

LAYER = "event loop"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    idle, wall = spans.stage(ctx, "loop-idle"), spans.wall_s(ctx)
    if idle is None or not wall:
        return None
    return 100.0 * (1.0 - idle / wall)
