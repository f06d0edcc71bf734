"""What one turn of the loop costs outside every event: busy time less the
event spans, over the ``select`` calls (``loop-idle``'s count)."""
import loop_spans
import spans

LAYER = "event loop"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 1e6 * loop_spans.loop_turn_s(ctx) \
        / spans.stage(ctx, "loop-idle", "count")
