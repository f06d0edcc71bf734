"""Busy time that no span names: wall time less every leaf stage of the
ledger and every per-query stage, over wall time less ``loop-idle``.  The
overlay stages (``await``, ``upstream``, ``upstream-rtt``, ``loop-wait``)
are not summed."""
import spans

LAYER = "event loop"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    idle, wall = spans.stage(ctx, "loop-idle"), spans.wall_s(ctx)
    named = spans.stages(ctx, spans.LEDGER_STAGES + spans.QUERY_STAGES)
    if idle is None or not wall or wall <= idle:
        return None
    return 100.0 * (wall - named) / (wall - idle)
