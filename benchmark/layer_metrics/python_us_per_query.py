"""Time a Python-lane query took from its context to its log line (its
per-query stages and ``log-line``), over the answers the C lanes did not
give."""
import spans

LAYER = "Python lanes"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    queries = spans.answers(ctx) - spans.native_serves(ctx)
    if spans.stage(ctx, "log-line") is None or queries <= 0:
        return None
    return 1e6 * spans.stages(ctx, spans.PYTHON_LANE_STAGES) / queries
