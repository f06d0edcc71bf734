"""What one lazy render costs: the ``lazy-render`` stage's sum over its
count (plan, records and encode of a set above 64 records)."""
import spans
import stream_spans

LAYER = "Python lanes"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    renders = stream_spans.positive(
        spans.stage(ctx, stream_spans.LAZY_STAGE, "count"))
    return 1e6 * spans.stage(ctx, stream_spans.LAZY_STAGE) / renders
