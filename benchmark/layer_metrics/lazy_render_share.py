"""Share of the answers rendered at query time because their set is above
``Precompiler.MAX_SET_RECORDS``: observations of the ``lazy-render``
stage over ``binder_requests_completed``.  Nothing to read where no such
render happened or the program has no such stage."""
import spans
import stream_spans

LAYER = "Python lanes"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * spans.stage(ctx, stream_spans.LAZY_STAGE, "count") \
        / spans.answers(ctx)
