"""What a packet costs in ``_handle_raw`` before its first stamp, as a
share of the workers' busy time: the leaf ``query-ingress`` (RRL's
``decide`` or ``note_tcp``, the ``_fp_call`` of a frame the bulk serve
never saw, ``_decode_query``, the ``QueryCtx`` up to its own ``start``).
One of the three parts of ``busy_unnamed_share``."""
import loop_spans
import spans

LAYER = "Python lanes"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * loop_spans.ingress(ctx) / loop_spans.busy_s(ctx)
