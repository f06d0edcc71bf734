"""Bytes one write of the query log carries: the stream's growth
(``binder_query_log_bytes``) over the writes of the window (``log-write``'s
count).  A UDP callback that chains its drains writes once, after the
last of them, so the mean rises with the drains a callback holds; the
few lines that went through ``logging`` are in the bytes and in no
write.  Nothing to read on a program without the counter or the span,
or in a window without a write."""
import spans

LAYER = "query log"
UNIT = "bytes"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return spans.counter(ctx, "binder_query_log_bytes") \
        / spans.stage(ctx, "log-write", "count")
