"""How long one readiness callback holds the loop, 99th percentile: of
``binder_loop_event_seconds`` over the lanes ``udp`` and ``tcp`` (the
callbacks that stand in front of an answer), all workers' bucket deltas
added before the percentile is taken; a bucket's upper edge, and the last
finite edge for an event past it."""
import loop_spans
import spans
import stats

LAYER = "event loop"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    buckets = loop_spans.hold_buckets(ctx)
    p99 = stats.bucketed_percentile(buckets, 99)
    if p99 == float("inf"):
        p99 = max(le for le, _ in buckets if le != float("inf"))
    return 1e6 * p99
