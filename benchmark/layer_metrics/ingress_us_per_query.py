"""What one packet costs in ``_handle_raw`` before its first stamp (or up
to the return that ends it): ``query-ingress``'s sum over its count."""
import loop_spans
import spans

LAYER = "Python lanes"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 1e6 * loop_spans.ingress(ctx) / loop_spans.ingress(ctx, "count")
