"""Observations of the four ``tcp-*`` spans over the connections accepted:
the calls a leg makes (an ``accept`` and the EAGAIN that ends its burst,
the frame's ``recv`` and the EOF's, a ``send``, a ``close``).  The reader
registration after the first serve is no span and is not counted."""
import spans
import stream_spans

LAYER = "TCP stream lane"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return stream_spans.tcp(ctx, "count") / stream_spans.legs(ctx)
