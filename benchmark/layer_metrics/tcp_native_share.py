"""Of the answers the stream lane gave on one-shot connections
(``binder_tcp_fast_serves``), the share the native bulk frame serve gave,
from the answer cache or the zone table (``binder_tcp_native_serves``);
the rest were the Python lanes'.  In a cell whose TCP legs are all the
retries of answers that did not fit their UDP payload, it says how many
of them the zone table served whole.  Nothing to read on a program
without the counter, or in a window with no stream answer."""
import spans
import stream_spans

LAYER = "TCP stream lane"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * spans.counter(ctx, "binder_tcp_native_serves") \
        / stream_spans.stream_answers(ctx)
