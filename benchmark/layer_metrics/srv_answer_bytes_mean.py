"""Mean size of an SRV answer, UDP and TCP, C lanes and Python lanes alike:
``binder_response_size_bytes{type="SRV"}`` sum over count."""
import spans
import stream_spans

LAYER = "Python lanes"
UNIT = "bytes"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    size = "binder_response_size_bytes"
    count = stream_spans.positive(
        spans.counter(ctx, size + "_count", type="SRV"))
    return spans.counter(ctx, size + "_sum", type="SRV") / count
