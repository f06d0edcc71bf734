"""What the kernel crossings of one TCP leg cost a worker: the sums of the
ledger's ``tcp-accept``, ``tcp-recv``, ``tcp-send`` and ``tcp-close``
spans over the connections accepted.  The frames' serve is not in it (the
per-query stages' and the bulk frame serve's)."""
import spans
import stream_spans

LAYER = "TCP stream lane"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 1e6 * stream_spans.tcp(ctx) / stream_spans.legs(ctx)
