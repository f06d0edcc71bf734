"""Share of the workers' busy time (wall time less ``loop-idle``) spent
inside the four ``tcp-*`` spans: the stream lane's kernel crossings."""
import spans
import stream_spans

LAYER = "TCP stream lane"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * stream_spans.tcp(ctx) / stream_spans.busy_s(ctx)
