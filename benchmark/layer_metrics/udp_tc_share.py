"""Share of the UDP answers that left with TC=1
(``binder_truncated_responses`` over ``binder_udp_datagrams{dir="out"}``):
the control beside ``tcp_leg_share``, since every such answer is fetched
again once.  Nothing to read on a program without the counter."""
import spans
import stream_spans

LAYER = "Python lanes"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * stream_spans.truncated(ctx) \
        / stream_spans.udp_answers(ctx)
