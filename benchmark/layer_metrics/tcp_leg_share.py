"""How many queries came back a second time: answers the stream lane gave
on one-shot connections (``binder_tcp_fast_serves``) as a share of the UDP
answers sent (``binder_udp_datagrams{dir="out"}``).  In a cell whose
clients fetch every truncated answer again over a TCP connection of its
own, it equals ``udp_tc_share``."""
import spans
import stream_spans

LAYER = "TCP stream lane"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * stream_spans.stream_answers(ctx) \
        / stream_spans.udp_answers(ctx)
