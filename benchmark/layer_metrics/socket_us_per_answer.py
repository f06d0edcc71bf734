"""Time inside ``recvmmsg`` and ``sendmmsg`` per answer."""
import spans

LAYER = "kernel socket path"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return spans.per_answer_us(ctx, ("udp-recv", "udp-send"))
