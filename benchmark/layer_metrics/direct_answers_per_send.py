"""Answers one send of the direct-return lane carries, behind the balancer:
``binder_udp_datagrams{dir="out"}`` over the count of the stage
``udp-send`` (one ``sendmmsg`` of ``bal_flush`` for the native serves of one
read of the link, at most 64).  The reuseport group's counterpart on the
way in is ``recv_batch_mean``."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    if balancer_spans.fronts(ctx) is None:
        return None
    return (spans.counter(ctx, "binder_udp_datagrams", dir="out")
            / spans.stage(ctx, "udp-send", "count"))
