"""Share of the UDP drains that followed another in their readiness
callback, with no ``select`` between (``binder_udp_chained_drains_total``)
among all drains: every callback of the ``udp`` lane
(``binder_loop_event_seconds``'s count) starts with one that followed
none.  Nothing to read on a program without the counter."""
import loop_spans
import spans

LAYER = "kernel socket path"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    chained = spans.counter(ctx, "binder_udp_chained_drains_total")
    first = spans.counter(ctx, loop_spans.EVENT + "_count", lane="udp")
    return 100.0 * chained / (first + chained)
