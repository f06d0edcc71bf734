"""Share of the ``recvmmsg`` calls (``udp-recv``'s count) that brought
nothing: the calls less those ``binder_udp_batch_size`` counted.  What a
chain of drains pays to learn that the socket is empty."""
import spans

LAYER = "kernel socket path"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    calls = spans.stage(ctx, "udp-recv", "count")
    return 100.0 * (calls - spans.counter(
        ctx, "binder_udp_batch_size_count")) / calls
