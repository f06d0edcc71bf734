"""Datagrams a ``recvmmsg`` call brought, over the calls that brought any."""
import spans

LAYER = "kernel socket path"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    calls = spans.counter(ctx, "binder_udp_batch_size_count")
    if not calls:
        return None
    return spans.counter(ctx, "binder_udp_datagrams", dir="in") / calls
