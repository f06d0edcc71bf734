"""UDP answers dropped between the scrapes because the socket's send
buffer was still full at the one retry, every lane's together
(``binder_udp_send_drops_total``); 0 is a value.  Nothing to read on a
program without the counter: what it drops there, it drops unseen."""
import spans

LAYER = "kernel socket path"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return spans.counter(ctx, "binder_udp_send_drops_total")
