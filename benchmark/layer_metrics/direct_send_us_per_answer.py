"""Time an instance behind the balancer spends inside the sends of the
direct-return lane, per answer: the stage ``udp-send`` (``bal_flush``'s
``sendmmsg`` on the balancer's socket, passed to the instance; the Python
lanes' ``send_batch`` on the same socket) over the answers.  An instance
behind a balancer sends on no other UDP socket.  ``socket_us_per_answer``
is not read there: the link's read (``recv_fds``) is under no stage, so it
would hold the out half alone under a name that says both."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    if balancer_spans.fronts(ctx) is None:
        return None
    return spans.per_answer_us(ctx, ("udp-send",))
