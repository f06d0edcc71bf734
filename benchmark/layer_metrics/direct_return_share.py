"""Queries handed to a backend that answers the client itself, over the
balancer's socket passed to it (``direct_forwards`` of the balancer's
stats socket), % of all queries handed to backends between the scrapes
(the backends' ``forwarded``): the rest came back through the balancer,
the relay lane (TCP queries always do)."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    handed = sum(balancer_spans.forwarded(ctx))
    if handed <= 0:
        return None
    return 100.0 * balancer_spans.grew(ctx, "direct_forwards") / handed
