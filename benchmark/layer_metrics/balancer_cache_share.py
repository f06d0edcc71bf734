"""Answers the balancer gave from its own answer cache (``cache_hits`` of
its stats socket), % of the queries it took between the scrapes.  0 is a
value: a query that goes to a direct-return backend is never looked up
(``mbalancer.cpp`` ``handle_udp``: no answer of such a backend passes the
balancer, so nothing could fill the cache)."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * balancer_spans.grew(ctx, "cache_hits") \
        / balancer_spans.queries(ctx)
