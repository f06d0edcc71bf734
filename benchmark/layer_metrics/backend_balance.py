"""How evenly the balancer's affinity (a client's address stays with one
backend, new addresses are dealt in turn) spread the load: least over
greatest number of queries handed to a backend between the scrapes
(``backends[].forwarded`` of the balancer's stats socket)."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "ratio"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    handed = balancer_spans.forwarded(ctx)
    if not handed or max(handed) <= 0:
        return None
    return min(handed) / max(handed)
