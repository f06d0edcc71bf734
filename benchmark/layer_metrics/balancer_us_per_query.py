"""The balancer's CPU time a query: its process's CPU seconds between the
scrapes over the queries it took off its clients' sockets in that time
(``udp_queries`` + ``tcp_queries`` of its stats socket)."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 1e6 * balancer_spans.cpu_s(ctx) / balancer_spans.queries(ctx)
