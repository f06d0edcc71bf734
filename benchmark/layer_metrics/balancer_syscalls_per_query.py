"""Kernel crossings of the balancer's packet path a query: ``syscalls`` of
its stats socket (``epoll_wait``, ``recvmmsg``, ``sendmmsg``, reads and
writes of the backends' sockets, counted where they are made) over the
queries it took between the scrapes.  It falls as batches grow; read
beside ``balancer_cpu_share``, it says whether a thread that is busy at
every rate is busy with queries."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "count"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return balancer_spans.grew(ctx, "syscalls") / balancer_spans.queries(ctx)
