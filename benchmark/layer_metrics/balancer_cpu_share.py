"""The balancer's CPU seconds between the scrapes (``/proc/<pid>/stat`` of
the pid in ``balancer.pid``, both modes), % of one core over the time
between them.  The balancer is one thread: 100 is the ceiling of the
topology, whatever the number of instances behind it."""
import balancer_spans
import spans

LAYER = "balancer front end"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * balancer_spans.cpu_s(ctx) / (
        ctx["after"]["at"] - ctx["before"]["at"])
