"""Share of the window's answers that the zone table gave by the
question's type alone (``binder_zone_type_serves``: the type row's
answer to a type the engine declines before any lookup), over the answers
(``binder_requests_completed``).  In a cell half of whose questions are of
a declined type it says how much of that half never reached the Python
lanes: the rest were the sampled drains' and what the row declined for
want of room in the log ring.  Nothing to read on a program without the
counter."""
import spans

LAYER = "native answer cache and zone table"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * spans.counter(ctx, "binder_zone_type_serves") \
        / spans.answers(ctx)
