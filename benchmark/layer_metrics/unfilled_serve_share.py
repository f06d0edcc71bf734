"""% of the answers of the workers that were new at the closing scrape
(a roll's replacements: another pid than the shard had at the first
scrape) that they gave before they were *filled*
(``binder_unfilled_serves_total`` over ``binder_requests_completed``, both
from the worker's start).  A replacement reads its shard's sockets only
once its zone fill and precompile seed are complete, so 0 is the promise,
and a value.  Nothing to read where no worker was replaced or on a program
without the counter."""
import stats

LAYER = "precompile and zone fill"
UNIT = "%"
MOVES = "p50_us"
NAME = "binder_unfilled_serves_total"


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after:
        return None
    try:
        new = [a["metrics"] for b, a in stats.worker_pairs(before, after)
               if b is stats.FRESH]
    except (KeyError, TypeError):
        return None
    if not new or not all(stats.samples(text, NAME) for text in new):
        return None
    answers = sum(stats.total(text, "binder_requests_completed")
                  for text in new)
    if answers <= 0:
        return None
    return 100.0 * sum(stats.total(text, NAME) for text in new) / answers
