"""Median latency of the window's A questions alone, on the generator's
clock from the due time: where the mix has more than one entry the
generator keeps its histogram once more for each.  Beside ``aaaa_p50_us``
it says which kind of question waits; the answers are the native lanes'
and the Python lanes' together, as in ``p50_us``."""
import stats

LAYER = "load generator"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    return stats.qtype_percentile(ctx, "A", 50)
