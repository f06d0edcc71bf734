"""Median latency of the window's AAAA questions alone, on the generator's
clock from the due time.  The zone declines the type (NOTIMP), so this is
what an answer that is a header costs a client, beside ``a_p50_us``."""
import stats

LAYER = "load generator"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    return stats.qtype_percentile(ctx, "AAAA", 50)
