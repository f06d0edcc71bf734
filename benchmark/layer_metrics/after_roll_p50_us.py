"""Median latency, from the due time, of the queries due in the third segment
of the window: after the roll, the new workers filling or filled.
The generator keeps its histogram once more for each segment that the
workload's ``segments_at_s`` cuts; this reads the generator alone, and the
answers behind it are every lane's together, as in ``p50_us``."""
import stats

LAYER = "load generator"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    return stats.segment_percentile(ctx, 2, 50)
