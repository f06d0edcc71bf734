"""Median latency, from the due time, of the queries due in the first segment
of the window: before the workload's event, the group as it was settled.
The generator keeps its histogram once more for each segment that the
workload's ``segments_at_s`` cuts; this reads the generator alone, and the
answers behind it are every lane's together, as in ``p50_us``."""
import stats

LAYER = "load generator"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    return stats.segment_percentile(ctx, 0, 50)
