"""How late sends left against the schedule, 99th percentile (open loops).
Small against the cell's own p99_us, or the generator was starved."""
import stats

LAYER = "load generator"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    g = ctx["generator"]
    if not g["late_ns"]:
        return None
    return stats.hist_percentile(g["late_ns"], g["hist_bits"], 99) / 1e3
