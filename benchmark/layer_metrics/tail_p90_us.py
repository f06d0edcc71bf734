"""90th percentile of the window's latency, from the due time.  Steady
within 2-4% on a machine that is quiet or freezes evenly, but a machine
that drifts or freezes for a second at a time moves it by 15-40% between
two sets of the same code (PERF.md section 6), so it carries no bound; the
bounded metric is the median, ``p50_us``."""
import stats

LAYER = "Python lanes"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    g = ctx["generator"]
    if not g["latency_ns"]:
        return None
    return stats.hist_percentile(g["latency_ns"], g["hist_bits"], 90) / 1e3
