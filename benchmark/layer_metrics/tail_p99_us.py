"""99th percentile of the window's latency, from the due time.  The chip
host's sandbox freezes whole for about 110 ms at irregular times (PERF.md
section 6); a window that holds a freeze reads 150-340 ms here and one that
does not reads 5 ms, so this cannot carry a bound.  It stands beside the
bounded ``p50_us`` so that a pause shows to a reader."""
import stats

LAYER = "Python lanes"
UNIT = "us"
MOVES = "p50_us"


def read(ctx):
    g = ctx["generator"]
    if not g["latency_ns"]:
        return None
    return stats.hist_percentile(g["latency_ns"], g["hist_bits"], 99) / 1e3
