"""Event-loop lag, 99th percentile on the worst worker (a bucket's upper
edge)."""
import stats

LAYER = "Python lanes"
UNIT = "ms"
MOVES = "p50_us"


def read(ctx):
    return stats.loop_lag_p99_ms(ctx)
