"""The median of the queries due while the roll ran over the median of
those due before it (``in_roll_p50_us`` over ``before_roll_p50_us``): 1.0
is a roll nobody feels."""
import stats

LAYER = "load generator"
UNIT = "ratio"
MOVES = "p50_us"


def read(ctx):
    before = stats.segment_percentile(ctx, 0, 50)
    during = stats.segment_percentile(ctx, 1, 50)
    if not before or during is None:
        return None
    return during / before
