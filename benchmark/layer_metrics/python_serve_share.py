"""Share of the window's answers that reached the Python lanes: the
remainder of ``native_serve_share``."""
import stats

LAYER = "Python lanes"
UNIT = "%"
MOVES = "p50_us"


def read(ctx):
    native = stats.native_serve_percent(ctx)
    return None if native is None else 100.0 - native
