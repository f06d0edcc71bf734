"""Share of the window's answers given by the C lanes (zone table and
native answer cache)."""
import stats

LAYER = "native answer cache and zone table"
UNIT = "%"
MOVES = "p50_us"


def read(ctx):
    return stats.native_serve_percent(ctx)
