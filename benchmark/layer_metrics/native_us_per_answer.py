"""Time of the native serve loop (parse, probe, patch, log-line format, miss
surfacing: after ``recvmmsg`` to before ``sendmmsg``) per answer the C lanes
gave."""
import spans

LAYER = "native answer cache and zone table"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    served = spans.native_serves(ctx)
    if not served or served <= 0:
        return None
    return 1e6 * spans.stage(ctx, "native-serve") / served
