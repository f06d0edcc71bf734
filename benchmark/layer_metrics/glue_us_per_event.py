"""A readiness callback's self time: the event spans less the leaves and
per-query stages inside them, over the events observed."""
import loop_spans
import spans

LAYER = "event loop"
UNIT = "us"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 1e6 * loop_spans.glue_s(ctx) / loop_spans.events(ctx, "count")
