"""Share of the answers given by the native answer cache (the ``tier`` label
of ``binder_answer_cache_hits``); with the zone table's share it adds up to
``native_serve_share``."""
import spans

LAYER = "native answer cache and zone table"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return (100.0 * spans.counter(ctx, "binder_answer_cache_hits",
                                  tier="native") / spans.answers(ctx))
