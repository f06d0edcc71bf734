"""The kernel's account of the workers over the ledger's: their CPU
seconds (``binder_process_cpu_seconds_total``, both modes, by
``os.times()`` at the scrapes) over their busy time (wall less
``loop-idle``).  1.0 means the two agree; above it the process was charged
CPU while its loop counted itself waiting inside ``select``."""
import loop_spans
import spans

LAYER = "event loop"
UNIT = "ratio"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return loop_spans.cpu_s(ctx) / loop_spans.busy_s(ctx)
