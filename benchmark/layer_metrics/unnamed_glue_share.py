"""The readiness callbacks' self time, as a share of the workers' busy
time: the event spans less every leaf and per-query stage observed inside
them (the callback's set-up, the C call's argument and list building, the
per-packet closure, a ``TcpConn``, a ``_fp_call`` and the header walk a
leg).  One of the three parts of ``busy_unnamed_share``."""
import loop_spans
import spans

LAYER = "event loop"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * loop_spans.glue_s(ctx) / loop_spans.busy_s(ctx)
