"""The loop's own turn, as a share of the workers' busy time: busy (wall
less ``loop-idle``) less the event spans of every lane
(``binder_loop_event_seconds``): asyncio's ``_run_once`` and ``Handle._run``,
the selector's Python, the timers and tasks that hold no leaf.  One of the
three parts of ``busy_unnamed_share`` (``loop_spans.py``)."""
import loop_spans
import spans

LAYER = "event loop"
UNIT = "%"
MOVES = "p50_us"


@spans.reader
def read(ctx):
    return 100.0 * loop_spans.loop_turn_s(ctx) / loop_spans.busy_s(ctx)
