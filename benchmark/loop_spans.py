"""Arithmetic shared by the per-layer readers of what the time ledger says
of the loop itself: the event span around every readiness callback of the
served path (``binder_loop_event_seconds{lane}``, a family of its own: it
overlays the leaf stages inside it), the leaves ``query-ingress`` (a packet
in ``_handle_raw`` before its first stamp) and ``tcp-register`` (a selector
change of an open leg), and the workers' CPU seconds by ``os.times()``
(``binder_process_cpu_seconds_total{mode}``).

With them a worker's busy time splits three ways::

    busy = (busy - events) + (events - inside) + inside

the loop's own turn (asyncio's machinery, the selector's Python, the few
timers), the callbacks' glue (their self time) and what a stage names.
``busy_unnamed_share`` holds the first two and the two new leaves, which
``spans.py``'s lists do not know.

Built on ``spans.py``: deltas between the two scrapes of a traced run,
summed over the workers, ``None`` where the program exports no such span
or counter (a program older than these), which the readers pass on.
"""
import spans
import stats
from stream_spans import busy_s

EVENT = "binder_loop_event_seconds"
CPU = "binder_process_cpu_seconds_total"
INGRESS = "query-ingress"
REGISTER = "tcp-register"
#: every stage observed inside an event: the ledger's leaves but the
#: wait, the per-query stages of the Python lanes, and the two leaves
#: that ``spans.py``'s lists leave to ``busy_unnamed_share``
INSIDE_STAGES = tuple(s for s in spans.LEDGER_STAGES if s != "loop-idle") \
    + spans.QUERY_STAGES + (INGRESS, REGISTER)
#: the lanes whose callbacks stand in front of an answer: a socket's
#: reader, writer or accept (a deferred log write or late flush holds
#: the loop as long, but runs after the answers of its turn left)
HOLD_LANES = ("udp", "tcp")


def events(ctx, part="sum"):
    """Seconds inside the event spans of every lane (``part="sum"``) or
    the events observed (``"count"``); None without the family."""
    return spans.counter(ctx, f"{EVENT}_{part}")


def loop_turn_s(ctx):
    """Busy time outside every event: the loop's own turn."""
    return busy_s(ctx) - events(ctx)


def glue_s(ctx):
    """The events' self time: what the callbacks spent in no leaf and
    no per-query stage."""
    return events(ctx) - spans.stages(ctx, INSIDE_STAGES)


def ingress(ctx, part="sum"):
    return spans.stage(ctx, INGRESS, part)


def cpu_s(ctx, **labels):
    """CPU seconds the workers were charged between the scrapes (both
    modes, or ``mode="user"`` / ``mode="system"``)."""
    return spans.counter(ctx, CPU, **labels)


def _lanes_only(text):
    keep = tuple(f'lane="{lane}"' for lane in HOLD_LANES)
    return "\n".join(line for line in text.splitlines()
                     if line.startswith(EVENT + "_bucket")
                     and any(k in line for k in keep)) + "\n"


def hold_buckets(ctx):
    """``[(upper edge, events in the window), ...]`` of the lanes
    ``HOLD_LANES``, the workers' bucket deltas added edge by edge; None
    where no worker has the family."""
    ps = spans.pairs(ctx)
    if ps is None:
        return None
    total = {}
    for b, a in ps:
        for le, n in stats.histogram_delta(_lanes_only(b["metrics"]),
                                           _lanes_only(a["metrics"]), EVENT):
            total[le] = total.get(le, 0.0) + n
    return sorted(total.items()) or None
