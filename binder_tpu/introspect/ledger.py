"""The worker's time ledger: leaf spans of ``binder_query_stage_seconds``.

The per-query stages (``QueryCtx.stamp``) name what a Python-lane query
spent; they cover a few percent of a worker's second.  The ledger adds
the rest as *leaf* spans, timed where the work happens, that overlap
neither each other nor the per-query stages, so that their sums over a
window add up to the worker's wall time less a remainder a reader can
report as such:

==============  =================  ====================================
stage           observed per       where the clock is read
==============  =================  ====================================
``loop-idle``   ``select`` call    :class:`TimingSelector`, inside select
``udp-recv``    ``recvmmsg`` call  C (``native/fastio``), EAGAIN included
``native-serve``  batch            C, after recvmmsg to before sendmmsg;
                                   a ``fastpath_serve_frames`` call (the
                                   stream lane's bulk frame serve)
``udp-send``    ``sendmmsg`` call  C
``log-write``   log write          ``BinderServer._write_log``
``log-line``    Python-lane line   ``BinderServer._on_after``
``tcp-accept``  ``accept`` call    ``DnsServer._on_accept_ready``, EAGAIN
                                   included; with the new socket's
                                   ``setblocking``
``tcp-recv``    ``recv`` call      ``TcpConn._on_readable``, the EOF and
                                   EAGAIN reads included
``tcp-send``    ``send``/          ``TcpConn._flush`` and
                ``sendmsg`` call   ``_on_writable``
``tcp-close``   connection closed  ``TcpConn.close`` (and ``abort``): the
                                   selector unregistrations and ``close``
``tcp-register``  selector change  ``TcpConn.start``, ``_flush``,
                of an open leg     ``_on_writable``: an ``add_reader``,
                                   ``add_writer`` or ``remove_writer``
                                   outside ``tcp-close`` (an
                                   ``epoll_ctl`` each)
``query-ingress``  packet in       ``DnsServer._handle_raw``: its entry
                ``_handle_raw``    to the ``QueryCtx``'s own ``start``,
                                   or to the return of a packet that
                                   ends there (an RRL drop or slip, a
                                   native serve, a malformed packet)
==============  =================  ====================================

The ``tcp-*`` spans are the stream lane's kernel crossings
(``dns/stream.py``); the frames' serve between them is
``native-serve``'s (the bulk frame serve, one observation a call) and,
for the frames it declines, ``query-ingress`` and the per-query
stages'.  A connection's reader *registration* after its first serve,
the one crossing of a one-shot leg that no other span names, is
``tcp-register``'s.

**The event span** is the parent whose children the leaf stages are:
every readiness callback the loop calls on the served path is
registered through :func:`event`, which times the whole callback into
``binder_loop_event_seconds{lane}``, a family of its own.  It is no
stage of ``binder_query_stage_seconds``: it overlays the leaves inside
it, and a reader that sums that histogram's stages would count them
twice.  With it a worker's busy time (wall less ``loop-idle``) splits
three ways: *the loop's own turn* (busy less the events: asyncio's
``_run_once``, the selector's Python, the timers and tasks that hold no
leaf), *the callbacks' glue* (the events less the leaves and per-query
stages inside them: their self time) and what a stage names.  An event
never nests in another: the wrap is put on at the registration
(``add_reader``, ``add_writer``, ``call_soon``, ``call_later``), not in
the method, so an accept event holds the first read of the legs it
accepted and that read is observed once.

``binder_process_cpu_seconds_total{mode}`` is the process's user and
system CPU time by ``os.times()``, read at a scrape only
(:func:`install_process_cpu`): what ``/proc/<pid>/stat`` says of the
same process, beside the ledger's own busy time.

Always on, like the stage histogram: no switch, option or environment
variable.  Every span reads ``CLOCK_MONOTONIC``.  The Python spans
observe straight into their stage's child of the histogram; the C spans
accumulate sum, count and cells on the stage grid in ``fastio_io`` and
are folded in by deltas at scrape, as the native per-qtype latency is
(``HistogramChild.merge``).
"""
from __future__ import annotations

import asyncio
import os
import selectors
import threading
import time
from typing import Optional, Sequence

from binder_tpu.metrics.collector import (DEFAULT_STAGE_BUCKETS,
                                          HistogramChild)

METRIC_STAGE_HISTOGRAM = "binder_query_stage_seconds"
STAGE_HISTOGRAM_HELP = "per-stage decomposition of request processing time"
METRIC_EVENT_HISTOGRAM = "binder_loop_event_seconds"
METRIC_PROCESS_CPU = "binder_process_cpu_seconds_total"

#: the stream lane's four (dns/stream.py), in the order a one-shot leg
#: passes them
TCP_STAGES = ("tcp-accept", "tcp-recv", "tcp-send", "tcp-close")
#: the ledger's leaf stages (docs/observability.md); the per-query
#: stages beside them are whatever ``QueryCtx.stamp`` was given
LEAF_STAGES = ("loop-idle", "udp-recv", "native-serve", "udp-send",
               "log-write", "log-line") + TCP_STAGES \
    + ("tcp-register", "query-ingress")
#: the event span's lanes: the UDP readers, the stream lane's accept,
#: reader and writer callbacks, the balancer lane's sockets, and the
#: ``call_soon`` and timer callbacks that hold a leaf outside a
#: socket's callback (a deferred log write, a late flush)
EVENT_LANES = ("udp", "tcp", "balancer", "deferred")


def stage_child(collector, stage: str) -> HistogramChild:
    """The stage histogram's child for one stage."""
    return collector.histogram(
        METRIC_STAGE_HISTOGRAM, STAGE_HISTOGRAM_HELP,
        buckets=DEFAULT_STAGE_BUCKETS).labelled({"stage": stage})


def event(collector, lane: str):
    """The event span of one lane: ``run(fn, *args)`` reads the clock,
    calls ``fn(*args)`` and observes how long it held the loop into
    ``binder_loop_event_seconds{lane}``.  Made once a lane and handed
    to the loop in front of the callback (``loop.add_reader(fd, run,
    callback)``), so a call allocates nothing."""
    observe = collector.histogram(
        METRIC_EVENT_HISTOGRAM,
        "time one readiness callback of the served path held the loop",
        buckets=DEFAULT_STAGE_BUCKETS).labelled({"lane": lane}).observe
    monotonic = time.monotonic

    def run(fn, *args):
        t0 = monotonic()
        try:
            return fn(*args)
        finally:
            observe(monotonic() - t0)
    return run


def install_process_cpu(collector) -> None:
    """Export the process's CPU seconds, user and system, as
    ``binder_process_cpu_seconds_total{mode}``: ``os.times()`` read in
    a pre-scrape hook and nowhere else."""
    counter = collector.counter(
        METRIC_PROCESS_CPU,
        "CPU time of this process by os.times(), read at the scrape")
    children = (counter.labelled({"mode": "user"}),
                counter.labelled({"mode": "system"}))
    last = [0.0, 0.0]
    lock = threading.Lock()     # scrapes run on their own threads

    def fold() -> None:
        with lock:
            now = os.times()[:2]
            for i, child in enumerate(children):
                child.inc(max(0.0, now[i] - last[i]))
                last[i] = now[i]
    collector.on_expose(fold)


class SpanFold:
    """Folds one monotone span source kept in C into its stage's child
    by the delta since the last fold.  A source that stepped back (a
    test's ``io_stats(True)``, a grid handed over anew) is taken as the
    new baseline and that fold is skipped, never folded as negative."""

    def __init__(self, collector, stage: str) -> None:
        self.child = stage_child(collector, stage)
        self.skipped = 0
        self._cells: Sequence[int] = ()
        self._sum = 0.0
        self._lock = threading.Lock()   # scrapes run on their own threads

    def fold(self, cells: Sequence[int], total: float) -> None:
        with self._lock:
            cells = list(cells)
            last = self._cells or [0] * len(cells)
            delta = [c - p for c, p in zip(cells, last)]
            stepped_back = (len(last) != len(cells) or min(delta) < 0
                            or total < self._sum)
            self._cells, prev_sum, self._sum = cells, self._sum, total
            if stepped_back:
                self.skipped += 1
            elif any(delta):
                self.child.merge(delta, total - prev_sum)


class TimingSelector(selectors.DefaultSelector):
    """The loop's selector with the wait timed: ``loop-idle`` is the
    time inside ``select()``, its count the ``epoll_wait`` calls.  A
    loop is made before its process has a collector, so the waits are
    timed from ``install_loop_idle`` on."""

    def __init__(self) -> None:
        super().__init__()
        self.observe = None     # the `loop-idle` child's, once installed

    def select(self, timeout=None):
        observe = self.observe
        if observe is None:
            return super().select(timeout)
        t0 = time.monotonic()
        try:
            return super().select(timeout)
        finally:
            observe(time.monotonic() - t0)


def timed_loop() -> asyncio.AbstractEventLoop:
    """``loop_factory`` for ``asyncio.Runner``: a selector loop that
    carries its timing selector as ``ledger_selector``."""
    selector = TimingSelector()
    loop = asyncio.SelectorEventLoop(selector)
    loop.ledger_selector = selector
    return loop


def run(main):
    """``asyncio.run(main)`` on a ``timed_loop`` (``asyncio.run`` takes
    a ``loop_factory`` from Python 3.12 only, ``Runner`` from 3.11)."""
    with asyncio.Runner(loop_factory=timed_loop) as runner:
        return runner.run(main)


def install_loop_idle(collector) -> Optional[HistogramChild]:
    """Time the running loop's waits into ``collector``'s ``loop-idle``
    (workers, the supervisor and the single process alike).  A loop
    that ``timed_loop`` did not make has nothing to time."""
    selector = getattr(asyncio.get_running_loop(), "ledger_selector", None)
    if selector is None:
        return None
    child = stage_child(collector, "loop-idle")
    selector.observe = child.observe
    return child
