"""Arithmetic shared by the per-layer readers of the program's time
ledger (``binder_query_stage_seconds`` leaf spans, the socket and log
counters beside them, and the loop-lag watchdog's ring of stall
instants).

Everything is a delta between the two scrapes of a traced run, summed
over the workers.  Every function returns ``None`` where the program
exports no such span, counter or ring (a program older than the ledger,
a ``ctx`` without scrapes), and the readers pass that on: a metric with
nothing to read is left out of the line.
"""
import functools
import math

import stats

STAGE = "binder_query_stage_seconds"

#: the stream lane's four leaf spans, in the order a one-shot leg passes
#: them: one kernel crossing of a TCP connection each (``accept``,
#: ``recv``, ``send``, ``close``)
TCP_STAGES = ("tcp-accept", "tcp-recv", "tcp-send", "tcp-close")
#: spans that neither overlap each other nor the per-query stages: one
#: ``select``, ``recvmmsg`` or ``sendmmsg`` call each, the native serve
#: loop between them, a write of the native log ring, a Python-lane
#: log line, the stream lane's crossings
LEDGER_STAGES = ("loop-idle", "udp-recv", "native-serve", "udp-send",
                 "log-write", "log-line") + TCP_STAGES
#: the per-query cursor stages of the Python lanes that run on the loop
#: (``QueryCtx.stamp``).  ``await`` and ``upstream`` span a wait of the
#: loop and ``upstream-rtt`` / ``loop-wait`` overlay it: none of the
#: four is summed anywhere here.
QUERY_STAGES = ("cache-hit", "precompile-hit", "store-lookup", "pre-resp",
                "lazy-render", "log-after", "dispatch", "splice", "rebuild",
                "foreign-stale", "foreign-withheld")
#: what one Python-lane query of the cells' kinds passes through
#: (``lazy-render`` stands in place of ``store-lookup`` and ``pre-resp``
#: where a set is rendered at query time)
PYTHON_LANE_STAGES = ("cache-hit", "precompile-hit", "store-lookup",
                      "pre-resp", "lazy-render", "log-after", "log-line")
#: one kernel crossing per observation.  ``log-line`` is none: a
#: Python-lane line is rendered to bytes and leaves with the next
#: ``log-write``
SYSCALL_STAGES = ("loop-idle", "udp-recv", "udp-send", "log-write") \
    + TCP_STAGES
#: the calls that move a query in or an answer out
SOCKET_STAGES = ("udp-recv", "udp-send") + TCP_STAGES

FREEZE_SHARE = 0.75     # of the workers, stalled ...
FREEZE_WITHIN_S = 0.15  # ... within this of each other: the sandbox


def reader(fn):
    """A reader never raises: a scrape that lacks what it reads gives
    ``None``."""
    @functools.wraps(fn)
    def guarded(ctx):
        try:
            value = fn(ctx)
        except (KeyError, TypeError, ValueError, IndexError,
                AttributeError, ZeroDivisionError):
            return None
        if value is None or not math.isfinite(value):
            return None
        return value
    return guarded


def pairs(ctx):
    """``[(before, after), ...]`` per worker, by shard, or None.  A worker
    whose pid changed between the scrapes (a roll) is taken as starting
    from zero (``stats.worker_pairs``)."""
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after:
        return None
    return stats.worker_pairs(before, after) or None


def wall_s(ctx):
    """Seconds between the scrapes times the number of workers: the
    time the ledger has to account for."""
    ps = pairs(ctx)
    if ps is None:
        return None
    return (ctx["after"]["at"] - ctx["before"]["at"]) * len(ps)


def counter(ctx, name, **labels):
    """How much one counter grew over all workers; None when no worker
    exports a sample of that name with those labels."""
    ps = pairs(ctx)
    if ps is None:
        return None

    def value(scrape):
        hits = [v for lab, v in stats.samples(scrape["metrics"], name)
                if all(lab.get(k) == want for k, want in labels.items())]
        return sum(hits) if hits else None

    grew, seen = 0.0, False
    for b, a in ps:
        now = value(a)
        if now is None:
            continue
        seen = True
        grew += now - (value(b) or 0.0)
    return grew if seen else None


def stage(ctx, name, part="sum"):
    """Seconds (``part="sum"``) or observations (``"count"``) one stage
    gained over all workers, or None when no worker has that stage."""
    return counter(ctx, f"{STAGE}_{part}", stage=name)


def stages(ctx, names, part="sum"):
    """Sum over several stages; a stage no worker has counts as 0, and
    the whole is None only when none of them is there."""
    got = [v for v in (stage(ctx, n, part) for n in names) if v is not None]
    return sum(got) if got else None


def answers(ctx):
    grew = counter(ctx, "binder_requests_completed")
    return grew if grew and grew > 0 else None


def native_serves(ctx):
    """Answers the C lanes gave: zone-table serves plus native
    answer-cache hits (the ``tier`` label splits the hit counter)."""
    cache = counter(ctx, "binder_answer_cache_hits", tier="native")
    if cache is None:
        return None
    return cache + (counter(ctx, "binder_zone_serves") or 0.0)


def per_answer_us(ctx, names):
    total, n = stages(ctx, names), answers(ctx)
    if total is None or n is None:
        return None
    return 1e6 * total / n


def stall_split(ctx):
    """``(freeze_ms, stall_ms)`` from the workers' rings of stall
    instants (``/status`` ``loop.stalls``: ``t_mono`` on the clock of
    ``scrape["at"]``, ``lag_s``), cut to the window between the scrapes.

    Instants within ``FREEZE_WITHIN_S`` of the earliest of them form one
    event.  An event that ``FREEZE_SHARE`` of the workers share is a
    freeze of the sandbox and counts once, with the worst worker's lag;
    every other instant is that worker's own stall and counts with its
    lag.  With a single worker nothing is shared and every instant is a
    stall.  None where no worker has a ring; 0 is a value."""
    ps = pairs(ctx)
    if ps is None:
        return None
    lo, hi = ctx["before"]["at"], ctx["after"]["at"]
    instants, rings = [], 0
    for worker, (_, after) in enumerate(ps):
        ring = (after["status"].get("loop") or {}).get("stalls")
        if not isinstance(ring, list):
            continue
        rings += 1
        instants += [(s["t_mono"], s["lag_s"], worker) for s in ring
                     if lo <= s["t_mono"] <= hi]
    if not rings:
        return None
    instants.sort()
    need = max(2, math.ceil(FREEZE_SHARE * len(ps)))
    freeze = stall = 0.0
    i = 0
    while i < len(instants):
        j = i
        while (j < len(instants)
               and instants[j][0] - instants[i][0] <= FREEZE_WITHIN_S):
            j += 1
        event = instants[i:j]
        if len({w for _, _, w in event}) >= need:
            freeze += max(lag for _, lag, _ in event)
        else:
            stall += sum(lag for _, lag, _ in event)
        i = j
    return 1e3 * freeze, 1e3 * stall
