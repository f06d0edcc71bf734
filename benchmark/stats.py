"""The benchmark's arithmetic: percentiles from the generator's histograms,
sums and histogram deltas from Prometheus text, spreads of runs, and which
queries a stop of the machine covers."""
import re
import statistics

import numpy as np


def bucket_bounds(idx: int, bits: int):
    """[low, high) of one bucket of the generator's log-linear histogram:
    values under 2**(bits+1) have a bucket each, above that every power of
    two is cut into 2**bits buckets."""
    if idx < (1 << (bits + 1)):
        return idx, idx + 1
    shift = (idx >> bits) - 1
    low = (idx - (shift << bits)) << shift
    return low, low + (1 << shift)


def hist_count(hist: list) -> int:
    return sum(count for _, count in hist)


def hist_percentile(hist: list, bits: int, q: float) -> float:
    """The q-th percentile (0-100) of ``[[bucket, count], ...]``, linear
    inside the bucket that holds it; the rank is the nearest-rank one
    (the smallest value with at least q% of the samples at or under it)."""
    total = hist_count(hist)
    if total == 0:
        raise ValueError("percentile of an empty histogram")
    rank = max(1.0, q / 100.0 * total)
    seen = 0
    for idx, count in sorted(hist):
        if seen + count >= rank:
            low, high = bucket_bounds(idx, bits)
            return low + (high - low) * (rank - seen) / count
        seen += count
    raise AssertionError("unreachable")


SAMPLE_RX = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})? ([0-9.eE+-]+|NaN)$", re.M)
LABEL_RX = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def samples(text: str, name: str) -> list:
    """Every sample of one metric in Prometheus text, as
    ``(labels dict, value)``."""
    return [(dict(LABEL_RX.findall(m.group(2) or "")), float(m.group(3)))
            for m in SAMPLE_RX.finditer(text) if m.group(1) == name]


def total(text: str, name: str) -> float:
    return sum(value for _, value in samples(text, name))


def histogram_delta(before: str, after: str, name: str) -> list:
    """``[(upper bound, count in the window), ...]`` of a Prometheus
    histogram, summed over its other labels, from two scrapes."""
    def buckets(text):
        out = {}
        for labels, value in samples(text, name + "_bucket"):
            le = float(labels["le"].replace("+Inf", "inf"))
            out[le] = out.get(le, 0.0) + value
        return out

    b, a = buckets(before), buckets(after)
    cumulative = sorted((le, a[le] - b.get(le, 0.0)) for le in a)
    out, prev = [], 0.0
    for le, c in cumulative:
        out.append((le, c - prev))
        prev = c
    return out


def bucketed_percentile(buckets: list, q: float):
    """Upper bound of the bucket that holds the q-th percentile (what a
    fixed-bucket histogram can say), or None when it counted nothing."""
    count = sum(c for _, c in buckets)
    if count <= 0:
        return None
    rank, seen = q / 100.0 * count, 0.0
    for le, c in buckets:
        seen += c
        if seen >= rank:
            return le
    return buckets[-1][0]


def spread(values: list) -> float:
    """Distance between the first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# -- a stop of the machine (README.md, "A stop of the machine") --
# Every time is a whole number of nanoseconds from the window's first due
# time, as the generator writes them: 249,999,999 is under the rule's
# quarter second and 250,000,000 is not, whatever a float would say.

#: a stop this long or longer covers queries; a shorter one is only named
STOP_COVERS_NS = 250_000_000
#: how many lengths after its end a stop still covers, as a fraction: two
#: and a quarter.  Behind a stop the generator sends the whole backlog at
#: once, the workers' socket buffers stay full and two queries in three
#: time out until the backlog is served: for 1.98 s behind a stop of 1.50 s
#: and 4.68 s behind one of 2.71 s in ``hosts_zipf_open60`` at 0.6 of its
#: knee (PERF.md section 6, PR 44), a line of slope 2.24
STOP_TAIL = (9, 4)
#: the kinds of failure a stop can cost; a wrong answer (``rcode``,
#: ``ancount``) is the program's whenever it was due
VOIDABLE = frozenset(("timeout", "tcp", "send", "overflow",
                      "unanswered_at_end"))


def machine_stops(gaps_by_thread: list, least_ns: int) -> list:
    """``[(start, length), ...]``: every interval of *least_ns* or more
    that lies inside a gap of every sender thread (each thread's gaps as
    ``[start, length]``).  A gap that one thread alone saw is the
    scheduler's or the generator's and names nothing."""
    if not gaps_by_thread:
        return []
    common = [(s, s + n) for s, n in gaps_by_thread[0]]
    for gaps in gaps_by_thread[1:]:
        common = [(max(a, s), min(b, s + n)) for a, b in common
                  for s, n in gaps if min(b, s + n) > max(a, s)]
    return [(a, b - a) for a, b in sorted(common) if b - a >= least_ns]


def covered_spans(stops: list, timeout_ns: int, join_ns: int) -> list:
    """``[(first, last), ...]``, both ends included and no two touching:
    the due times that stops cover.  Stops less than *join_ns* apart are
    one stop from the first's start to the last's end (the machine ran for
    an instant between them: a stop of 2.18 s, one of 0.12 s and one of
    0.41 s, each beginning where the last ended, left the backlog of one
    stop of 2.71 s); a stop of ``STOP_COVERS_NS`` or more covers from the
    timeout before it began (a query due then could still be out when the
    machine stood) to its end plus ``STOP_TAIL`` lengths."""
    joined = []
    for start, length in sorted(stops):
        if joined and start - joined[-1][1] < join_ns:
            joined[-1][1] = max(joined[-1][1], start + length)
        else:
            joined.append([start, start + length])
    spans = []
    for start, end in joined:
        if end - start < STOP_COVERS_NS:
            continue
        first = start - timeout_ns
        last = end + STOP_TAIL[0] * (end - start) // STOP_TAIL[1]
        if spans and first <= spans[-1][1]:
            spans[-1] = (spans[-1][0], max(last, spans[-1][1]))
        else:
            spans.append((first, last))
    return spans


def void_account(sends, failures, kinds: list, spans: list,
                 cuts_ns: list) -> dict:
    """What the spans leave of a window's counts.  *sends*: the due time
    of every measured send; *failures*: rows of (due time, index into
    *kinds*) of every one that failed or was unanswered at the end.  A
    covered query leaves both counts, answered or not, but one that got a
    wrong answer: that one stays in both.  ``failed_by_segment`` counts,
    as the generator does, the failures it saw itself (not
    ``unanswered_at_end``) by the segment their due time lies in."""
    sends = np.asarray(sends, dtype=np.int64)
    failures = np.asarray(failures, dtype=np.int64).reshape(-1, 2)
    due, kind = failures[:, 0], failures[:, 1]

    def inside(times):
        hit = np.zeros(len(times), dtype=bool)
        for first, last in spans:
            hit |= (times >= first) & (times <= last)
        return hit

    voidable = np.isin(kind, [i for i, k in enumerate(kinds)
                              if k in VOIDABLE])
    voided = inside(due) & voidable
    wrong_in_spans = int((inside(due) & ~voidable).sum())
    left = ~voided
    seen = left & (kind != kinds.index("unanswered_at_end"))
    segment = np.searchsorted(np.asarray(cuts_ns, dtype=np.int64),
                              due[seen], side="right")
    return {"queries": int(inside(sends).sum()) - wrong_in_spans,
            "of_them_failed": int(voided.sum()),
            "failed_by_kind": {k: int((kind[left] == i).sum())
                               for i, k in enumerate(kinds)},
            "failed_by_segment": np.bincount(
                segment, minlength=len(cuts_ns) + 1).tolist()}


# -- helpers for the per-layer readers (layer_metrics/*.py) --

def qtype_percentile(ctx: dict, qtype: str, q: float):
    """The q-th percentile, in microseconds, of the latency of the mix
    entries that ask *qtype*, from the generator's per-entry histograms;
    None where the mix asks no such type or none was answered."""
    g = ctx.get("generator") or {}
    by_entry = g.get("latency_ns_by_entry")
    mix = (ctx.get("workload") or {}).get("mix")
    if not by_entry or not mix:
        return None
    merged = {}
    for entry, hist in zip(mix, by_entry):
        if entry["qtype"] != qtype:
            continue
        for bucket, count in hist["latency_ns"]:
            merged[bucket] = merged.get(bucket, 0) + count
    if not merged:
        return None
    return hist_percentile(list(merged.items()), g["hist_bits"], q) / 1e3


def segment_percentile(ctx: dict, i: int, q: float):
    """The q-th percentile, in microseconds, of the latency of the
    queries that were *due* in the i-th segment of the window (the
    workload's ``segments_at_s`` cut it; the generator keeps its
    histogram once more for each); None where the window is not cut, has
    no such segment, or none of its queries was answered."""
    g = ctx.get("generator") or {}
    segments = g.get("latency_ns_by_segment") or []
    if len(segments) < 2 or not 0 <= i < len(segments):
        return None
    hist = segments[i]["latency_ns"]
    if not hist:
        return None
    return hist_percentile(hist, g["hist_bits"], q) / 1e3


#: the scrape that stands before a worker that was not there yet: every
#: counter of a process that started inside the window starts from zero
FRESH = {"metrics": "", "status": {}}


def worker_pairs(before: dict, after: dict) -> list:
    """``[(before, after), ...]`` per worker of the closing scrape, paired
    by shard.  A shard whose pid changed between the scrapes was replaced
    (a roll, a respawn): the new process counts from zero, so ``FRESH``
    stands before it; what the old one did between the first scrape and
    its exit is in no scrape.  Scrapes that name no shard (hand-made
    ones) pair in order."""
    if not all("shard" in w and "pid" in w
               for w in before["workers"] + after["workers"]):
        return list(zip(before["workers"], after["workers"]))
    was = {w["shard"]: w for w in before["workers"]}
    return [(b if b is not None and b["pid"] == a["pid"] else FRESH, a)
            for a in after["workers"] for b in [was.get(a["shard"])]]


def replaced_workers(before: dict, after: dict) -> int:
    return sum(1 for b, _ in worker_pairs(before, after) if b is FRESH)


def window_delta(ctx: dict, name: str) -> list:
    """Per worker, how much a counter of its own ``/metrics`` grew
    between the two scrapes of a traced run."""
    return [total(a["metrics"], name) - total(b["metrics"], name)
            for b, a in worker_pairs(ctx["before"], ctx["after"])]


def native_serve_percent(ctx: dict):
    """Share of the window's answers that the C lanes gave: zone-table
    serves plus the answer-cache hits that the Python AnswerCache
    (``/status``) did not count itself, over requests completed.  The
    subtraction is ``chip_smoke.py``'s, until the program splits the
    counter."""
    served = sum(window_delta(ctx, "binder_requests_completed"))
    if served <= 0:
        return None
    native = sum(window_delta(ctx, "binder_zone_serves")) \
        + sum(window_delta(ctx, "binder_answer_cache_hits")) \
        - sum(a["status"]["answer_cache"]["hits"]
              - (b["status"].get("answer_cache") or {"hits": 0})["hits"]
              for b, a in worker_pairs(ctx["before"], ctx["after"]))
    return 100.0 * native / served


def shard_balance(ctx: dict):
    """Least over greatest per-worker share of the requests between the
    scrapes, from the supervisor's ``binder_shard_requests``."""
    def by_shard(scrape):
        return {labels.get("shard"): value for labels, value in samples(
            scrape["supervisor"]["metrics"], "binder_shard_requests")}

    before, after = by_shard(ctx["before"]), by_shard(ctx["after"])
    grew = [after[shard] - before.get(shard, 0.0) for shard in after]
    if not grew or max(grew) <= 0:
        return None
    return min(grew) / max(grew)


def loop_lag_p99_ms(ctx: dict):
    """Event-loop lag between the scrapes, 99th percentile on the worst
    worker, from ``binder_loop_lag_seconds`` (a bucket's upper edge)."""
    worst = None
    for b, a in worker_pairs(ctx["before"], ctx["after"]):
        buckets = histogram_delta(b["metrics"], a["metrics"],
                                  "binder_loop_lag_seconds")
        p99 = bucketed_percentile(buckets, 99)
        if p99 == float("inf"):         # past the last edge: say that edge
            p99 = max(le for le, _ in buckets if le != float("inf"))
        if p99 is not None:
            worst = p99 if worst is None else max(worst, p99)
    return None if worst is None else worst * 1e3
