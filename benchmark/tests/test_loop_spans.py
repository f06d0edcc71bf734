"""The readers of the loop's own ledger (``loop_spans.py`` and the nine
files of ``layer_metrics/`` that use it), on hand-made scrapes: known sums
in, known values out; the three parts and ``tcp-register`` add up to
``busy_unnamed_share`` of the same scrapes; a scrape of a program without
the event family or the CPU counter and an empty ``ctx`` give ``None`` and
never raise; the hold's percentile is taken over all workers' buckets; a
replaced worker counts from zero."""
import json
import os

import pytest

import loop_spans
import spans
from test_spans import BENCH, metrics_text, parent_ctx, readers

SHARES = ("unnamed_loop_share", "unnamed_glue_share",
          "unnamed_ingress_share")
EVENT_READERS = SHARES + ("loop_us_per_turn", "glue_us_per_event",
                          "ingress_us_per_query", "event_hold_p99_us")
CPU_READERS = ("cpu_over_busy", "cpu_system_share")
NINE = EVENT_READERS + CPU_READERS
STEADY = ["hosts_zipf_open60", "services_srv_open60", "hosts_a_aaaa_open60",
          "services_srv_edns"]
SOCKET_LANES_ONLY = ("event_hold_p99_us",)
EDGES = (0.00001, 0.0001, 0.001, 0.01)


def event_text(lanes):
    """``{lane: (seconds, [events under each of EDGES, past the last])}``
    as the family's Prometheus text, cumulative buckets and all."""
    lines = []
    for lane, (seconds, cells) in lanes.items():
        running = 0
        for le, n in zip(EDGES + ("+Inf",), cells):
            running += n
            lines.append('%s_bucket{port="53",lane="%s",le="%s"} %d'
                         % (loop_spans.EVENT, lane, le, running))
        lines.append('%s_sum{port="53",lane="%s"} %r'
                     % (loop_spans.EVENT, lane, seconds))
        lines.append('%s_count{port="53",lane="%s"} %d'
                     % (loop_spans.EVENT, lane, running))
    return "\n".join(lines) + "\n"


def worker(sums, counts, lanes=None, cpu=None, **ident):
    counters = {}
    if cpu is not None:
        counters = {(loop_spans.CPU, '{mode="user"}'): cpu[0],
                    (loop_spans.CPU, '{mode="system"}'): cpu[1]}
    text = metrics_text(sums, counts, counters)
    if lanes is not None:
        text += event_text(lanes)
    return dict({"metrics": text, "status": {}}, **ident)


NO_EVENTS = {lane: (0.0, [0, 0, 0, 0, 0])
             for lane in ("udp", "tcp", "balancer", "deferred")}
ZERO = worker({s: 0.0 for s in loop_spans.INSIDE_STAGES + ("loop-idle",)},
              {s: 0 for s in loop_spans.INSIDE_STAGES + ("loop-idle",)},
              NO_EVENTS, cpu=(0.0, 0.0))

# one worker's 20 s: 12 s in select, 8 s busy; 6.5 s inside events, of
# which 4.9 s in stages spans.py knows, 0.4 s query-ingress, 0.2 s
# tcp-register: glue 1.0 s, the loop's turn 1.5 s
SUMS = {"loop-idle": 12.0, "udp-recv": 1.0, "native-serve": 0.9,
        "udp-send": 2.0, "log-write": 0.5, "tcp-close": 0.1,
        "cache-hit": 0.2, "log-after": 0.2,
        "query-ingress": 0.4, "tcp-register": 0.2,
        # overlays: summed nowhere
        "await": 50.0, "loop-wait": 50.0}
COUNTS = {"loop-idle": 600, "query-ingress": 80, "tcp-register": 20}
LANES = {"udp": (5.0, [100, 300, 90, 8, 2]),
         "tcp": (1.0, [0, 50, 40, 10, 0]),
         "balancer": (0.0, [0, 0, 0, 0, 0]),
         # a long deferred write: not among the holds
         "deferred": (0.5, [0, 0, 0, 0, 100])}


def known_ctx(workers=2):
    after = worker(SUMS, COUNTS, LANES, cpu=(6.0, 3.2))
    return {"before": {"at": 100.0, "workers": [ZERO] * workers},
            "after": {"at": 120.0, "workers": [after] * workers}}


@pytest.mark.parametrize("name,want", [
    # per worker: busy 8.0, events 6.5, inside 5.5
    ("unnamed_loop_share", 100.0 * 1.5 / 8.0),
    ("unnamed_glue_share", 100.0 * 1.0 / 8.0),
    ("unnamed_ingress_share", 100.0 * 0.4 / 8.0),
    ("loop_us_per_turn", 1e6 * 1.5 / 600),
    ("glue_us_per_event", 1e6 * 1.0 / 700),
    ("ingress_us_per_query", 1e6 * 0.4 / 80),
    # udp + tcp: 100, 350, 130, 18, 2 of 600: rank 594 lies under 10 ms
    ("event_hold_p99_us", 1e6 * 0.01),
    ("cpu_over_busy", 9.2 / 8.0),
    ("cpu_system_share", 100.0 * 3.2 / 9.2),
])
def test_reader_on_known_sums(name, want):
    assert readers()[name].read(known_ctx()) == pytest.approx(want)


def test_the_parts_add_up_to_busy_unnamed_share():
    ctx = known_ctx()
    got = readers()
    parts = sum(got[name].read(ctx) for name in SHARES) \
        + 100.0 * spans.stage(ctx, "tcp-register") / loop_spans.busy_s(ctx)
    assert parts == pytest.approx(got["busy_unnamed_share"].read(ctx),
                                  abs=0.01)
    assert got["busy_unnamed_share"].read(ctx) == pytest.approx(
        100.0 * (8.0 - 4.9) / 8.0)


def without(ctx, needle):
    """The same scrapes less every line that names *needle*."""
    for scrape in (ctx["before"], ctx["after"]):
        scrape["workers"] = [
            dict(w, metrics="\n".join(
                line for line in w["metrics"].splitlines()
                if needle not in line) + "\n")
            for w in scrape["workers"]]
    return ctx


@pytest.mark.parametrize("name", NINE)
@pytest.mark.parametrize("ctx", [
    {}, {"before": None, "after": None},
    {"before": {"at": 0.0, "workers": []},
     "after": {"at": 30.0, "workers": []}},
    {"before": {"at": 0.0, "workers": [{"metrics": "", "status": {}}]},
     "after": {"at": 30.0, "workers": [{"metrics": "", "status": {}}]}},
    parent_ctx(),
    without(without(without(known_ctx(), loop_spans.EVENT),
                    loop_spans.CPU), "query-ingress"),
], ids=["empty", "untraced", "no-workers", "blank-scrapes",
        "before-the-ledger", "parent"])
def test_reader_gives_none_where_there_is_nothing_to_read(name, ctx):
    assert readers()[name].read(ctx) is None


def test_each_reader_needs_only_its_own_family():
    """A scrape without the CPU counter still reads the spans, and one
    without the event family still reads the CPU counter's split."""
    got = readers()
    no_cpu = without(known_ctx(), loop_spans.CPU)
    for name in CPU_READERS:
        assert got[name].read(no_cpu) is None
    for name in EVENT_READERS:
        assert got[name].read(no_cpu) is not None
    no_events = without(known_ctx(), loop_spans.EVENT)
    for name in ("unnamed_loop_share", "unnamed_glue_share",
                 "loop_us_per_turn", "glue_us_per_event",
                 "event_hold_p99_us"):
        assert got[name].read(no_events) is None
    for name in CPU_READERS + ("unnamed_ingress_share",
                               "ingress_us_per_query"):
        assert got[name].read(no_events) is not None


def test_the_hold_is_taken_over_all_workers_buckets_not_a_worker_each():
    """One worker's events all under 10 us, the other's 3% past 1 ms:
    the first worker's own p99 is 10 us, the second's 10 ms, and the
    group's, over 2,000 events of which 30 are slow, 10 ms too; the
    mean or the worst of per-worker percentiles would read otherwise
    once the slow worker has few events."""
    quick = dict(NO_EVENTS, udp=(0.01, [1000, 0, 0, 0, 0]))
    slow = dict(NO_EVENTS, udp=(0.3, [970, 0, 0, 30, 0]))
    few = dict(NO_EVENTS, udp=(0.01, [7, 0, 0, 3, 0]))

    def ctx(*lanes):
        return {"before": {"at": 0.0, "workers": [ZERO] * len(lanes)},
                "after": {"at": 10.0, "workers": [
                    worker({"loop-idle": 5.0}, {"loop-idle": 10}, l)
                    for l in lanes]}}

    read = readers()["event_hold_p99_us"].read
    assert read(ctx(quick)) == pytest.approx(10.0)
    assert read(ctx(slow)) == pytest.approx(10000.0)
    assert read(ctx(quick, slow)) == pytest.approx(10000.0)
    # 3 slow events of 1,010: under the group's p99, though a third of
    # that worker's own
    assert read(ctx(quick, few)) == pytest.approx(10.0)
    # past the last edge: that edge
    late = dict(NO_EVENTS, tcp=(9.0, [0, 0, 0, 0, 5]))
    assert read(ctx(late)) == pytest.approx(1e6 * EDGES[-1])


def test_a_replaced_worker_counts_from_zero():
    """A shard whose pid changed between the scrapes (a roll) started
    inside the window: everything its closing scrape holds is the
    window's (``stats.worker_pairs``), where an unchanged worker's
    opening scrape is subtracted."""
    half = worker({k: v / 2 for k, v in SUMS.items()},
                  {k: v // 2 for k, v in COUNTS.items()},
                  {lane: (s / 2, [n // 2 for n in cells])
                   for lane, (s, cells) in LANES.items()}, cpu=(3.0, 1.6))
    whole = worker(SUMS, COUNTS, LANES, cpu=(6.0, 3.2))
    ctx = {"before": {"at": 100.0, "workers": [
               dict(half, shard=0, pid=10), dict(half, shard=1, pid=11)]},
           "after": {"at": 120.0, "workers": [
               dict(whole, shard=0, pid=10), dict(whole, shard=1, pid=99)]}}
    # the kept worker grew by a half, the new one by the whole
    assert loop_spans.events(ctx) == pytest.approx(1.5 * 6.5)
    assert loop_spans.events(ctx, "count") == pytest.approx(350 + 700)
    assert loop_spans.cpu_s(ctx) == pytest.approx(1.5 * 9.2)
    assert loop_spans.ingress(ctx, "count") == pytest.approx(40 + 80)
    assert sum(n for _, n in loop_spans.hold_buckets(ctx)) \
        == pytest.approx(300 + 600)


def test_the_manifest_states_what_the_readers_state():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NINE:
        module, entry = readers()[name], by_name[name]
        assert entry["unit"] == module.UNIT
        assert entry["layer"] == module.LAYER
        assert entry["moves"] == module.MOVES == "p50_us"
        assert entry["better"] == "lower"
        # by name: the four steady cells of a reuseport group; never the
        # rolled one (a delta of worker counters undercounts there); behind
        # the balancer the event span is the link's (lane "balancer"), so
        # the split of busy time, a packet's ingress (``_handle_raw`` is
        # the link's Python lane too) and the CPU's account hold, and the
        # one that reads the socket lanes' events (``udp``, ``tcp``) does
        # not
        assert set(entry["workloads"]) <= set(cells)
        assert entry["workloads"][:4] == STEADY
        assert "hosts_zipf_rolling" not in entry["workloads"]
        assert ("hosts_zipf_balancer_open60" in entry["workloads"]) \
            == (name not in SOCKET_LANES_ONLY)
        assert entry["source"] == ("program_counter" if name in CPU_READERS
                                   else "program_span")
