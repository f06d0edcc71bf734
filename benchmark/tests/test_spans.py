"""The readers of the program's time ledger (``spans.py`` and the eleven
files of ``layer_metrics/`` that use it), on hand-made scrapes: known
sums in, known values out; a scrape of a program without the ledger and
an empty ``ctx`` give ``None`` from every reader and never raise."""
import os
import sys

import pytest

import spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("loop_busy_share", "busy_unnamed_share", "syscalls_per_answer",
       "recv_batch_mean", "socket_us_per_answer", "native_us_per_answer",
       "native_cache_share", "python_us_per_query", "log_us_per_answer",
       "sandbox_freeze_ms", "worker_stall_ms")


def readers():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()


def metrics_text(stage_sums, stage_counts, counters):
    """Prometheus text of one worker: stages as ``{name: seconds}`` and
    ``{name: observations}``, counters as ``{(name, labels): value}``."""
    lines = []
    for stage, v in stage_sums.items():
        lines.append('binder_query_stage_seconds_sum{port="53",stage="%s"} %r'
                     % (stage, v))
    for stage, v in stage_counts.items():
        lines.append('binder_query_stage_seconds_count{port="53",'
                     'stage="%s"} %r' % (stage, v))
    for (name, labels), v in counters.items():
        lines.append("%s%s %r" % (name, labels, v))
    return "\n".join(lines) + "\n"


def worker(sums=None, counts=None, counters=None, stalls=()):
    return {"metrics": metrics_text(sums or {}, counts or {},
                                    counters or {}),
            "status": {"loop": {"stalls": [
                {"t_mono": t, "lag_s": lag} for t, lag in stalls]}}}


ZERO = worker(
    sums={s: 0.0 for s in spans.LEDGER_STAGES + spans.QUERY_STAGES},
    counts={s: 0 for s in spans.LEDGER_STAGES + spans.QUERY_STAGES},
    counters={("binder_requests_completed", '{type="A"}'): 0,
              ("binder_answer_cache_hits", '{tier="native"}'): 0,
              ("binder_answer_cache_hits", '{tier="python"}'): 0,
              ("binder_zone_serves", ""): 0,
              ("binder_udp_datagrams", '{dir="in"}'): 0,
              ("binder_udp_datagrams", '{dir="out"}'): 0,
              ("binder_udp_batch_size_count", ""): 0})


def known_ctx():
    """Two workers, 20 s between the scrapes: 40 s of wall time.  Each
    worker idles 12 s and serves 1,000 answers: 900 from C (880 zone,
    20 native cache) and 100 from the Python lanes."""
    after = worker(
        sums={"loop-idle": 12.0, "udp-recv": 1.0, "native-serve": 0.9,
              "udp-send": 2.0, "log-write": 0.5, "log-line": 0.3,
              "cache-hit": 0.2, "precompile-hit": 0.1,
              "store-lookup": 0.15, "pre-resp": 0.05, "log-after": 0.4,
              # overlays: must not be summed anywhere
              "await": 50.0, "upstream": 50.0, "upstream-rtt": 50.0,
              "loop-wait": 50.0},
        counts={"loop-idle": 600, "udp-recv": 700, "native-serve": 500,
                "udp-send": 550, "log-write": 450, "log-line": 100,
                "cache-hit": 60, "log-after": 100},
        counters={("binder_requests_completed", '{type="A"}'): 990,
                  ("binder_requests_completed", '{type="PTR"}'): 10,
                  ("binder_answer_cache_hits", '{tier="native"}'): 20,
                  ("binder_answer_cache_hits", '{tier="python"}'): 60,
                  ("binder_zone_serves", ""): 880,
                  ("binder_udp_datagrams", '{dir="in"}'): 1000,
                  ("binder_udp_datagrams", '{dir="out"}'): 1000,
                  ("binder_udp_batch_size_count", ""): 500})
    return {"before": {"at": 100.0, "workers": [ZERO, ZERO]},
            "after": {"at": 120.0, "workers": [after, after]}}


@pytest.mark.parametrize("name,want", [
    # 40 s of wall, 24 s idle
    ("loop_busy_share", 100.0 * (1 - 24.0 / 40.0)),
    # named: 2 x (12 + 1 + .9 + 2 + .5 + .3 + .2 + .1 + .15 + .05 + .4)
    ("busy_unnamed_share", 100.0 * (40.0 - 2 * 17.6) / (40.0 - 24.0)),
    # 2 x (600 + 700 + 550 + 450) crossings for 2,000 answers: the 100
    # Python-lane lines are rendered, not written
    ("syscalls_per_answer", 2 * 2300 / 2000.0),
    ("recv_batch_mean", 2.0),
    ("socket_us_per_answer", 1e6 * 2 * 3.0 / 2000),
    ("native_us_per_answer", 1e6 * 2 * 0.9 / 1800),
    ("native_cache_share", 100.0 * 40 / 2000),
    # cache-hit .. log-after and log-line, over the 200 the C lanes left
    ("python_us_per_query", 1e6 * 2 * 1.2 / 200),
    ("log_us_per_answer", 1e6 * 2 * 0.8 / 2000),
    ("sandbox_freeze_ms", 0.0),
    ("worker_stall_ms", 0.0),
])
def test_reader_on_known_sums(name, want):
    assert readers()[name].read(known_ctx()) == pytest.approx(want)


def test_native_cache_share_and_zone_share_add_up_to_native_serve_share():
    """With the label the two shares need no subtraction; the accepted
    reader's subtraction (hits of both tiers less ``/status``'s Python
    hits) must read the same number."""
    ctx = known_ctx()
    for scrape, hits in ((ctx["before"], 0), (ctx["after"], 60)):
        for w in scrape["workers"]:
            w["status"] = dict(w["status"], answer_cache={"hits": hits})
    zone_share = 100.0 * 2 * 880 / 2000
    assert (readers()["native_cache_share"].read(ctx) + zone_share
            == pytest.approx(readers()["native_serve_share"].read(ctx),
                             abs=0.1))


def stall_ctx(rings, workers=4, lo=100.0, hi=160.0):
    ws = [worker(stalls=rings.get(i, ())) for i in range(workers)]
    return {"before": {"at": lo, "workers": [ZERO] * workers},
            "after": {"at": hi, "workers": ws}}


def test_three_workers_sharing_an_instant_is_a_freeze_one_alone_a_stall():
    ctx = stall_ctx({0: [(110.00, 0.090), (130.0, 0.060)],
                     1: [(110.02, 0.110)],
                     2: [(110.10, 0.070)]})
    # the shared instant counts once, with the worst worker's lag
    assert readers()["sandbox_freeze_ms"].read(ctx) == pytest.approx(110.0)
    assert readers()["worker_stall_ms"].read(ctx) == pytest.approx(60.0)


def test_two_of_four_are_two_stalls_and_only_the_window_counts():
    ctx = stall_ctx({0: [(99.0, 0.5), (120.0, 0.08), (161.0, 0.5)],
                     3: [(120.1, 0.07)]})
    assert readers()["sandbox_freeze_ms"].read(ctx) == 0.0
    assert readers()["worker_stall_ms"].read(ctx) == pytest.approx(150.0)


def test_a_freeze_needs_the_instants_within_150_ms():
    ctx = stall_ctx({0: [(120.0, 0.1)], 1: [(120.2, 0.1)],
                     2: [(120.4, 0.1)], 3: [(120.6, 0.1)]})
    assert readers()["sandbox_freeze_ms"].read(ctx) == 0.0
    assert readers()["worker_stall_ms"].read(ctx) == pytest.approx(400.0)


def test_a_single_worker_shares_nothing():
    one = stall_ctx({0: [(120.0, 0.1)]}, workers=1)
    assert spans.stall_split(one) == pytest.approx((0.0, 100.0))


def test_a_window_as_short_as_a_rehearsals_reports_both_and_0_is_a_value():
    short = stall_ctx({0: [(101.0, 0.1)]}, hi=104.0)
    assert readers()["sandbox_freeze_ms"].read(short) == 0.0
    assert readers()["worker_stall_ms"].read(short) == pytest.approx(100.0)
    quiet = stall_ctx({}, hi=104.0)
    assert readers()["sandbox_freeze_ms"].read(quiet) == 0.0
    assert readers()["worker_stall_ms"].read(quiet) == 0.0


def parent_ctx():
    """What the parent of the ledger exports: per-query stages only, one
    unlabelled hit counter, ``loop.stalls`` a count."""
    def w(n):
        return {"metrics": metrics_text(
                    {"cache-hit": 0.1 * n, "log-after": 0.2 * n},
                    {"cache-hit": 5 * n, "log-after": 5 * n},
                    {("binder_requests_completed", '{type="A"}'): 100 * n,
                     ("binder_answer_cache_hits", ""): 50 * n,
                     ("binder_zone_serves", ""): 40 * n}),
                "status": {"loop": {"stalls": 0},
                           "answer_cache": {"hits": 5 * n}}}
    return {"before": {"at": 1.0, "workers": [w(0), w(0)]},
            "after": {"at": 31.0, "workers": [w(1), w(1)]}}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("ctx", [
    {}, {"before": None, "after": None},
    {"before": {"at": 0.0, "workers": []},
     "after": {"at": 30.0, "workers": []}},
    {"before": {"at": 0.0, "workers": [{"metrics": "", "status": {}}]},
     "after": {"at": 30.0, "workers": [{"metrics": "", "status": {}}]}},
    parent_ctx(),
], ids=["empty", "untraced", "no-workers", "blank-scrapes", "parent"])
def test_reader_gives_none_where_there_is_nothing_to_read(name, ctx):
    assert readers()[name].read(ctx) is None


def test_the_manifest_lists_the_eleven_readers_by_name():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert by_name[name]["source"] == "program_counter"
        # every cell that asks one of the zones over UDP sockets of the
        # workers' own and replaces no worker inside its window reports all
        # eleven; the cell behind the balancer those that do not read such
        # a socket (``test_topology_group.py`` says which)
        listed = set(by_name[name]["workloads"])
        assert {"hosts_a_aaaa_open60", "hosts_zipf_open60",
                "services_srv_edns", "services_srv_open60"} <= listed
        assert listed <= {"hosts_a_aaaa_open60", "hosts_zipf_open60",
                          "services_srv_edns", "services_srv_open60",
                          "hosts_zipf_balancer_open60"}
