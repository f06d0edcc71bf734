"""``log_direct_share``: the share of the Python lanes' query-log lines
that were rendered straight to bytes, on hand-made scrapes.  A program
without the counter, and a window without a Python-lane line, give
``None`` and never raise."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()["log_direct_share"]


def scrape(direct=None, logged=None):
    lines = ['binder_requests_completed{type="A"} 1000']
    if direct is not None:
        lines.append('binder_query_log_lines{path="direct",port="53"} %r'
                     % direct)
    if logged is not None:
        lines.append('binder_query_log_lines{path="logging",port="53"} %r'
                     % logged)
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 151.0, "workers": after}}


@pytest.mark.parametrize("before,after,want", [
    # two workers: 900 + 600 lines direct, 0 + 1 through logging
    ([scrape(100, 0), scrape(0, 0)], [scrape(1000, 0), scrape(600, 1)],
     100.0 * 1500 / 1501),
    # only deltas count: what was logged before the window is not in it
    ([scrape(0, 50)], [scrape(30, 60)], 75.0),
    # a logger that is no JSON stream: every line through logging
    ([scrape(0, 0)], [scrape(0, 40)], 0.0),
    # the counter is there but the Python lanes logged nothing
    ([scrape(5, 5)], [scrape(5, 5)], None),
    # a program without the counter (the parent of the PR that adds it)
    ([scrape()], [scrape()], None),
    ([], [], None),
], ids=["two-workers", "deltas", "all-logging", "zero-lines", "no-counter",
        "no-workers"])
def test_log_direct_share(before, after, want):
    got = reader().read(ctx(before, after))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("empty", [{}, {"before": None, "after": None}])
def test_nothing_to_read_is_none(empty):
    assert reader().read(empty) is None


def test_the_manifest_states_what_the_reader_states():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == "log_direct_share")
    module = reader()
    assert entry == {"name": "log_direct_share", "unit": module.UNIT,
                     "better": "higher", "source": "program_counter",
                     "layer": module.LAYER, "moves": module.MOVES,
                     # (the log's one writer serves every lane, the
                     # balancer's link too)
                     "workloads": ["hosts_zipf_open60",
                                   "services_srv_open60",
                                   "hosts_a_aaaa_open60",
                                   "services_srv_edns",
                                   "hosts_zipf_balancer_open60"]}
