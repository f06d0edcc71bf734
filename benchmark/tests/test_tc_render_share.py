"""``tc_render_share``: of the UDP answers that left with TC=1, the share
a resolve rendered the whole set for, on hand-made scrapes.  A program
without the counter, and a window in which nothing left truncated, give
``None`` and never raise."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "tc_render_share"


def reader():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()[NAME]


def scrape(truncated, renders=None):
    lines = ['binder_requests_completed{type="SRV"} 1000',
             'binder_truncated_responses{type="A",port="53"} 0',
             'binder_truncated_responses{type="SRV",port="53"} %r'
             % truncated]
    if renders is not None:
        lines.append('binder_truncated_renders{port="53"} %r' % renders)
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 151.0, "workers": after}}


@pytest.mark.parametrize("before,after,want", [
    # two workers: 60 + 40 renders of 700 + 300 truncated answers
    ([scrape(100, 0), scrape(0, 0)], [scrape(800, 60), scrape(300, 40)],
     10.0),
    # only deltas count: what was rendered before the window is not in it
    ([scrape(200, 50)], [scrape(240, 60)], 25.0),
    # every truncated answer of the window was a cache's
    ([scrape(10, 5)], [scrape(50, 5)], 0.0),
    # the counter is there but nothing left truncated (the hosts cell)
    ([scrape(0, 0)], [scrape(0, 0)], None),
    # a program without the counter (the parent of the PR that adds it)
    ([scrape(0)], [scrape(400)], None),
    ([], [], None),
], ids=["two-workers", "deltas", "all-cached", "none-truncated",
        "no-counter", "no-workers"])
def test_tc_render_share(before, after, want):
    got = reader().read(ctx(before, after))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("empty", [{}, {"before": None, "after": None}])
def test_nothing_to_read_is_none(empty):
    assert reader().read(empty) is None


def test_the_manifest_states_what_the_reader_states():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] == NAME]
    module = reader()
    assert entries == [{"name": NAME, "unit": module.UNIT,
                        "better": "lower", "source": "program_counter",
                        "layer": module.LAYER, "moves": module.MOVES,
                        "workloads": ["services_srv_open60",
                                      "services_srv_edns"]}]
