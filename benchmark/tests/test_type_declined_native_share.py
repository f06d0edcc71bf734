"""``type_declined_native_share``: of the window's answers, the share the
zone table's type row gave, on hand-made scrapes.  A program without the
counter, and a window without answers, give ``None`` and never raise."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "type_declined_native_share"


def reader():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()[NAME]


def scrape(a, aaaa, row=None):
    lines = ['binder_requests_completed{type="A"} %r' % a,
             'binder_requests_completed{type="AAAA"} %r' % aaaa,
             'binder_zone_serves %r' % (a // 2 + (row or 0))]
    if row is not None:
        lines.append('binder_zone_type_serves %r' % row)
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 151.0, "workers": after}}


@pytest.mark.parametrize("before,after,want", [
    # two workers: 440 + 420 row serves of 1,000 + 1,000 answers
    ([scrape(0, 0, 0), scrape(100, 100, 90)],
     [scrape(500, 500, 440), scrape(600, 600, 510)], 43.0),
    # only deltas count: what was served before the window is not in it
    ([scrape(300, 300, 250)], [scrape(400, 400, 300)], 25.0),
    # the counter is there and the mix has no declined type (the A cell)
    ([scrape(0, 0, 0)], [scrape(800, 0, 0)], 0.0),
    # the counter is there but nothing was answered
    ([scrape(10, 10, 5)], [scrape(10, 10, 5)], None),
    # a program without the counter (the parent of the PR that adds it)
    ([scrape(0, 0)], [scrape(500, 500)], None),
    ([], [], None),
], ids=["two-workers", "deltas", "no-declined-type", "no-answer",
        "no-counter", "no-workers"])
def test_type_declined_native_share(before, after, want):
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
                        "better": "higher", "source": "program_counter",
                        "layer": module.LAYER, "moves": module.MOVES,
                        "workloads": ["hosts_a_aaaa_open60"]}]
