"""``tcp_native_share``: of the stream lane's answers, the share the
native bulk frame serve gave, on hand-made scrapes.  A program without
the counter, and a window with no stream answer, give ``None`` and never
raise."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "tcp_native_share"


def reader():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()[NAME]


def scrape(fast, native=None):
    lines = ['binder_requests_completed{type="SRV"} 1000',
             'binder_tcp_accepts{port="53"} %r' % fast,
             'binder_tcp_fast_serves{port="53"} %r' % fast]
    if native is not None:
        lines.append('binder_tcp_native_serves{port="53"} %r' % native)
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 151.0, "workers": after}}


@pytest.mark.parametrize("before,after,want", [
    # two workers: 500 + 350 native serves of 700 + 300 stream answers
    ([scrape(100, 0), scrape(0, 0)], [scrape(800, 500), scrape(300, 350)],
     85.0),
    # only deltas count: what was served before the window is not in it
    ([scrape(200, 150)], [scrape(240, 160)], 25.0),
    # the counter is there and every leg went to the Python lanes
    ([scrape(10, 0)], [scrape(410, 0)], 0.0),
    # the counter is there but no connection came (the hosts cell)
    ([scrape(0, 0)], [scrape(0, 0)], None),
    # a program without the counter (the parent of the PR that adds it)
    ([scrape(0)], [scrape(400)], None),
    ([], [], None),
], ids=["two-workers", "deltas", "all-python", "no-stream-answer",
        "no-counter", "no-workers"])
def test_tcp_native_share(before, after, want):
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
                        "workloads": ["services_srv_open60",
                                      "services_srv_edns"]}]
