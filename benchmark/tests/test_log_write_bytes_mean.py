"""``log_write_bytes_mean``: the bytes one write of the query log
carries, on hand-made scrapes.  A program without the counter or the
span, and a window without a write, give ``None`` and never raise."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader():
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()["log_write_bytes_mean"]


def scrape(nbytes=None, writes=None):
    lines = ['binder_requests_completed{type="A"} 1000']
    if nbytes is not None:
        lines.append('binder_query_log_bytes{port="53"} %r' % nbytes)
    if writes is not None:
        lines.append('binder_query_stage_seconds_count'
                     '{port="53",stage="log-write"} %r' % writes)
        lines.append('binder_query_stage_seconds_sum'
                     '{port="53",stage="log-write"} %r' % (65e-6 * writes))
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 151.0, "workers": after}}


@pytest.mark.parametrize("before,after,want", [
    # two workers: a write a drain of 3.1 lines of 388 bytes, and one a
    # callback of 3.1 such drains
    ([scrape(0, 0), scrape(0, 0)],
     [scrape(1203 * 300, 300), scrape(3729 * 100, 100)],
     (1203 * 300 + 3729 * 100) / 400),
    # only deltas count: what was written before the window is not in it
    ([scrape(5000, 10)], [scrape(5000 + 3300 * 40, 50)], 3300.0),
    # the counter is there but nothing was written in the window
    ([scrape(5000, 10)], [scrape(5000, 10)], None),
    # the span without the counter, the counter without the span
    ([scrape(None, 0)], [scrape(None, 40)], None),
    ([scrape(0, None)], [scrape(4000, None)], None),
    # a program without either
    ([scrape()], [scrape()], None),
    ([], [], None),
], ids=["two-workers", "deltas", "no-write", "no-counter", "no-span",
        "neither", "no-workers"])
def test_log_write_bytes_mean(before, after, want):
    got = reader().read(ctx(before, after))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("empty", [{}, {"before": None, "after": None}])
def test_nothing_to_read_is_none(empty):
    assert reader().read(empty) is None


def test_the_manifest_states_what_the_reader_states():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "log_write_bytes_mean"]    # by name
    module = reader()
    # the four steady cells of a reuseport group first, as PR 46 listed
    # them; behind them whatever cell a later PR found the log's one writer
    # in (the instances behind the balancer write it the same way)
    assert entry["workloads"][:4] == [
        "hosts_zipf_open60", "services_srv_open60", "hosts_a_aaaa_open60",
        "services_srv_edns"]
    assert "hosts_zipf_rolling" not in entry["workloads"]
    assert dict(entry, workloads=None) == {
        "name": "log_write_bytes_mean", "unit": module.UNIT,
        "better": "higher", "source": "program_counter",
        "layer": module.LAYER, "moves": module.MOVES, "workloads": None}
    assert module.LAYER == "query log" and module.UNIT == "bytes"
