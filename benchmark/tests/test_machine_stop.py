"""A stop of the machine is not a failure of the program (ISSUE 44): the
interval arithmetic alone (``stats.machine_stops``, ``covered_spans``,
``void_account``), the harness's account on hand-made generator files, the
two readers, the manifest's entries, and the two controls as CPU rehearsals:
``--break machine-stop`` (generator and server group stopped together: the
stop is named, what it cost is voided) and ``--break server-stop`` (the
server alone: nothing is named, nothing forgiven)."""
import json
import os
import sys

import numpy as np
import pytest

import stats
from test_benchmark import BENCH, ROOT, rehearse

MS = 1_000_000
S = 1_000_000_000
KINDS = ["timeout", "rcode", "ancount", "tcp", "send", "overflow",
         "unanswered_at_end"]
CELLS = ["hosts_zipf_open60", "services_srv_open60", "hosts_a_aaaa_open60",
         "services_srv_edns"]


def run_module():
    sys.path.insert(0, BENCH)
    import run
    return run


# -- which intervals are stops --

def test_a_stop_is_what_every_threads_gap_holds():
    gaps = [[[10 * S, 2 * S], [20 * S, 100 * MS]],        # thread 0
            [[10 * S + 5 * MS, 2 * S], [30 * S, 1 * S]],  # thread 1
            [[9 * S, 3 * S + 7 * MS]]]                    # thread 2
    # thread 0 ends it first, thread 1 began it last
    assert stats.machine_stops(gaps, 50 * MS) \
        == [(10 * S + 5 * MS, 2 * S - 5 * MS)]
    # one thread: its gaps as they are; no thread: nothing
    assert stats.machine_stops(gaps[:1], 50 * MS) \
        == [(10 * S, 2 * S), (20 * S, 100 * MS)]
    assert stats.machine_stops([], 50 * MS) == []


def test_a_gap_one_thread_saw_alone_names_no_stop():
    alone = [[[5 * S, 3 * S]], [], [[40 * S, 1 * S]]]
    assert stats.machine_stops(alone, 50 * MS) == []
    # nor do two threads whose gaps do not meet
    assert stats.machine_stops([[[5 * S, 1 * S]], [[6 * S, 1 * S]]],
                               50 * MS) == []


def test_what_the_gaps_share_has_to_reach_the_least_length():
    def shared(ns):
        return stats.machine_stops(
            [[[S, 400 * MS]], [[S + 400 * MS - ns, 400 * MS]]], 50 * MS)

    assert shared(50 * MS - 1) == []
    assert shared(50 * MS) == [(S + 350 * MS, 50 * MS)]
    # one gap of a thread can hold two stops (the others ran in between)
    assert stats.machine_stops(
        [[[0, 10 * S]], [[S, 100 * MS], [5 * S, 300 * MS]]], 50 * MS) \
        == [(S, 100 * MS), (5 * S, 300 * MS)]


# -- what a stop covers --

def spans(stops, timeout=S):
    return stats.covered_spans(stops, timeout, 50 * MS)


def test_a_stop_of_249_ms_covers_nothing_and_one_of_250_ms_does():
    assert spans([(10 * S, 250 * MS - 1)]) == []
    # the timeout before it began, to its end plus two and a quarter lengths
    assert spans([(10 * S, 250 * MS)]) \
        == [(9 * S, 10 * S + 250 * MS + 562 * MS + MS // 2)]
    assert spans([(10 * S, 2 * S)], S // 2) \
        == [(10 * S - S // 2, 16 * S + S // 2)]
    # the short ones among long ones are passed over
    assert spans([(3 * S, 110 * MS), (10 * S, 2 * S), (40 * S, 120 * MS)]) \
        == [(9 * S, 16 * S + S // 2)]
    assert stats.STOP_TAIL == (9, 4)


def test_stops_less_than_the_least_gap_apart_are_one_stop():
    # as on the chip: 2.18 s, 0.12 s and 0.41 s, each where the last ended
    one = spans([(29_394 * MS, 2_182 * MS), (29_394 * MS + 2_182 * MS,
                                             118 * MS),
                 (31_694 * MS + 1, 407 * MS)])
    start, end = 29_394 * MS, 31_694 * MS + 1 + 407 * MS
    assert one == [(start - S, end + 9 * (end - start) // 4)]
    # two short ones 49.999999 ms apart are a stop of 250 ms; 50 ms apart
    # they are two stops that cover nothing
    near = [(10 * S, 100 * MS), (10 * S + 150 * MS - 1, 100 * MS + 1)]
    assert spans(near) == [(9 * S, 10 * S + 250 * MS + 562 * MS + MS // 2)]
    assert spans([(10 * S, 100 * MS), (10 * S + 150 * MS, 200 * MS)]) == []


def test_spans_that_meet_are_one_span():
    # the second stop begins inside the first one's tail
    assert spans([(10 * S, S), (12 * S, S)]) \
        == [(9 * S, 15 * S + S // 4)]
    # and a stop inside another's tail whose own tail ends before it adds
    # nothing
    assert spans([(10 * S, 2 * S), (13 * S, 250 * MS)]) \
        == [(9 * S, 16 * S + S // 2)]
    assert spans([(10 * S, S), (20 * S, S)]) \
        == [(9 * S, 13 * S + S // 4), (19 * S, 23 * S + S // 4)]


def account(sends, failures, stops, cuts=(), timeout=S):
    return stats.void_account(sends, failures, KINDS, spans(stops, timeout),
                              list(cuts))


def test_a_covered_query_leaves_both_counts_answered_or_not():
    sends = np.arange(0, 51 * S, 10 * MS)           # 100 a second, 51 s
    t = KINDS.index("timeout")
    failures = [[9 * S - 1, t],                     # a nanosecond too early
                [9 * S, t], [10 * S + 7, t], [14 * S + 875 * MS, t],
                [14 * S + 875 * MS + 1, t]]         # a nanosecond too late
    got = account(sends, failures, [(10 * S, 1500 * MS)])
    # covered: [9 s, 14.875 s], both ends included
    assert got["queries"] == 588
    assert got["of_them_failed"] == 3
    assert got["failed_by_kind"]["timeout"] == 2
    assert sum(got["failed_by_kind"].values()) == 2
    # no stop of 250 ms: the counts as the generator has them
    quiet = account(sends, failures, [(10 * S, 249 * MS)])
    assert (quiet["queries"], quiet["of_them_failed"]) == (0, 0)
    assert quiet["failed_by_kind"]["timeout"] == 5


def test_the_covered_span_at_the_windows_edges_and_in_the_warm_up():
    sends = np.arange(0, 51 * S, 10 * MS)           # the window's alone
    t, u = KINDS.index("timeout"), KINDS.index("unanswered_at_end")
    # a stop in the warm-up (before the window's first due time) whose
    # tail reaches into the window: [-2.5 s, 1.75 s]
    warm = account(sends, [[400 * MS, t], [1750 * MS + 1, t]],
                   [(-1500 * MS, S)])
    assert (warm["queries"], warm["of_them_failed"]) == (176, 1)
    # one that ends before the window and whose tail does too: nothing
    assert account(sends, [[400 * MS, t]],
                   [(-1900 * MS, 400 * MS)])["queries"] == 0
    # a stop in the wait after the window: the last second's queries were
    # still out when it began, and are unanswered at the end
    late = account(sends, [[50 * S + 500 * MS, u], [49 * S, u]],
                   [(51 * S + 200 * MS, 800 * MS)])
    assert (late["queries"], late["of_them_failed"]) == (80, 1)
    assert late["failed_by_kind"]["unanswered_at_end"] == 1
    # a stop that begins in the window and ends after it
    across = account(sends, [], [(50 * S, 3 * S)])
    assert across["queries"] == 200


def test_a_wrong_answer_inside_a_covered_span_still_failed():
    sends = np.arange(0, 20 * S, 10 * MS)
    rows = [[11 * S, KINDS.index(kind)] for kind in KINDS]
    got = account(sends, rows, [(10 * S, S)])
    assert got["of_them_failed"] == 5
    assert got["failed_by_kind"] == {
        "timeout": 0, "rcode": 1, "ancount": 1, "tcp": 0, "send": 0,
        "overflow": 0, "unanswered_at_end": 0}
    # and stays attempted: 9 s to 13.25 s hold 426 sends, two of them wrong
    assert got["queries"] == 426 - 2
    assert stats.VOIDABLE == set(KINDS) - {"rcode", "ancount"}


def test_per_segment_counts_are_what_is_left_of_each_segment():
    sends = np.arange(0, 30 * S, 10 * MS)
    t, r, u = (KINDS.index(k) for k in ("timeout", "rcode",
                                        "unanswered_at_end"))
    failures = [[1 * S, t],                         # segment 0, no stop near
                [2 * S, t],                         # the cut itself: segment 1
                [10 * S, t], [11 * S, r],           # covered; wrong stays
                [22 * S, t], [22 * S + 1, t], [29 * S, u]]
    cuts = [2 * S, 22 * S]
    got = account(sends, failures, [(10 * S, S)], cuts)
    assert got["failed_by_segment"] == [1, 2, 2]
    # (unanswered_at_end is in no segment's count, as in the generator's)
    assert account(sends, failures, [], cuts)["failed_by_segment"] \
        == [1, 3, 2]
    assert account(sends, failures, [])["failed_by_segment"] == [6]


# -- the harness's account, on hand-made generator files --

def hand_made(tmp_path, gaps, failures, sends, segments=1):
    files = {"-f": str(tmp_path / "failures.bin"),
             "-n": str(tmp_path / "sends.bin")}
    np.asarray(failures, dtype="<i8").reshape(-1, 2).tofile(files["-f"])
    if sends is not None:
        np.asarray(sends, dtype="<i8").tofile(files["-n"])
    seen = sum(1 for _, k in failures if KINDS[k] != "unanswered_at_end")
    g = {"gaps_ns": gaps, "gap_least_ns": 50 * MS, "fail_kinds": KINDS,
         "sent": 0 if sends is None else len(sends), "failed": seen,
         "unanswered_at_end": len(failures) - seen, "window_s": 20.0,
         "latency_ns_by_segment": [{"failed": -1} for _ in range(segments)]}
    return g, files


def test_the_account_names_the_stops_and_rewrites_the_counts(tmp_path):
    run = run_module()
    t = KINDS.index("timeout")
    sends = np.arange(0, 20 * S, 1 * MS)
    g, files = hand_made(
        tmp_path, [[[10 * S, S], [15 * S, 100 * MS]],
                   [[10 * S, S], [15 * S, 100 * MS]]],
        [[3 * S, t], [10 * S + 5, t], [12 * S, t], [16 * S, t]], sends, 2)
    run.account_for_stops(g, {"timeout_s": 1.0, "segments_at_s": [11]}, files)
    assert g["stops"] == [[10.0, 1.0], [15.0, 0.1]]
    assert g["voided"] == {"queries": 4251, "of_them_failed": 2}
    assert g["attempted"] == 20000 - 4251
    assert g["failed_by_kind"]["timeout"] == 2
    assert [s["failed"] for s in g["latency_ns_by_segment"]] == [1, 1]
    assert g["failed"] == 4             # the generator's own stays
    ctx = {"generator": g}
    readers = run.layer_readers()
    assert readers["gen_stop_ms"].read(ctx) == pytest.approx(1100.0)
    assert readers["voided_share"].read(ctx) == pytest.approx(
        100 * 4251 / 20000)


def test_a_quiet_window_needs_no_sends_file_and_counts_as_before(tmp_path):
    run = run_module()
    t = KINDS.index("timeout")
    g, files = hand_made(tmp_path, [[[5 * S, 120 * MS]], [[5 * S, 120 * MS]]],
                         [[5 * S, t]], None)
    g["sent"] = 777
    run.account_for_stops(g, {"timeout_s": 1.0}, files)
    assert g["stops"] == [[5.0, 0.12]]
    assert g["voided"] == {"queries": 0, "of_them_failed": 0}
    assert (g["attempted"], g["failed_by_kind"]["timeout"]) == (777, 1)
    readers = run.layer_readers()
    assert readers["gen_stop_ms"].read({"generator": g}) \
        == pytest.approx(120.0)
    assert readers["voided_share"].read({"generator": g}) == 0.0


def test_files_that_do_not_hold_the_generators_counts_fail_the_run(tmp_path):
    run = run_module()
    t = KINDS.index("timeout")
    g, files = hand_made(tmp_path, [[[5 * S, S]]], [[5 * S, t]],
                         np.arange(100))
    g["failed"] = 2
    with pytest.raises(SystemExit, match="failures on file"):
        run.account_for_stops(g, {"timeout_s": 1.0}, files)
    g["failed"], g["sent"] = 1, 101
    with pytest.raises(SystemExit, match="sends on file"):
        run.account_for_stops(g, {"timeout_s": 1.0}, files)


def test_the_stop_readers_cut_to_the_window_and_say_nothing_without_keys():
    readers = run_module().layer_readers()
    g = {"window_s": 20.0, "sent": 1000, "voided": {"queries": 250},
         "stops": [[-1.0, 1.5], [5.0, 0.06], [19.5, 2.0], [25.0, 1.0]]}
    # 0.5 s of the first, the short one whole, 0.5 s of the third
    assert readers["gen_stop_ms"].read({"generator": g}) \
        == pytest.approx(1060.0)
    assert readers["voided_share"].read({"generator": g}) == 25.0
    for name in ("gen_stop_ms", "voided_share"):
        assert readers[name].read({"generator": {"window_s": 20.0}}) is None
        assert readers[name].read({}) is None


# -- the manifest --

def test_the_manifest_holds_the_bound_and_the_two_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    bounds = {e["name"]: e["bound"] for e in m["end_to_end"]}
    assert bounds == {"setup_s": 0.25, "p50_us": 0.17}
    by_name = {p["name"]: p for p in m["per_layer"]}
    readers = run_module().layer_readers()
    # the generator's own clock is there in every cell: the four cells of
    # PR 44 by name, and whatever cell a later PR added
    cells = [w["name"] for w in m["workloads"]]
    assert set(CELLS) <= set(cells)
    for name in ("gen_stop_ms", "voided_share"):
        module = readers[name]
        assert by_name[name] == {
            "name": name, "unit": module.UNIT, "better": "lower",
            "source": "host_clock", "layer": module.LAYER,
            "moves": module.MOVES, "workloads": cells}


# -- the two controls, rehearsed on the CPU --

def test_rehearsal_machine_stop_is_named_and_what_it_cost_is_voided():
    result = rehearse("tiny_open", 44, 0, "machine-stop", seconds=8)
    assert result["correct"], result["stdout"][-3000:]
    long = [(start, length) for start, length in result["stops"]
            if length >= 0.25]
    assert len(long) == 1, result["stops"]
    start, length = long[0]
    assert 1.4 <= length <= 1.7 and 3.0 <= start <= 3.6
    # tiny_open sends 2,000 a second, and 1 s before the stop, the stop and
    # 2.25 lengths after it are 5.9 s of the window's 8 s
    with open(os.path.join(BENCH, "out", "tiny_open", "generator.json")) as f:
        g = json.load(f)
    assert result["failed"] == 0
    assert 0.6 * g["sent"] < result["voided"]["queries"] < 0.8 * g["sent"]
    assert result["attempted"] == g["sent"] - result["voided"]["queries"]
    assert result["voided"]["of_them_failed"] \
        == g["failed"] + g["unanswered_at_end"]
    assert "benchmark: --break: stopped generator and server group" \
        in result["stdout"]


def test_rehearsal_server_stop_names_nothing_and_forgives_nothing():
    result = rehearse("tiny_open", 45, 0, "server-stop", seconds=8)
    # the comparison with the reference is untouched by a stall
    assert result["correct"], result["stdout"][-3000:]
    assert not [s for s in result["stops"] if s[1] >= 0.25], result["stops"]
    assert result["voided"] == {"queries": 0, "of_them_failed": 0}
    # every query due in the stop's first half second passed its timeout
    assert result["failed"] > 500
    assert result["failed_by_kind"]["timeout"] == result["failed"]
    with open(os.path.join(BENCH, "out", "tiny_open", "generator.json")) as f:
        g = json.load(f)
    assert result["attempted"] == g["sent"]
    assert result["failed"] == g["failed"] + g["unanswered_at_end"]
