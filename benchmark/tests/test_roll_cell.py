"""An event inside the window and the window read by segments (PR 31): the
arithmetic of ``stats.segment_percentile``, of the pairing by shard and of
the five readers on hand-made contexts; the generator's files of a workload
without events or segments held to what the parent's ``traffic.py`` wrote;
and the CPU rehearsal ``tiny_roll`` (the tiny zone, SIGHUP to the supervisor
at 1 s of a 4 s window, cut at 1 s and 3 s) with its control."""
import hashlib
import json
import os
import re
import sys

import pytest

import stats
from reference import Zone
from test_benchmark import BENCH, HERE, manifest, rehearse
from traffic import Traffic

ROLL_READERS = ("roll_s", "before_roll_p50_us", "in_roll_p50_us",
                "after_roll_p50_us", "roll_p50_ratio")
ROLL_ROWS = ("events_not_delivered", "roll_not_complete",
             "shards_not_rolled", "roll_aborts",
             "segments_with_too_few_answers_compared",
             "workers_not_serving_the_write", "orphan_processes")
CELL = "hosts_roll_under_load"


def readers() -> dict:
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()


def tiny(name: str) -> dict:
    with open(os.path.join(HERE, "tiny", "workloads", name + ".json")) as f:
        return json.load(f)


# -- arithmetic --

def segments_ctx() -> dict:
    """Three segments: 100 answers of 100-199 ns, 100 of 400-499 ns, 10 of
    1000-1009 ns (one to a bucket: under 1,024 a bucket is a nanosecond)."""
    def hist(lo, n):
        return [[lo + k, 1] for k in range(n)]
    parts = [hist(100, 100), hist(400, 100), hist(1000, 10)]
    return {"generator": {
        "hist_bits": 9, "latency_ns": sum(parts, []),
        "latency_ns_by_segment": [
            {"from_s": lo, "to_s": hi, "failed": 0, "latency_ns": part}
            for (lo, hi), part in zip(((0, 2), (2, 27), (27, 51)), parts)]},
        "harness": {"roll_s": 23.5}}


def test_segment_percentile_reads_one_segment_alone():
    ctx = segments_ctx()
    assert stats.segment_percentile(ctx, 0, 50) == pytest.approx(0.150)
    assert stats.segment_percentile(ctx, 1, 50) == pytest.approx(0.450)
    assert stats.segment_percentile(ctx, 2, 100) == pytest.approx(1.010)
    assert stats.segment_percentile(ctx, 3, 50) is None     # no such segment
    assert stats.segment_percentile(ctx, -1, 50) is None
    # a window that is not cut has one histogram, the whole: nothing to read
    whole = {"generator": dict(ctx["generator"], latency_ns_by_segment=[
        {"from_s": 0, "to_s": 51, "failed": 0,
         "latency_ns": ctx["generator"]["latency_ns"]}])}
    assert stats.segment_percentile(whole, 0, 50) is None
    # a segment in which nothing was answered
    ctx["generator"]["latency_ns_by_segment"][2]["latency_ns"] = []
    assert stats.segment_percentile(ctx, 2, 50) is None
    assert stats.segment_percentile({}, 0, 50) is None


def test_the_five_readers_on_a_known_result():
    got = {name: readers()[name].read(segments_ctx())
           for name in ROLL_READERS}
    assert got == {"roll_s": 23.5,
                   "before_roll_p50_us": pytest.approx(0.150),
                   "in_roll_p50_us": pytest.approx(0.450),
                   "after_roll_p50_us": pytest.approx(1.005),
                   "roll_p50_ratio": pytest.approx(3.0)}


@pytest.mark.parametrize("ctx", [
    {}, {"generator": {}, "harness": {}},
    {"generator": {"hist_bits": 9, "latency_ns": [[5, 1]],
                   "latency_ns_by_segment": [{"latency_ns": [[5, 1]]}]},
     "harness": {"roll_s": None}}])
def test_the_five_readers_give_none_where_there_is_nothing_to_read(ctx):
    for name in ROLL_READERS:
        assert readers()[name].read(ctx) is None, name


def test_workers_pair_by_shard_and_a_new_pid_starts_from_zero():
    def w(shard, pid, n):
        return {"shard": shard, "pid": pid, "status": {},
                "metrics": f"binder_requests_completed {n}\n"}
    before = {"workers": [w(0, 10, 100), w(1, 11, 200)]}
    # shard 1 listed first, shard 0 replaced by pid 12 counting from zero
    after = {"workers": [w(1, 11, 260), w(0, 12, 40)]}
    ctx = {"before": before, "after": after}
    assert stats.window_delta(ctx, "binder_requests_completed") == [60, 40]
    assert stats.replaced_workers(before, after) == 1
    pairs = stats.worker_pairs(before, after)
    assert pairs[0][0]["pid"] == 11 and pairs[1][0] is stats.FRESH
    import spans
    assert spans.answers(ctx) == 100
    # hand-made scrapes that name no shard pair in order, as they did
    bare = {"workers": [{"metrics": "x 1\n", "status": {}}]}
    assert stats.worker_pairs(bare, bare) == [(bare["workers"][0],) * 2]


# -- the traffic of a workload without events is the parent's --

#: SHA-256 over the generator's files (arrivals, sequence, templates) that
#: the parent commit's ``traffic.py`` (2297e75) wrote for these workloads
#: under seed 2**31 + 11 and a 2 s window
PARENT_FILES = {
    "tiny_open":
        "1ed2c56ed48be8282be6272e376d54049d627fe6fa3438884bcec1feb9d19716",
    "tiny_kinds":
        "696bff353d1a2134c5a3b9f4b1c54c5b19797bbfadddfb98566c93382a45854c",
    "tiny_closed":
        "99ecd5daddd7cb2d993c2f0b4effbfd775b3c87f8f3689f992b8329ca947ed91",
}


def files_digest(workload: dict, tmp_path, seed: int = 2**31 + 11) -> str:
    with open(os.path.join(HERE, "tiny", "configs", "tiny-x2.json")) as f:
        config = json.load(f)
    os.makedirs(tmp_path, exist_ok=True)
    files = Traffic(workload, Zone(config, "foo.com", seed), seed,
                    2.0).write(str(tmp_path))
    digest = hashlib.sha256()
    for flag in sorted(files):
        with open(files[flag], "rb") as f:
            digest.update(f.read())
    return digest.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT_FILES))
def test_a_workload_without_events_makes_the_parents_files(name, tmp_path):
    assert files_digest(tiny(name), tmp_path) == PARENT_FILES[name]


def test_events_and_segments_change_no_question_and_no_due_time(tmp_path):
    roll = tiny("tiny_roll")
    plain = {k: v for k, v in roll.items()
             if k not in ("events", "segments_at_s")}
    assert files_digest(roll, tmp_path / "a") \
        == files_digest(plain, tmp_path / "b")
    sys.path.insert(0, BENCH)
    import run
    # (the window's first due time is written in every run since PR 44:
    # the stop controls place their stop by it, as the events do)
    outs = {flag: "out/" + name for flag, name in run.GENERATOR_OUT.items()}
    argv = run.generator_argv(plain, {}, 53, 2.0, outs)
    assert "-g" not in argv
    assert argv[argv.index("-s") + 1] == "out/window_start"
    argv = run.generator_argv(roll, {}, 53, 2.0, outs)
    assert argv[argv.index("-g") + 1] == "1.0,3.0"


def test_an_event_that_is_not_built_is_refused():
    sys.path.insert(0, BENCH)
    import run
    for events in ([{"at_s": 1, "signal": "SIGKILL", "to": "supervisor"}],
                   [{"at_s": 1, "signal": "SIGHUP", "to": "worker"}],
                   [{"at_s": 1, "write": 8}],
                   [{"at_s": 2, "signal": "SIGHUP", "to": "supervisor"},
                    {"at_s": 1, "signal": "SIGHUP", "to": "supervisor"}]):
        with pytest.raises(SystemExit):
            run.Events(events, run.SupervisorGroup, "/nonexistent")


# -- the manifest --

def test_the_roll_workload_is_the_hosts_cell_with_an_event():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        roll = json.load(f)
    with open(os.path.join(BENCH, "workloads",
                           "hosts_zipf_open60.json")) as f:
        hosts = json.load(f)
    # the hosts cell's traffic but for its rate, the event, the cuts and
    # how many answers are kept
    for key in hosts:
        if key not in ("name", "why", "rate_per_s", "expect_per_s",
                       "rate_from", "posture", "capture_answers"):
            assert roll[key] == hosts[key], key
    assert roll["events"] == [{"at_s": 2, "signal": "SIGHUP",
                               "to": "supervisor"}]
    assert len(roll["segments_at_s"]) == 2 \
        and roll["segments_at_s"][0] == 2
    assert {"answers", "roll", "read_your_writes", "zero_query_loss",
            "shutdown"} <= set(roll["guarantees"])
    # every segment can hold 200 kept answers
    assert roll["capture_answers"] * 2 / 51 > 220


def test_the_manifest_lists_the_roll_cell_whole_or_not_at_all():
    """Measured and not admitted in PR 31 (PERF.md section 6): until a
    later ``benchmark`` PR lists the cell, no metric may list it; once
    it is listed, it reports the generator's and the harness's readers
    only (a rolled worker's counters start from zero)."""
    m = manifest()
    listed = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    if CELL not in [w["name"] for w in m["workloads"]]:
        assert not listed
        return
    assert listed == set(ROLL_READERS) | {
        "gen_late_p99_us", "tail_p90_us", "tail_p99_us", "ready_s",
        "seed_s"}
    for p in m["per_layer"]:
        if p["name"] in ROLL_READERS:
            assert p["workloads"] == [CELL] and p["moves"] == "p50_us"


# -- the rehearsal --

def rehearse_roll(seed: int, trace: int, broken=None):
    result = rehearse("tiny_roll", seed, trace, broken, seconds=4)
    return result, result["stdout"]


def test_rehearsal_of_a_roll_inside_the_window():
    result, stdout = rehearse_roll(2**31 + 31, 1)
    assert result["correct"], stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 4000
    for row in ROLL_ROWS:
        assert result["compared"][row] == {"value": 0, "limit": 0}, row
    # the SIGHUP left within 100 ms of its offset
    (event,) = result["events"]
    assert event["signal"] == "SIGHUP" and event["at_s"] == 1
    assert 1.0 <= event["left_at_s"] < 1.1
    # the group was read again: both workers replaced, and the orphan
    # check covers the two pids from before and the two from after
    assert result["breakdown"]["replaced_workers"] == 2
    assert "orphan check over 4 worker pids (both generations)" in stdout
    assert re.search(r"worker CPU between the scrapes, % of a core: "
                     r"replaced, replaced;", stdout)
    rolled = re.findall(r"shard (\d) rolled: pid (\d+) -> (\d+)",
                        open(os.path.join(BENCH, "out", "tiny_roll",
                                          "server.log")).read())
    assert sorted(s for s, _, _ in rolled) == ["0", "1"]
    assert all(old != new for _, old, new in rolled)
    # the five readers read (the cell is not listed, so every reader runs)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(ROLL_READERS) <= set(metrics)
    assert 0.5 < metrics["roll_s"] < 120
    assert metrics["roll_p50_ratio"] == pytest.approx(
        metrics["in_roll_p50_us"] / metrics["before_roll_p50_us"])
    # the segments' histograms sum to the window's, bucket for bucket
    with open(os.path.join(BENCH, "out", "tiny_roll",
                           "generator.json")) as f:
        g = json.load(f)
    segments = g["latency_ns_by_segment"]
    assert [(s["from_s"], s["to_s"]) for s in segments] \
        == [(0.0, 1.0), (1.0, 3.0), (3.0, 4.0)]
    summed = {}
    for segment in segments:
        for bucket, count in segment["latency_ns"]:
            summed[bucket] = summed.get(bucket, 0) + count
    assert sorted(summed.items()) == [tuple(b) for b in g["latency_ns"]]
    assert sum(s["failed"] for s in segments) == g["failed"]
    assert all(stats.hist_count(s["latency_ns"]) > 500 for s in segments)


def test_rehearsal_of_a_roll_under_the_control_is_not_correct():
    """``--break skew-replica`` with events present: the worker cut off
    from the mutation log does not serve the write before the window,
    whatever the roll replaces afterwards."""
    result, stdout = rehearse_roll(2**31 + 32, 0, "skew-replica")
    assert result["correct"] is False
    assert re.search(r"compared written_names_mismatching = [1-9]\d* "
                     r"\(limit 0\)  <-- outside", stdout), stdout[-3000:]
    # the roll itself went through
    for row in ("roll_not_complete", "shards_not_rolled", "roll_aborts"):
        assert result["compared"][row]["value"] == 0, row
