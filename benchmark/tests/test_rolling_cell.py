"""The cell ``hosts_zipf_rolling`` (PR 45): its five readers of what a roll
did on hand-made scrapes (a supervisor that rolled four shards, two new
workers and two kept), what they give on a program without the counters
(the parent commit: nothing, and no raise), what the manifest says of the
cell, and the CPU rehearsal ``tiny_roll`` read by all ten readers of a
roll."""
import json
import os
import sys

import pytest

from test_benchmark import BENCH, manifest, rehearse
from test_roll_cell import ROLL_READERS, segments_ctx

CELL = "hosts_zipf_rolling"
CONFIG = "dc-hosts-100k-x4-rolling"
NEW_READERS = ("roll_failed_queries", "roll_fill_share", "roll_drain_ms",
               "roll_unserved_queries", "unfilled_serve_share")
#: what a cell with a roll in its window lists: the generator's and the
#: harness's readers, and the supervisor's account of the roll; no delta of
#: worker counters (``benchmark/README.md``, "What a roll does to the
#: traced readers")
LISTED = set(ROLL_READERS) | set(NEW_READERS) | {
    "gen_late_p99_us", "tail_p90_us", "tail_p99_us", "ready_s", "seed_s",
    "gen_stop_ms", "voided_share"}
PHASES = "binder_shard_roll_phase_seconds"


def readers() -> dict:
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def supervisor_text(phases: dict, unserved) -> str:
    """``phases``: name -> (seconds, shards)."""
    lines = []
    for name, (seconds, shards) in phases.items():
        labels = f'datacenter="coal",port="53",phase="{name}"'
        lines += [f'{PHASES}_bucket{{{labels},le="+Inf"}} {shards}',
                  f"{PHASES}_sum{{{labels}}} {seconds}",
                  f"{PHASES}_count{{{labels}}} {shards}"]
    if unserved is not None:
        lines.append(f"binder_shard_roll_unserved_total {unserved}")
    return "\n".join(lines) + "\n"


def worker(shard: int, pid: int, answers: int, unfilled) -> dict:
    text = f"binder_requests_completed {answers}\n"
    if unfilled is not None:
        text += f"binder_unfilled_serves_total {unfilled}\n"
    return {"shard": shard, "pid": pid, "status": {}, "metrics": text}


def known_ctx(counters: bool = True) -> dict:
    """A roll of 40 s: four shards attached in 3 s each, filled in 6 s
    each, drained in 0.2, 0.2, 0.2 and 0.4 s; an earlier roll (before the
    first scrape) had observed 1 s, 2 s and 0.5 s of one shard.  Shards 0
    and 1 are new pids at the closing scrape, with 900 and 100 answers, 3
    of them given before the worker was filled; shards 2 and 3 were kept
    (and had answered through their own fill at the start: 500 each, which
    the reader leaves out).  The generator failed 0 queries before the
    SIGHUP, 2 in the roll and 1 after it."""
    zero = {"attach": (1.0, 1), "fill": (2.0, 1), "drain": (0.5, 1)}
    done = {"attach": (13.0, 5), "fill": (26.0, 5), "drain": (1.5, 5)}
    ctx = segments_ctx()
    for segment, failed in zip(
            ctx["generator"]["latency_ns_by_segment"], (0, 2, 1)):
        segment["failed"] = failed
    n = (lambda v: v) if counters else (lambda v: None)
    ctx.update({
        "events": [{"at_s": 2, "signal": "SIGHUP", "to": "supervisor",
                    "left_at_s": 2.0004}],
        "harness": {"roll_s": 40.0},
        "before": {"at": 10.0, "supervisor": {
            "metrics": supervisor_text(zero if counters else {}, n(4))},
            "workers": [worker(s, 10 + s, 1000, n(500)) for s in range(4)]},
        "after": {"at": 70.0, "supervisor": {
            "metrics": supervisor_text(done if counters else {}, n(4))},
            "workers": [worker(0, 20, 900, n(3)), worker(1, 21, 100, n(0)),
                        worker(2, 12, 5000, n(500)),
                        worker(3, 13, 5000, n(500))]}})
    return ctx


@pytest.mark.parametrize("name,want", [
    ("roll_failed_queries", 3),
    ("roll_fill_share", 100.0 * 24.0 / 40.0),
    ("roll_drain_ms", 1e3 * 1.0 / 4),
    ("roll_unserved_queries", 0.0),
    ("unfilled_serve_share", 100.0 * 3 / 1000),
])
def test_the_new_readers_on_a_known_roll(name, want):
    assert readers()[name].read(known_ctx()) == pytest.approx(want)


def test_unserved_queries_are_the_supervisors_delta():
    ctx = known_ctx()
    ctx["after"]["supervisor"]["metrics"] = supervisor_text(
        {"attach": (13.0, 5), "fill": (26.0, 5), "drain": (1.5, 5)}, 6)
    assert readers()["roll_unserved_queries"].read(ctx) == 2.0
    # a window in which no shard was rolled has nothing to say
    ctx["after"]["supervisor"] = ctx["before"]["supervisor"]
    assert readers()["roll_unserved_queries"].read(ctx) is None


def test_the_parent_commit_gives_nothing_for_the_programs_counters():
    """A program without the histogram and the two counters: the four
    readers of the program return None and do not raise; the generator's
    reader reads as it does on every program."""
    ctx = known_ctx(counters=False)
    got = readers()
    for name in NEW_READERS[1:]:
        assert got[name].read(ctx) is None, name
    assert got["roll_failed_queries"].read(ctx) == 3


@pytest.mark.parametrize("ctx", [
    {}, {"generator": {}, "harness": {}, "events": []},
    {"before": None, "after": None, "generator": {
        "latency_ns_by_segment": [{"failed": 0}]}},
    # a window with no roll in it: nothing was replaced, no event left
    {"before": {"supervisor": {"metrics": ""}, "workers": [
        worker(0, 1, 10, 0)]},
     "after": {"supervisor": {"metrics": ""}, "workers": [
         worker(0, 1, 20, 0)]},
     "harness": {"roll_s": None}, "events": [], "generator": {
         "latency_ns_by_segment": [{"failed": 0}, {"failed": 0}]}}])
def test_the_new_readers_give_none_where_there_is_nothing_to_read(ctx):
    for name in NEW_READERS:
        assert readers()[name].read(ctx) is None, name


def test_a_replacement_that_answered_nothing_yet_is_no_share():
    ctx = known_ctx()
    ctx["after"]["workers"][:2] = [worker(0, 20, 0, 0), worker(1, 21, 0, 0)]
    assert readers()["unfilled_serve_share"].read(ctx) is None


# -- the manifest and the files --

def test_the_cell_is_the_hosts_cell_with_a_roll():
    cell, hosts = load("workloads", CELL), load("workloads",
                                                "hosts_zipf_open60")
    for key in hosts:
        if key not in ("name", "config", "why", "rate_per_s", "expect_per_s",
                       "rate_from", "posture", "capture_answers"):
            assert cell[key] == hosts[key], key
    assert cell["config"] == CONFIG
    assert cell["events"] == [{"at_s": 2, "signal": "SIGHUP",
                               "to": "supervisor"}]
    first, end = cell["segments_at_s"]
    assert first == 2 and first < end < 51 and end == int(end)
    assert cell["end_to_end"] == ["p50_us", "setup_s"]
    # a rate the sweep's steps hold, and the sweep is written down
    assert cell["rate_per_s"] == cell["expect_per_s"]
    assert cell["rate_per_s"] % 2800 == 0 and "sweep" in cell["rate_from"]
    # every segment can hold its 200 kept answers
    shortest = min(first, end - first, 51 - end)
    assert cell["capture_answers"] * shortest / 51 > 220


def test_the_configuration_is_the_hosts_deployment_with_a_procedure():
    rolling, hosts = load("configs", CONFIG), load("configs",
                                                   "dc-hosts-100k-x4")
    for key in ("hosts", "racks", "subtree", "services", "base_config",
                "entry", "shards", "posture_overrides", "chaos", "reduced"):
        assert rolling[key] == hosts[key], key
    assert set(hosts["guarantees"]) | {"zero_query_loss", "roll"} \
        == set(rolling["guarantees"])
    assert "rolls" in rolling["assumed"]
    assert len(rolling["source"]) <= 200
    (entry,) = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert entry["source"] == rolling["source"]
    assert entry["reduced"] == ["hosts"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_manifest_lists_for_the_cell_what_a_rolled_cell_can_read():
    m = manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic=CELL, chips=1)
    assert CONFIG in [c["name"] for c in m["configs"]]
    listed = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert listed == LISTED
    got = readers()
    for p in m["per_layer"]:
        if p["name"] in ROLL_READERS + NEW_READERS:
            module = got[p["name"]]
            assert p["workloads"] == [CELL]
            assert (p["unit"], p["layer"], p["moves"], p["better"]) \
                == (module.UNIT, module.LAYER, module.MOVES, "lower")
        elif CELL in p["workloads"]:
            # appended behind the four steady cells, nothing else moved
            assert p["workloads"][:5] == [
                "hosts_zipf_open60", "services_srv_open60",
                "hosts_a_aaaa_open60", "services_srv_edns", CELL]
    # the ten stand together, in this order
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(ROLL_READERS[0])
    assert names[at:at + 10] == list(ROLL_READERS + NEW_READERS)
    # no cell is on four chips, and the benchmark holds no more than 24
    assert {w["chips"] for w in m["workloads"]} == {1}
    assert len(m["workloads"]) <= 24


# -- the rehearsal --

def test_rehearsal_of_a_roll_read_by_the_new_readers():
    result = rehearse("tiny_roll", 2**31 + 45, 1, seconds=4)
    assert result["correct"], result["stdout"][-3000:]
    assert result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_READERS) <= set(metrics)
    assert metrics["roll_failed_queries"] == 0
    assert metrics["roll_unserved_queries"] == 0
    # both workers are replacements: they read their sockets only once
    # filled (the tiny zone fills inline, before the first query)
    assert metrics["unfilled_serve_share"] == 0
    assert result["breakdown"]["replaced_workers"] == 2
    assert 0 <= metrics["roll_fill_share"] < 100
    assert 0 < metrics["roll_drain_ms"] < 10_000
    log = open(os.path.join(BENCH, "out", "tiny_roll", "server.log")).read()
    assert log.count("quiesced clean") >= 2
    assert "attach " in log and "fill " in log and "drain " in log
