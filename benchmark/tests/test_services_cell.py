"""The service-discovery deployment ``dc-services-x4`` and its cell
``services_srv_open60`` (ISSUE 26): the files load and say what the
manifest says, the zone stays inside the source's three limits under every
seed, each new reader gives ``None`` on a scrape that lacks its span or
counter (the parent of the PR that adds them) and the right number on a
hand-made one, and a CPU rehearsal of the cell on a cut of its zone ends
``correct`` with truncated answers fetched again over TCP."""
import json
import os
import re
import subprocess
import sys

import pytest

from reference import Zone

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL, CONFIG = "services_srv_open60", "dc-services-x4"
NEW = ("tcp_leg_share", "udp_tc_share", "tcp_us_per_leg",
       "tcp_crossings_per_leg", "stream_busy_share", "lazy_render_share",
       "lazy_render_us", "srv_answer_bytes_mean")
# the thresholds of kubernetes/community sig-scalability thresholds.md
MAX_SERVICES, MAX_ENDPOINTS, MAX_PODS = 10_000, 250, 150_000


def load(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def readers() -> dict:
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()


# -- the files --

def test_config_and_workload_say_what_the_manifest_says():
    m = load("BENCHMARK.json")
    config = load("benchmark", "configs", CONFIG + ".json")
    workload = load("benchmark", "workloads", CELL + ".json")
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == sorted(config["reduced"])
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 1, "why": workload["why"]}
    assert len(cell["why"]) <= 200 and workload["config"] == CONFIG
    # SRV only, as a libc-less SRV-first client sends it; open loop at a
    # fixed rate; truncated answers fetched again
    assert workload["mix"] == [{"share": 1.0, "qtype": "SRV",
                                "target": "service"}]
    assert workload["distribution"] == {"kind": "zipfian", "constant": 0.99}
    assert (workload["edns_share"], workload["rd_share"],
            workload["tc_retry"], workload["loop"], workload["burst"]) \
        == (0.0, 1.0, True, "open", None)
    assert isinstance(workload["rate_per_s"], int) and workload["rate_from"]
    assert sorted(workload["end_to_end"]) == ["p50_us", "setup_s"]
    # every limit of the guarantees is 0, and what no source gives is
    # said to be assumed
    assert config["architecture"] is None
    assert {"answers", "truncation", "read_your_writes", "shutdown"} \
        <= set(config["guarantees"])
    assert {"size_classes", "size_and_popularity", "no_opt", "hosts",
            "shards", "rrl_allowlist"} <= set(config["assumed"])
    assert (config["shards"], config["base_config"]) \
        == (4, "etc/config.json")
    spec = config["services"]
    assert (spec["srvce"], spec["proto"], spec["port"],
            spec["rank_period"]) == ("_http", "_tcp", 80, 40)
    assert [(c["name"], c["members"], c.get("ranks_in_period"))
            for c in spec["classes"]] == [
        ("large", [65, 250], [10]), ("medium", [9, 64], [5, 15, 25, 35]),
        ("small", [2, 8], None)]


def test_the_new_metrics_list_the_cell_alone_and_the_old_ones_gain_it():
    m = load("BENCHMARK.json")
    by_name = {p["name"]: p for p in m["per_layer"]}
    mods = readers()
    for name in NEW:
        entry, module = by_name[name], mods[name]
        # (and, since PR 31, the same traffic with an OPT record)
        assert entry["workloads"] == [CELL, "services_srv_edns"] \
            and entry["moves"] == "p50_us"
        assert (entry["layer"], entry["unit"], entry["moves"]) \
            == (module.LAYER, module.UNIT, module.MOVES)
    # the four whose stage tuples knew neither the stream stages nor the
    # lazy render list the cell since ``spans.py`` names both (PR 30)
    for name in ("busy_unnamed_share", "syscalls_per_answer",
                 "socket_us_per_answer", "python_us_per_query"):
        assert CELL in by_name[name]["workloads"]
    # by name, whatever later PRs appended: PR 26's eight, PR 27's and
    # PR 29's one each list the services cells alone; every metric that the
    # hosts cell lists and that is not of its own topology or mix is the
    # services cell's too
    listed = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert set(NEW) | {"tc_render_share", "tcp_native_share"} <= listed
    for p in m["per_layer"]:
        if p["name"] in NEW + ("tc_render_share", "tcp_native_share"):
            assert not any(w.startswith("hosts_") for w in p["workloads"])
        elif "hosts_zipf_open60" in p["workloads"]:
            assert p["name"] in listed, p["name"]


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_zone_stays_inside_the_sources_limits(seed):
    config = load("benchmark", "configs", CONFIG + ".json")
    zone = Zone(config, "foo.com", seed)
    sizes = [len(s.members) for s in zone.services]
    assert len(sizes) == config["services"]["count"] <= MAX_SERVICES
    assert 2 <= min(sizes) and max(sizes) == MAX_ENDPOINTS
    assert sum(sizes) + zone.hosts <= MAX_PODS
    by_class = {}
    for s in zone.services:
        by_class.setdefault(s.size_class, []).append(len(s.members))
    share = {k: len(v) / len(sizes) for k, v in by_class.items()}
    assert share == {"large": 0.025, "medium": 0.1, "small": 0.875}
    assert (min(by_class["large"]), max(by_class["medium"]),
            min(by_class["medium"]), max(by_class["small"])) \
        == (65, 64, 9, 8)


# -- the readers, on hand-made scrapes --

STAGE = "binder_query_stage_seconds"


def scrape(stages=None, counters=None):
    """Prometheus text of one worker: ``stages`` is name -> (seconds,
    observations), ``counters`` a list of (name, labels text, value)."""
    lines = []
    for name, (total, count) in (stages or {}).items():
        lines.append(f'{STAGE}_sum{{stage="{name}"}} {total!r}')
        lines.append(f'{STAGE}_count{{stage="{name}"}} {count!r}')
    for name, labels, value in counters or []:
        lines.append(f"{name}{labels} {value!r}")
    return {"metrics": "\n".join(lines) + "\n", "status": {}}


def ctx(before, after, seconds=50.0):
    return {"before": {"at": 100.0, "workers": before},
            "after": {"at": 100.0 + seconds, "workers": after}}


def zero(s):
    """The same series at 0: the scrape before the window."""
    return {"metrics": re.sub(r" [0-9.e+-]+$", " 0.0", s["metrics"],
                              flags=re.M), "status": {}}


# two workers over 50 s: 1,000 legs, 6,000 calls inside 0.4 s; 40 s of
# idle of 100 s of wall; 3,000 UDP answers of which 1,000 truncated;
# 200 lazy renders of 0.3 s in 4,000 answers; SRV answers of 800 bytes
WORKER_A = scrape(
    {"tcp-accept": (0.10, 1200), "tcp-recv": (0.06, 1200),
     "tcp-send": (0.02, 600), "tcp-close": (0.06, 600),
     "loop-idle": (30.0, 9000), "lazy-render": (0.2, 150)},
    [("binder_tcp_accepts", "", 600.0),
     ("binder_tcp_fast_serves", "", 600.0),
     ("binder_udp_datagrams", '{dir="out"}', 1800.0),
     ("binder_udp_datagrams", '{dir="in"}', 1800.0),
     ("binder_truncated_responses", '{type="SRV"}', 590.0),
     ("binder_truncated_responses", '{type="A"}', 10.0),
     ("binder_requests_completed", '{type="SRV"}', 2400.0),
     ("binder_response_size_bytes_sum", '{type="SRV"}', 1920000.0),
     ("binder_response_size_bytes_count", '{type="SRV"}', 2400.0),
     ("binder_response_size_bytes_sum", '{type="A"}', 5000.0),
     ("binder_response_size_bytes_count", '{type="A"}', 100.0)])
WORKER_B = scrape(
    {"tcp-accept": (0.06, 800), "tcp-recv": (0.04, 800),
     "tcp-send": (0.02, 400), "tcp-close": (0.04, 400),
     "loop-idle": (10.0, 7000), "lazy-render": (0.1, 50)},
    [("binder_tcp_accepts", "", 400.0),
     ("binder_tcp_fast_serves", "", 400.0),
     ("binder_udp_datagrams", '{dir="out"}', 1200.0),
     ("binder_truncated_responses", '{type="SRV"}', 400.0),
     ("binder_requests_completed", '{type="SRV"}', 1600.0),
     ("binder_response_size_bytes_sum", '{type="SRV"}', 1280000.0),
     ("binder_response_size_bytes_count", '{type="SRV"}', 1600.0)])
WANT = {"tcp_leg_share": 100.0 * 1000 / 3000,
        "udp_tc_share": 100.0 * 1000 / 3000,
        "tcp_us_per_leg": 400.0,
        "tcp_crossings_per_leg": 6.0,
        "stream_busy_share": 100.0 * 0.4 / (100.0 - 40.0),
        "lazy_render_share": 100.0 * 200 / 4000,
        "lazy_render_us": 1500.0,
        "srv_answer_bytes_mean": 800.0}
# what each reader cannot do without: a program that lacks it (the
# parent of the PR that adds it) gives nothing, and never raises
NEEDS = {"tcp_leg_share": "binder_tcp_fast_serves",
         "udp_tc_share": "binder_truncated_responses",
         "tcp_us_per_leg": 'stage="tcp-close"',
         "tcp_crossings_per_leg": 'stage="tcp-accept"',
         "stream_busy_share": 'stage="tcp-send"',
         "lazy_render_share": 'stage="lazy-render"',
         "lazy_render_us": 'stage="lazy-render"',
         "srv_answer_bytes_mean": 'binder_response_size_bytes_count{type="SRV"'}


def without(s, needle):
    return {"metrics": "\n".join(ln for ln in s["metrics"].splitlines()
                                 if needle not in ln) + "\n", "status": {}}


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_the_right_number(name):
    after = [WORKER_A, WORKER_B]
    got = readers()[name].read(ctx([zero(w) for w in after], after))
    assert got == pytest.approx(WANT[name])
    # only deltas count: what was there before the window is not in it
    assert readers()[name].read(ctx(after, after)) in (None, 0.0)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_none_without_its_span_or_counter(name):
    after = [without(w, NEEDS[name]) for w in (WORKER_A, WORKER_B)]
    read = readers()[name].read
    assert read(ctx([zero(w) for w in after], after)) is None
    for empty in ({}, {"before": None, "after": None}, ctx([], [])):
        assert read(empty) is None


# -- the cell on the CPU, on a cut of its zone --

def test_rehearsal_of_the_cell_is_correct_and_retries_over_tcp():
    """``benchmark/tests/services``: dc-services-x4's classes, ranges and
    period over 120 services, two workers; the cell's mix at 1,500/s."""
    cut = load("benchmark", "tests", "services", "configs",
               "dc-services-cut.json")
    real = load("benchmark", "configs", CONFIG + ".json")
    for key in ("srvce", "proto", "port", "rank_period", "classes"):
        assert cut["services"][key] == real["services"][key]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--dir",
         "benchmark/tests/services", "--workload", CELL, "--seed",
         str(2**31 + 26), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, text=True, timeout=240, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL)
    lines = [ln for ln in proc.stdout.splitlines() if "REHEARSAL" in ln]
    assert lines and proc.returncode == 0, proc.stdout[-3000:]
    result = json.loads(lines[-1].split("measurement): ", 1)[1])
    assert result["correct"] and result["failed"] == 0
    retries = int(re.search(r"TC retries (\d+)", proc.stdout).group(1))
    assert retries > 0.2 * result["attempted"]
    longest = int(re.search(r"the longest with (\d+) records",
                            proc.stdout).group(1))
    assert longest > 2 * 64         # a large set, whole, over TCP
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics)
    # every TC=1 answer is fetched again once
    assert metrics["udp_tc_share"] == pytest.approx(
        metrics["tcp_leg_share"], abs=1.0)
    assert metrics["tcp_leg_share"] == pytest.approx(
        100.0 * retries / result["attempted"], abs=3.0)
    assert 5.0 <= metrics["tcp_crossings_per_leg"] <= 7.0
    assert metrics["tcp_us_per_leg"] > 0 and metrics["lazy_render_us"] > 0
    assert 0 < metrics["stream_busy_share"] < 100
    assert 0 < metrics["lazy_render_share"] < 100
    assert 100 < metrics["srv_answer_bytes_mean"] < 20000
    # (the lazy render is held by ``lazy_render_us`` above: since PR 39 the
    # zone table gives every leg, and on this cut the stage is the tenth
    # largest in some runs and the eleventh in others)
    stages = dict(result["breakdown"]["idle_gaps"])
    assert {"tcp-accept", "tcp-close"} <= set(stages)
    # the four readers that know the stream stages and the lazy render
    # since PR 30: the crossings of the legs are among the calls counted
    assert {"busy_unnamed_share", "syscalls_per_answer",
            "socket_us_per_answer", "python_us_per_query"} <= set(metrics)
    # a mix of one entry: the generator keeps no second histogram (it does
    # per answer what it did before mixes had kinds) and gives the whole
    # as the entry's
    g = load("benchmark", "out", CELL, "generator.json")
    assert g["latency_ns_by_entry"] == [{"latency_ns": g["latency_ns"]}]
    assert 0 < metrics["busy_unnamed_share"] < 100
    assert metrics["syscalls_per_answer"] > metrics["tcp_leg_share"] / 100 \
        * metrics["tcp_crossings_per_leg"]
