"""Tests of the benchmark's own arithmetic, reference and manifest, and a CPU
rehearsal of ``run.py`` end to end on a tiny cell that ``BENCHMARK.json`` does
not list: a sound run, the control (one guarantee broken: a worker cut off
from the mutation log) and the timed path broken underneath (the program's
data altered; the reference altered).  Run them before any chip call:

    python -m pytest benchmark/tests -q
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import dnswire
import stats
from reference import Service, Zone, compare
from traffic import Traffic, arrival_times, draw_ranks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME_RX = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RX = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- arithmetic --

def test_histogram_buckets_tile_the_line():
    edge = 0
    for idx in range(0, 12000):
        low, high = stats.bucket_bounds(idx, 9)
        assert low == edge and high > low
        edge = high
    # 512 buckets to the octave: a bucket is at most 1/512 of its value
    low, high = stats.bucket_bounds(9000, 9)
    assert (high - low) / low <= 1 / 512


@pytest.mark.parametrize("q,want", [(50, 50.0), (99, 99.0), (100, 100.0),
                                    (1, 1.0)])
def test_percentile_of_known_histogram(q, want):
    # the values 0..99 once each: bucket v holds [v, v+1), so the q-th
    # nearest-rank percentile ends its bucket at q
    hist = [[v, 1] for v in range(100)]
    assert stats.hist_percentile(hist, 9, q) == pytest.approx(want)


def test_percentile_interpolates_inside_a_wide_bucket():
    idx = (3 << 9) + 600            # a bucket eight wide
    low, high = stats.bucket_bounds(idx, 9)
    assert high - low == 8
    assert stats.hist_percentile([[idx, 4]], 9, 50) == low + 4.0
    with pytest.raises(ValueError):
        stats.hist_percentile([], 9, 50)


def test_prometheus_sums_and_histogram_delta():
    before = ('x_total{shard="0"} 5\nx_total{shard="1"} 7\n'
              'lag_bucket{le="0.001"} 10\nlag_bucket{le="0.01"} 12\n'
              'lag_bucket{le="+Inf"} 12\n')
    after = ('x_total{shard="0"} 9\nx_total{shard="1"} 7\n'
             'lag_bucket{le="0.001"} 110\nlag_bucket{le="0.01"} 113\n'
             'lag_bucket{le="+Inf"} 114\n')
    assert stats.total(after, "x_total") - stats.total(before, "x_total") == 4
    delta = stats.histogram_delta(before, after, "lag")
    assert delta == [(0.001, 100.0), (0.01, 1.0), (float("inf"), 1.0)]
    assert stats.bucketed_percentile(delta, 50) == 0.001
    assert stats.bucketed_percentile(delta, 99) == 0.01
    assert stats.bucketed_percentile([(1.0, 0.0)], 99) is None


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# -- traffic --

def test_zipf_ranks_reproducible_and_skewed():
    dist = {"kind": "zipfian", "constant": 0.99}
    a = draw_ranks(np.random.default_rng([7, 2]), 100000, dist, 50000)
    b = draw_ranks(np.random.default_rng([7, 2]), 100000, dist, 50000)
    c = draw_ranks(np.random.default_rng([8, 2]), 100000, dist, 50000)
    assert (a == b).all() and not (a == c).all()
    # Zipf 0.99 over 100k: rank 0 draws about 1/H = 8% of the asks
    assert 0.06 < (a == 0).mean() < 0.10
    assert a.max() < 100000


def test_poisson_schedule_keeps_its_rate_with_and_without_bursts():
    plain = arrival_times(np.random.default_rng(1), 5000.0, 10.0)
    assert (np.diff(plain.astype(np.int64)) >= 0).all()
    assert abs((plain < 10e9).sum() / 10.0 - 5000.0) < 150
    burst = arrival_times(np.random.default_rng(1), 5000.0, 10.0,
                          {"on_s": 1.0, "off_s": 1.0, "factor": 4.0})
    in_window = burst[burst < 10e9]
    assert abs(len(in_window) / 10.0 - 5000.0) < 150
    on = ((in_window // 1e9).astype(int) % 2 == 0).sum()
    assert 3.5 < on / (len(in_window) - on) < 4.5


def tiny_cell():
    with open(os.path.join(HERE, "tiny", "configs", "tiny-x2.json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "tiny", "workloads",
                           "tiny_closed.json")) as f:
        return config, json.load(f)


def test_traffic_same_seed_same_files_other_seed_other_order():
    config, workload = tiny_cell()
    made = [Traffic(workload, Zone(config, "foo.com", seed), seed, 1.0)
            for seed in (3, 3, 4)]
    assert (made[0].sequence == made[1].sequence).all()
    assert made[0].templates == made[1].templates
    assert len(made[0].sequence) == len(made[2].sequence)
    assert not (made[0].sequence == made[2].sequence).all()
    # every template's question is answered by the reference with the
    # answer count the generator will hold the server to
    zone = Zone(config, "foo.com", 3)
    for (qname, qtype), (_, rcode, ancount, entry) in zip(
            made[0].questions[:500], made[0].templates):
        want = zone.expected(qname, qtype)
        assert (want["rcode"], len(want["answers"])) == (rcode, ancount)
        assert workload["mix"][entry]["qtype"] == {
            v: k for k, v in dnswire.QTYPES.items()}[qtype]
    assert (made[0].sequence & 0x80000000).any()


def test_service_sizes_are_the_same_under_every_seed():
    config, _ = tiny_cell()
    sizes = [sorted((s.rank, s.size_class, len(s.members))
                    for s in Zone(config, "foo.com", seed).services)
             for seed in (1, 2)]
    assert sizes[0] == sizes[1]
    by_rank = {rank: cls for rank, cls, _ in sizes[0]}
    assert by_rank[10] == "large" and by_rank[5] == "medium"
    assert all(by_rank[r] == "small" for r in (1, 2, 3, 4))
    labels = [{s.label for s in Zone(config, "foo.com", seed).services}
              for seed in (1, 2)]
    assert labels[0] != labels[1]


# -- the reference against a zone written by hand --

def hand_zone() -> Zone:
    """Ten records: four hosts in one rack, the service ``web`` with three
    load balancers, the service ``db`` with one moray host, and the rack
    itself."""
    config = {"hosts": 4, "racks": 0, "subtree": "zs", "chaos": {"writes": 2},
              "services": {"count": 2, "srvce": "_http", "proto": "_tcp",
                           "port": 80}}
    return Zone(config, "foo.com", 0, services=[
        Service("web", 1, "small", "load_balancer",
                [("lb0", "10.200.0.1"), ("lb1", "10.200.0.2"),
                 ("lb2", "10.200.0.3")]),
        Service("db", 2, "small", "moray_host", [("m0", "10.200.1.1")])])


@pytest.mark.parametrize("qname,qtype,rcode,answers,glue", [
    ("h000002.r0000.zs.foo.com", "A", 0, [(1, "10.0.0.2")], []),
    ("h000004.r0000.zs.foo.com", "A", 5, [], []),       # past the zone
    ("h000001.r0001.zs.foo.com", "A", 5, [], []),       # wrong rack
    ("2.0.0.10.in-addr.arpa", "PTR", 0,
     [(12, "h000002.r0000.zs.foo.com")], []),
    ("3.0.200.10.in-addr.arpa", "PTR", 0, [(12, "lb2.web.foo.com")], []),
    ("9.9.9.10.in-addr.arpa", "PTR", 5, [], []),
    ("web.foo.com", "A", 0,
     [(1, "10.200.0.1"), (1, "10.200.0.2"), (1, "10.200.0.3")], []),
    ("lb1.web.foo.com", "A", 0, [(1, "10.200.0.2")], []),
    ("_http._tcp.db.foo.com", "SRV", 0, [(33, (0, 10, 80, "m0.db.foo.com"))],
     [("m0.db.foo.com", "10.200.1.1")]),
    ("_http._tcp.web.foo.com", "SRV", 0,
     [(33, (0, 10, 80, f"lb{k}.web.foo.com")) for k in range(3)],
     [(f"lb{k}.web.foo.com", f"10.200.0.{k + 1}") for k in range(3)]),
    ("_ftp._tcp.web.foo.com", "SRV", 3, [], []),        # not its service
    ("nosuch.foo.com", "A", 5, [], []),
    ("web.bar.com", "A", 5, [], []),
    ("chaos1.foo.com", "A", 5, [], []),                 # not written yet
])
def test_reference_on_hand_written_zone(qname, qtype, rcode, answers, glue):
    want = hand_zone().expected(qname, dnswire.QTYPES[qtype])
    assert (want["rcode"], want["answers"], want["glue"]) \
        == (rcode, sorted(answers), sorted(glue))


def test_reference_nodata_and_written_names():
    zone = hand_zone()
    want = zone.expected("_http._tcp.h000001.r0000.zs.foo.com", dnswire.SRV)
    assert want["nodata"] and want["rcode"] == 0 and not want["answers"]
    zone.writes_done = True
    assert zone.expected("chaos1.foo.com", dnswire.A)["answers"] \
        == [(1, "10.254.1.2")]
    assert len(zone.fixture()) == 2 + 3 + 1


def response(qname, qtype, rcode, answers, ttl=30, tc=False) -> bytes:
    """A response assembled by hand, with a compression pointer to the
    question's name in every record."""
    import struct
    wire = struct.pack(">HHHHHH", 7, 0x8400 | (0x0200 if tc else 0) | rcode,
                       1, len(answers), 0, 0)
    wire += dnswire.encode_name(qname) + struct.pack(">HH", qtype, 1)
    for rtype, rdata in answers:
        wire += b"\xc0\x0c" + struct.pack(">HHIH", rtype, 1, ttl,
                                          len(rdata)) + rdata
    return wire


def test_codec_and_compare_catch_each_kind_of_wrong_answer():
    zone = hand_zone()
    qname, want = "web.foo.com", None
    want = zone.expected(qname, dnswire.A)
    good = [(1, bytes([10, 200, 0, k])) for k in (3, 1, 2)]    # any order
    ok = dnswire.Answer(response(qname, 1, 0, good))
    assert ok.qid == 7 and ok.question == (qname, 1)
    assert compare(ok, qname, 1, want) == []
    wrong_addr = [(1, bytes([10, 200, 0, k])) for k in (1, 2, 9)]
    for broken in (response(qname, 1, 0, wrong_addr),
                   response(qname, 1, 0, good[:2]),
                   response(qname, 1, 0, good, ttl=60),
                   response(qname, 1, 5, []),
                   response(qname, 1, 0, good, tc=True),
                   response("web.foo.org", 1, 0, good)):
        assert compare(dnswire.Answer(broken), qname, 1, want)
    # TC=1 over UDP: only the header is held to anything
    assert compare(dnswire.Answer(response(qname, 1, 0, [], tc=True)),
                   qname, 1, want, whole=False) == []
    query = dnswire.make_query(qname, 1, qid=9, rd=True, edns_payload=1232)
    assert query[:2] == b"\x00\x09" and query[2] & 1 and query[-11] == 0
    with pytest.raises(ValueError):
        dnswire.Answer(query)           # a query is not a response


# -- the manifest --

def test_manifest_names_units_and_files():
    m = manifest()
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in m[key]]
    for name in names + [w["traffic"] for w in m["workloads"]] \
            + [k for c in m["configs"] for k in c["reduced"]]:
        assert NAME_RX.match(name), name
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT_RX.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    for key in ("configs", "workloads"):
        listed = [e["name"] for e in m[key]]
        assert len(set(listed)) == len(listed)
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert sorted(held["reduced"]) == c["reduced"]
    for w in m["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            held = json.load(f)
        assert held["config"] == w["config"]
        assert held["config"] in [c["name"] for c in m["configs"]]
    for metric in m["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           metric["name"] + ".py"))


def test_every_cell_reports_what_the_manifest_says_and_moves_point_right():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    reports = {}
    for cell in cells:
        with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
            reports[cell] = set(json.load(f)["end_to_end"])
    for metric in m["end_to_end"]:
        for cell in cells:
            listed = cell in metric.get("workloads", cells)
            assert listed == (metric["name"] in reports[cell]), (metric, cell)
        assert 0.01 <= metric["bound"] <= 0.25
    e2e = {e["name"] for e in m["end_to_end"]}
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e
        for cell in metric["workloads"]:
            assert metric["moves"] in reports[cell], (metric["name"], cell)
    for cell in cells:
        assert "setup_s" in reports[cell] and len(reports[cell]) >= 2
        assert any(cell in p["workloads"] for p in m["per_layer"])


def test_layer_readers_state_what_the_manifest_states():
    sys.path.insert(0, BENCH)
    import run
    readers = run.layer_readers()
    for metric in manifest()["per_layer"]:
        module = readers[metric["name"]]
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (metric["layer"], metric["unit"], metric["moves"])


# -- run.py end to end, on the CPU, at a size a test can hold --

def rehearse(workload: str, seed: int, trace: int, broken=None,
             cell: str = "tiny", seconds: int = 2) -> dict:
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--cpu",
            "--dir", "benchmark/tests/" + cell, "--workload", workload,
            "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    if broken:
        argv += ["--break", broken]
    proc = subprocess.run(argv, cwd=ROOT, text=True, timeout=300,
                          stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    lines = [ln for ln in proc.stdout.splitlines() if "REHEARSAL" in ln]
    assert lines, proc.stdout[-3000:]
    result = json.loads(lines[-1].split("measurement): ", 1)[1])
    # the exit code says what the line says; no result line on the CPU
    assert proc.returncode == (0 if result["correct"] else 1)
    assert not proc.stdout.splitlines()[-1].startswith("{")
    result["stdout"] = proc.stdout
    return result


@pytest.mark.parametrize("workload,seed,trace,expects", [
    ("tiny_closed", 2**31 + 11, 0, {"answers_per_s", "p99_us", "setup_s"}),
    ("tiny_open", 12, 1, {"gen_late_p99_us", "shard_balance", "ready_s",
                          "seed_s", "loop_lag_p99_ms"}),
])
def test_rehearsal_sound_run_is_correct(workload, seed, trace, expects):
    result = rehearse(workload, seed, trace)
    assert result["correct"], result["stdout"][-3000:]
    assert result["failed"] == 0 and result["attempted"] > 1000
    assert expects <= set(result["metrics"])
    # (0 is the value, in a quiet window, of the two stall metrics, of the
    # answers dropped at a full send buffer and of the generator's stops)
    assert all(m["value"] > 0 for name, m in result["metrics"].items()
               if m["unit"] != "%" and name not in (
                   "sandbox_freeze_ms", "worker_stall_ms", "udp_send_drops",
                   "gen_stop_ms"))
    # a window without a stop of the machine of 250 ms counts what the
    # generator counted: nothing is voided
    with open(os.path.join(BENCH, "out", workload, "generator.json")) as f:
        g = json.load(f)
    if not any(length >= 0.25 for _, length in result["stops"]):
        assert result["voided"] == {"queries": 0, "of_them_failed": 0}
        assert result["attempted"] == g["sent"]
        assert result["failed"] == g["failed"] + g["unanswered_at_end"]
    assert sum(result["failed_by_kind"].values()) == result["failed"]
    assert result["attempted"] + result["voided"]["queries"] == g["sent"]
    assert [key for key in result if key != "stdout"][-1] == "compared"
    assert "compared window_answers_mismatching = 0 (limit 0)" \
        in result["stdout"]
    if trace:
        assert result["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("broken,number", [
    # the control: read-your-writes broken on one worker by the program's
    # own skew-replica chaos action
    ("skew-replica", "written_names_mismatching"),
    # the timed path broken underneath: the program serves altered data
    ("fixture-address", "window_answers_mismatching"),
    # and the reference given an altered zone
    ("reference-address", "window_answers_mismatching"),
])
def test_rehearsal_broken_run_is_not_correct(broken, number):
    result = rehearse("tiny_closed", 13, 0, broken)
    assert result["correct"] is False
    assert re.search(r"compared %s = [1-9]\d* \(limit 0\)  <-- outside"
                     % number, result["stdout"]), result["stdout"][-3000:]


def test_without_a_chip_and_without_cpu_flag_the_run_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--dir",
         "benchmark/tests/tiny", "--workload", "tiny_closed", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env, text=True,
        timeout=240, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    assert proc.returncode != 0
    assert "FAILED in device" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
