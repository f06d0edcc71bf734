"""The questions a stub really asks and the zone answers without records
(ISSUE 30): the AAAA twin of every A, which binder declines (NOTIMP);
cueball's SRV-first question on a plain host (NODATA with the SOA); a miss
(REFUSED).  The reference and the comparison against wires written by
hand, the traffic generator's templates for each new ``target``/``qtype``
pair, the three readers of the A/AAAA cell on a hand-made generator result,
the largest set among the kept and the asked under ten seeds, and a CPU
rehearsal of a mix that holds every kind: ``correct`` true, and false
under the control ``--break reference-declined``."""
import json
import os
import re
import struct
import sys

import numpy as np
import pytest

import dnswire
from reference import Zone, compare
from test_benchmark import BENCH, HERE, ROOT, hand_zone, rehearse, tiny_cell
from traffic import CAPTURE_FLAG, KIND_KEPT, LONGEST_KEPT, Traffic

HOST = "h000001.r0000.zs.foo.com"
SRV_ON_HOST = "_http._tcp." + HOST
ABSENT = "h000004.r0000.zs.foo.com"     # the hand zone has hosts 0..3


def record(owner: str, rtype: int, ttl: int, rdata: bytes) -> bytes:
    return dnswire.encode_name(owner) \
        + struct.pack(">HHIH", rtype, 1, ttl, len(rdata)) + rdata


def soa(owner: str, ttl: int = 30, minimum: int = 30) -> bytes:
    return record(owner, dnswire.SOA, ttl,
                  dnswire.encode_name("ns0.foo.com")
                  + dnswire.encode_name("hostmaster.foo.com")
                  + struct.pack(">IIIII", 7, 3600, 600, 86400, minimum))


def wire(qname: str, qtype: int, rcode: int, authorities=(),
         additionals=(), echo=None) -> bytes:
    """A response without answers, written out by hand."""
    return struct.pack(">HHHHHH", 7, 0x8400 | rcode, 1, 0, len(authorities),
                       len(additionals)) \
        + dnswire.encode_name(echo or qname) + struct.pack(">HH", qtype, 1) \
        + b"".join(authorities) + b"".join(additionals)


GLUE = record("x.foo.com", dnswire.A, 30, bytes([10, 0, 0, 9]))


@pytest.mark.parametrize("qname,qtype,rcode,nodata", [
    (HOST, dnswire.AAAA, dnswire.NOTIMP, None),
    # routed by type before any look at the name: a miss is declined too
    (ABSENT, dnswire.AAAA, dnswire.NOTIMP, None),
    ("nosuch.bar.org", dnswire.AAAA, dnswire.NOTIMP, None),
    (HOST, 16, dnswire.NOTIMP, None),                   # TXT
    (SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR, HOST),
    (ABSENT, dnswire.A, dnswire.REFUSED, None),
    ("_http._tcp." + ABSENT, dnswire.SRV, dnswire.REFUSED, None),
], ids=["aaaa-host", "aaaa-absent", "aaaa-elsewhere", "txt-host",
        "srv-on-host", "a-absent", "srv-absent"])
def test_reference_answers_without_records(qname, qtype, rcode, nodata):
    want = hand_zone().expected(qname, qtype)
    assert want == {"rcode": rcode, "answers": [], "glue": [],
                    "nodata": nodata, "opt": None, "payload": None}


@pytest.mark.parametrize("qname,qtype,good,bad", [
    (HOST, dnswire.AAAA, wire(HOST, dnswire.AAAA, dnswire.NOTIMP), {
        "answered-empty": wire(HOST, dnswire.AAAA, dnswire.NOERROR),
        "refused": wire(HOST, dnswire.AAAA, dnswire.REFUSED),
        "an-soa": wire(HOST, dnswire.AAAA, dnswire.NOTIMP, [soa(HOST)]),
        "glue": wire(HOST, dnswire.AAAA, dnswire.NOTIMP, (), [GLUE]),
        "type-echoed-as-a": wire(HOST, dnswire.A, dnswire.NOTIMP),
        "other-name-echoed": wire(HOST, dnswire.AAAA, dnswire.NOTIMP,
                                  echo=ABSENT)}),
    (SRV_ON_HOST, dnswire.SRV,
     wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR, [soa(HOST)]), {
        "no-soa": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR),
        "nxdomain": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NXDOMAIN,
                         [soa(HOST)]),
        "soa-ttl": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR,
                        [soa(HOST, ttl=60)]),
        "soa-minimum": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR,
                            [soa(HOST, minimum=3600)]),
        "soa-of-the-zone": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR,
                                [soa("foo.com")]),
        "two-soas": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR,
                         [soa(HOST), soa(HOST)]),
        "glue": wire(SRV_ON_HOST, dnswire.SRV, dnswire.NOERROR, [soa(HOST)],
                     [GLUE])}),
    (ABSENT, dnswire.A, wire(ABSENT, dnswire.A, dnswire.REFUSED), {
        "nxdomain": wire(ABSENT, dnswire.A, dnswire.NXDOMAIN),
        "answered-empty": wire(ABSENT, dnswire.A, dnswire.NOERROR),
        "an-soa": wire(ABSENT, dnswire.A, dnswire.REFUSED, [soa("foo.com")])}),
], ids=["notimp", "nodata", "refused"])
def test_compare_holds_an_answer_without_records_to_its_header(
        qname, qtype, good, bad):
    want = hand_zone().expected(qname, qtype)
    assert compare(dnswire.Answer(good), qname, qtype, want) == []
    for what, broken in bad.items():
        assert compare(dnswire.Answer(broken), qname, qtype, want), what


def test_the_codec_reads_an_soa_whole():
    answer = dnswire.Answer(wire(SRV_ON_HOST, dnswire.SRV, 0,
                                 [soa(HOST, minimum=45)]))
    assert answer.authorities == [(HOST, dnswire.SOA, 30, (
        "ns0.foo.com", "hostmaster.foo.com", 7, 3600, 600, 86400, 45))]


def test_the_control_tells_the_reference_that_aaaa_is_answered():
    zone = hand_zone()
    zone.answered_empty = frozenset({dnswire.AAAA})
    want = zone.expected(HOST, dnswire.AAAA)
    assert want["rcode"] == dnswire.NOERROR and not want["answers"]
    # what the deployment really gives no longer passes, nor would an SOA
    assert compare(dnswire.Answer(wire(HOST, dnswire.AAAA, dnswire.NOTIMP)),
                   HOST, dnswire.AAAA, want)
    assert zone.expected(HOST, 16)["rcode"] == dnswire.NOTIMP


# -- the traffic generator's templates --

def one_entry(qtype: str, target: str, seed: int = 5):
    config, workload = tiny_cell()
    workload = dict(workload, mix=[{"share": 1.0, "qtype": qtype,
                                    "target": target}])
    zone = Zone(config, "foo.com", seed)
    return zone, Traffic(workload, zone, seed, 0.2)


@pytest.mark.parametrize("qtype,target,rcode,ancount,name_rx", [
    ("A", "host", 0, 1, r"^h00[01]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("AAAA", "host", 4, 0, r"^h00[01]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("SRV", "host", 0, 0, r"^_http\._tcp\.h00[01]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("PTR", "host", 0, 1, r"^\d+\.\d+\.0\.10\.in-addr\.arpa$"),
    # past the zone's 2,000 hosts, in the hosts' own label shape
    ("A", "absent", 5, 0, r"^h00[23]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("AAAA", "absent", 4, 0, r"^h00[23]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("SRV", "absent", 5, 0,
     r"^_http\._tcp\.h00[23]\d{3}\.r\d{4}\.zs\.foo\.com$"),
    ("PTR", "absent", 5, 0, r"^\d+\.\d+\.0\.10\.in-addr\.arpa$"),
    ("AAAA", "service", 4, 0, r"^svc-[0-9a-f]{6}\.foo\.com$"),
    ("AAAA", "member", 4, 0, r"^[0-9a-f]{8}\.svc-[0-9a-f]{6}\.foo\.com$"),
])
def test_templates_of_each_target_and_type(qtype, target, rcode, ancount,
                                           name_rx):
    zone, traffic = one_entry(qtype, target)
    assert len(traffic.templates) > 20
    for (qname, code), (raw, want_rcode, want_count, entry) in zip(
            traffic.questions, traffic.templates):
        assert re.match(name_rx, qname), qname
        assert (code, want_rcode, want_count, entry) \
            == (dnswire.QTYPES[qtype], rcode, ancount, 0)
        # the question on the wire is the one the reference was asked
        end = 12 + len(dnswire.encode_name(qname))
        assert raw[12:end] == dnswire.encode_name(qname)
        assert struct.unpack(">HH", raw[end:end + 4]) == (code, 1)
        if target == "absent" and qtype != "PTR":
            index = int(qname.split(".")[-5][1:] if qtype != "SRV"
                        else qname.split(".")[2][1:])
            assert zone.hosts <= index < 2 * zone.hosts


def test_an_unknown_target_or_a_ptr_of_a_set_is_refused_by_the_generator():
    with pytest.raises(ValueError):
        one_entry("A", "rack")
    with pytest.raises(ValueError):
        one_entry("PTR", "service")
    with pytest.raises(KeyError):
        one_entry("TXT", "host")


def test_both_halves_of_a_pair_draw_from_one_ranking():
    """The A and the AAAA entry draw their own ranks over the same seeded
    permutation: the most asked name of one half is that of the other."""
    config, workload = tiny_cell()
    workload = dict(workload, mix=[
        {"share": 0.5, "qtype": "A", "target": "host"},
        {"share": 0.5, "qtype": "AAAA", "target": "host"}])
    traffic = Traffic(workload, Zone(config, "foo.com", 9), 9, 1.0)
    top = []
    for entry in (0, 1):
        sends = [t for t in (traffic.sequence & ~np.uint32(CAPTURE_FLAG))
                 if traffic.templates[t][3] == entry]
        assert 0.45 < len(sends) / len(traffic.sequence) < 0.55
        most = max(set(sends), key=sends.count)
        top.append(traffic.questions[most][0])
    assert top[0] == top[1]
    assert {t[1] for t in traffic.templates} == {0, 4}


# -- what is always kept and always asked --

def services_cut():
    with open(os.path.join(HERE, "services", "configs",
                           "dc-services-cut.json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "services", "workloads",
                           "services_srv_open60.json")) as f:
        return config, json.load(f)


@pytest.mark.parametrize("seed", [2**31 + k for k in range(10)])
def test_the_zones_largest_set_is_kept_and_asked_under_every_seed(seed):
    """``--break fixture-address`` alters a member of the largest set, so
    it bites only where that set is among the answers compared: in every
    seed, from the window's start to its end, and in the asks."""
    config, workload = services_cut()
    zone = Zone(config, "foo.com", seed)
    traffic = Traffic(workload, zone, seed, 3.0)
    largest = max(zone.services, key=lambda s: len(s.members))
    qname = f"_http._tcp.{largest.label}.foo.com"
    tmpl = traffic.questions.index((qname, dnswire.SRV))
    assert traffic.templates[tmpl][2] == len(largest.members) \
        == max(t[2] for t in traffic.templates)
    kept = np.flatnonzero((traffic.sequence & CAPTURE_FLAG != 0)
                          & (traffic.sequence & ~np.uint32(CAPTURE_FLAG)
                             == tmpl))
    assert len(kept) >= LONGEST_KEPT
    third = len(traffic.sequence) // 3
    assert kept[0] < third and kept[-1] > 2 * third
    assert tmpl in traffic.always_asked
    # the asks laid over the draw are too few to move the work
    assert LONGEST_KEPT < 0.01 * len(traffic.sequence)


@pytest.mark.parametrize("seed", [3, 2**31 + 30])
def test_every_mix_entry_is_kept_and_asked(seed):
    config, _ = tiny_cell()
    with open(os.path.join(HERE, "tiny", "workloads",
                           "tiny_kinds.json")) as f:
        workload = json.load(f)
    traffic = Traffic(workload, Zone(config, "foo.com", seed), seed, 2.0)
    kept = traffic.sequence[traffic.sequence & CAPTURE_FLAG != 0] \
        & ~np.uint32(CAPTURE_FLAG)
    by_entry = np.bincount([traffic.templates[t][3] for t in kept],
                           minlength=len(workload["mix"]))
    assert (by_entry >= KIND_KEPT).all()
    assert {traffic.templates[t][3] for t in traffic.always_asked} \
        == set(range(len(workload["mix"])))
    # a zone without services and a mix without sets lay nothing
    hosts_only = dict(config, services=dict(config["services"], count=0))
    plain = dict(workload, mix=workload["mix"][:5])
    traffic = Traffic(plain, Zone(hosts_only, "foo.com", seed), seed, 2.0)
    assert len(traffic.always_asked) == 5


# -- the three readers of the A/AAAA cell --

def readers() -> dict:
    sys.path.insert(0, BENCH)
    import run
    return run.layer_readers()


def generator_result() -> dict:
    """A answered 100 times at 200-299 ns, AAAA 50 times at 600-649 ns;
    a third entry (A again) 100 times at 400-499 ns (under 1,024 ns a
    value is its own bucket)."""
    def hist(lo, n):
        return {"latency_ns": [[v, 1] for v in range(lo, lo + n)]}
    return {"hist_bits": 9,
            "latency_ns_by_entry": [hist(200, 100), hist(600, 50),
                                    hist(400, 100)]}


MIX = [{"share": 0.4, "qtype": "A", "target": "host"},
       {"share": 0.2, "qtype": "AAAA", "target": "host"},
       {"share": 0.4, "qtype": "A", "target": "member"}]


def test_the_type_readers_on_a_known_result():
    ctx = {"generator": generator_result(), "workload": {"mix": MIX},
           "mix_rcodes": [[0], [4], [0]]}
    mods = readers()
    # the median of both A entries together: the 100th of 200 values
    assert mods["a_p50_us"].read(ctx) == pytest.approx(0.300)
    assert mods["aaaa_p50_us"].read(ctx) == pytest.approx(0.625)
    assert mods["declined_type_share"].read(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("ctx", [
    {}, {"generator": None, "workload": None},
    # a generator older than the per-entry histograms
    {"generator": {"hist_bits": 9, "latency_ns": [[5, 1]]},
     "workload": {"mix": MIX}},
    # a mix that asks neither type and declines nothing (an entry that
    # holds NOTIMP beside another rcode is not a declined type's)
    {"generator": generator_result(), "mix_rcodes": [[0], [0, 4], [0, 5]],
     "workload": {"mix": [{"share": 1.0, "qtype": "SRV",
                           "target": "service"}] * 3}},
], ids=["empty", "untraced", "old-generator", "other-mix"])
def test_the_type_readers_give_none_where_there_is_nothing_to_read(ctx):
    mods = readers()
    for name in ("a_p50_us", "aaaa_p50_us", "declined_type_share"):
        assert mods[name].read(ctx) is None


CELL = "hosts_a_aaaa_open60"


def test_the_manifest_lists_the_cell_and_the_three_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == ("dc-hosts-100k-x4", 1)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        held = json.load(f)
    assert held["why"] == cell["why"] and held["rate_from"]
    assert held["rate_per_s"] == held["expect_per_s"]
    by_name = {p["name"]: p for p in m["per_layer"]}
    mods = readers()
    for name in ("a_p50_us", "aaaa_p50_us", "declined_type_share"):
        assert by_name[name]["workloads"] == [CELL]
        # all three are the generator's own, on the benchmark's clock
        assert (by_name[name]["layer"], by_name[name]["unit"],
                by_name[name]["moves"], by_name[name]["source"]) == (
            mods[name].LAYER, mods[name].UNIT, mods[name].MOVES,
            "host_clock")
        assert mods[name].LAYER == "load generator"
    # the new cell reports every reader the hosts cell reports
    for p in m["per_layer"]:
        if "hosts_zipf_open60" in p["workloads"]:
            assert CELL in p["workloads"], p["name"]


def test_the_new_cell_is_the_hosts_cell_but_for_what_it_says():
    def held(name):
        with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
            return json.load(f)
    hosts, cell = held("hosts_zipf_open60"), held(CELL)
    # (its own sweep found the hosts cell's knee: the rate is equal)
    assert {k for k in cell if cell[k] != hosts.get(k)} \
        == {"name", "why", "rate_from", "posture", "mix", "assumed"}
    assert cell["rate_per_s"] == 0.6 * 56000
    assert cell["mix"] == [{"share": 0.5, "qtype": "A", "target": "host"},
                           {"share": 0.5, "qtype": "AAAA",
                            "target": "host"}]
    # the file says what it does not do, and follows the rule it states
    assert "NOT a stub's pairs" in cell["assumed"]["pairing"]
    assert "TRIGGERED AND NOT TAKEN" in cell["rate_from"]


# -- run.py end to end on a mix that holds every kind --

KINDS = "tiny_kinds"


def by_mix_entry(stdout: str) -> list:
    return json.loads(re.search(r"compared: \d+ \((\[[^\]]*\]) by mix entry",
                                stdout).group(1))


def test_rehearsal_of_every_kind_is_correct():
    result = rehearse(KINDS, 2**31 + 30, 1)
    assert result["correct"], result["stdout"][-3000:]
    assert result["failed"] == 0 and result["attempted"] > 1000
    # A, AAAA, SRV on a host, a miss, AAAA of a miss, a set: each among
    # the window's answers compared, and each among the asks
    assert min(by_mix_entry(result["stdout"])) >= 20
    asks = int(re.search(r"before the window: (\d+) sampled asks",
                         result["stdout"]).group(1))
    assert asks > 96
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # 35% + 5% of the mix expect NOTIMP
    assert metrics["declined_type_share"] == pytest.approx(40.0, abs=3.0)
    assert metrics["a_p50_us"] > 0 and metrics["aaaa_p50_us"] > 0
    compared = result["compared"]
    assert compared["window_answers_mismatching"] == {"value": 0, "limit": 0}
    assert compared["mix_entries_with_no_window_answer_compared"]["value"] == 0
    assert list(result)[-2] == "compared"       # last in the line


def test_rehearsal_under_the_declined_control_is_not_correct():
    result = rehearse(KINDS, 31, 0, "reference-declined")
    assert result["correct"] is False
    for number in ("window_answers_mismatching",
                   "asks_mismatching_before_window",
                   "asks_mismatching_after_window"):
        assert re.search(r"compared %s = [1-9]\d* \(limit 0\)  <-- outside"
                         % number, result["stdout"]), result["stdout"][-3000:]
    # the generator held every answer to NOTIMP, as the deployment gives it:
    # it is the comparison that fails, not the window
    assert result["failed"] == 0
    assert result["compared"]["window_answers_wrong_rcode_or_count"][
        "value"] == 0
    assert "rcode 4, reference 0" in result["stdout"]
