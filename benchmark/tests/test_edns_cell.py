"""``services_srv_edns`` (PR 31): what an OPT record on the question changes
in the comparison that decides ``correct``, against wires written by hand;
the cell's files against the manifest; a CPU rehearsal of the cell on the
services zone's cut, sound and under its control ``--break reference-opt``."""
import json
import os
import re
import struct

import pytest

import dnswire
from reference import compare, whole_size
from test_benchmark import BENCH, hand_zone, manifest
from test_benchmark import rehearse as rehearse_cell

CELL, TWIN = "services_srv_edns", "services_srv_open60"
QNAME = "_http._tcp.web.foo.com"


def opt(payload: int = 1232, version: int = 0, owner: bytes = b"\0") -> bytes:
    return owner + struct.pack(">HHBBHH", dnswire.OPT, payload, 0, version,
                               0, 0)


def srv_answer(members=3, opts=(), tc=False, pad: int = 0) -> bytes:
    """The service ``web``'s SRV set as the reference lays it out: every
    owner compressed against the question, the targets spelled out, the
    glue after them, then the OPT records given; *pad* bytes of an
    unknown record's rdata make a wire longer without changing a record
    of the zone (no test here reads past the sizes)."""
    rrs = b""
    for k in range(members):
        target = dnswire.encode_name(f"lb{k}.web.foo.com")
        rrs += b"\xc0\x0c" + struct.pack(">HHIH", 33, 1, 30,
                                         6 + len(target)) \
            + struct.pack(">HHH", 0, 10, 80) + target
    extra = b""
    for k in range(members):
        # lb<k> + a pointer to "web.foo.com" inside the question's name
        extra += b"\x03lb%d" % k + b"\xc0\x17" \
            + struct.pack(">HHIH", 1, 1, 30, 4) + bytes([10, 200, 0, k + 1])
    extra += b"".join(opts)
    if pad:
        extra = b"\xc0\x0c" + struct.pack(">HHIH", 99, 1, 30, pad) \
            + b"x" * pad + extra
    flags = 0x8400 | (0x0200 if tc else 0)
    an = 0 if tc else members
    wire = struct.pack(">HHHHHH", 7, flags, 1, an, 0,
                       0 if tc else members + len(opts) + (1 if pad else 0))
    if tc:
        wire = wire[:10] + struct.pack(">H", len(opts))
        return wire + dnswire.encode_name(QNAME) + struct.pack(">HH", 33, 1) \
            + b"".join(opts)
    return wire + dnswire.encode_name(QNAME) + struct.pack(">HH", 33, 1) \
        + rrs + extra


def want(payload=0, echoed=True) -> dict:
    zone = hand_zone()
    zone.opt_echoed = echoed
    return zone.expected(QNAME, dnswire.SRV, payload)


def problems(wire: bytes, payload, **kw) -> list:
    return compare(dnswire.Answer(wire), QNAME, dnswire.SRV,
                   want(payload or 0), **kw)


def test_the_codec_keeps_opt_records_apart_and_reads_a_querys_payload():
    answer = dnswire.Answer(srv_answer(opts=[opt(4096, version=1)]))
    assert answer.opts == [(2, "", 4096, 0, 1)]
    assert len(answer.additionals) == 3 and answer.size == len(
        srv_answer(opts=[opt(4096, version=1)]))
    query = dnswire.make_query(QNAME, dnswire.SRV, edns_payload=1232)
    assert dnswire.query_payload(query) == 1232
    assert dnswire.query_payload(dnswire.make_query(QNAME, 33)) == 0


def test_the_reference_expects_an_opt_only_where_the_question_had_one():
    assert (want()["opt"], want()["payload"]) == (0, 512)
    assert (want(1232)["opt"], want(1232)["payload"]) == (1, 1232)
    # the control: the reference told that no OPT comes back
    assert want(1232, echoed=False)["opt"] == 0
    assert want(1232, echoed=False)["payload"] == 1232
    # a caller that does not say what the question carried (the tier-1
    # tests of the program against this reference) is held to neither row
    unsaid = hand_zone().expected(QNAME, dnswire.SRV)
    assert (unsaid["opt"], unsaid["payload"]) == (None, None)
    for wire in (srv_answer(), srv_answer(opts=[opt()]),
                 srv_answer(pad=400)):
        assert compare(dnswire.Answer(wire), QNAME, dnswire.SRV, unsaid,
                       truncated=False) == []


def test_the_references_whole_answer_is_the_wire_written_by_hand():
    # 12 + the question's 28 bytes; 35 + 20 bytes a member here (a
    # three-letter target label under a three-letter service:
    # dc-services-x4's labels make it 47 + 72 a member); 11 for the OPT
    assert whole_size(QNAME, want()) == len(srv_answer()) == 40 + 3 * 55
    assert whole_size(QNAME, want(1232)) == len(srv_answer(opts=[opt()]))


@pytest.mark.parametrize("wire,payload,kw,says", [
    # exactly one OPT, version 0, where the question had one
    (srv_answer(opts=[opt()]), 1232, {}, None),
    (srv_answer(), 1232, {}, "0 OPT records, reference 1"),
    (srv_answer(opts=[opt(), opt()]), 1232, {}, "2 OPT records"),
    (srv_answer(opts=[opt(version=1)]), 1232, {}, "version"),
    (srv_answer(opts=[opt(owner=b"\x01x\0")]), 1232, {}, "owned by the root"),
    # none where it had none
    (srv_answer(), None, {}, None),
    (srv_answer(opts=[opt()]), None, {}, "1 OPT records, reference 0"),
    # a TC=1 header keeps the OPT, and is right only past the payload
    (srv_answer(opts=[opt()], tc=True), 200,
     {"whole": False, "truncated": True}, None),
    (srv_answer(tc=True), 200, {"whole": False, "truncated": True},
     "0 OPT records"),
    (srv_answer(opts=[opt()], tc=True), 1232,
     {"whole": False, "truncated": True}, "fits the 1232 advertised"),
    (srv_answer(tc=True), None, {"whole": False, "truncated": True},
     "fits the 512 advertised"),
    # the answer fetched over TCP after a TC=1 that had no cause
    (srv_answer(opts=[opt()]), 1232, {"truncated": True},
     "fits the 1232 advertised"),
    # a whole answer over UDP fits what was advertised
    (srv_answer(opts=[opt()]), 1232, {"truncated": False}, None),
    (srv_answer(opts=[opt()]), 200, {"truncated": False},
     "whole over UDP past the 200"),
    (srv_answer(pad=400), None, {"truncated": False},
     "whole over UDP past the 512"),
])
def test_compare_holds_what_an_opt_changes(wire, payload, kw, says):
    got = problems(wire, payload, **kw)
    if says is None:
        assert got == [], got
    else:
        assert any(says in line for line in got), got


# -- the cell's files --

def test_the_cell_is_the_services_cell_with_an_opt_on_every_query():
    def held(name):
        with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
            return json.load(f)
    edns, twin = held(CELL), held(TWIN)
    for key in twin:
        if key not in ("name", "why", "edns_share", "rate_per_s",
                       "expect_per_s", "rate_from", "posture"):
            assert edns[key] == twin[key], key
    assert (edns["edns_share"], edns["edns_payload"]) == (1.0, 1232)
    assert edns["rate_per_s"] == edns["expect_per_s"] >= 5000
    m = manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell["config"] == "dc-services-x4" and cell["chips"] == 1
    assert cell["why"] == edns["why"] and len(cell["why"]) <= 200
    # it joins every reader the services cell is in, and no new one
    for p in m["per_layer"]:
        assert (CELL in p["workloads"]) == (TWIN in p["workloads"]), p["name"]


# -- the cell on the CPU, on the cut of its zone --

def rehearse(workload: str, seed: int, broken=None):
    result = rehearse_cell(workload, seed, 0, broken, cell="services",
                           seconds=3)
    retries = int(re.search(r"TC retries (\d+)", result["stdout"]).group(1))
    return result, retries, result["stdout"]


def test_rehearsal_with_an_opt_truncates_a_fraction_of_what_it_did():
    """One seed, the services zone's cut with and without the OPT record:
    both correct under the OPT and TC rows, the legs a fraction."""
    seed = 2**31 + 33
    plain, plain_retries, out = rehearse(TWIN, seed)
    assert plain["correct"] and plain["failed"] == 0, out[-3000:]
    edns, edns_retries, out = rehearse(CELL, seed)
    assert edns["correct"] and edns["failed"] == 0, out[-3000:]
    assert plain["attempted"] == edns["attempted"]
    assert 0 < edns_retries < 0.5 * plain_retries
    longest = int(re.search(r"the longest with (\d+) records",
                            out).group(1))
    assert longest > 2 * 64         # the largest set still comes over TCP


def test_rehearsal_under_the_opt_control_is_not_correct():
    result, _, out = rehearse(CELL, 2**31 + 34, "reference-opt")
    assert result["correct"] is False
    assert re.search(r"compared window_answers_mismatching = [1-9]\d* "
                     r"\(limit 0\)  <-- outside", out), out[-3000:]
    assert "1 OPT records, reference 0" in out
    assert result["failed"] == 0    # the answers came; the comparison failed
