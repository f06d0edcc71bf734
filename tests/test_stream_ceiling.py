"""A stream frame has no UDP ceiling (ISSUE 29), and its set has the
stream's limit (ISSUE 39).

A native key carries no transport: a query without an OPT record has the
payload 512 in its key whatever socket it came from.  So the ceiling of a
native serve is the serving entry's to say.  ``fastpath_serve_frames``,
the stream lane's bulk frame serve, is the one entry that knows its
frames came over a stream: it passes over a cached TC=1 wire (promoted
off the UDP path) and serves the zone table's whole set up to what a DNS
message over TCP carries, ``FP_MAX_STREAM_WIRE`` (65,535 bytes), which
is also what the zone table holds of a set: no 4,096-byte body or
fragment limit, no 64-member rule.  A datagram is held to the payload of
its key and to ``FP_MAX_WIRE`` as before, and ``fastpath_serve_wire``,
which is not told its caller's transport, keeps both rules (the key's
payload, no truncated wire).

Two layers, as ``test_fastpath.py`` has them:

- C-unit: ``fastpath_zone_put`` / ``fastpath_put`` on a bare cache, and
  the three serving entries over the same bytes;
- served: a ``BinderServer`` in the production posture's serving shape
  (zone table, query log through the native ring)
  beside the generic path (no cache, no zone table)
  over one zone of SRV sets with glue, frame for frame.

The zone table's SRV bodies spell the glue's owner names out (the fill
writes them uncompressed), where the generic encoder points them at the
question: the same records in the same order, in a longer wire.  So a
zone-served frame is held to the generic path record for record and
field for field, byte for byte where the lengths agree (the sets the
seed installed in the native answer cache), and byte for byte to what
the same zone entry gives a datagram whose payload admits it.
"""
import asyncio
import importlib.machinery
import importlib.util
import json
import os
import socket
import struct
import threading

import pytest

from binder_tpu.dns import ARecord, Message, OPTRecord, Type, make_query
from binder_tpu.introspect.status import Introspector
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger
from tests.test_fastpath import (
    QNAME,
    ckey,
    edns_tail,
    make_cache,
    query_pkt,
    udp_pair,
)
from tests.test_ledger import tcp_oneshot
from tests.test_log_ring import byte_stream, query_lines
from tests.test_truncated_header import put_service
from tools.lint import validate_ledger_metrics, validate_status_snapshot

fastio = pytest.importorskip(
    "binder_tpu._binderfastio",
    reason="fastio extension not built (make -C native)")

DOMAIN = "foo.com"
FP_MAX_WIRE = 4096
FP_MAX_STREAM_WIRE = 65535
FP_ZONE_MAX_BYTES = 512 << 20
GEN = 1
OPTS = {"no-opt": None, "opt1232": 1232, "opt4096": 4096}
SRC = ("127.0.0.9", 4242)


def frame(wire: bytes) -> bytes:
    return struct.pack(">H", len(wire)) + wire


def unframe(block: bytes) -> list:
    out, off = [], 0
    while off < len(block):
        (n,) = struct.unpack_from(">H", block, off)
        out.append(block[off + 2:off + 2 + n])
        off += 2 + n
    return out


# -- C-unit: one zone entry, three entries to serve it --

#: whole response lengths around every ceiling a key can hold, and
#: above them what only a stream carries, up to its bound and past it
STREAM_TOTALS = (FP_MAX_WIRE + 1, 8192, 34300, FP_MAX_STREAM_WIRE,
                 FP_MAX_STREAM_WIRE + 1)
TOTALS = (100, 512, 513, 1232, 1233, FP_MAX_WIRE) + STREAM_TOTALS
#: what ``fp_zone_put`` admits of a body under QNAME: the stream's bound
#: less the header, the question and the OPT echo, so that a stored
#: entry serves a frame in every posture
MAX_BODY = FP_MAX_STREAM_WIRE - (12 + len(QNAME) + 4 + 11)


def zone_query(payload, rd, qid=0x2222):
    return query_pkt(qid=qid, rd=rd, qtype=1,
                     tail=edns_tail(payload) if payload else b"")


def zone_body(total, payload, tag=0x41):
    """An opaque A-answer body that makes the assembled response
    *total* bytes long: header, question, body, and the OPT echo of a
    query that carried one."""
    blen = total - 12 - len(QNAME) - 4 - (11 if payload else 0)
    return bytes([tag]) * blen


def put_zone(cache, bodies, frags=None, qname=QNAME):
    zkey = b"\x00\x01\x00\x01" + qname.lower()
    args = (cache, zkey, GEN, 1, bodies, qname.lower(), 0)
    if frags is not None:
        args += (frags,)
    return fastio.fastpath_zone_put(*args)


def stored(total, payload):
    """Whether the zone table holds ``zone_body(total, payload)``."""
    return len(zone_body(total, payload)) <= MAX_BODY


def skips(cache):
    stats = fastio.fastpath_stats(cache)
    return stats["zone_put_skips_size"], stats["zone_put_skips_bytes"]


def serve_frames(cache, pkt, logged=False):
    extra = (SRC[0], SRC[1], "tcp") if logged else ()
    block, consumed, misses = fastio.fastpath_serve_frames(
        cache, frame(pkt), GEN, *extra)
    assert consumed == 2 + len(pkt)
    return unframe(block), misses


def drain_one(cache, pkt):
    srv, cli, port = udp_pair()
    try:
        cli.sendto(pkt, ("127.0.0.1", port))
        for _ in range(200):
            misses, hits = fastio.fastpath_drain(cache, srv.fileno(), GEN)[:2]
            if hits or misses:
                break
        return (cli.recvfrom(65535)[0] if hits else None), misses
    finally:
        srv.close()
        cli.close()


@pytest.mark.parametrize("rd", (0, 1))
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("total", TOTALS)
def test_a_stream_frames_ceiling_is_the_streams(total, opt, rd):
    payload = OPTS[opt]
    cache = make_cache()
    body = zone_body(total, payload)
    assert put_zone(cache, [body]) is stored(total, payload)
    pkt = zone_query(payload, rd)
    served, misses = serve_frames(cache, pkt)
    if not stored(total, payload):
        # above what a message over TCP carries (or within it only
        # without the OPT echo a frame may ask for): in no table,
        # counted, and the frame is Python's
        assert served == [] and misses == [pkt]
        assert fastio.fastpath_stats(cache)["zone_hits"] == 0
        assert skips(cache) == (1, 0)
        return
    assert skips(cache) == (0, 0)
    assert misses == [] and len(served) == 1
    wire = served[0]
    assert len(wire) == total
    # the id, QR|AA with the RD echo, never TC; the question as asked;
    # the body whole; the OPT echo last
    assert wire[:2] == pkt[:2] and wire[2] == 0x84 | rd and wire[3] == 0
    assert wire[12:12 + len(QNAME) + 4] == pkt[12:12 + len(QNAME) + 4]
    at = 12 + len(QNAME) + 4
    assert wire[at:at + len(body)] == body
    assert wire[10:12] == (b"\x00\x01" if payload else b"\x00\x00")
    assert fastio.fastpath_stats(cache)["zone_hits"] == 1


@pytest.mark.parametrize("entry", ("drain", "serve_wire"))
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("total", TOTALS)
def test_a_datagram_and_serve_wire_keep_the_keys_payload(total, opt,
                                                         entry):
    """Regression: the payload in the key decides for a datagram, and
    for the entry that is not told its transport."""
    payload = OPTS[opt]
    cache = make_cache()
    assert put_zone(cache, [zone_body(total, payload)]) is \
        stored(total, payload)
    pkt = zone_query(payload, rd=1)
    if entry == "drain":
        wire, misses = drain_one(cache, pkt)
        assert [m[0] for m in misses] == ([] if wire else [pkt])
    else:
        wire = fastio.fastpath_serve_wire(cache, pkt, GEN)
    fits = total <= min(payload or 512, FP_MAX_WIRE)
    assert (wire is not None) == fits, (total, payload)
    if fits:
        assert len(wire) == total and not wire[2] & 0x02
    assert fastio.fastpath_stats(cache)["zone_hits"] == int(fits)


@pytest.mark.parametrize("entry", ("drain", "serve_wire"))
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("total", [t for t in STREAM_TOTALS
                                   if t <= FP_MAX_STREAM_WIRE - 11])
def test_a_set_only_a_stream_carries_declines_every_datagram(total, opt,
                                                             entry):
    """An entry above ``FP_MAX_WIRE`` declines a datagram (and the entry
    that is not told its transport) whatever payload its key holds,
    before rotation and log accounting; the frame after it takes the
    variant the datagram left, and has the one log line."""
    payload = OPTS[opt]
    cache = make_cache()
    fastio.fastpath_log_enable(cache, b'{"name":"t","time":"')
    bodies = [zone_body(total, payload, tag) for tag in (0x41, 0x42)]
    frag = b'"rcode":"NOERROR"'
    assert put_zone(cache, bodies, [frag, frag]) is True
    pkt = zone_query(payload, rd=1)
    for _ in range(2):
        if entry == "drain":
            wire, misses = drain_one(cache, pkt)
            assert wire is None and [m[0] for m in misses] == [pkt]
        else:
            assert fastio.fastpath_serve_wire(
                cache, pkt, GEN, SRC[0], SRC[1], "udp") is None
    stats = fastio.fastpath_stats(cache)
    assert (stats["zone_hits"], stats["log_lines"],
            stats["log_declines"]) == (0, 0, 0)
    at = 12 + len(QNAME) + 4
    served, misses = serve_frames(cache, pkt, logged=True)
    assert misses == [] and len(served[0]) == total
    assert served[0][at] == 0x41
    stats = fastio.fastpath_stats(cache)
    assert (stats["zone_hits"], stats["log_lines"]) == (1, 1)


def test_a_declined_datagram_leaves_the_rotation_to_the_stream():
    cache = make_cache()
    bodies = [zone_body(700, None, tag) for tag in (0x41, 0x42, 0x43)]
    assert put_zone(cache, bodies) is True
    pkt = zone_query(None, rd=1)
    at = 12 + len(QNAME) + 4
    seen = []
    for _ in range(4):
        wire, misses = drain_one(cache, pkt)      # over 512: Python's
        assert wire is None and len(misses) == 1
        assert fastio.fastpath_serve_wire(cache, pkt, GEN) is None
        served, _ = serve_frames(cache, pkt)
        seen.append(served[0][at])
    assert seen == [0x41, 0x42, 0x43, 0x41]


def tc_header(qid=0):
    """What the UDP lane promotes for a set that does not fit: header
    with TC=1 and the question."""
    return (qid.to_bytes(2, "big") + bytes.fromhex("8700") + b"\x00\x01"
            + b"\x00" * 6 + QNAME.lower() + b"\x00\x01\x00\x01")


def test_a_promoted_tc_header_is_the_datagrams_and_passed_over_by_a_frame():
    cache = make_cache()
    assert fastio.fastpath_put(cache, ckey(rd=1, payload=512), 1, GEN,
                               [tc_header()]) is True
    body = zone_body(900, None)
    assert put_zone(cache, [body]) is True
    pkt = zone_query(None, rd=1)
    before = fastio.fastpath_stats(cache)

    # over a stream: the whole set, from the zone table, and nothing
    # counted on the entry that was passed over
    served, misses = serve_frames(cache, pkt)
    assert misses == [] and len(served[0]) == 900
    assert not served[0][2] & 0x02
    stats = fastio.fastpath_stats(cache)
    assert stats["hits"] == before["hits"]
    assert stats["zone_hits"] == before["zone_hits"] + 1

    # the entry that is not told its transport declines it outright
    assert fastio.fastpath_serve_wire(cache, pkt, GEN) is None
    stats = fastio.fastpath_stats(cache)
    assert (stats["hits"], stats["zone_hits"]) == (
        before["hits"], before["zone_hits"] + 1)

    # over UDP: still the header, a hit
    wire, misses = drain_one(cache, pkt)
    assert misses == [] and wire == pkt[:2] + tc_header()[2:]
    stats = fastio.fastpath_stats(cache)
    assert stats["hits"] == before["hits"] + 1
    assert stats["per_qtype"][1]["truncated"] == 1


def test_a_passed_over_entry_burns_no_rotation_step():
    cache = make_cache()
    key = ckey(rd=1, payload=512)
    first = tc_header()
    # a second variant that differs in a byte a client cannot see (RA),
    # so the variant served next names the rotation's place
    second = first[:3] + b"\x80" + first[4:]
    assert fastio.fastpath_put(cache, key, 1, GEN,
                               [first, second]) is True
    assert put_zone(cache, [zone_body(900, None)]) is True
    pkt = zone_query(None, rd=1)
    for _ in range(3):
        served, misses = serve_frames(cache, pkt)
        assert misses == [] and len(served[0]) == 900
        assert fastio.fastpath_serve_wire(cache, pkt, GEN) is None
    wire, _ = drain_one(cache, pkt)
    assert wire[3] == first[3]
    wire, _ = drain_one(cache, pkt)
    assert wire[3] == second[3]


def test_a_frame_without_a_zone_entry_behind_a_tc_header_is_a_miss():
    cache = make_cache()
    assert fastio.fastpath_put(cache, ckey(rd=1, payload=512), 1, GEN,
                               [tc_header()]) is True
    pkt = zone_query(None, rd=1)
    served, misses = serve_frames(cache, pkt)
    assert served == [] and misses == [pkt]
    assert fastio.fastpath_stats(cache)["hits"] == 0


@pytest.mark.parametrize("what,body_len,frag_len,held", [
    ("both-at-the-old-limit", 4096, 4096, True),
    ("body-above-the-old-limit", 4097, 100, True),
    ("fragment-above-the-old-limit", 600, 4097, True),
    ("both-at-the-limit", MAX_BODY, FP_MAX_STREAM_WIRE, True),
    ("body-above", MAX_BODY + 1, 100, False),
    ("body-above-a-uint16", FP_MAX_STREAM_WIRE + 1, 100, False),
    ("fragment-above", 600, FP_MAX_STREAM_WIRE + 1, False),
])
def test_an_entry_above_either_limit_is_in_no_table(what, body_len,
                                                    frag_len, held):
    cache = make_cache()
    fastio.fastpath_log_enable(cache, b'{"name":"t","time":"')
    frag = b'"rcode":"NOERROR","pad":"' + b"x" * (frag_len - 26) + b'"'
    assert len(frag) == frag_len
    assert put_zone(cache, [b"\x41" * body_len], [frag]) is held
    assert skips(cache) == (int(not held), 0)
    assert fastio.fastpath_stats(cache)["zone_entries"] == int(held)
    pkt = zone_query(None, rd=1)
    # what the table holds a frame is served whole and logged, with
    # the fragment whole in its line; what it lacks is Python's
    served, misses = serve_frames(cache, pkt, logged=True)
    if not held:
        assert served == [] and misses == [pkt]
        return
    assert misses == [] and len(served[0]) == 12 + len(QNAME) + 4 + body_len
    line = fastio.fastpath_log_drain(cache)
    assert line.count(b"\n") == 1 and frag in line
    # and every datagram above its payload declines
    assert drain_one(cache, pkt)[0] is None


def test_the_answer_cache_and_the_type_row_keep_their_4096():
    """``FP_MAX_WIRE`` and ``FP_MAX_FRAG`` are still what the native
    answer cache and the type row take."""
    cache = make_cache()
    fastio.fastpath_log_enable(cache, b'{"name":"t","time":"')
    key = ckey(rd=1, payload=512)
    wire = tc_header() + b"\x00" * (4096 - len(tc_header()))
    frag = b'"rcode":"NOERROR","pad":"' + b"x" * (4096 - 26) + b'"'
    assert fastio.fastpath_put(cache, key, 1, GEN, [wire + b"\x00"], -1,
                               QNAME.lower(), [frag]) is False
    assert fastio.fastpath_put(cache, key, 1, GEN, [wire], -1,
                               QNAME.lower(), [frag + b"x"]) is False
    assert fastio.fastpath_put(cache, key, 1, GEN, [wire], -1,
                               QNAME.lower(), [frag]) is True
    assert fastio.fastpath_type_row(cache, [1, 12, 33], 4,
                                    frag + b"x") is False
    assert fastio.fastpath_type_row(cache, [1, 12, 33], 4, frag) is True
    # neither is the zone table's refusal
    assert skips(cache) == (0, 0)


def test_a_put_the_byte_cap_refuses_is_counted_and_changes_nothing():
    cache = make_cache()
    body = b"\x41" * 65000
    variants = [body] * 8
    fit = FP_ZONE_MAX_BYTES // (8 * len(body))

    def qname(i):
        return b"\x05n%04d" % i + QNAME[4:]

    size = 8 * len(body)
    n = 0
    while put_zone(cache, variants, qname=qname(n)):
        before = fastio.fastpath_stats(cache)
        assert skips(cache) == (0, 0)
        n += 1
        # (a put whose probe window is full takes a resident's place,
        # so the puts may be a few more than the entries)
        assert n <= fit + 64
    assert before["zone_bytes"] == before["zone_entries"] * size
    assert before["zone_bytes"] <= FP_ZONE_MAX_BYTES < \
        before["zone_bytes"] + size
    after = fastio.fastpath_stats(cache)
    assert skips(cache) == (0, 1)
    assert (after["zone_entries"], after["zone_bytes"]) == (
        before["zone_entries"], before["zone_bytes"])
    # a smaller entry still fits, and the refused name is a miss
    assert put_zone(cache, [b"\x42" * 16], qname=qname(n + 1)) is True
    pkt = query_pkt(qname=qname(n), rd=1, qtype=1)
    assert serve_frames(cache, pkt) == ([], [pkt])
    fastio.fastpath_clear(cache)


def test_a_frame_declined_for_want_of_log_room_is_pythons():
    cache = make_cache()
    frag = b'"rcode":"NOERROR","pad":"' + b"x" * 3000 + b'"'
    # a ring of 4 KiB: room for one such line, not for two
    fastio.fastpath_log_enable(cache, b'{"name":"t","time":"', 4096)
    bodies = [zone_body(900, None, tag) for tag in (0x41, 0x42)]
    assert put_zone(cache, bodies, [frag, frag]) is True
    pkt = zone_query(None, rd=1)
    at = 12 + len(QNAME) + 4
    served, misses = serve_frames(cache, pkt, logged=True)
    assert misses == [] and served[0][at] == 0x41
    served, misses = serve_frames(cache, pkt, logged=True)
    assert served == [] and misses == [pkt]
    stats = fastio.fastpath_stats(cache)
    assert (stats["zone_hits"], stats["log_declines"]) == (1, 1)
    line = fastio.fastpath_log_drain(cache)
    assert line.count(b"\n") == 1 and b'"port":"4242/tcp"' in line
    # the declined serve took no rotation step
    served, misses = serve_frames(cache, pkt, logged=True)
    assert misses == [] and served[0][at] == 0x42
    # without the client's address no line can be made: declined too
    fastio.fastpath_log_drain(cache)
    served, misses = serve_frames(cache, pkt)
    assert served == [] and misses == [pkt]


def test_the_ring_has_room_for_the_line_of_the_largest_set_or_declines():
    """A 250-member set's fragment is about 22 KB: the server's ring of
    1 MiB takes 40 such lines between two drains; a ring that lacks the
    room declines the leg to Python, counted, and serves it again once
    drained."""
    frag = b'"rcode":"NOERROR","pad":"' + b"x" * 22000 + b'"'
    bodies = [zone_body(34300, None, tag) for tag in (0x41, 0x42)]
    pkt = zone_query(None, rd=1)
    at = 12 + len(QNAME) + 4

    cache = make_cache()
    fastio.fastpath_log_enable(cache, b'{"name":"t","time":"', 1 << 20)
    assert put_zone(cache, bodies, [frag, frag]) is True
    for _ in range(40):
        served, misses = serve_frames(cache, pkt, logged=True)
        assert misses == [] and len(served[0]) == 34300
    stats = fastio.fastpath_stats(cache)
    assert (stats["log_lines"], stats["log_declines"]) == (40, 0)
    lines = fastio.fastpath_log_drain(cache).splitlines()
    assert len(lines) == 40 and all(frag in ln for ln in lines)

    small = make_cache()
    fastio.fastpath_log_enable(small, b'{"name":"t","time":"', 32 << 10)
    assert put_zone(small, bodies, [frag, frag]) is True
    served, misses = serve_frames(small, pkt, logged=True)
    assert misses == [] and served[0][at] == 0x41
    served, misses = serve_frames(small, pkt, logged=True)
    assert served == [] and misses == [pkt]
    stats = fastio.fastpath_stats(small)
    assert (stats["zone_hits"], stats["log_declines"]) == (1, 1)
    assert fastio.fastpath_log_drain(small).count(b"\n") == 1
    served, misses = serve_frames(small, pkt, logged=True)
    assert misses == [] and served[0][at] == 0x42


def test_a_set_that_passes_what_is_left_of_the_arena_is_pythons():
    """The bulk serve's arena is 256 KiB.  A set longer than what is
    left of it behind the answers before it surfaces as a miss, with
    the rotation where it was."""
    cache = make_cache()
    total = 60000
    bodies = [zone_body(total, None, tag)
              for tag in (0x41, 0x42, 0x43, 0x44, 0x45)]
    assert put_zone(cache, bodies) is True
    pkt = zone_query(None, rd=1)
    at = 12 + len(QNAME) + 4
    block, consumed, misses = fastio.fastpath_serve_frames(
        cache, frame(pkt) * 5, GEN)
    served = unframe(block)
    # four answers leave 22 KB of the arena: room for a frame's answer
    # from the answer cache, not for a fifth of these
    assert [(len(w), w[at]) for w in served] == [
        (total, tag) for tag in (0x41, 0x42, 0x43, 0x44)]
    assert misses == [pkt] and consumed == 5 * (2 + len(pkt))
    assert fastio.fastpath_stats(cache)["zone_hits"] == 4
    # the next frame takes the variant the miss left
    served, misses = serve_frames(cache, pkt)
    assert misses == [] and served[0][at] == 0x45


def test_the_bulk_frame_serve_is_timed_as_native_serve():
    cache = make_cache()
    assert put_zone(cache, [zone_body(900, None)]) is True
    pkt = zone_query(None, rd=1)
    before = fastio.io_stats()["spans"]["native-serve"]
    serve_frames(cache, pkt)
    # a miss is part of a call's work too, as it is of a drained batch
    serve_frames(cache, query_pkt(qname=b"\x04none\x00", rd=1))
    after = fastio.io_stats()["spans"]["native-serve"]
    assert after["count"] == before["count"] + 2
    assert after["sum"] > before["sum"]
    # a call that consumed no frame (a partial one) is no observation
    block, consumed, misses = fastio.fastpath_serve_frames(
        cache, frame(pkt)[:-1], GEN)
    assert (block, consumed, misses) == (b"", 0, [])
    assert fastio.io_stats()["spans"]["native-serve"]["count"] == \
        after["count"]


# -- served: SRV sets with glue, frame for frame --

#: 6/7 is where 512 bytes run out, 8/9 the cell's small and medium
#: classes, 16/17 where 1232 bytes run out, 32/33 the 64 records from
#: which the Python lanes render a set lazily
NATIVE_SIZES = (2, 6, 7, 8, 17, 32, 33)
#: the sizes among which a datagram's 4096 bytes run out (and, until
#: ISSUE 39, both of the zone table's 4096-byte limits)
EDGE_SIZES = tuple(range(40, 58))
#: what only a stream carries: 45 and 53 are where the 4096-byte wire
#: and fragment limits bound in the services zone, 64/65 the table's old
#: member rule, 250 the zone's largest set
STREAM_SIZES = (45, 52, 53, 64, 65, 128, 250)
LARGEST = STREAM_SIZES[-1]
#: a set whose zone-table wire (its glue's owner names spelled out)
#: passes 65,535 bytes while the generic encoder's does not: the first
#: the table cannot hold
ABOVE_STREAM = 800
ALL_SIZES = tuple(sorted(set(NATIVE_SIZES + EDGE_SIZES + STREAM_SIZES))
                  ) + (ABOVE_STREAM,)
VARIANTS = 8
#: the fields of a query-log line that name the lane or the moment
LANE_FIELDS = ("time", "latency", "timers", "trace", "cached")


class Rotation:
    """The generic path's shuffle, held to the rotation the zone table
    is at: the variant the Python lane gives under that draw."""
    k = 0

    def shuffle(self, groups):
        k = self.k % len(groups)
        groups[:] = groups[k:] + groups[:k]


class Pair:
    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.rotation = Rotation()
        self.call(self._start())

    def call(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(60)

    def on_loop(self, fn, *args):
        async def run():
            return fn(*args)
        return self.call(run())

    async def _start(self):
        def zone():
            store = FakeStore()
            self.stores.append(store)
            cache = MirrorCache(store, DOMAIN)
            for n in ALL_SIZES:
                put_service(store, f"s{n}", n)
            store.start_session()
            return cache

        def server(raw_of, **kw):
            stream, raw = byte_stream()
            raw_of.append(raw)
            return BinderServer(
                zk_cache=zone(), dns_domain=DOMAIN, datacenter_name="coal",
                host="127.0.0.1", port=0, collector=MetricsCollector(),
                log=make_logger("binder-stream-test", stream=stream),
                query_log=True, **kw)

        raws = []
        self.stores = []
        self.served = server(raws, zone_precompile=True)
        self.generic = server(raws, zone_precompile=False, cache_size=0)
        self.served_raw, self.generic_raw = raws
        self.generic.resolver.rng = self.rotation
        await self.served.start()
        await self.generic.start()

    def stop(self):
        self.call(self.served.stop())
        self.call(self.generic.stop())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)

    @staticmethod
    def _lines(server, raw):
        server._write_log()
        lines = query_lines(raw)
        raw.seek(0)
        raw.truncate()
        return lines

    def native(self, wire):
        """One frame through the bulk frame serve: the answer (None for
        a miss) and the query-log line C wrote for it."""
        def serve():
            self._lines(self.served, self.served_raw)   # others' lines
            block, consumed, misses = \
                self.served.engine._serve_frames_bulk(frame(wire), SRC)
            assert consumed == 2 + len(wire)
            lines = self._lines(self.served, self.served_raw)
            if misses:
                assert misses == [wire] and not block and not lines
                return None, None
            (line,) = lines
            return unframe(block)[0], line
        return self.on_loop(serve)

    def python(self, server, raw, wire, k=0, proto="tcp"):
        def serve():
            self.rotation.k = k
            self._lines(server, raw)
            out = []
            server.engine._handle_raw(wire, SRC, proto, out.append,
                                      fastpath_checked=True)
            (line,) = self._lines(server, raw)
            return out[0], line
        return self.on_loop(serve)

    def python_generic(self, wire, k, proto="tcp"):
        return self.python(self.generic, self.generic_raw, wire, k, proto)

    def python_served(self, wire):
        return self.python(self.served, self.served_raw, wire)

    def zone_hits(self):
        return fastio.fastpath_stats(self.served._fastpath)["zone_hits"]


@pytest.fixture(scope="module")
def pair():
    p = Pair()
    yield p
    p.stop()


def srv_query(size, payload=None, rd=1, qid=7):
    return make_query(f"_http._tcp.s{size}.{DOMAIN}", Type.SRV, qid=qid,
                      rd=bool(rd), edns_payload=payload).encode()


def fields(wire):
    """A response, field for field and record for record in the order
    sent: what a client can tell two encodings of one answer by.  The
    OPT echo is told apart from the glue: the zone table appends it to
    the additional section, the generic path leads the section with it
    (so over UDP too, since the zone table holds sets with glue)."""
    m = Message.decode(wire)
    qend = 12
    while wire[qend]:
        qend += 1 + wire[qend]
    glue = [r for r in m.additionals if isinstance(r, ARecord)]
    opts = [r for r in m.additionals if isinstance(r, OPTRecord)]
    assert len(glue) + len(opts) == len(m.additionals)
    return (wire[:12], wire[12:qend + 5],
            [(r.name, r.ttl, r.priority, r.weight, r.port, r.target)
             for r in m.answers],
            len(m.authorities),
            [(r.name, r.ttl, r.address) for r in glue],
            [(r.udp_payload_size, r.has_options) for r in opts])


def line_differences(native, python):
    """The fields two lines differ in, those that name the lane or the
    moment aside."""
    return {k: (native.get(k), python.get(k))
            for k in set(native) | set(python)
            if k not in LANE_FIELDS and native.get(k) != python.get(k)}


def fragment_len(line):
    """The answer-dependent middle of a line, as the zone fill renders
    it for the native ring (``BinderServer._log_frag``)."""
    return len(json.dumps({k: line[k] for k in
                           ("query", "rcode", "answers", "additional")})
               ) - 2


@pytest.fixture(scope="module")
def largest(pair):
    """The largest set a datagram carries from the zone table: the last
    size whose zone wire, with the OPT echo, is within ``FP_MAX_WIRE``
    (a frame is served every size)."""
    sizes = [n for n in EDGE_SIZES
             if len(pair.native(srv_query(n, 4096))[0]) <= FP_MAX_WIRE]
    assert sizes and sizes == list(EDGE_SIZES[:len(sizes)])
    assert sizes[-1] < EDGE_SIZES[-1]
    return sizes[-1]


@pytest.mark.parametrize("rd", (0, 1))
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("size", NATIVE_SIZES + ("largest",)
                         + STREAM_SIZES)
def test_a_frame_is_served_whole_and_logged_as_python_would(
        pair, largest, size, opt, rd):
    n = largest if size == "largest" else size
    query = srv_query(n, OPTS[opt], rd)
    rotations = min(n, VARIANTS)
    python = [pair.python_generic(query, k) for k in range(rotations)]
    assert len({w for w, _ in python}) == rotations
    order = []
    # once round the rotation and one step more
    for _ in range(rotations + 1):
        wire, line = pair.native(query)
        assert wire is not None, "a miss"
        assert not wire[2] & 0x02 and len(wire) <= FP_MAX_STREAM_WIRE
        assert len(Message.decode(wire).answers) == n
        # one of the variants the Python lane gives for this frame
        ks = [k for k, (w, _) in enumerate(python)
              if fields(w) == fields(wire)]
        assert len(ks) == 1, ks
        want_wire, want_line = python[ks[0]]
        assert len(wire) >= len(want_wire)
        if len(wire) == len(want_wire):
            assert wire == want_wire
        assert line_differences(line, want_line) == {}
        assert line["port"] == f"{SRC[1]}/tcp" and line["timers"] == {}
        order.append(ks[0])
    # every rotation once, each a step on from the one before, and then
    # the first again
    assert sorted(order[:rotations]) == list(range(rotations))
    assert all((b - a) % rotations == 1 for a, b in zip(order, order[1:]))
    assert order[rotations] == order[0]


@pytest.mark.parametrize("size", (7, 8, 17, 32, 33, "largest"))
def test_a_frame_equals_the_datagram_of_the_same_zone_entry(
        pair, largest, size):
    """With a payload that admits the set, a datagram is served from
    the same zone entry, under the same key: the same bytes, a rotation
    step apart."""
    n = largest if size == "largest" else size
    query = srv_query(n, 4096)
    before = pair.zone_hits()
    by_udp = set()
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.settimeout(5.0)
        for _ in range(min(n, VARIANTS)):
            s.sendto(query, ("127.0.0.1", pair.served.udp_port))
            by_udp.add(s.recvfrom(65535)[0])
    assert len(by_udp) == min(n, VARIANTS)
    assert pair.zone_hits() == before + min(n, VARIANTS)
    wire, _ = pair.native(query)
    assert wire in by_udp


def records(wire):
    """A response's header and record sets, whatever rotation the
    sections are in."""
    head, question, answers, ns, glue, opts = fields(wire)
    return (head[2:], question, sorted(answers), ns, sorted(glue), opts)


@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("size", ("first-above",) + STREAM_SIZES)
def test_a_datagram_of_a_streams_set_is_answered_as_before(
        pair, largest, size, opt):
    """What ISSUE 39 admits to the table is a stream's alone.  The UDP
    question of the same name is the Python lanes' as it was (the zone
    entry declines it before any accounting): the same TC bit as the
    generic path, the same bytes where it is truncated (a header with no
    rotation in it), the same records where it is not, and no more than
    the payload in any case."""
    n = largest + 1 if size == "first-above" else size
    query = srv_query(n, OPTS[opt], qid=n)
    want, _ = pair.python_generic(query, 0, "udp")
    before = pair.zone_hits()
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.settimeout(5.0)
        for _ in range(3):      # a first sight, and the caches' answers
            s.sendto(query, ("127.0.0.1", pair.served.udp_port))
            wire = s.recvfrom(65535)[0]
            assert len(wire) <= (OPTS[opt] or 512)
            assert wire[2] & 0x02 == want[2] & 0x02
            if wire[2] & 0x02:
                assert wire == want
            else:
                assert records(wire) == records(want)
    # (a payload of 4096 admits the sets up to *largest* from the zone
    # entry itself, as it did)
    by_zone = 3 if OPTS[opt] == 4096 and n <= largest else 0
    assert pair.zone_hits() == before + by_zone
    # and the leg after the truncated answer is C's, whole
    wire, _ = pair.native(query)
    assert len(Message.decode(wire).answers) == n
    assert pair.zone_hits() == before + by_zone + 1


@pytest.mark.parametrize("opt", OPTS)
def test_a_set_above_the_limits_is_a_miss_python_answers_whole(pair, opt):
    """The first set above the stream's bound is in no table (counted
    under ``size``); the Python lanes, whose encoder compresses the
    glue's owner names, answer it whole."""
    query = srv_query(ABOVE_STREAM, OPTS[opt])
    assert pair.native(query) == (None, None)
    wire, line = pair.python_served(query)
    m = Message.decode(wire)
    assert not m.tc and len(m.answers) == len(m.additionals) - (
        1 if OPTS[opt] else 0) == ABOVE_STREAM
    assert line["port"] == f"{SRC[1]}/tcp"
    assert pair.on_loop(pair.served.zone_put_skips) == {
        "size": 1, "bytes": 0}


def test_the_largest_set_is_the_last_under_both_limits(pair):
    """The zone's largest set is within the stream's bound in wire and
    fragment, and what a member costs puts the first set the table
    lacks between it and ``ABOVE_STREAM``."""
    def sizes(n):
        wire, line = pair.native(srv_query(n))
        return len(wire), fragment_len(line)

    wire_len, frag_len = sizes(LARGEST)
    assert FP_MAX_WIRE < frag_len < wire_len <= FP_MAX_STREAM_WIRE
    # a member more: 86 bytes of wire here (SRV and glue, their owner
    # names spelled out), about 69 of fragment
    per_member = ((wire_len - sizes(128)[0]) / (LARGEST - 128),
                  (frag_len - sizes(128)[1]) / (LARGEST - 128))
    assert 80 < per_member[0] < 95 and 60 < per_member[1] < 75
    assert wire_len + (ABOVE_STREAM - LARGEST) * per_member[0] \
        > FP_MAX_STREAM_WIRE
    assert pair.native(srv_query(ABOVE_STREAM)) == (None, None)


def test_a_members_mutation_of_the_largest_set_is_seen_by_the_next_leg(
        pair):
    query = srv_query(LARGEST)
    member = f"pod-007-aaaa.s{LARGEST}.{DOMAIN}"

    def address(wire):
        return {r.name: r.address for r in Message.decode(wire).additionals
                if isinstance(r, ARecord)}[member]

    def write(addr):
        for store in pair.stores:
            store.put_json(f"/com/foo/s{LARGEST}/pod-007-aaaa", {
                "type": "load_balancer",
                "load_balancer": {"address": addr}})

    async def settle():
        for _ in range(10):
            await asyncio.sleep(0)

    old = address(pair.native(query)[0])
    try:
        pair.on_loop(write, "10.99.99.99")
        pair.call(settle())
        wire, line = pair.native(query)
        assert wire is not None, "the set was not pushed again"
        assert address(wire) == "10.99.99.99" != old
        assert any("10.99.99.99" in a for a in line["additional"])
        # the same set as the generic path's, after the same write
        assert records(wire) == records(pair.python_generic(query, 0)[0])
    finally:
        pair.on_loop(write, old)
        pair.call(settle())
    assert address(pair.native(query)[0]) == old


@pytest.mark.parametrize("size", (8, LARGEST))
def test_the_fills_fragment_is_the_log_fragments_bytes(pair, size):
    """``_zone_push_service_srv`` joins each member's summary, rendered
    once, into its variants' fragments: byte for byte what ``_log_frag``
    renders for the whole variant (were it not for that one's bound)."""
    def serve():
        pair._lines(pair.served, pair.served_raw)
        block, _, misses = pair.served.engine._serve_frames_bulk(
            frame(srv_query(size)), SRC)
        assert block and not misses
        pair.served._write_log()
        (raw,) = [ln for ln in pair.served_raw.getvalue().splitlines()
                  if b'"req_id"' in ln]
        pair._lines(pair.served, pair.served_raw)
        return raw

    raw = pair.on_loop(serve)
    line = json.loads(raw)
    want = json.dumps({k: line[k] for k in
                       ("query", "rcode", "answers", "additional")}
                      )[1:-1].encode()
    assert want in raw
    if len(want) <= 4096:
        assert want == BinderServer._log_frag(
            {"query": line["query"]}, 0, line["answers"],
            line["additional"])


def test_a_key_with_a_promoted_tc_header_serves_both_transports(pair):
    """Seven members, no OPT record: over UDP the header with TC=1, from
    the third sight on C's answer-cache entry; over TCP the whole set
    from the zone table, with that entry in the way under the same
    key."""
    query = srv_query(7, None, qid=99)

    def stats():
        return fastio.fastpath_stats(pair.served._fastpath)

    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.settimeout(5.0)

        def ask():
            s.sendto(query, ("127.0.0.1", pair.served.udp_port))
            return s.recvfrom(65535)[0]

        for _ in range(3):
            assert ask()[2] & 0x02
        before = stats()
        header = ask()
        assert header[2] & 0x02 and header[6:8] == b"\x00\x00"
        assert stats()["hits"] == before["hits"] + 1    # C replays it
        before = stats()
        wire, line = pair.native(query)
        assert not wire[2] & 0x02 and wire[6:8] == b"\x00\x07"
        assert len(line["answers"]) == 7
        after = stats()
        assert after["hits"] == before["hits"]
        assert after["zone_hits"] == before["zone_hits"] + 1
        assert ask() == header
        assert stats()["hits"] == after["hits"] + 1


def test_the_counter_says_how_many_frames_c_answered(pair):
    def counts():
        def read():
            pair.served.collector.fold()
            get = pair.served.collector.get
            return (pair.served.engine.tcp_stats.snapshot(),
                    get("binder_tcp_fast_serves").value(),
                    get("binder_tcp_native_serves").value())
        return pair.on_loop(read)

    before, fast0, native0 = counts()
    whole = tcp_oneshot(pair.served.tcp_port, srv_query(8, qid=11))
    large = tcp_oneshot(pair.served.tcp_port,
                        srv_query(LARGEST, qid=13))
    lazy = tcp_oneshot(pair.served.tcp_port,
                       srv_query(ABOVE_STREAM, qid=12))
    assert len(Message.decode(whole).answers) == 8
    assert len(Message.decode(large).answers) == LARGEST
    assert len(Message.decode(lazy).answers) == ABOVE_STREAM
    after, fast1, native1 = counts()
    # three legs; the bulk frame serve answered two (the zone's largest
    # set among them), Python the one no table holds
    assert after["fast_serves"] == before["fast_serves"] + 3
    assert after["native_serves"] == before["native_serves"] + 2
    assert (fast1 - fast0, native1 - native0) == (3, 2)
    status = pair.on_loop(pair.served.engine.tcp_introspect)
    assert status["native_serves"] == after["native_serves"]


def test_the_refusals_are_in_the_exposition_the_status_and_bstat(pair):
    """``binder_zone_put_skips{reason}`` is folded from C at a scrape,
    ``/status`` ``answer_cache.zone_put_skips`` and ``bstat``'s
    ``answer cache:`` line read the same counts, and the lint pins hold
    the family with both reasons."""
    def read():
        text = pair.served.collector.expose()
        snap = Introspector(server=pair.served).snapshot()
        return text, snap
    text, snap = pair.on_loop(read)
    # (``main.py`` installs the ``loop-idle`` span, not a bare server)
    assert validate_ledger_metrics(text) == [
        "binder_query_stage_seconds: missing leaf stage='loop-idle'"]
    assert 'binder_zone_put_skips{reason="size"} 1' in text
    assert 'binder_zone_put_skips{reason="bytes"} 0' in text
    assert validate_status_snapshot(snap) == []
    assert snap["answer_cache"]["zone_put_skips"] == {
        "size": 1, "bytes": 0}
    cut = dict(snap, answer_cache={
        k: v for k, v in snap["answer_cache"].items()
        if k != "zone_put_skips"})
    assert validate_status_snapshot(cut) == [
        "answer_cache: missing key 'zone_put_skips'"]
    missing = "\n".join(ln for ln in text.splitlines()
                        if 'reason="bytes"' not in ln)
    assert ("binder_zone_put_skips: missing pinned series reason='bytes'"
            in validate_ledger_metrics(missing))
    loader = importlib.machinery.SourceFileLoader(
        "bstat", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bin", "bstat"))
    bstat = importlib.util.module_from_spec(
        importlib.util.spec_from_loader("bstat", loader))
    loader.exec_module(bstat)
    line = next(ln for ln in bstat.render(snap).splitlines()
                if ln.startswith("answer cache:"))
    assert "zone puts refused: 1 size / 0 bytes" in line
    # the generic path has no zone table: nothing refused, nothing read
    assert pair.on_loop(pair.generic.zone_put_skips) == {
        "size": 0, "bytes": 0}


def test_with_the_limiter_hot_a_frame_reaches_note_tcp():
    """While RRL sheds, the fastpath gate is shut: the bulk frame serve
    stands down and the frame surfaces in ``_handle_raw``, where a TCP
    query is the limiter's evidence of a completed handshake."""
    async def run():
        store = FakeStore()
        cache = MirrorCache(store, DOMAIN)
        put_service(store, "s8", 8)
        store.start_session()
        stream, raw = byte_stream()
        server = BinderServer(
            zk_cache=cache, dns_domain=DOMAIN, datacenter_name="coal",
            host="127.0.0.1", port=0, collector=MetricsCollector(),
            log=make_logger("binder-stream-rrl", stream=stream),
            query_log=True, zone_precompile=True,
            rrl={"responsesPerSecond": 5, "burst": 5})
        await server.start()
        try:
            noted = []
            rrl = server._rrl
            note = rrl.note_tcp

            def noting(ip):
                noted.append(ip)
                note(ip)

            rrl.note_tcp = noting
            query = srv_query(8)
            loop = asyncio.get_running_loop()

            async def leg():
                return await loop.run_in_executor(
                    None, tcp_oneshot, server.tcp_port, query)

            cold = await leg()
            stats = server.engine.tcp_stats
            assert (stats.native_serves, noted) == (1, [])
            rrl._hot_until = float("inf")
            hot = await leg()
            assert (stats.native_serves, noted) == (1, ["127.0.0.1"])
            assert stats.fast_serves == 2
            for wire in (cold, hot):
                m = Message.decode(wire)
                assert not m.tc and len(m.answers) == 8
        finally:
            await server.stop()

    asyncio.run(run())
