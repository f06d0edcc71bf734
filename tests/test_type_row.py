"""A declined type is its header (ISSUE 32).

binder answers every type but A, SRV and PTR with NOTIMP by the type
alone, before any lookup (``Resolver.handle``, upstream
``lib/server.js:491-506``): 12 header bytes and the question echoed.
The zone table's *type row* gives that answer from C with no name and no
generation in its key, installed from the engine's own statement of the
rule (``resolver.engine.TYPE_RULE``) where ``BinderServer`` arms the
zone table.  All four native entries go through ``fp_serve_one_lx``, so
one branch ahead of both probes serves every lane.

Two layers, as ``test_fastpath.py`` has them:

- C-unit: ``fastpath_type_row`` on a bare cache and the serving entries
  over the same bytes;
- served: a ``BinderServer`` in the production posture's serving shape
  (zone table, query log through the native ring) beside a reference
  server that resolves everything (no zone table, no cache), byte for
  byte and log line for log line.
"""
import asyncio
import socket
import threading
import time

import pytest

from binder_tpu.dns import Message, Rcode, Type
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.resolver.engine import TYPE_RULE
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger
from tests.test_fastpath import edns_tail, make_cache, udp_pair
from tests.test_log_ring import byte_stream, query_lines
from tests.test_stream_ceiling import GEN, SRC, frame, unframe
from tests.test_truncated_header import put_service

fastio = pytest.importorskip(
    "binder_tpu._binderfastio",
    reason="fastio extension not built (make -C native)")

DOMAIN = "foo.com"
SERVED, DECLINED = TYPE_RULE
#: what a stub, a mail agent, a scanner and a browser ask beside A
DECLINED_TYPES = {"AAAA": 28, "MX": 15, "TXT": 16, "ANY": 255, "HTTPS": 65}
#: (qtype, qclass): the row looks at the type alone, as the engine does
QUESTIONS = {name: (qtype, 1) for name, qtype in DECLINED_TYPES.items()}
QUESTIONS["AAAA-CH"] = (28, 3)
#: a DNS cookie: option bytes are in no key and in no answer
OPTS = {"no-opt": None, "opt1232": edns_tail(1232),
        "opt4096-options": edns_tail(
            4096, b"\x00\x0a\x00\x08" + b"\x5a" * 8)}
#: an existing host in a client's own case, a service name in the form
#: an SRV question has, an absent name, a name outside the zone
NAMES = ("WeB.fOo.CoM", "_http._tcp.svc.foo.com", "absent.foo.com",
         "example.org")
LANES = ("drain", "frames", "wire")
#: the fields of a query-log line that name the moment or the lane's
#: own bookkeeping
VOLATILE = ("time", "latency", "timers", "trace")
FRAG = b'"rcode": "NOTIMP", "answers": [], "additional": []'
PREFIX = b'{"name":"t","time":"'


def qname_wire(name: str) -> bytes:
    return b"".join(bytes([len(p)]) + p.encode()
                    for p in name.split(".")) + b"\x00"


def question(name, qtype, qclass=1, rd=1, tail=None, qid=0x3232):
    return (qid.to_bytes(2, "big") + (b"\x01\x00" if rd else b"\x00\x00")
            + b"\x00\x01\x00\x00\x00\x00"
            + (b"\x00\x01" if tail else b"\x00\x00") + qname_wire(name)
            + qtype.to_bytes(2, "big") + qclass.to_bytes(2, "big")
            + (tail or b""))


def declined_answer(pkt: bytes, rcode=DECLINED) -> bytes:
    """What ``QueryCtx.respond`` sends for the engine's decision: the
    id, QR|AA with the RD echo, the rcode, the question as asked, and
    the OPT echo (1232, no options) of a question that had one."""
    qend = 12
    while pkt[qend]:
        qend += 1 + pkt[qend]
    edns = pkt[11] == 1
    return (pkt[:2] + bytes([0x84 | (pkt[2] & 1), rcode])
            + b"\x00\x01\x00\x00\x00\x00" + (b"\x00\x01" if edns
                                             else b"\x00\x00")
            + pkt[12:qend + 5]
            + (bytes.fromhex("00002904d0000000000000") if edns else b""))


# -- C-unit: the row on a bare cache --

def row_cache(frag=None, ring=None):
    cache = make_cache()
    if ring is not None:
        fastio.fastpath_log_enable(cache, PREFIX, ring)
    assert fastio.fastpath_type_row(cache, sorted(SERVED), DECLINED,
                                    frag) is True
    return cache


def serve(cache, lane, pkt, logged=False):
    """One packet through one native entry: the answer or None."""
    src = (SRC[0], SRC[1], "tcp") if logged else ()
    if lane == "wire":
        return fastio.fastpath_serve_wire(cache, pkt, GEN, *src)
    if lane == "frames":
        block, consumed, misses = fastio.fastpath_serve_frames(
            cache, frame(pkt), GEN, *src)
        assert consumed == 2 + len(pkt)
        assert misses == ([] if block else [pkt])
        return unframe(block)[0] if block else None
    srv, cli, port = udp_pair()
    try:
        cli.sendto(pkt, ("127.0.0.1", port))
        for _ in range(200):
            misses, hits = fastio.fastpath_drain(cache, srv.fileno(), GEN)[:2]
            if hits or misses:
                break
        assert [m[0] for m in misses] == ([] if hits else [pkt])
        return cli.recvfrom(65535)[0] if hits else None
    finally:
        srv.close()
        cli.close()


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("what", QUESTIONS)
def test_the_row_answers_with_the_header_and_the_question(what, opt, lane):
    qtype, qclass = QUESTIONS[what]
    cache = row_cache()
    for rd in (0, 1):
        for name in NAMES:
            pkt = question(name, qtype, qclass, rd, OPTS[opt])
            assert serve(cache, lane, pkt) == declined_answer(pkt)
    stats = fastio.fastpath_stats(cache)
    n = 2 * len(NAMES)
    assert (stats["zone_type_hits"], stats["zone_hits"], stats["hits"]) \
        == (n, n, 0)
    # no name, so nothing resident: the row is no entry of any table
    assert (stats["zone_entries"], stats["zone_bytes"],
            stats["entries"]) == (0, 0, 0)
    assert stats["per_qtype"][qtype]["count"] == n


@pytest.mark.parametrize("opt", OPTS)
def test_the_balancer_lane_takes_the_same_branch(opt):
    """The fourth caller of ``fp_serve_one_lx``: a UDP-transport frame
    of the balancer socket is answered straight onto the balancer's own
    fd, to the client the frame names (docs/balancer-protocol.md)."""
    cache = row_cache()
    out, cli, _ = udp_pair()
    try:
        host, port = cli.getsockname()
        pkt = question("web.foo.com", 28, tail=OPTS[opt])
        a = question("web.foo.com", 1, tail=OPTS[opt])

        def bal_frame(payload):
            body = (bytes([1, 4, 0]) + socket.inet_aton(host) + b"\x00" * 12
                    + port.to_bytes(2, "big") + payload)
            return len(body).to_bytes(4, "big") + body

        chunk = bal_frame(pkt) + bal_frame(a)
        consumed, served, misses = fastio.fastpath_serve_balancer(
            cache, chunk, GEN, out.fileno())
        assert (consumed, served) == (len(chunk), 1)
        assert misses == [bal_frame(a)[4:]]     # a resolved type: Python's
        assert cli.recvfrom(65535)[0] == declined_answer(pkt)
        assert fastio.fastpath_stats(cache)["zone_type_hits"] == 1
    finally:
        out.close()
        cli.close()


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("qtype", sorted(SERVED))
def test_a_resolved_type_never_takes_the_row(qtype, lane):
    cache = row_cache()
    assert serve(cache, lane, question("web.foo.com", qtype)) is None
    assert fastio.fastpath_stats(cache)["zone_type_hits"] == 0


@pytest.mark.parametrize("lane", LANES)
def test_without_a_row_a_declined_type_is_a_miss(lane):
    cache = make_cache()
    assert serve(cache, lane, question("web.foo.com", 28)) is None
    stats = fastio.fastpath_stats(cache)
    assert (stats["zone_type_hits"], stats["zone_hits"]) == (0, 0)


def test_the_row_has_the_rule_it_is_given_and_none_of_its_own():
    """C carries no list: another rule, another row."""
    cache = make_cache()
    assert fastio.fastpath_type_row(cache, [28], Rcode.REFUSED) is True
    pkt = question("web.foo.com", 1)
    assert serve(cache, "wire", pkt) == declined_answer(pkt, Rcode.REFUSED)
    assert serve(cache, "wire", question("web.foo.com", 28)) is None
    # a second put replaces the first
    assert fastio.fastpath_type_row(cache, sorted(SERVED), DECLINED) is True
    assert serve(cache, "wire", pkt) is None


@pytest.mark.parametrize("served,rcode,frag", [
    ([], DECLINED, None),
    (list(range(1, 18)), DECLINED, None),
    ([1, 0x10000], DECLINED, None),
    ([1, -1], DECLINED, None),
    ([1], 16, None),
    ([1], -1, None),
    ([1], DECLINED, b""),
    ([1], DECLINED, b"x" * 4097),
], ids=["no-type", "too-many-types", "type-above-16-bits", "negative-type",
        "rcode-above-4-bits", "negative-rcode", "empty-fragment",
        "fragment-above-the-limit"])
def test_a_rule_out_of_bounds_installs_no_row(served, rcode, frag):
    cache = make_cache()
    assert fastio.fastpath_type_row(cache, served, rcode, frag) is False
    assert serve(cache, "wire", question("web.foo.com", 28)) is None


def test_the_row_outlives_a_clear_and_every_generation():
    """It holds nothing of the store: no epoch and no mutation drops
    it."""
    cache = row_cache()
    pkt = question("web.foo.com", 28)
    fastio.fastpath_clear(cache)
    fastio.fastpath_invalidate(cache, qname_wire("web.foo.com"))
    for gen in (1, 2, 99):
        assert fastio.fastpath_serve_wire(cache, pkt, gen) \
            == declined_answer(pkt)


def test_a_client_that_cycles_types_fills_the_catch_all_slot_only():
    cache = row_cache()
    for qtype in range(100, 140):
        serve(cache, "wire", question("web.foo.com", qtype))
    per = fastio.fastpath_stats(cache)["per_qtype"]
    assert len(per) == 16 and per[0xFFFF]["count"] == 40 - 15
    assert sum(s["count"] for s in per.values()) == 40


@pytest.mark.parametrize("lane", LANES)
def test_a_logged_serve_appends_the_rows_one_fragment(lane):
    cache = row_cache(FRAG, ring=1 << 16)
    pkt = question("web.foo.com", 28, tail=OPTS["opt1232"], qid=0x0102)
    if lane == "drain":
        assert serve(cache, lane, pkt) == declined_answer(pkt)
    else:
        assert serve(cache, lane, pkt, logged=True) == declined_answer(pkt)
    line = fastio.fastpath_log_drain(cache)
    assert line.count(b"\n") == 1 and line.startswith(PREFIX)
    assert b'"req_id":258,' in line and b'"edns":true,' + FRAG in line
    assert (b'/udp",' if lane == "drain" else b'"port":"4242/tcp",') in line


@pytest.mark.parametrize("why", ("no-room", "no-source", "no-fragment"))
def test_a_serve_that_cannot_log_is_pythons(why):
    """The log-ring parity rule of every native serve: declined before
    any accounting, never served and dropped."""
    pad = b'"rcode": "NOTIMP", "pad": "' + b"x" * 3000 + b'"'
    # a ring of 4 KiB: room for one such line, not for two
    cache = row_cache(None if why == "no-fragment" else pad, ring=4096)
    pkt = question("web.foo.com", 28)
    if why == "no-room":
        assert serve(cache, "frames", pkt, logged=True) \
            == declined_answer(pkt)
    assert serve(cache, "frames", pkt, logged=why != "no-source") is None
    stats = fastio.fastpath_stats(cache)
    served = int(why == "no-room")
    assert (stats["zone_type_hits"], stats["zone_hits"],
            stats["log_declines"], stats["log_lines"]) \
        == (served, served, 1, served)
    assert sum(s["count"] for s in stats["per_qtype"].values()) == served


# -- served: beside a server that resolves everything --

class Pair:
    """``served`` in the production posture's serving shape and
    ``reference`` with no zone table (so no row) and no cache, over one
    zone, on one loop."""

    def __init__(self, query_log=True):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.query_log = query_log
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
            cache = MirrorCache(store, DOMAIN)
            store.put_json("/com/foo/web", {
                "type": "host", "host": {"address": "192.168.0.1"}})
            put_service(store, "svc", 3)
            store.start_session()
            return cache

        def server(raw_of, **kw):
            stream, raw = byte_stream()
            raw_of.append(raw)
            self.streams.append(stream)     # or its end closes `raw`
            return BinderServer(
                zk_cache=zone(), dns_domain=DOMAIN, datacenter_name="coal",
                host="127.0.0.1", port=0, collector=MetricsCollector(),
                log=make_logger(f"binder-type-row-test-{self.query_log}",
                                stream=stream),
                query_log=self.query_log, **kw)

        raws, self.streams = [], []
        self.served = server(raws)
        self.reference = server(raws, zone_precompile=False,
                                cache_size=0)
        self.served_raw, self.reference_raw = raws
        await self.served.start()
        await self.reference.start()

    def stop(self):
        self.call(self.served.stop())
        self.call(self.reference.stop())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)

    def lines(self, server, raw):
        """The query-log lines written since the last call (on the
        loop, so after the readiness callback that owes them)."""
        def take():
            server._write_log()
            got = query_lines(raw)
            raw.seek(0)
            raw.truncate()
            return got
        return self.on_loop(take)

    def stats(self):
        return fastio.fastpath_stats(self.served._fastpath)

    def ask(self, server, raw, lane, pkt):
        """One question through one lane of one server: the answer and
        the lines it left."""
        self.lines(server, raw)
        if lane == "drain":
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.bind(("127.0.0.1", 0))
                s.settimeout(5.0)
                s.sendto(pkt, ("127.0.0.1", server.udp_port))
                wire = s.recvfrom(65535)[0]
            return wire, self.lines(server, raw)

        def through():
            out = []
            bulk = (server.engine._serve_frames_bulk(frame(pkt), SRC)
                    if lane == "frames" and server is self.served else None)
            if bulk is None:    # the gate is shut, or the lane is "wire"
                server.engine._handle_raw(pkt, SRC, "tcp", out.append)
                return out[0]
            block, consumed, misses = bulk
            assert consumed == 2 + len(pkt)
            out += unframe(block)
            for miss in misses:         # as the stream lane does
                server.engine._handle_raw(miss, SRC, "tcp", out.append,
                                          fastpath_checked=True)
            return out[0]
        return self.on_loop(through), self.lines(server, raw)

    def native(self, lane, pkt):
        return self.ask(self.served, self.served_raw, lane, pkt)

    def python(self, lane, pkt):
        return self.ask(self.reference, self.reference_raw, lane, pkt)


@pytest.fixture(scope="module")
def pair():
    p = Pair()
    yield p
    p.stop()


def same_but_for(native, python, drop=VOLATILE):
    return {k: (native.get(k), python.get(k))
            for k in set(native) | set(python)
            if k not in drop and native.get(k) != python.get(k)}


def test_the_server_installs_the_engines_rule(pair):
    assert pair.served._type_row == SERVED
    assert pair.reference._type_row is None
    assert (Type.A in SERVED and Type.SRV in SERVED and Type.PTR in SERVED
            and len(SERVED) == 3 and DECLINED == Rcode.NOTIMP)


@pytest.mark.parametrize("rd", (0, 1))
@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("what", QUESTIONS)
def test_a_native_answer_is_the_engines_byte_for_byte_and_line_for_line(
        pair, what, opt, lane, rd):
    qtype, qclass = QUESTIONS[what]
    for i, name in enumerate(NAMES):
        pkt = question(name, qtype, qclass, rd, OPTS[opt], qid=0x4000 + i)
        before = pair.stats()
        wire, lines = pair.native(lane, pkt)
        want_wire, want_lines = pair.python(lane, pkt)
        assert wire == want_wire == declined_answer(pkt)
        after = pair.stats()
        assert after["zone_type_hits"] == before["zone_type_hits"] + 1
        assert after["zone_hits"] == before["zone_hits"] + 1
        assert (after["hits"], after["entries"]) == (
            before["hits"], before["entries"])
        # one line, the Python lane's first-sight line: no `cached`, no
        # `query` (the engine never planned)
        (line,), (want,) = lines, want_lines
        if lane == "drain":     # two sockets, two source ports
            assert line.pop("port").endswith("/udp")
            assert want.pop("port").endswith("/udp")
        assert same_but_for(line, want) == {}
        assert line["timers"] == {} and "trace" not in line
        assert (line["rcode"], line["answers"], line["additional"]) \
            == ("NOTIMP", [], [])
        assert line["edns"] is (OPTS[opt] is not None)
        assert not {"cached", "query"} & set(line)


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("what,name,qtype", [
    ("A", "web.foo.com", Type.A),
    ("A-absent", "absent.foo.com", Type.A),
    ("SRV", "_http._tcp.svc.foo.com", Type.SRV),
    ("SRV-host", "_http._tcp.web.foo.com", Type.SRV),
    ("PTR", "1.0.168.192.in-addr.arpa", Type.PTR),
    ("PTR-absent", "9.9.9.9.in-addr.arpa", Type.PTR),
])
def test_a_resolved_type_is_answered_as_before(pair, what, name, qtype,
                                               lane):
    pkt = question(name, qtype, qid=0x5151)
    before = pair.stats()["zone_type_hits"]
    wire, lines = pair.native(lane, pkt)
    want, _ = pair.python(lane, pkt)
    assert pair.stats()["zone_type_hits"] == before
    got, ref = Message.decode(wire), Message.decode(want)
    assert got.rcode == ref.rcode != Rcode.NOTIMP
    assert len(got.answers) == len(ref.answers)
    assert len(lines) == 1


def test_a_full_ring_sends_the_question_to_python_and_logs_it_once(pair):
    """More declined questions than the ring holds lines, and nobody
    drains it meanwhile: the row declines what it cannot log, the
    Python lanes answer those with the same bytes, and every question
    leaves exactly one line."""
    n = 6000
    pkts = [question("web.foo.com", 28, qid=i) for i in range(n)]
    pair.lines(pair.served, pair.served_raw)

    def flood():
        before = pair.stats()
        out = []
        for at in range(0, n, 500):
            block = b"".join(frame(p) for p in pkts[at:at + 500])
            resp, consumed, misses = \
                pair.served.engine._serve_frames_bulk(block, SRC)
            assert consumed == len(block)
            out += unframe(resp)
            for miss in misses:
                pair.served.engine._handle_raw(
                    miss, SRC, "tcp", out.append, fastpath_checked=True)
        return before, out, pair.stats()

    before, out, after = pair.on_loop(flood)
    assert sorted(out) == sorted(declined_answer(p) for p in pkts)
    declined = after["log_declines"] - before["log_declines"]
    native = after["zone_type_hits"] - before["zone_type_hits"]
    assert declined > 0 and native > 0 and native + declined == n
    lines = pair.lines(pair.served, pair.served_raw)
    assert sorted(ln["req_id"] for ln in lines) == list(range(n))
    assert sum(1 for ln in lines if "trace" in ln) == declined
    assert {ln["rcode"] for ln in lines} == {"NOTIMP"}
    # with the ring drained the row serves again
    wire, (line,) = pair.native("frames", pkts[0])
    assert wire == declined_answer(pkts[0]) and "trace" not in line


@pytest.mark.parametrize("lane", ("frames", "wire"))
def test_an_attached_probe_stands_the_row_down(pair, lane):
    pkt = question("web.foo.com", 28, qid=0x6161)
    seen = []

    def sink(name, args):
        seen.append(name)

    pair.on_loop(pair.served.probes.subscribe, sink)
    try:
        before = pair.stats()["zone_type_hits"]
        wire, (line,) = pair.native(lane, pkt)
        assert pair.stats()["zone_type_hits"] == before
        assert wire == declined_answer(pkt) and "trace" in line
        assert seen == ["op-req-start", "op-req-done"]
    finally:
        pair.on_loop(pair.served.probes.unsubscribe, sink)
    wire, (line,) = pair.native(lane, pkt)
    assert pair.stats()["zone_type_hits"] == before + 1
    assert wire == declined_answer(pkt) and "trace" not in line


def rate_limited_server():
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/web",
                   {"type": "host", "host": {"address": "192.168.0.1"}})
    store.start_session()
    return BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="coal",
        collector=MetricsCollector(), query_log=False,
        rrl={"responsesPerSecond": 0, "burst": 1, "slipRatio": 0})


@pytest.mark.parametrize("entry", ("frames", "wire"))
def test_a_hot_limiter_stands_the_row_down(entry):
    server = rate_limited_server()
    pkt = question("web.foo.com", 28, qid=0x7171)

    def ask():
        out = []
        if entry == "frames":
            bulk = server.engine._serve_frames_bulk(frame(pkt), SRC)
            if bulk is not None:
                return unframe(bulk[0])[0]
        server.engine._handle_raw(pkt, SRC, "tcp", out.append,
                                  fastpath_checked=entry == "frames")
        return out[0]

    def type_hits():
        return fastio.fastpath_stats(server._fastpath)["zone_type_hits"]

    assert server._type_row == SERVED and not server._rrl.hot()
    assert ask() == declined_answer(pkt) and type_hits() == 1
    flood = []
    for _ in range(4):      # over the burst: the limiter turns hot
        server.engine._handle_raw(pkt, ("203.0.113.5", 9000), "udp",
                                  flood.append)
    assert server._rrl.hot() and not server._fastpath_active()
    assert ask() == declined_answer(pkt) and type_hits() == 1
    server._rrl._hot_until = time.monotonic() - 1.0     # it cools
    assert ask() == declined_answer(pkt) and type_hits() == 2


def python_lane_udp(server, pkt):
    """One datagram as a sampled drain hands it over: to the Python
    lanes, whatever the native lanes hold."""
    out = []
    server.engine._handle_raw(pkt, ("127.0.0.7", 5300), "udp", out.append)
    return out[0]


@pytest.mark.parametrize("row", ("row", "no-zone-table"))
def test_a_hot_declined_name_leaves_no_native_cache_entry(row):
    """Promote-on-first-hit skips a key the row covers: such an entry
    could never be probed.  Without the zone table there is no row, and
    the answer is resolved, cached and promoted as before."""
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    server = BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="coal",
        collector=MetricsCollector(), query_log=False,
        zone_precompile=row == "row")
    assert (server._type_row == SERVED) is (row == "row")
    pkt = question("web.foo.com", 28, qid=0x0808)
    hits = server.collector.get("binder_answer_cache_hits")
    for _ in range(4):
        assert python_lane_udp(server, pkt) == declined_answer(pkt)
    # the first sight resolved, the Python answer cache gave the rest
    assert hits.value({"tier": "python"}) == 3
    stats = fastio.fastpath_stats(server._fastpath)
    assert stats["entries"] == (0 if row == "row" else 1)
    # and the native lanes answer it either way
    wire = fastio.fastpath_serve_wire(server._fastpath, pkt,
                                      server._epoch_source())
    assert wire == declined_answer(pkt)
    stats = fastio.fastpath_stats(server._fastpath)
    assert (stats["zone_type_hits"], stats["hits"]) == (
        (1, 0) if row == "row" else (0, 1))
    # a resolved type is still promoted on its first hit
    a = question("absent.foo.com", Type.A, qid=0x0909)
    for _ in range(2):
        python_lane_udp(server, a)
    assert fastio.fastpath_stats(server._fastpath)["entries"] \
        == (1 if row == "row" else 2)


def test_the_counters_add_up(pair):
    """``binder_zone_type_serves`` is the subset of ``binder_zone_serves``
    the row gave; every answer, C's or Python's, is one
    ``binder_requests_completed`` of its type; the row is no entry."""
    collector = pair.served.collector

    def read():
        collector.expose()      # folds C's counters in
        return {
            "type": collector.get("binder_zone_type_serves").value(),
            "zone": collector.get("binder_zone_serves").value(),
            "AAAA": collector.get("binder_requests_completed").value(
                {"type": "AAAA"}),
            "MX": collector.get("binder_requests_completed").value(
                {"type": "MX"}),
            "A": collector.get("binder_requests_completed").value(
                {"type": "A"}),
            "entries": collector.get("binder_zone_entries").value(),
        }

    before = pair.on_loop(read)
    for i in range(5):
        pair.native("drain", question("web.foo.com", 28, qid=i + 1))
    for i in range(3):
        pair.native("frames", question("absent.foo.com", 15, qid=i + 1))
    pair.native("wire", question("web.foo.com", Type.A, qid=9))
    # two more AAAA that the Python lanes answer (a sampled drain)
    for i in range(2):
        pair.on_loop(python_lane_udp, pair.served,
                     question("web.foo.com", 28, qid=i + 20))
    after = pair.on_loop(read)
    grew = {k: after[k] - before[k] for k in after}
    assert grew == {"type": 8, "zone": 9, "AAAA": 7, "MX": 3, "A": 1,
                    "entries": 0}


@pytest.fixture(scope="module")
def quiet_pair():
    p = Pair(query_log=False)
    yield p
    p.stop()


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("opt", OPTS)
def test_with_the_query_log_off_the_row_serves_without_a_fragment(
        quiet_pair, opt, lane):
    assert quiet_pair.served._type_row == SERVED
    assert not quiet_pair.served._log_ring
    pkt = question("web.foo.com", 28, tail=OPTS[opt], qid=0x0a0a)
    before = quiet_pair.stats()["zone_type_hits"]
    wire, lines = quiet_pair.native(lane, pkt)
    want, _ = quiet_pair.python(lane, pkt)
    assert wire == want == declined_answer(pkt) and lines == []
    assert quiet_pair.stats()["zone_type_hits"] == before + 1
