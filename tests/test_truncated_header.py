"""A truncated UDP answer is its header (ISSUE 27).

A UDP answer that leaves with TC=1 carries no record, whatever set the
resolve rendered for the encode to drop, so ``BinderServer._on_query``
stores the wire it sent as an entry of one variant: complete in the
answer cache from its first sight.  The second sight is a plain
answer-cache hit, which promotes the entry to the native answer cache,
and the third is replayed by C.  (Before, the entry waited for eight
resolves of eight byte-identical headers.)  The first sight is a
resolve.

Held here: every byte on the wire against the generic path (a server
with no zone table and no answer cache, whose every answer is
``resolver.handle`` + ``QueryCtx.respond``), over the postures a client
can take; which answers are truncated; rotation of every answer that
carries records; invalidation by tag and by epoch; the two counters; the
log lines.
"""
import asyncio
import socket
import threading

import pytest

from binder_tpu.dns import Message, Type, make_query
from binder_tpu.dns.server import (
    TRANSPORT_TCP,
    TRANSPORT_UDP,
    pack_balancer_frame,
)
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger
from tests.test_ledger import tcp_oneshot
from tests.test_log_ring import byte_stream, query_lines
from tests.test_server import read_data_frame

DOMAIN = "foo.com"
#: members of a set: 7 is where 512 bytes run out and 17 where 1232 do
#: (a member costs 73 bytes here); 32/33 straddle the lazy render's 64
#: records for an SRV set with glue; 250 is the service cell's largest
SIZES = (7, 17, 32, 33, 250)
PROTOCOLS = ("udp", "balancer")
#: the stream clients, each with services of its own ("x": through the
#: balancer socket, for a TCP client)
STREAMS = ("tcp", "xbalancer")
#: the OPT record's payload; 4096 admits every set here but the largest
OPTS = {"no-opt": None, "opt1232": 1232, "opt600": 600, "opt4096": 4096}


def label_of(size, protocol):
    return f"s{size}{protocol[0]}"


def qname_of(size, protocol):
    return f"_http._tcp.{label_of(size, protocol)}.{DOMAIN}"


def put_service(store, label, members):
    store.put_json(f"/com/foo/{label}", {
        "type": "service",
        "service": {"srvce": "_http", "proto": "_tcp", "port": 80}})
    for i in range(members):
        put_member(store, label, i)


def put_member(store, label, i):
    # a 12-byte label: a member costs 73 bytes of an SRV set with glue
    store.put_json(f"/com/foo/{label}/pod-{i:03d}-{'a' * 4}", {
        "type": "load_balancer",
        "load_balancer": {"address": f"10.{i // 250}.{len(label)}."
                                     f"{i % 250 + 1}"}})


def mixed_case(wire: bytes, mask: int) -> bytes:
    """The query with its question's letters upper-cased where *mask*
    has a bit (dns0x20)."""
    b = bytearray(wire)
    off, n = 12, 0
    while b[off]:
        for i in range(off + 1, off + 1 + b[off]):
            if 97 <= b[i] <= 122:
                if mask >> (n % 16) & 1:
                    b[i] -= 32
                n += 1
        off += 1 + b[off]
    return bytes(b)


def records(wire: bytes):
    m = Message.decode(wire)
    return (m.rcode, m.tc, m.aa, m.rd, m.ra, len(m.questions),
            sorted((r.name, r.ttl, r.target, r.port, r.priority, r.weight)
                   for r in m.answers),
            len(m.authorities),
            sorted((type(r).__name__, r.name, getattr(r, "address", None))
                   for r in m.additionals))


class Pair:
    """The server under test (the production posture's serving shape:
    zone table, query log through the native ring, a balancer socket)
    and the generic path beside it (no answer cache, no zone table),
    over one zone, on a loop of their
    own thread, for blocking asks."""

    def __init__(self, sock_path):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.call(self._start(sock_path))

    def call(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(60)

    async def _start(self, sock_path):
        def zone():
            store = FakeStore()
            cache = MirrorCache(store, DOMAIN)
            for size in SIZES:
                for protocol in PROTOCOLS + STREAMS:
                    put_service(store, label_of(size, protocol), size)
            store.start_session()
            return cache

        self.stream, self.raw = byte_stream()
        self.server = BinderServer(
            zk_cache=zone(), dns_domain=DOMAIN, datacenter_name="coal",
            host="127.0.0.1", port=0, collector=MetricsCollector(),
            log=make_logger("binder-tc-test", stream=self.stream),
            query_log=True, zone_precompile=True,
            balancer_socket=sock_path)
        self.generic = BinderServer(
            zk_cache=zone(), dns_domain=DOMAIN, datacenter_name="coal",
            host="127.0.0.1", port=0, collector=MetricsCollector(),
            query_log=False, zone_precompile=False, cache_size=0)
        await self.server.start()
        await self.generic.start()
        self.reader, self.writer = \
            await asyncio.open_unix_connection(sock_path)

    def stop(self):
        async def down():
            self.writer.close()
            await self.server.stop()
            await self.generic.stop()
        self.call(down())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)

    @staticmethod
    def _udp(port, wire):
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.settimeout(5.0)
            s.sendto(wire, ("127.0.0.1", port))
            return s.recvfrom(65535)[0]

    def ask(self, protocol, wire, transport=TRANSPORT_UDP):
        if protocol == "udp":
            return self._udp(self.server.udp_port, wire)
        if protocol == "tcp":
            return tcp_oneshot(self.server.tcp_port, wire)

        async def through_the_balancer_socket():
            self.writer.write(pack_balancer_frame(
                4, "203.0.113.9", 5353, wire, transport=transport))
            await self.writer.drain()
            return (await read_data_frame(self.reader))[-1]
        return self.call(through_the_balancer_socket())

    def ask_generic(self, wire, tcp=False):
        if tcp:
            return tcp_oneshot(self.generic.tcp_port, wire)
        return self._udp(self.generic.udp_port, wire)

    def renders(self):
        return renders_of(self.server)

    def truncated(self):
        self.server.collector.fold()
        return int(self.server.collector.get(
            "binder_truncated_responses").value({"type": "SRV"}))


def renders_of(server):
    return int(server.collector.get("binder_truncated_renders").value({}))


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    p = Pair(str(tmp_path_factory.mktemp("tc") / "b.sock"))
    yield p
    p.stop()


# -- the differential: every posture against the generic path --

@pytest.mark.parametrize("rd", [False, True], ids=["rd0", "rd1"])
@pytest.mark.parametrize("opt", OPTS)
@pytest.mark.parametrize("protocol", PROTOCOLS)
@pytest.mark.parametrize("size", SIZES)
def test_a_udp_answer_equals_the_generic_paths(pair, size, protocol, opt,
                                               rd):
    """Three sights of one question in one posture, each in a question
    case of its own: the first is a resolve, the second is the answer
    cache's, the third the native cache's where
    it took the entry.  Each equals what the generic path answers to the same bytes:
    byte for byte where it is truncated, record for record where the
    whole set fits and rotates."""
    payload = OPTS[opt]
    limit = min(payload, 4096) if payload else 512
    qname = qname_of(size, protocol)
    plain = make_query(qname, Type.SRV, qid=0x2700 + size, rd=rd,
                       edns_payload=payload).encode()
    # the edges the sizes were chosen for: a set costs about 40 + 73 bytes a
    # member here, and 11 more with the OPT echo
    fits = {"no-opt": False, "opt600": size < 8, "opt1232": size < 17,
            "opt4096": size < 250}[opt]
    assert (40 + 73 * size + (11 if payload else 0) <= limit) == fits
    truncated_before = pair.truncated()
    for sight, mask in enumerate((0x0000, 0x5A5A, 0xFFFF)):
        wire = mixed_case(plain[:1] + bytes([sight]) + plain[2:], mask)
        before = pair.renders()
        got = pair.ask(protocol, wire)
        want = pair.ask_generic(wire)
        assert bool(got[2] & 0x02) == bool(want[2] & 0x02) == (not fits)
        grew = pair.renders() - before
        if fits:
            assert records(got) == records(want)
            # (the zone table's wire compresses fewer names than the
            # encoder's: the same records, not the same length)
            assert got[:12] == want[:12]
            # the question's case, echoed
            assert got[12:len(wire) - (11 if payload else 0)] \
                == wire[12:len(wire) - (11 if payload else 0)]
            assert grew == 0
        else:
            assert got == want
            assert int.from_bytes(got[6:8], "big") == 0
            assert len(got) == len(wire)
            # one render a key; then the cache's: nothing made
            assert grew == (0 if sight else 1)
    # each TC=1 answer counted once, whoever gave it
    assert pair.truncated() - truncated_before == (0 if fits else 3)


@pytest.mark.parametrize("stream", STREAMS)
@pytest.mark.parametrize("size", SIZES)
def test_never_over_tcp(pair, size, stream):
    """A client on a stream gets the whole set, before and after the
    same question left truncated over UDP and was cached so."""
    protocol = stream.lstrip("x")
    wire = make_query(qname_of(size, stream), Type.SRV, qid=0x7c9, rd=True,
                      edns_payload=None).encode()
    before = pair.renders()
    for step in range(2):
        got = pair.ask(protocol, wire, transport=TRANSPORT_TCP)
        assert not got[2] & 0x02
        assert records(got) == records(pair.ask_generic(wire, tcp=True))
        assert int.from_bytes(got[6:8], "big") == size
        if step == 0:
            for _ in range(3):
                udp = pair.ask("udp" if protocol == "tcp" else protocol,
                               wire)
                assert udp[2] & 0x02 and udp == pair.ask_generic(wire)
    # one truncated answer was made, over UDP; the stream answers made
    # none
    assert pair.renders() - before == 1


# -- rotation: an answer that carries records keeps its variants --

def small_zone(members=4, label="few"):
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    put_service(store, label, members)
    store.start_session()
    return store, cache


async def start_server(cache, **kw):
    kw.setdefault("query_log", False)
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="coal", host="127.0.0.1", port=0,
                          collector=MetricsCollector(), **kw)
    await server.start()
    return server


async def ask_udp(server, wire):
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, Pair._udp, server.udp_port,
                                      wire)


def entry_of(server, qname, payload=None, rd=False):
    key = (True, rd, Type.SRV, 1, qname, payload is not None,
           payload or 512)
    return server.answer_cache._entries.get(key)


def test_an_answer_that_fits_keeps_rotatable_and_its_eight_variants():
    """Nine members under a payload of 1400 (no zone table, so every
    sight is the Python lanes'): the set fits, and its entry serves no
    hit before it holds eight rotations."""
    async def run():
        store, cache = small_zone(9)
        server = await start_server(cache, zone_precompile=False)
        qname = f"_http._tcp.few.{DOMAIN}"
        wire = make_query(qname, Type.SRV, qid=5,
                          edns_payload=1400).encode()
        seen = []
        for _ in range(9):
            got = await ask_udp(server, wire)
            assert not got[2] & 0x02
            assert int.from_bytes(got[6:8], "big") == 9
            e = entry_of(server, qname, payload=1400)
            seen.append((server.answer_cache.hits, e[4], len(e[3])))
        made = renders_of(server)
        await server.stop()
        return seen, made

    seen, made = asyncio.run(run())
    # eight sights each store a variant and none is a hit; the ninth is
    assert [n for _, _, n in seen] == [1, 2, 3, 4, 5, 6, 7, 8, 8]
    assert [hits for hits, _, _ in seen] == [0] * 8 + [1]
    assert not any(complete for _, complete, _ in seen)
    assert made == 0


@pytest.mark.parametrize("members", [7, 33], ids=["under-64", "above-64"])
def test_a_truncated_wire_is_complete_from_its_first_sight(members):
    """Whether the set's first sight is a plain resolve (7 members) or a
    lazy render (33, above ``engine.MAX_SET_RECORDS`` with its glue)."""
    async def run():
        store, cache = small_zone(members)
        server = await start_server(cache, zone_precompile=False)
        qname = f"_http._tcp.few.{DOMAIN}"
        wire = make_query(qname, Type.SRV, qid=5,
                          edns_payload=None).encode()
        seen = []
        for _ in range(3):
            got = await ask_udp(server, wire)
            assert got[2] & 0x02
            e = entry_of(server, qname)
            seen.append((server.answer_cache.hits, e[4], len(e[3])))
        made = renders_of(server)
        # the same set over TCP still rotates: its entry collects
        tcp_key = (False, False, Type.SRV, 1, qname, False, 512)
        loop = asyncio.get_running_loop()
        for _ in range(2):
            await loop.run_in_executor(None, tcp_oneshot, server.tcp_port,
                                       wire)
        tcp_entry = server.answer_cache._entries[tcp_key]
        native = server._fastpath is not None
        await server.stop()
        return seen, made, (tcp_entry[4], len(tcp_entry[3])), native

    seen, made, tcp_entry, native = asyncio.run(run())
    # the second sight is a hit and promotes; the third is C's where the
    # extension is built
    assert seen == [(0, True, 1), (1, True, 1),
                    (1 if native else 2, True, 1)]
    assert made == 1
    assert tcp_entry == (False, 2)


# -- invalidation --

@pytest.mark.parametrize("members", [7, 33])
def test_a_members_mutation_drops_the_truncated_entry(members):
    """By tag: a member leaves, a set of 7 then fits 512 bytes and is
    served whole; a member joins, and the next TC=1 answer is made anew
    (no hit).  By epoch: after a rebuild of the mirror, the same."""
    async def run():
        store, cache = small_zone(members)
        server = await start_server(cache, zone_precompile=False)
        qname = f"_http._tcp.few.{DOMAIN}"
        wire = make_query(qname, Type.SRV, qid=5,
                          edns_payload=None).encode()
        async def sight():
            got = await ask_udp(server, wire)
            await asyncio.sleep(0)
            return (bool(got[2] & 0x02),
                    int.from_bytes(got[6:8], "big"),
                    server.answer_cache.hits, renders_of(server))

        out = [await sight(), await sight()]
        put_member(store, "few", members)            # one joins
        out += [await sight(), await sight()]
        cache.rebuild()                              # the epoch moves
        out += [await sight(), await sight()]
        for i in range(members, 5, -1):              # down to 6 members
            store.delete(f"/com/foo/few/pod-{i:03d}-{'a' * 4}")
        out += [await sight()]
        await server.stop()
        return out

    out = asyncio.run(run())
    assert [tc for tc, _, _, _ in out] == [True] * 6 + [False]
    assert out[-1][1] == 6                           # served whole
    # made anew after the mutation and after the rebuild, a hit between
    assert [made for _, _, _, made in out[:6]] == [1, 1, 2, 2, 3, 3]
    assert [hits for _, _, hits, _ in out[:6]] == [0, 1, 1, 2, 2, 3]


# -- the counters, the log lines, the native replay --

def test_one_render_then_the_caches_and_c_replays_it_truncated():
    async def run():
        store, cache = small_zone(7)
        stream, raw = byte_stream()
        server = await start_server(
            cache, log=make_logger("binder-tc-log-test", stream=stream),
            query_log=True, zone_precompile=True)
        qname = f"_http._tcp.few.{DOMAIN}"
        wire = make_query(qname, Type.SRV, qid=5,
                          edns_payload=None).encode()
        loop = asyncio.get_running_loop()
        tcp = await loop.run_in_executor(None, tcp_oneshot,
                                         server.tcp_port, wire)
        answers = [await ask_udp(server, wire) for _ in range(5)]
        await asyncio.sleep(0.05)
        server.collector.fold()
        server._write_log()
        get = server.collector.get
        counts = {
            "truncated": get("binder_truncated_responses").value(
                {"type": "SRV"}),
            "renders": renders_of(server),
            "python": get("binder_answer_cache_hits").value(
                {"tier": "python"}),
            "native": get("binder_answer_cache_hits").value(
                {"tier": "native"}),
            "completed": get("binder_requests_completed").value(
                {"type": "SRV"}),
        }
        native = server._fastpath is not None
        await server.stop()
        return tcp, answers, counts, query_lines(raw), native

    tcp, answers, counts, lines, native = asyncio.run(run())
    assert not tcp[2] & 0x02 and int.from_bytes(tcp[6:8], "big") == 7
    assert len(set(answers)) == 1 and answers[0][2] & 0x02
    # each TC=1 answer counted once, whoever gave it; one of them made
    assert counts["truncated"] == 5 and counts["completed"] == 6
    assert counts["renders"] == 1
    if native:
        # the second sight is the answer cache's and promotes the entry;
        # from the third on C replays it, truncated
        assert (counts["python"], counts["native"]) == (1, 3)
    else:
        assert counts["python"] == 4
    assert len(lines) == 6
    whole, first, hits = lines[0], lines[1], lines[2:]
    # the stream answer is the zone table's, whole, in C's line (no
    # lane field, no timers; a resolve's without the extension); the
    # first UDP sight is a resolve, and its line summarizes the set
    # that was rendered
    assert whole["rcode"] == "NOERROR" and whole["port"].endswith("/tcp")
    if native:
        assert whole["timers"] == {}
    assert "cached" not in whole and "cached" not in first
    assert len(first["answers"]) == len(first["additional"]) == 7
    for line in [first] + hits:
        assert sorted(line["answers"]) == sorted(whole["answers"])
        assert sorted(line["additional"]) == sorted(whole["additional"])
    for line in hits:
        assert line["cached"] is True
