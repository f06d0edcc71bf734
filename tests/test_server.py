"""Integration tests: full server over real UDP/TCP/balancer-socket
transports.

The protocol-level replacement for the reference's dig(1)-scraping
integration suite (SURVEY §4) — same scenarios, but asserting on decoded
wire responses, and runnable without a live ZooKeeper thanks to the fake
store.
"""
import asyncio
import socket
import struct


from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns.server import pack_balancer_frame, unpack_balancer_frame
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import (
    METRIC_LATENCY_HISTOGRAM,
    METRIC_REQUEST_COUNTER,
    BinderServer,
)
from binder_tpu.store import FakeStore, MirrorCache

DOMAIN = "foo.com"


def fixture_store():
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/web",
                   {"type": "host", "host": {"address": "192.168.0.1"}})
    store.put_json("/com/foo/svc", {
        "type": "service",
        "service": {"srvce": "_pg", "proto": "_tcp", "port": 5432},
    })
    for i in range(40):
        store.put_json(f"/com/foo/svc/lb{i}",
                       {"type": "load_balancer",
                        "load_balancer": {"address": f"10.0.1.{i + 1}"}})
    store.start_session()
    return store, cache


async def start_server(cache, **kw):
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="coal", host="127.0.0.1", port=0,
                          collector=MetricsCollector(), **kw)
    await server.start()
    return server


async def udp_ask(port, name, qtype, payload=1232, timeout=2.0,
                  qid=4242):
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    class Proto(asyncio.DatagramProtocol):
        def connection_made(self, transport):
            self.transport = transport
            q = make_query(name, qtype, qid=qid, edns_payload=payload)
            transport.sendto(q.encode())

        def datagram_received(self, data, addr):
            if not fut.done():
                fut.set_result(data)

    transport, _ = await loop.create_datagram_endpoint(
        Proto, remote_addr=("127.0.0.1", port))
    try:
        data = await asyncio.wait_for(fut, timeout)
    finally:
        transport.close()
    return Message.decode(data)


async def tcp_ask(port, name, qtype, qid=7, edns_payload=1232):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    wire = make_query(name, qtype, qid=qid,
                      edns_payload=edns_payload).encode()
    writer.write(struct.pack(">H", len(wire)) + wire)
    await writer.drain()
    (length,) = struct.unpack(">H", await reader.readexactly(2))
    data = await reader.readexactly(length)
    writer.close()
    await writer.wait_closed()
    return Message.decode(data)


class TestUdp:
    def test_a_query(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            r = await udp_ask(server.udp_port, "web.foo.com", Type.A)
            await server.stop()
            return r

        r = asyncio.run(run())
        assert r.rcode == Rcode.NOERROR and r.aa
        assert r.answers[0].address == "192.168.0.1"

    def test_refused_unknown(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            r = await udp_ask(server.udp_port, "nope.foo.com", Type.A)
            await server.stop()
            return r

        assert asyncio.run(run()).rcode == Rcode.REFUSED

    def test_truncation_under_small_payload(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            r = await udp_ask(server.udp_port, "svc.foo.com", Type.A,
                              payload=None)  # classic 512-byte limit
            await server.stop()
            return r

        r = asyncio.run(run())
        # 30 answers don't fit in 512b: TC set, client should retry TCP
        assert r.tc and len(r.answers) == 0

    def test_formerr_on_garbage(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            loop = asyncio.get_running_loop()
            fut = loop.create_future()

            class Proto(asyncio.DatagramProtocol):
                def connection_made(self, transport):
                    transport.sendto(b"\xde\xad\xff\xff\xff\xff")

                def datagram_received(self, data, addr):
                    if not fut.done():
                        fut.set_result(data)

            transport, _ = await loop.create_datagram_endpoint(
                Proto, remote_addr=("127.0.0.1", server.udp_port))
            data = await asyncio.wait_for(fut, 2)
            transport.close()
            await server.stop()
            return Message.decode(data)

        r = asyncio.run(run())
        assert r.rcode == Rcode.FORMERR and r.id == 0xDEAD


class TestTcp:
    def test_tcp_full_answer_set(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            r = await tcp_ask(server.tcp_port, "svc.foo.com", Type.A)
            await server.stop()
            return r

        r = asyncio.run(run())
        assert not r.tc and len(r.answers) == 40

    def test_tcp_multiple_queries_one_connection(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            out = []
            for i, (name, qtype) in enumerate(
                    [("web.foo.com", Type.A),
                     ("_pg._tcp.svc.foo.com", Type.SRV)]):
                wire = make_query(name, qtype, qid=i + 1).encode()
                writer.write(struct.pack(">H", len(wire)) + wire)
                await writer.drain()
                (ln,) = struct.unpack(">H", await reader.readexactly(2))
                out.append(Message.decode(await reader.readexactly(ln)))
            writer.close()
            await writer.wait_closed()
            await server.stop()
            return out

        r1, r2 = asyncio.run(run())
        assert r1.id == 1 and r1.answers[0].address == "192.168.0.1"
        assert r2.id == 2 and len(r2.answers) == 40


async def read_data_frame(reader):
    """Next non-control frame (backends announce their mirror generation
    with family-0 control frames, which a real balancer consumes)."""
    while True:
        (ln,) = struct.unpack(">I", await reader.readexactly(4))
        frame = await reader.readexactly(ln)
        if frame[1] != 0:   # family 0 == control
            return unpack_balancer_frame(frame)


class TestBalancerSocket:
    def test_query_via_balancer_frame(self, tmp_path):
        sock_path = str(tmp_path / "b.sock")

        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, balancer_socket=sock_path)
            reader, writer = await asyncio.open_unix_connection(sock_path)
            # pretend to be the balancer forwarding a client query
            q = make_query("web.foo.com", Type.A, qid=55).encode()
            writer.write(pack_balancer_frame(4, "203.0.113.9", 5353, q))
            await writer.drain()
            family, addr, port, transport, payload = \
                await read_data_frame(reader)
            writer.close()
            await writer.wait_closed()
            await server.stop()
            return family, addr, port, Message.decode(payload)

        family, addr, port, r = asyncio.run(run())
        # response frame echoes the original client address for routing
        assert (family, addr, port) == (4, "203.0.113.9", 5353)
        assert r.id == 55 and r.answers[0].address == "192.168.0.1"

    def test_bad_version_closes_connection(self, tmp_path):
        sock_path = str(tmp_path / "b.sock")

        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, balancer_socket=sock_path)
            reader, writer = await asyncio.open_unix_connection(sock_path)
            frame = bytearray(pack_balancer_frame(4, "1.2.3.4", 1,
                                                  b"\x00" * 12))
            frame[4] = 99  # bad version
            writer.write(bytes(frame))
            await writer.drain()
            data = await reader.read()
            writer.close()
            await writer.wait_closed()
            await server.stop()
            return data

        # the server may have sent its initial control frames (the
        # generation report, the direct-return announce) before
        # closing; nothing but control frames may precede the close
        data = asyncio.run(run())
        off = 0
        while off < len(data):
            (ln,) = struct.unpack(">I", data[off:off + 4])
            assert data[off + 4] == 1 and data[off + 5] == 0
            off += 4 + ln
        assert off == len(data)   # no partial trailing frame either


class TestMetrics:
    def test_counters_and_latency(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            await udp_ask(server.udp_port, "web.foo.com", Type.A)
            await udp_ask(server.udp_port, "web.foo.com", Type.A)
            await udp_ask(server.udp_port, "1.0.168.192.in-addr.arpa",
                          Type.PTR)
            # let 'after' hooks run
            await asyncio.sleep(0)
            counter = server.collector.get(METRIC_REQUEST_COUNTER)
            hist = server.collector.get(METRIC_LATENCY_HISTOGRAM)
            exposed = server.collector.expose()
            await server.stop()
            return counter, hist, exposed

        counter, hist, exposed = asyncio.run(run())
        assert counter.value({"type": "A"}) == 2
        assert counter.value({"type": "PTR"}) == 1
        assert hist.count({"type": "A"}) == 2
        assert 'binder_requests_completed{type="A"} 2' in exposed
        assert "binder_request_latency_seconds_bucket" in exposed

    def test_slow_query_promotes_log_to_warn(self, monkeypatch, caplog):
        """Latency > SLOW_QUERY_MS logs at warn even with the per-query
        log off (reference lib/server.js:511-514)."""
        import logging as _logging

        import binder_tpu.server as srv_mod

        async def run():
            store, cache = fixture_store()
            # zone-precompiled answers never surface to Python (no
            # latency stamp to promote); the warn path under test is the
            # Python lanes' (_on_after)
            server = await start_server(cache, query_log=False,
                                        zone_precompile=False)
            monkeypatch.setattr(srv_mod, "SLOW_QUERY_MS", -1.0)
            with caplog.at_level(_logging.INFO, logger="binder.server"):
                await udp_ask(server.udp_port, "web.foo.com", Type.A)
                await asyncio.sleep(0)
            await server.stop()

        asyncio.run(run())
        warns = [r for r in caplog.records
                 if r.levelno == _logging.WARNING and "DNS query" in
                 r.getMessage()]
        assert warns, [r.getMessage() for r in caplog.records]


class TestReviewRegressions:
    """Regressions from the second code-review pass."""

    def test_async_handler_path_works(self):
        """A handler that returns a real awaitable (the recursion shape)
        must complete, not die with a half-driven coroutine."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)

            orig = server.resolver.handle

            def handle(query):
                async def delayed():
                    await asyncio.sleep(0.01)  # real suspension
                    pending = orig(query)
                    if pending is not None:
                        await pending
                return delayed()

            server.resolver.handle = handle
            server.engine.on_query = lambda q: server.resolver.handle(q)
            r = await udp_ask(server.udp_port, "web.foo.com", Type.A)
            await server.stop()
            return r

        r = asyncio.run(run())
        assert r.rcode == Rcode.NOERROR
        assert r.answers[0].address == "192.168.0.1"

    def test_unencodable_record_yields_servfail(self):
        """host record without an address: client must get SERVFAIL, not
        silence."""
        async def run():
            store, cache = fixture_store()
            store.put_json("/com/foo/noaddr", {"type": "host", "host": {}})
            server = await start_server(cache)
            r = await udp_ask(server.udp_port, "noaddr.foo.com", Type.A)
            await server.stop()
            return r

        r = asyncio.run(run())
        assert r.rcode == Rcode.SERVFAIL and not r.answers

    def test_balancer_udp_transport_truncates(self, tmp_path):
        sock_path = str(tmp_path / "b.sock")

        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, balancer_socket=sock_path)
            reader, writer = await asyncio.open_unix_connection(sock_path)
            q = make_query("svc.foo.com", Type.A, qid=9,
                           edns_payload=None).encode()
            from binder_tpu.dns.server import TRANSPORT_TCP, TRANSPORT_UDP
            writer.write(pack_balancer_frame(4, "203.0.113.9", 5353, q,
                                             transport=TRANSPORT_UDP))
            await writer.drain()
            *_, payload_udp = await read_data_frame(reader)
            writer.write(pack_balancer_frame(4, "203.0.113.9", 5353, q,
                                             transport=TRANSPORT_TCP))
            await writer.drain()
            *_, payload_tcp = await read_data_frame(reader)
            writer.close()
            await writer.wait_closed()
            await server.stop()
            return Message.decode(payload_udp), Message.decode(payload_tcp)

        udp_r, tcp_r = asyncio.run(run())
        # UDP-origin (no EDNS): truncated at 512; TCP-origin: full answers
        assert udp_r.tc and not udp_r.answers
        assert not tcp_r.tc and len(tcp_r.answers) == 40

    def test_short_form_store_address_servfail(self):
        """inet_aton would map '10.1' -> 10.0.0.1; must SERVFAIL instead."""
        async def run():
            store, cache = fixture_store()
            store.put_json("/com/foo/shorty",
                           {"type": "host", "host": {"address": "10.1"}})
            server = await start_server(cache)
            r = await udp_ask(server.udp_port, "shorty.foo.com", Type.A)
            await server.stop()
            return r

        r = asyncio.run(run())
        assert r.rcode == Rcode.SERVFAIL and not r.answers


class TestTcpBounds:
    """The TCP front must survive misbehaving peers with bounded
    resources: idle holders, connection floods, and clients that ask
    but never read (VERDICT r1: no idle timeout or cap anywhere)."""

    def test_idle_connection_evicted(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=0.3)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            # hold the connection without sending a complete frame
            writer.write(b"\x00")
            await writer.drain()
            got = await asyncio.wait_for(reader.read(16), 5)
            writer.close()
            await server.stop()
            return got

        assert asyncio.run(run()) == b""   # server closed on us

    def test_slow_frame_gets_same_deadline(self):
        """A slowloris trickling bytes within one frame must be cut off
        by the same idle clock, not kept alive per-byte."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=0.4)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            wire = make_query("web.foo.com", Type.A, qid=5).encode()
            framed = struct.pack(">H", len(wire)) + wire
            start = asyncio.get_running_loop().time()
            closed_at = None
            try:
                for b in framed:          # one byte per 150 ms
                    writer.write(bytes([b]))
                    await writer.drain()
                    data = await asyncio.wait_for(
                        reader.read(64), 0.15)
                    if data == b"":
                        closed_at = asyncio.get_running_loop().time()
                        break
            except asyncio.TimeoutError:
                pass
            if closed_at is None:
                got = await asyncio.wait_for(reader.read(64), 5)
                assert got == b""
                closed_at = asyncio.get_running_loop().time()
            writer.close()
            await server.stop()
            return closed_at - start

        elapsed = asyncio.run(run())
        # cut off by the whole-frame deadline (0.4 s), well before the
        # ~2.5 s the full trickle would take
        assert elapsed < 2.0

    def test_connection_cap_refuses_newcomers(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, max_tcp_conns=2,
                                        tcp_idle_timeout=30.0)
            conns = []
            for _ in range(2):
                conns.append(await asyncio.open_connection(
                    "127.0.0.1", server.tcp_port))
            # give the handlers a turn to register
            await asyncio.sleep(0.1)
            r3, w3 = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            refused = await asyncio.wait_for(r3.read(16), 5)
            # the earlier connections still work
            wire = make_query("web.foo.com", Type.A, qid=8).encode()
            r1, w1 = conns[0]
            w1.write(struct.pack(">H", len(wire)) + wire)
            await w1.drain()
            (ln,) = struct.unpack(">H", await asyncio.wait_for(
                r1.readexactly(2), 5))
            reply = Message.decode(await r1.readexactly(ln))
            for r, w in conns + [(r3, w3)]:
                w.close()
            await server.stop()
            return refused, reply

        refused, reply = asyncio.run(run())
        assert refused == b""
        assert reply.rcode == Rcode.NOERROR

    def test_cap_slot_recycles_after_close(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, max_tcp_conns=1)
            r1, w1 = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            await asyncio.sleep(0.1)
            w1.close()
            await w1.wait_closed()
            await asyncio.sleep(0.1)   # give the handler a turn to exit
            r2, w2 = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            wire = make_query("web.foo.com", Type.A, qid=3).encode()
            w2.write(struct.pack(">H", len(wire)) + wire)
            await w2.drain()
            (ln,) = struct.unpack(">H", await asyncio.wait_for(
                r2.readexactly(2), 5))
            reply = Message.decode(await r2.readexactly(ln))
            w2.close()
            await server.stop()
            return reply

        reply = asyncio.run(run())
        assert reply.rcode == Rcode.NOERROR

    def test_client_not_reading_responses_aborted(self):
        """Pipelines queries, never reads answers: the write buffer must
        hit its cap and the connection must be aborted, not grow."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=30.0,
                                        max_tcp_write_buffer=4096)
            # tiny receive window so the kernel can't absorb much
            raw = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            raw.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            raw.setblocking(False)
            loop = asyncio.get_running_loop()
            await loop.sock_connect(raw, ("127.0.0.1", server.tcp_port))
            # SRV answer for svc.foo.com is large (40 targets)
            wire = make_query("svc.foo.com", Type.A, qid=1,
                              edns_payload=4096).encode()
            frame = struct.pack(">H", len(wire)) + wire
            aborted = False
            try:
                # the kernel absorbs up to ~tcp_wmem max (4 MB) before
                # the transport buffer grows, so pump well past that
                # (~700 B per response x 20k queries = ~14 MB)
                for i in range(20000):
                    await loop.sock_sendall(raw, frame)
                    if i % 64 == 0:
                        await asyncio.sleep(0)
            except (ConnectionResetError, BrokenPipeError, OSError):
                aborted = True
            # the server process itself must still serve other clients
            r = await udp_ask(server.udp_port, "web.foo.com", Type.A)
            raw.close()
            await server.stop()
            return aborted, r

        aborted, r = asyncio.run(run())
        assert aborted
        assert r.rcode == Rcode.NOERROR


class TestPairBind:
    """Ephemeral-port UDP/TCP pairing (the r4 CI flake): with port=0 the
    kernel picks the UDP port and TCP must bind the same number, which
    any unrelated socket may hold — start() must redraw, not die."""

    def test_tcp_collision_redraws(self):
        async def run():
            store, cache = fixture_store()
            # occupy a TCP port the first UDP draw will be forced onto
            blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            blocker.bind(("127.0.0.1", 0))
            blocker.listen(1)
            taken = blocker.getsockname()[1]

            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=0,
                                  collector=MetricsCollector())
            real_listen_udp = server.engine.listen_udp
            calls = []

            async def forced_listen_udp(host, port, announce=True,
                                        **kw):
                # first draw lands on the TCP-occupied port (what the
                # kernel did to CI); later draws are honest
                calls.append(port)
                if len(calls) == 1:
                    return await real_listen_udp(host, taken,
                                                 announce=announce, **kw)
                return await real_listen_udp(host, port,
                                             announce=announce, **kw)

            server.engine.listen_udp = forced_listen_udp
            await server.start()
            try:
                assert len(calls) >= 2          # it retried
                assert server.udp_port == server.tcp_port != taken
                # the failed draw was released: only ONE UDP listener
                assert len(server.engine._udp_socks) == 1
                r = await udp_ask(server.udp_port, "web.foo.com", Type.A)
                assert r.rcode == Rcode.NOERROR
                r = await tcp_ask(server.tcp_port, "web.foo.com", Type.A)
                assert r.rcode == Rcode.NOERROR
            finally:
                blocker.close()
                await server.stop()

        asyncio.run(run())

    def test_fixed_port_collision_raises(self):
        """A FIXED port that is TCP-occupied is a real error: no silent
        redraw to a different number, and the UDP draw is released."""
        async def run():
            store, cache = fixture_store()
            blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            blocker.bind(("127.0.0.1", 0))
            blocker.listen(1)
            taken = blocker.getsockname()[1]
            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=taken,
                                  collector=MetricsCollector())
            try:
                await server.start()
            except OSError:
                assert server.engine._udp_socks == []
                return True
            finally:
                blocker.close()
                await server.stop()
            return False

        assert asyncio.run(run())

    def test_fixed_udp_port_taken_releases_balancer(self, tmp_path):
        """A fixed UDP port already bound: start() must raise AND
        release the balancer listener opened before the pair bind."""
        async def run():
            store, cache = fixture_store()
            blocker = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            blocker.bind(("127.0.0.1", 0))
            taken = blocker.getsockname()[1]
            sock_path = str(tmp_path / "b.sock")
            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=taken,
                                  balancer_socket=sock_path,
                                  collector=MetricsCollector())
            try:
                await server.start()
            except OSError:
                assert server.engine._udp_socks == []
                assert server.engine._unix_servers == []
                return True
            finally:
                blocker.close()
                await server.stop()
            return False

        assert asyncio.run(run())

    def test_concurrent_ephemeral_startups(self):
        """Hammer: many port=0 servers starting concurrently while TCP
        churn occupies ephemeral ports.  Every start must succeed with
        udp_port == tcp_port (probabilistic companion to the
        deterministic collision test above)."""
        async def run():
            store, cache = fixture_store()

            async def one():
                s = await start_server(cache)
                assert s.udp_port == s.tcp_port
                return s

            for _ in range(4):
                servers = await asyncio.gather(*[one() for _ in range(8)])
                for s in servers:
                    r = await udp_ask(s.udp_port, "web.foo.com", Type.A)
                    assert r.rcode == Rcode.NOERROR
                    await s.stop()

        asyncio.run(run())


class TestTcpFrameDeadline:
    def test_byte_trickler_disconnected(self):
        """Slowloris: steady 1-byte-per-interval traffic must NOT reset
        the idle deadline — only a complete frame does (r5 regression
        guard for the bulk-reframe read loop)."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=0.6)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            try:
                t0 = asyncio.get_running_loop().time()
                closed_at = None
                # header promising a 100-byte frame, then 1 byte per
                # interval: bytes keep flowing, the frame never completes
                writer.write(b"\x00\x64")
                for _ in range(20):
                    writer.write(b"\x01")
                    await writer.drain()
                    try:
                        got = await asyncio.wait_for(reader.read(16), 0.25)
                    except (TimeoutError, asyncio.TimeoutError):
                        continue
                    except (ConnectionResetError, BrokenPipeError):
                        closed_at = asyncio.get_running_loop().time()
                        break
                    if got == b"":
                        closed_at = asyncio.get_running_loop().time()
                        break
                assert closed_at is not None, "trickler never disconnected"
                assert closed_at - t0 < 3.0
            finally:
                writer.close()
                try:
                    await writer.wait_closed()
                except (ConnectionResetError, BrokenPipeError, OSError):
                    pass
                await server.stop()

        asyncio.run(run())

    def test_steady_frames_stay_connected(self):
        """Complete frames slower than the byte-level interval but
        faster than the idle deadline keep the connection alive."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=0.6)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            try:
                for qid in range(5):
                    wire = make_query("web.foo.com", Type.A,
                                      qid=qid).encode()
                    writer.write(struct.pack(">H", len(wire)) + wire)
                    await writer.drain()
                    (ln,) = struct.unpack(
                        ">H", await reader.readexactly(2))
                    m = Message.decode(await reader.readexactly(ln))
                    assert m.rcode == Rcode.NOERROR
                    await asyncio.sleep(0.4)   # < deadline per frame
            finally:
                writer.close()
                try:
                    await writer.wait_closed()
                except (ConnectionResetError, BrokenPipeError, OSError):
                    pass
                await server.stop()

        asyncio.run(run())


class TestPairBindAnnouncement:
    def test_redraw_announces_only_final_port(self, caplog):
        """The 'service started' lines are the port-discovery contract
        for harnesses (bench/systemd logs): a redrawn (released) draw
        must never be announced — only the secured pair, exactly once
        (the r5 CI failure: dnsblast latched a dead first-draw port)."""
        async def run():
            store, cache = fixture_store()
            blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            blocker.bind(("127.0.0.1", 0))
            blocker.listen(1)
            taken = blocker.getsockname()[1]
            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=0,
                                  collector=MetricsCollector())
            real_listen_udp = server.engine.listen_udp
            first = []

            async def forced(host, port, announce=True, **kw):
                if not first:
                    first.append(True)
                    return await real_listen_udp(host, taken,
                                                 announce=announce, **kw)
                return await real_listen_udp(host, port,
                                             announce=announce, **kw)

            server.engine.listen_udp = forced
            await server.start()
            try:
                udp_lines = [r.getMessage() for r in caplog.records
                             if "UDP DNS service started" in r.getMessage()]
                tcp_lines = [r.getMessage() for r in caplog.records
                             if "TCP DNS service started" in r.getMessage()]
                assert udp_lines == \
                    [f"UDP DNS service started on 127.0.0.1:"
                     f"{server.udp_port}"]
                assert tcp_lines == \
                    [f"TCP DNS service started on 127.0.0.1:"
                     f"{server.tcp_port}"]
                assert server.udp_port != taken
            finally:
                blocker.close()
                await server.stop()

        import logging as _logging
        caplog.set_level(_logging.INFO, logger="binder.server")
        asyncio.run(run())


class TestTcpBulkServe:
    def test_mixed_hit_miss_pipelined_chunk(self):
        """One write carrying interleaved zone-served and
        Python-resolved frames: every query must be answered correctly
        by id whatever path served it (the native bulk frame serve
        splits a chunk into C-served hits and surfaced misses)."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            names = [("web.foo.com", Type.A),        # zone hit
                     ("nope.example.org", Type.A),   # REFUSED via Python
                     ("web.foo.com", Type.A),        # zone hit
                     ("_pg._tcp.svc.foo.com", Type.SRV),  # zone SRV
                     ("nope2.example.org", Type.A)]  # Python again
            block = b""
            for qid, (name, qt) in enumerate(names, start=1):
                wire = make_query(name, qt, qid=qid).encode()
                block += struct.pack(">H", len(wire)) + wire
            writer.write(block)
            await writer.drain()
            got = {}
            buf = b""
            while len(got) < len(names):
                buf += await reader.read(65536)
                while len(buf) >= 2:
                    (ln,) = struct.unpack(">H", buf[:2])
                    if len(buf) - 2 < ln:
                        break
                    m = Message.decode(buf[2:2 + ln])
                    buf = buf[2 + ln:]
                    got[m.id] = m
            assert got[1].answers[0].address == "192.168.0.1"
            assert got[2].rcode == Rcode.REFUSED
            assert got[3].answers[0].address == "192.168.0.1"
            assert got[4].answers[0].port == 5432
            assert got[5].rcode == Rcode.REFUSED
            writer.close()
            await writer.wait_closed()
            await server.stop()

        asyncio.run(run())
