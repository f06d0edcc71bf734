"""Stream-lane (TCP) fast path: accept fast path, pipelined coalescing,
connection-table hardening (ISSUE 5).

Pins the serving contracts the rewritten lane must keep:

- **byte-for-byte parity** — responses served via the accept fast path
  and via the promoted pipelined path match the UDP lane's wire output
  (modulo ID), and a truncated cached UDP wire is never replayed on TCP
  (TC-decline);
- **RFC 7766 conformance** — out-of-order responses carry the right
  IDs (a slow query never head-of-line-blocks the batch), half-close
  still gets its owed answers, a mid-frame RST never wedges the
  connection table, and the idle deadline still fires under pipelining;
- **bounded resources** — a slow reader is disconnected at
  ``MAX_TCP_WRITE_BUFFER`` with the ``binder_tcp_slow_reader_drops``
  metric advanced, never buffered unboundedly;
- **observability** — the ``binder_tcp_*`` exposition passes
  ``tools/lint.py validate_tcp_metrics`` (this is the family's tier-1
  wiring) and the ``/status`` ``tcp`` section is schema-complete; a
  one-shot leg observes the time ledger's four ``tcp-*`` spans once a
  call, and ``binder_truncated_responses`` counts the UDP answers that
  send clients to the lane, and nothing else;
- **chaos** — the stream-fault DSL actions drive a live server and the
  table re-converges to empty.
"""
import asyncio
import socket
import struct
import time

from binder_tpu.chaos import ChaosDriver, FaultPlan
from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns.server import DnsServer
from binder_tpu.dns.wire import ARecord
from binder_tpu.introspect import Introspector
from binder_tpu.introspect.ledger import TCP_STAGES
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from tools.lint import (validate_status_snapshot, validate_tcp_metrics)

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
                          datacenter_name="coal", host="127.0.0.1",
                          port=0, collector=MetricsCollector(), **kw)
    await server.start()
    return server


async def udp_ask_raw(port, wire, timeout=2.0):
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    class Proto(asyncio.DatagramProtocol):
        def connection_made(self, transport):
            transport.sendto(wire)

        def datagram_received(self, data, addr):
            if not fut.done():
                fut.set_result(data)

    transport, _ = await loop.create_datagram_endpoint(
        Proto, remote_addr=("127.0.0.1", port))
    try:
        return await asyncio.wait_for(fut, timeout)
    finally:
        transport.close()


async def tcp_oneshot_raw(port, wire):
    """The accept-fast-path client: connect, one query, read, close."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(struct.pack(">H", len(wire)) + wire)
    await writer.drain()
    (ln,) = struct.unpack(">H", await reader.readexactly(2))
    data = await reader.readexactly(ln)
    writer.close()
    await writer.wait_closed()
    return data


async def read_frames(reader, n, timeout=5.0):
    out = []
    for _ in range(n):
        hdr = await asyncio.wait_for(reader.readexactly(2), timeout)
        (ln,) = struct.unpack(">H", hdr)
        out.append(await asyncio.wait_for(reader.readexactly(ln),
                                          timeout))
    return out


def norm_id(wire: bytes) -> bytes:
    return b"\x00\x00" + wire[2:]


async def wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(0.02)
    return pred()


class TestParity:
    def test_fast_path_and_promoted_match_udp_wire(self):
        """One-shot (accept fast path) and second-burst (promoted
        pipelined) responses are byte-identical to the UDP lane's
        output for the same question, modulo the query ID."""
        shapes = [("web.foo.com", Type.A, 1232),
                  ("web.foo.com", Type.A, None),
                  ("nope.foo.com", Type.A, 1232),
                  ("1.0.168.192.in-addr.arpa", Type.PTR, 1232)]

        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            results = []
            for name, qtype, payload in shapes:
                wire = make_query(name, qtype, qid=11,
                                  edns_payload=payload).encode()
                udp = await udp_ask_raw(server.udp_port, wire)
                one = await tcp_oneshot_raw(server.tcp_port, wire)
                # promoted path: same query in the SECOND burst of a
                # pipelined connection
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.tcp_port)
                writer.write(struct.pack(">H", len(wire)) + wire)
                await writer.drain()
                await read_frames(reader, 1)
                writer.write(struct.pack(">H", len(wire)) + wire)
                await writer.drain()
                (piped,) = await read_frames(reader, 1)
                writer.close()
                await writer.wait_closed()
                results.append((name, udp, one, piped))
            assert server.engine.tcp_stats.promotions >= len(shapes)
            await server.stop()
            return results

        for name, udp, one, piped in asyncio.run(run()):
            assert norm_id(one) == norm_id(udp), name
            assert norm_id(piped) == norm_id(udp), name

    def test_tc_decline_for_cached_udp_wire(self):
        """A no-EDNS UDP answer that truncated (and was cached) must
        never be replayed on TCP: the TCP serve re-renders the full
        answer set (the tc=1 retry flow's whole point)."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            wire = make_query("svc.foo.com", Type.A, qid=3,
                              edns_payload=None).encode()
            # twice over UDP: second serve comes from the answer cache
            await udp_ask_raw(server.udp_port, wire)
            udp = Message.decode(
                await udp_ask_raw(server.udp_port, wire))
            tcp = Message.decode(
                await tcp_oneshot_raw(server.tcp_port, wire))
            await server.stop()
            return udp, tcp

        udp, tcp = asyncio.run(run())
        assert udp.tc and not udp.answers
        assert not tcp.tc and len(tcp.answers) == 40


class TestRfc7766:
    def test_out_of_order_responses_with_right_ids(self):
        """A slow (async) query pipelined ahead of fast ones must not
        head-of-line-block them: the fast responses come back first,
        each under its own ID (RFC 7766 §6.2.1.1)."""
        async def run():
            eng = DnsServer()

            def on_query(q):
                if q.name().startswith("slow"):
                    async def later():
                        await asyncio.sleep(0.15)
                        q.response.answers.append(ARecord(
                            name=q.name(), ttl=5, address="10.9.9.9"))
                        q.respond()
                    return later()
                q.response.answers.append(ARecord(
                    name=q.name(), ttl=5, address="10.1.1.1"))
                q.respond()
                return None

            eng.on_query = on_query
            port = await eng.listen_tcp("127.0.0.1", 0, announce=False)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            block = b""
            for qid, name in ((1, "slow.example.com"),
                              (2, "fast1.example.com"),
                              (3, "fast2.example.com")):
                w = make_query(name, Type.A, qid=qid).encode()
                block += struct.pack(">H", len(w)) + w
            writer.write(block)
            await writer.drain()
            frames = await read_frames(reader, 3)
            writer.close()
            await writer.wait_closed()
            await eng.close()
            return [Message.decode(f) for f in frames]

        r1, r2, r3 = asyncio.run(run())
        # fast responses first (out of order vs the request stream),
        # the slow one last — all IDs intact
        assert (r1.id, r2.id, r3.id) == (2, 3, 1)
        assert r3.answers[0].address == "10.9.9.9"

    def test_half_close_still_gets_owed_response(self):
        """send-then-SHUT_WR with an async answer outstanding: the
        response must still be written, then the slot reclaimed."""
        async def run():
            eng = DnsServer()

            def on_query(q):
                async def later():
                    await asyncio.sleep(0.1)
                    q.response.answers.append(ARecord(
                        name=q.name(), ttl=5, address="10.2.2.2"))
                    q.respond()
                return later()

            eng.on_query = on_query
            port = await eng.listen_tcp("127.0.0.1", 0, announce=False)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            w = make_query("x.example.com", Type.A, qid=9).encode()
            writer.write(struct.pack(">H", len(w)) + w)
            await writer.drain()
            writer.write_eof()
            (frame,) = await read_frames(reader, 1)
            eof = await asyncio.wait_for(reader.read(16), 5)
            writer.close()
            await writer.wait_closed()
            stats = eng.tcp_stats
            empty = await wait_until(lambda: not eng._tcp_conns)
            await eng.close()
            return Message.decode(frame), eof, stats, empty

        r, eof, stats, empty = asyncio.run(run())
        assert r.id == 9 and r.answers[0].address == "10.2.2.2"
        assert eof == b""
        assert stats.half_closes >= 1
        assert empty

    def test_mid_frame_rst_never_wedges_table(self):
        """A torn frame followed by RST must shed the connection; the
        server keeps serving and the table re-converges to empty."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            loop = asyncio.get_running_loop()
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setblocking(False)
            await loop.sock_connect(s, ("127.0.0.1", server.tcp_port))
            # header promising 256 bytes, 3 sent: mid-frame
            await loop.sock_sendall(s, b"\x01\x00abc")
            await asyncio.sleep(0.1)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))
            s.close()   # RST
            engine = server.engine
            empty = await wait_until(lambda: not engine._tcp_conns)
            # the lane still serves
            r = Message.decode(await tcp_oneshot_raw(
                server.tcp_port,
                make_query("web.foo.com", Type.A, qid=4).encode()))
            stats = engine.tcp_stats
            await server.stop()
            return empty, r, stats

        empty, r, stats = asyncio.run(run())
        assert empty
        assert r.rcode == Rcode.NOERROR
        assert stats.rst_drops >= 1

    def test_idle_deadline_fires_under_pipelining(self):
        """Frames keep a pipelined connection alive; silence after the
        last frame still trips the idle deadline."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=0.4)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            for qid in range(3):
                w = make_query("web.foo.com", Type.A, qid=qid).encode()
                writer.write(struct.pack(">H", len(w)) + w)
                await writer.drain()
                await read_frames(reader, 1)
                await asyncio.sleep(0.2)   # < deadline per frame
            t0 = asyncio.get_running_loop().time()
            eof = await asyncio.wait_for(reader.read(16), 5)
            elapsed = asyncio.get_running_loop().time() - t0
            stats = server.engine.tcp_stats
            writer.close()
            await server.stop()
            return eof, elapsed, stats

        eof, elapsed, stats = asyncio.run(run())
        assert eof == b""
        assert elapsed < 2.0
        assert stats.idle_timeouts >= 1


class TestWriteBufferCap:
    def test_slow_reader_disconnected_at_cap_with_metric(self):
        """A client that pipelines queries and never reads must be
        disconnected once the server-side backlog passes
        MAX_TCP_WRITE_BUFFER — with the drop recorded in
        binder_tcp_slow_reader_drops, never buffered unboundedly."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache, tcp_idle_timeout=30.0,
                                        max_tcp_write_buffer=4096)
            raw = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            raw.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            raw.setblocking(False)
            loop = asyncio.get_running_loop()
            await loop.sock_connect(raw, ("127.0.0.1", server.tcp_port))
            wire = make_query("svc.foo.com", Type.A, qid=1,
                              edns_payload=4096).encode()
            frame = struct.pack(">H", len(wire)) + wire
            aborted = False
            try:
                # the kernel absorbs up to ~tcp_wmem max before the
                # user-space backlog grows, so pump well past that
                for i in range(20000):
                    await loop.sock_sendall(raw, frame)
                    if i % 64 == 0:
                        await asyncio.sleep(0)
            except (ConnectionResetError, BrokenPipeError, OSError):
                aborted = True
            raw.close()
            stats = server.engine.tcp_stats
            exposed = server.collector.expose()
            # other clients are unaffected
            r = Message.decode(await tcp_oneshot_raw(
                server.tcp_port,
                make_query("web.foo.com", Type.A, qid=2).encode()))
            await server.stop()
            return aborted, stats, exposed, r

        aborted, stats, exposed, r = asyncio.run(run())
        assert aborted
        assert stats.slow_reader_drops >= 1
        assert r.rcode == Rcode.NOERROR
        for line in exposed.splitlines():
            if line.startswith("binder_tcp_slow_reader_drops"):
                assert float(line.split()[-1]) >= 1.0
                break
        else:
            raise AssertionError(
                "binder_tcp_slow_reader_drops not exposed")


class TestAccountingAndCoalescing:
    def test_oneshot_vs_promotion_accounting(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            engine = server.engine
            wire = make_query("web.foo.com", Type.A, qid=1).encode()
            await tcp_oneshot_raw(server.tcp_port, wire)
            stats = engine.tcp_stats
            await wait_until(lambda: stats.oneshot_closes >= 1)
            assert stats.accepts >= 1
            assert stats.fast_serves >= 1
            assert stats.promotions == 0
            assert stats.oneshot_closes >= 1
            # now a client that keeps sending: second burst promotes
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            writer.write(struct.pack(">H", len(wire)) + wire)
            await writer.drain()
            await read_frames(reader, 1)
            writer.write(struct.pack(">H", len(wire)) + wire)
            await writer.drain()
            await read_frames(reader, 1)
            writer.close()
            await writer.wait_closed()
            assert stats.promotions == 1
            await server.stop()

        asyncio.run(run())

    def test_pipelined_burst_coalesces_into_one_write(self):
        """All responses produced while draining one read chunk go out
        as a single vectored write."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            block = b""
            for qid in range(1, 6):
                w = make_query("web.foo.com", Type.A, qid=qid).encode()
                block += struct.pack(">H", len(w)) + w
            writer.write(block)
            await writer.drain()
            frames = await read_frames(reader, 5)
            stats = server.engine.tcp_stats
            writer.close()
            await writer.wait_closed()
            await server.stop()
            return frames, stats

        frames, stats = asyncio.run(run())
        ids = sorted(Message.decode(f).id for f in frames)
        assert ids == [1, 2, 3, 4, 5]
        assert stats.coalesced_writes >= 1
        assert stats.coalesced_frames >= 5


class TestObservability:
    def test_tcp_metrics_family_validates(self):
        """Tier-1 wiring for tools/lint.py validate_tcp_metrics: the
        full binder_tcp_* family is present (right TYPEs, a sample
        each) on a live server's real exposition."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            await tcp_oneshot_raw(
                server.tcp_port,
                make_query("web.foo.com", Type.A, qid=1).encode())
            text = server.collector.expose()
            await server.stop()
            return text

        errs = validate_tcp_metrics(asyncio.run(run()))
        assert errs == []

    def test_status_snapshot_carries_tcp_section(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            await tcp_oneshot_raw(
                server.tcp_port,
                make_query("web.foo.com", Type.A, qid=1).encode())
            intro = Introspector(server=server)
            snap = intro.snapshot()
            await server.stop()
            return snap

        snap = asyncio.run(run())
        assert validate_status_snapshot(snap) == []
        tcp = snap["tcp"]
        assert tcp["accepts"] >= 1
        assert tcp["max_conns"] == DnsServer.MAX_TCP_CONNS
        assert tcp["max_write_buffer"] == DnsServer.MAX_TCP_WRITE_BUFFER


    def test_a_oneshot_legs_span_counts(self):
        """One truncation retry as the time ledger sees it: the accept
        and the EAGAIN that ends its burst, the frame's recv and the
        EOF's, one send, one close (docs/observability.md)."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            hist = server.collector.get("binder_query_stage_seconds")

            def counts():
                return {stage: hist.count({"stage": stage})
                        for stage in TCP_STAGES}

            before = counts()
            await tcp_oneshot_raw(
                server.tcp_port,
                make_query("_pg._tcp.svc.foo.com", Type.SRV, qid=3,
                           edns_payload=None).encode())
            await wait_until(
                lambda: counts()["tcp-close"] > before["tcp-close"])
            after = counts()
            stats = server.engine.tcp_stats.snapshot()
            await server.stop()
            return {k: after[k] - before[k] for k in after}, stats

        grew, stats = asyncio.run(run())
        assert grew == {"tcp-accept": 2, "tcp-recv": 2, "tcp-send": 1,
                        "tcp-close": 1}
        assert stats["accepts"] == stats["oneshot_closes"] == 1

    def test_truncated_responses_counts_a_tc_answer_and_nothing_else(self):
        """``binder_truncated_responses{type}``: a UDP answer that left
        with TC=1, by query type; not its retry over TCP, not a UDP
        answer that fit, not an answer EDNS made room for."""
        async def run():
            store, cache = fixture_store()
            # (no query log: the native lanes stand down for a logger
            # that is no JSON stream)
            server = await start_server(cache, query_log=False)
            trunc = server.collector.get("binder_truncated_responses")

            def seen():
                return {t: trunc.value({"type": t})
                        for t in ("A", "SRV", "PTR")}

            steps = [seen()]
            for name, qtype, payload, over_tcp in (
                    ("_pg._tcp.svc.foo.com", Type.SRV, None, False),
                    ("_pg._tcp.svc.foo.com", Type.SRV, None, True),
                    ("svc.foo.com", Type.A, None, False),
                    ("svc.foo.com", Type.A, 1232, False),
                    ("web.foo.com", Type.A, None, False),
                    ("1.0.168.192.in-addr.arpa", Type.PTR, None, False)):
                wire = make_query(name, qtype, qid=9,
                                  edns_payload=payload).encode()
                raw = await (tcp_oneshot_raw(server.tcp_port, wire)
                             if over_tcp
                             else udp_ask_raw(server.udp_port, wire))
                steps.append((bool(raw[2] & 0x02), seen()))
            text = server.collector.expose()
            snap = Introspector(server=server).snapshot()
            # asked often enough, the truncated answer is promoted to
            # the native answer cache, which is keyed by posture and
            # replays it truncated: counted there too
            wire = make_query("_pg._tcp.svc.foo.com", Type.SRV, qid=10,
                              edns_payload=None).encode()
            for _ in range(14):
                raw = await udp_ask_raw(server.udp_port, wire)
                assert raw[2] & 0x02
            server.collector.fold()
            native = 0
            if server._fastpath is not None:
                native = server.collector.get(
                    "binder_answer_cache_hits").value({"tier": "native"})
                assert native >= 1
            repeats = trunc.value({"type": "SRV"})
            await server.stop()
            return steps, text, snap, repeats

        steps, text, snap, repeats = asyncio.run(run())
        assert repeats == 1 + 14
        zero = {"A": 0, "SRV": 0, "PTR": 0}
        assert steps[0] == zero                     # and A, SRV from scrape 1
        assert steps[1] == (True, {**zero, "SRV": 1})    # 40 SRV + glue
        assert steps[2] == (False, {**zero, "SRV": 1})   # its TCP retry
        assert steps[3] == (True, {"A": 1, "SRV": 1, "PTR": 0})
        assert steps[4][0] is False and steps[4][1] == steps[3][1]
        assert steps[5][0] is False and steps[6][0] is False
        assert steps[6][1] == {"A": 1, "SRV": 1, "PTR": 0}
        assert 'binder_truncated_responses{type="SRV"} 1' in text
        assert validate_tcp_metrics(text) == []
        assert snap["tcp"]["udp_truncated"] == 2


class TestChaosStreamFaults:
    def test_dsl_parses_stream_actions(self):
        plan = FaultPlan.parse("""
            at 0.0 tcp-slow-reader conns=2 queries=64 hold_ms=100
            at 0.1 tcp-half-close queries=2
            at 0.2 tcp-rst conns=1
        """)
        assert [a for _, a, _ in plan.timeline] == [
            "tcp-slow-reader", "tcp-half-close", "tcp-rst"]

    def test_driver_soaks_live_server(self):
        """The scripted stream faults run against a live listener; the
        table re-converges to empty and serving never stops."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            plan = FaultPlan.parse(
                "at 0.0 tcp-slow-reader conns=1 queries=32 hold_ms=100;"
                "at 0.05 tcp-half-close queries=2;"
                "at 0.1 tcp-rst conns=2")
            driver = ChaosDriver(
                plan, store=store,
                tcp_target=("127.0.0.1", server.tcp_port,
                            "web.foo.com"))
            await driver.run()
            await driver.stream_quiesce()
            engine = server.engine
            empty = await wait_until(lambda: not engine._tcp_conns)
            r_tcp = Message.decode(await tcp_oneshot_raw(
                server.tcp_port,
                make_query("web.foo.com", Type.A, qid=7).encode()))
            stats = engine.tcp_stats
            await server.stop()
            return empty, r_tcp, stats

        empty, r_tcp, stats = asyncio.run(run())
        assert empty
        assert r_tcp.rcode == Rcode.NOERROR
        # the torn-frame RSTs were shed, not wedged (the half-close
        # fault is served synchronously here, so nothing is ever owed
        # at EOF — that path is pinned by TestRfc7766 with an async
        # handler)
        assert stats.rst_drops >= 1
        assert stats.accepts >= 3

    def test_driver_without_target_skips(self):
        drv = ChaosDriver(FaultPlan())
        # must not raise (and must not wedge waiting for a loop)
        drv.apply("tcp-rst", {})
        assert ("tcp-rst" in [a for _, a in drv.applied])
