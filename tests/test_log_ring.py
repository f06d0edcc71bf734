"""Native query-log ring: the fast path serving under per-query logging.

The reference logs every query unconditionally (lib/server.js:537-591);
before round 5 that posture forced the rebuild's native tier to stand
down entirely.  These tests pin the round-5 contract:

- with a JSON logger attached and queryLog on, the native path serves
  (zone + answer-cache) AND every serve produces a complete bunyan-style
  log line on the same stream the Python logger writes to;
- the line shape matches the Python path's for the same event class
  (cached hits log ``cached: true`` + rcode + summaries; zone serves log
  the resolve-shape ``query`` object);
- lanes without a C drain (TCP) log through the same ring;
- without a JSON stream logger the old stand-down gating is unchanged.

And the contract of ISSUE 25, one log writer a readiness event:

- a Python-lane line rendered straight to bytes parses to the object
  JsonFormatter gives for the same query (UDP, TCP, and the slow-query
  warning, which stays on ``logging``);
- every answered query leaves exactly one line, also when the server
  stops or the flusher is cancelled with lines pending;
- a batch's responses are handed to ``send_batch`` before any of its
  log bytes reach the stream, and one write carries the batch's lines;
- a record that goes through ``logging`` does not overtake the lines
  rendered before it;
- any other logger or stream keeps every line on ``logging``.
"""
import asyncio
import io
import json
import logging
import socket
import sys
import threading

import pytest

import binder_tpu.dns.server as dns_server
import binder_tpu.server as binder_server
from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger

try:
    from binder_tpu import _binderfastio as fastio
except ImportError:
    fastio = None

pytestmark = pytest.mark.skipif(
    fastio is None or not hasattr(fastio, "fastpath_log_enable"),
    reason="native extension with log ring not built")

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
    for i in range(3):
        store.put_json(f"/com/foo/svc/lb{i}",
                       {"type": "load_balancer",
                        "load_balancer": {"address": f"10.0.1.{i + 1}"}})
    store.start_session()
    return store, cache


async def start_logged_server(cache, stream, **kw):
    log = make_logger("binder-logring-test", stream=stream)
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="coal", host="127.0.0.1",
                          port=0, collector=MetricsCollector(),
                          log=log, query_log=True, **kw)
    await server.start()
    return server


from tests.test_server import tcp_ask  # shared DNS-ask helpers
from tests.test_server import udp_ask as _server_udp_ask


async def udp_ask(port, name, qtype, qid=4242, payload=1232):
    return await _server_udp_ask(port, name, qtype, payload=payload,
                                 qid=qid)


def log_lines(server, stream):
    server._write_log()
    return [json.loads(ln) for ln in stream.getvalue().splitlines()]


class TestLogRing:
    def test_ring_armed_with_json_logger(self):
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            server = await start_logged_server(cache, stream)
            try:
                assert server._log_ring
                assert server._fastpath_active()
            finally:
                await server.stop()

        asyncio.run(run())

    def test_zone_serve_logs_resolve_shape(self):
        """Cold A query in the logged posture: served natively from the
        precompiled zone AND logged with the resolve-shape line."""
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            server = await start_logged_server(cache, stream)
            try:
                r1 = await udp_ask(server.udp_port, "web.foo.com",
                                   Type.A, qid=100)
                r2 = await udp_ask(server.udp_port, "web.foo.com",
                                   Type.A, qid=101)
                assert r1.rcode == r2.rcode == Rcode.NOERROR
                assert r1.answers[0].address == "192.168.0.1"
                stats = fastio.fastpath_stats(server._fastpath)
                assert stats["zone_hits"] >= 2      # served natively
                assert stats["log_lines"] >= 2      # ...and logged
                lines = log_lines(server, stream)
                qlines = [l for l in lines if l.get("msg") == "DNS query"]
                assert len(qlines) == 2
                for ln, qid in zip(qlines, (100, 101)):
                    assert ln["req_id"] == qid
                    assert ln["client"] == "127.0.0.1"
                    assert ln["port"].endswith("/udp")
                    assert ln["edns"] is True
                    assert ln["rcode"] == "NOERROR"
                    assert ln["query"] == {"srv": None,
                                           "name": "web.foo.com",
                                           "type": "A"}
                    assert ln["answers"] == ["web... A 192.168.0.1"]
                    assert ln["additional"] == []
                    assert ln["level"] == 30
                    assert ln["name"] == "binder-logring-test"
                    assert isinstance(ln["latency"], float)
                    assert "T" in ln["time"] and ln["time"].endswith("Z")
            finally:
                await server.stop()

        asyncio.run(run())

    def test_cached_hit_logs_cached_shape(self):
        """A shape the zone can't serve (out-of-suffix REFUSED): first
        query logs through Python, repeats serve natively from the
        answer cache and log the Python hit-path shape (cached: true)."""
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            server = await start_logged_server(cache, stream)
            try:
                r1 = await udp_ask(server.udp_port, "x.example.com",
                                   Type.A, qid=200)
                # first repeat promotes (r5 promote-on-first-hit); the
                # next repeat is the native one
                await udp_ask(server.udp_port, "x.example.com",
                              Type.A, qid=205)
                r2 = await udp_ask(server.udp_port, "x.example.com",
                                   Type.A, qid=201)
                assert r1.rcode == r2.rcode == Rcode.REFUSED
                stats = fastio.fastpath_stats(server._fastpath)
                assert stats["hits"] >= 1           # native cache hit
                lines = log_lines(server, stream)
                by_id = {l["req_id"]: l for l in lines
                         if l.get("msg") == "DNS query"}
                # first: Python resolve line (has the reason field)
                assert by_id[200]["rcode"] == "REFUSED"
                assert by_id[200]["reason"] == \
                    "not within dns domain suffix"
                # repeat: native line with the hit-path shape
                assert by_id[201]["rcode"] == "REFUSED"
                assert by_id[201]["cached"] is True
                assert by_id[201]["answers"] == []
            finally:
                await server.stop()

        asyncio.run(run())

    def test_tcp_serve_logs_through_ring(self):
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            server = await start_logged_server(cache, stream)
            try:
                r = await tcp_ask(server.tcp_port, "web.foo.com", Type.A,
                                  qid=300, edns_payload=None)
                assert r.rcode == Rcode.NOERROR
                stats = fastio.fastpath_stats(server._fastpath)
                assert stats["zone_hits"] >= 1
                lines = log_lines(server, stream)
                tcp_lines = [l for l in lines
                             if l.get("req_id") == 300]
                assert len(tcp_lines) == 1
                assert tcp_lines[0]["port"].endswith("/tcp")
                assert tcp_lines[0]["edns"] is False
                assert tcp_lines[0]["answers"] == ["web... A 192.168.0.1"]
            finally:
                await server.stop()

        asyncio.run(run())

    def test_srv_zone_serve_logs_rotating_answers(self):
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            server = await start_logged_server(cache, stream)
            try:
                r = await udp_ask(server.udp_port,
                                  "_pg._tcp.svc.foo.com", Type.SRV,
                                  qid=400)
                assert r.rcode == Rcode.NOERROR
                assert len(r.answers) == 3
                lines = log_lines(server, stream)
                srv = [l for l in lines if l.get("req_id") == 400]
                assert len(srv) == 1
                assert srv[0]["query"]["srv"] == "_pg._tcp"
                assert srv[0]["query"]["type"] == "SRV"
                # logged answers must be the exact served rotation
                served = [f"SRV {a.target.split('.')[0]}.svc...:{a.port}"
                          for a in r.answers]
                assert srv[0]["answers"] == served
                assert len(srv[0]["additional"]) == 3
            finally:
                await server.stop()

        asyncio.run(run())

    def test_no_json_logger_keeps_stand_down(self):
        """queryLog on with a non-JSON logger: ring unavailable, the
        fast path stands down exactly as before round 5."""
        async def run():
            store, cache = fixture_store()
            plain = logging.getLogger("binder-logring-plain")
            plain.setLevel(logging.INFO)
            plain.propagate = False
            plain.handlers = [logging.NullHandler()]
            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=0,
                                  collector=MetricsCollector(),
                                  log=plain, query_log=True)
            await server.start()
            try:
                assert not server._log_ring
                assert not server._fastpath_active()
                r1 = await udp_ask(server.udp_port, "web.foo.com", Type.A)
                r2 = await udp_ask(server.udp_port, "web.foo.com", Type.A)
                assert r1.rcode == r2.rcode == Rcode.NOERROR
                stats = fastio.fastpath_stats(server._fastpath)
                assert stats["zone_hits"] == 0
                assert stats["hits"] == 0
            finally:
                await server.stop()

        asyncio.run(run())

    def test_logged_matches_unlogged_wire(self):
        """Differential: the logged posture must serve byte-identical
        answers to the log-off posture (modulo id) for the same store."""
        async def run():
            store, cache = fixture_store()
            stream = io.StringIO()
            logged = await start_logged_server(cache, stream)
            store2, cache2 = fixture_store()
            quiet = BinderServer(zk_cache=cache2, dns_domain=DOMAIN,
                                 datacenter_name="coal",
                                 host="127.0.0.1", port=0,
                                 collector=MetricsCollector(),
                                 query_log=False)
            await quiet.start()
            try:
                for name, qt in (("web.foo.com", Type.A),
                                 ("svc.foo.com", Type.A),
                                 ("_pg._tcp.svc.foo.com", Type.SRV),
                                 ("1.0.168.192.in-addr.arpa", Type.PTR),
                                 ("nope.foo.com", Type.A)):
                    a = await udp_ask(logged.udp_port, name, qt, qid=1)
                    b = await udp_ask(quiet.udp_port, name, qt, qid=1)
                    assert a.rcode == b.rcode, name
                    assert ([type(x).__name__ for x in a.answers]
                            == [type(x).__name__ for x in b.answers]), name
            finally:
                await logged.stop()
                await quiet.stop()

        asyncio.run(run())


# -- one log writer a readiness event (ISSUE 25) --

def byte_stream(**kw):
    """A stream as ``sys.stdout`` is one: a text layer over a binary
    one, whose bytes the test reads back."""
    raw = io.BytesIO()
    kw.setdefault("encoding", "utf-8")
    kw.setdefault("newline", "\n")
    return io.TextIOWrapper(raw, write_through=True, **kw), raw


def query_lines(raw):
    text = raw.getvalue()
    if isinstance(text, bytes):
        text = text.decode("utf-8")
    return [json.loads(ln) for ln in text.splitlines()
            if '"msg": "DNS query"' in ln]


def lines_by_path(server):
    counter = server.collector.get("binder_query_log_lines")
    return (int(counter.value({"path": "direct"})),
            int(counter.value({"path": "logging"})))


async def python_lane_server(stream, **kw):
    """Every query surfaces to the Python lanes: no zone table, no
    answer cache for the native tier to be fed from."""
    store, cache = fixture_store()
    return await start_logged_server(cache, stream, cache_size=0,
                                     zone_precompile=False, **kw)


#: what differs between two askings of one query
VOLATILE = ("time", "latency", "timers", "trace", "port")


@pytest.mark.parametrize("lane", ["udp", "tcp", "slow"])
def test_direct_line_parses_to_the_formatters_object(lane, monkeypatch):
    """The same query against a server whose logger takes bytes and one
    whose logger does not (a StringIO has no binary layer): equal
    objects, key for key in the same order, but for the values that
    differ between any two askings, whose types are equal."""
    if lane == "slow":
        monkeypatch.setattr(binder_server, "SLOW_QUERY_MS", -1.0)

    async def ask(server):
        if lane == "tcp":
            r = await tcp_ask(server.tcp_port, "web.foo.com", Type.A,
                              qid=900)
        else:
            r = await udp_ask(server.udp_port, "web.foo.com", Type.A,
                              qid=900)
        assert r.rcode == Rcode.NOERROR

    async def one(stream, want):
        # one after the other: the two share the logger's name, which
        # the line carries, and make_logger hands it one stream
        server = await python_lane_server(stream)
        try:
            await ask(server)
        finally:
            await server.stop()
        assert lines_by_path(server) == want

    async def run():
        stream, raw = byte_stream()
        # the slow-query warning goes through logging on both
        await one(stream, (0, 1) if lane == "slow" else (1, 0))
        text = io.StringIO()
        await one(text, (0, 1))
        return query_lines(raw), query_lines(text)

    (a,), (b,) = asyncio.run(run())
    assert list(a) == list(b)
    assert a["level"] == b["level"] == (40 if lane == "slow" else 30)
    for key in a:
        if key in VOLATILE:
            assert type(a[key]) is type(b[key]), key
        else:
            assert a[key] == b[key], key
    assert a["port"].endswith("/tcp" if lane == "tcp" else "/udp")
    assert set(a["timers"]) == set(b["timers"]) and a["timers"]
    # microsecond resolution, as datetime.isoformat() gives it
    assert len(a["time"]) == len(b["time"]) == len(
        "2026-01-01T00:00:00.000000Z")
    assert a["time"][10] == "T" and a["time"].endswith("Z")


@pytest.mark.parametrize("how", ["stop", "cancelled-flusher"])
def test_every_answered_query_leaves_one_line(how):
    """Lines that no lane wrote (here: the lanes' writer is taken away
    while the queries are served) are written by ``stop()`` and by the
    flusher's cancel path."""
    n = 7

    async def run():
        stream, raw = byte_stream()
        server = await python_lane_server(stream)
        stopped = False
        try:
            server.engine.log_flush = None
            for i in range(n):
                await udp_ask(server.udp_port, "web.foo.com", Type.A,
                              qid=1000 + i)
            assert lines_by_path(server) == (n, 0)
            assert query_lines(raw) == []        # all pending
            if how == "stop":
                await server.stop()
                stopped = True
            else:
                server._log_flush_task.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await server._log_flush_task
            return query_lines(raw)
        finally:
            if not stopped:
                await server.stop()

    lines = asyncio.run(run())
    assert [ln["req_id"] for ln in lines] == [1000 + i for i in range(n)]


def test_sampled_drain_sends_its_batch_before_one_log_write(monkeypatch):
    """RRL configured, gate open: every eighth drain goes through the
    Python lanes.  Its batch's responses reach ``send_batch`` before
    any of its log bytes reach the stream, and one ``log-write``
    carries the batch's lines."""
    k = 5
    sends = []
    real_send = fastio.send_batch

    async def run():
        stream, raw = byte_stream()

        def send_batch(fd, out):
            sends.append((len(out), len(query_lines(raw))))
            return real_send(fd, out)

        monkeypatch.setattr(dns_server._fastio, "send_batch", send_batch)
        store, cache = fixture_store()
        server = await start_logged_server(
            cache, stream,
            rrl={"responsesPerSecond": 100000, "burst": 100000})
        loop = asyncio.get_running_loop()
        rounds = []
        try:
            assert server._fastpath_active()
            for r in range(2 * server._rrl.FASTPATH_SAMPLE_EVERY):
                sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                sock.setblocking(False)
                sock.connect(("127.0.0.1", server.udp_port))
                # k datagrams queued before the server's reader runs:
                # one readiness event drains them as one batch
                for i in range(k):
                    sock.send(make_query("web.foo.com", Type.A,
                                         qid=100 * r + i).encode())
                before = (lines_by_path(server)[0], len(sends),
                          server.io_introspect()["log_writes"],
                          len(query_lines(raw)))
                for i in range(k):
                    data = await asyncio.wait_for(
                        loop.sock_recv(sock, 4096), 5)
                    assert Message.decode(data).rcode == Rcode.NOERROR
                sock.close()
                await asyncio.sleep(0)
                rounds.append((before, (
                    lines_by_path(server)[0], len(sends),
                    server.io_introspect()["log_writes"],
                    len(query_lines(raw)))))
            return rounds
        finally:
            await server.stop()

    rounds = asyncio.run(run())
    sampled = [(b, a) for b, a in rounds if a[0] - b[0] == k]
    assert len(sampled) == 2, rounds       # every eighth of sixteen
    for before, after in sampled:
        # the whole batch in one send_batch, no line out yet ...
        assert after[1] - before[1] == 1
        assert sends[before[1]] == (k, before[3])
        # ... then one write with the batch's k lines
        assert after[2] - before[2] == 1
        assert after[3] - before[3] == k
    # the other rounds are the native lanes': no direct line, the
    # ring's lines in one write each
    for before, after in rounds:
        if (before, after) not in sampled:
            assert after[0] == before[0]
            assert after[2] - before[2] == 1 and after[3] - before[3] == k


def test_direct_engages_without_the_extension(monkeypatch):
    """The render is read from the logger, not from the extension: the
    plain ``recvfrom`` reader writes its drain's lines in its own
    ``finally`` too."""
    monkeypatch.setattr(binder_server, "_fastio", None)
    monkeypatch.setattr(dns_server, "_fastio", None)

    async def run():
        stream, raw = byte_stream()
        server = await python_lane_server(stream)
        try:
            assert server._fastpath is None and not server._log_ring
            for i in range(3):
                await udp_ask(server.udp_port, "web.foo.com", Type.A,
                              qid=i)
                # answered, so its drain has ended: the line is out
                assert len(query_lines(raw)) == i + 1
            assert lines_by_path(server) == (3, 0)
            assert server.io_introspect()["log_writes"] == 3
        finally:
            await server.stop()

    asyncio.run(run())


def test_a_logging_record_does_not_overtake_pending_lines():
    async def run():
        stream, raw = byte_stream()
        server = await python_lane_server(stream)
        try:
            server.engine.log_flush = None      # nobody writes ...
            await udp_ask(server.udp_port, "web.foo.com", Type.A,
                          qid=1)
            assert query_lines(raw) == []
            # ... until a record of the same logger goes out
            server.log.warning("in between")
            await udp_ask(server.udp_port, "web.foo.com", Type.A,
                          qid=2)
        finally:
            await server.stop()
        return [json.loads(ln) for ln in
                raw.getvalue().decode().splitlines()]

    lines = asyncio.run(run())
    order = [ln.get("req_id", ln["msg"]) for ln in lines
             if ln["msg"] in ("DNS query", "in between")]
    assert order == [1, "in between", 2]


def test_records_of_another_thread_lose_and_tear_no_line():
    """A thread that logs through the same handler writes the pending
    lines too (the handler's filter): under a shortened switch interval
    every query still leaves exactly one whole line."""
    n = 300

    async def run():
        stream, raw = byte_stream()
        server = await python_lane_server(stream)
        stop = threading.Event()
        noise = [0]

        def chatter():
            while not stop.is_set():
                server.log.info("noise")
                noise[0] += 1

        threads = [threading.Thread(target=chatter) for _ in range(3)]
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for i in range(n):
                await udp_ask(server.udp_port, "web.foo.com", Type.A,
                              qid=i)
        finally:
            stop.set()
            for t in threads:
                t.join(10)
            sys.setswitchinterval(was)
            await server.stop()
        assert not any(t.is_alive() for t in threads)
        assert lines_by_path(server) == (n, 0)
        return raw.getvalue().decode(), noise[0]

    text, noise = asyncio.run(run())
    lines = [json.loads(ln) for ln in text.splitlines()]    # none torn
    assert [ln["req_id"] for ln in lines
            if ln["msg"] == "DNS query"] == list(range(n))
    assert sum(ln["msg"] == "noise" for ln in lines) == noise > 0


class ReportsTranslation(io.TextIOWrapper):
    """A text layer that says it translates newlines."""
    newlines = "\r\n"


def _plain_logger(stream):
    plain = logging.getLogger("binder-logring-plain-stream")
    plain.setLevel(logging.INFO)
    plain.propagate = False
    handler = logging.StreamHandler(stream)
    handler.setFormatter(logging.Formatter("%(message)s"))
    plain.handlers = [handler]
    return plain


@pytest.mark.parametrize("kind", ["non-json", "latin-1", "translating",
                                  "no-binary-layer"])
def test_other_loggers_and_streams_stay_on_logging(kind):
    n = 3

    async def run():
        raw = io.BytesIO()
        kw = {}
        if kind == "latin-1":
            stream = io.TextIOWrapper(raw, encoding="latin-1",
                                      write_through=True)
        elif kind == "translating":
            stream = ReportsTranslation(raw, encoding="utf-8",
                                        newline="\r\n",
                                        write_through=True)
        elif kind == "no-binary-layer":
            stream = raw = io.StringIO()
        else:
            stream, raw = byte_stream()
            kw["log"] = _plain_logger(stream)
        store, cache = fixture_store()
        if "log" in kw:
            server = BinderServer(
                zk_cache=cache, dns_domain=DOMAIN, datacenter_name="coal",
                host="127.0.0.1", port=0, collector=MetricsCollector(),
                query_log=True, cache_size=0, zone_precompile=False, **kw)
            await server.start()
        else:
            server = await start_logged_server(
                cache, stream, cache_size=0, zone_precompile=False)
        try:
            for i in range(n):
                await udp_ask(server.udp_port, "web.foo.com", Type.A,
                              qid=i)
            assert server._log_pending == []
        finally:
            await server.stop()
        assert lines_by_path(server) == (0, n)
        text = raw.getvalue()
        return text if isinstance(text, str) else text.decode("latin-1")

    text = asyncio.run(run())
    if kind == "non-json":
        assert text.count("DNS query") == n
    else:
        assert text.count('"msg": "DNS query"') == n
    if kind == "translating":
        assert text.count("\r\n") == text.count("\n") >= n


# -- the ring holds a UDP callback's lines between two writes (ISSUE 46) --

def test_a_callback_of_the_burst_and_63_native_serves_leaves_no_decline(
        monkeypatch):
    """One callback of the batched reader takes at most ``_UDP_BURST``
    + 63 datagrams (a drain starts its last ``recvmmsg`` of 64 with 127
    taken) and writes the log once, behind the last of them.  That many
    native serves of the fixture's longest line (the SRV set, three
    answers and three additional records) leave the ring without a
    decline: every one is C's, and its line is in the one write."""
    burst = dns_server.DnsServer._UDP_BURST
    n = burst + 63
    brought = []
    real_drain = fastio.fastpath_drain

    async def run():
        stream, raw = byte_stream()
        cli = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        cli.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
        cli.setblocking(False)

        def ask(k):
            for _ in range(k):
                cli.send(make_query("_pg._tcp.svc.foo.com", Type.SRV,
                                    qid=len(brought)).encode())

        def fastpath_drain(fp, fd, gen, cap):
            got = real_drain(fp, fd, gen, cap)
            brought.append((len(got[0]), got[1]))
            if len(brought) == 1:
                # behind a first drain of 63 the chain's second finds
                # two full batches: 63 + 64 = 127, then its last 64
                ask(n - 63)
            return got

        monkeypatch.setattr(dns_server._fastio, "fastpath_drain",
                            fastpath_drain)
        store, cache = fixture_store()
        server = await start_logged_server(cache, stream)
        loop = asyncio.get_running_loop()
        try:
            cli.connect(("127.0.0.1", server.udp_port))
            was = fastio.fastpath_stats(server._fastpath)
            writes = server.io_introspect()["log_writes"]
            ask(63)
            answers = []
            for _ in range(n):
                answers.append(await asyncio.wait_for(
                    loop.sock_recv(cli, 4096), 5))
            await asyncio.sleep(0)
            now = fastio.fastpath_stats(server._fastpath)
            return (answers, was, now,
                    server.io_introspect()["log_writes"] - writes,
                    server.engine.udp_chained_drains, raw)
        finally:
            await server.stop()
            cli.close()

    answers, was, now, writes, chained, raw = asyncio.run(run())
    assert brought[:3] == [(0, 63), (0, 64), (0, 64)]   # all C's
    assert chained >= 1 and writes == 1
    assert now["log_declines"] == was["log_declines"]
    assert now["log_lines"] - was["log_lines"] == n
    decoded = [Message.decode(a) for a in answers]
    assert all(m.rcode == Rcode.NOERROR and len(m.answers) == 3
               for m in decoded)
    lines = query_lines(raw)
    assert len(lines) == n
    assert all(ln["query"]["type"] == "SRV" for ln in lines)
    # the ring's size against the callback's bound: the lines of this
    # fixture, and the longest line a native serve can write (512 bytes
    # of prefix, 256 of overhead, a fragment of FP_MAX_FRAG 4,096)
    longest = max(len(ln) + 1 for ln in raw.getvalue().splitlines())
    assert n * longest < 1 << 20
    assert n * (512 + 256 + 4096) < 1 << 20
