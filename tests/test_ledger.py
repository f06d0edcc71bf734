"""The worker's time ledger (ISSUE 24): leaf spans of
``binder_query_stage_seconds`` timed where the work happens, the socket
and log counters beside them, and the loop-lag watchdog's ring of stall
instants.

What this pins:

- the spans are leaves: over a driven burst on a loopback server, UDP
  with one-shot TCP legs among it, ``loop-idle`` plus the named stages
  never exceed the wall time (they would if two of them overlapped)
  and come within 25% of it;
- the stream lane's four (``tcp-accept``, ``tcp-recv``, ``tcp-send``,
  ``tcp-close``) observe once per call;
- ``udp-recv`` counts a ``recvmmsg`` that returns EAGAIN, in
  ``recv_batch`` and in ``fastpath_drain``; a served batch gives one
  ``native-serve`` and one ``udp-send``;
- the C spans fold into the stage histogram by deltas, and a counter
  that stepped back (``io_stats(True)``) is skipped, not folded;
- ``binder_answer_cache_hits`` is split by ``tier`` and its children add
  up to what the one series counted; the whole exposition still passes
  ``tools/lint.py``;
- ``log-write`` / ``log-line`` time the log, and
  ``binder_query_log_bytes`` counts the bytes that reached the stream;
- the watchdog's ring keeps a forced block with its instant, caps at
  256, and ``/status`` carries ``io`` and ``loop.stalls``.
"""
import asyncio
import io
import socket
import struct
import threading
import time

import pytest

from binder_tpu.dns import Type, make_query
from binder_tpu.dns.server import DnsServer
from binder_tpu.dns.stream import TcpConn
from binder_tpu.introspect import Introspector, LoopLagWatchdog
from binder_tpu.introspect import ledger
from binder_tpu.introspect.watchdog import STALL_RING_SIZE
from binder_tpu.metrics.collector import (DEFAULT_STAGE_BUCKETS,
                                          MetricsCollector)
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger
from tests.test_log_ring import byte_stream as log_ring_byte_stream
from tests.test_server import udp_ask
from tools.lint import (validate_exposition, validate_ledger_metrics,
                        validate_status_snapshot)

try:
    from binder_tpu import _binderfastio as fastio
except ImportError:
    fastio = None

needs_native = pytest.mark.skipif(
    fastio is None or not hasattr(fastio, "io_span_grid"),
    reason="native extension with the time ledger not built")

DOMAIN = "foo.com"
HOSTS = 40


def fixture_cache():
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    for i in range(HOSTS):
        store.put_json(f"/com/foo/h{i}", {
            "type": "host", "host": {"address": f"10.0.{i // 250}.{i % 250 + 1}"}})
    store.start_session()
    return cache


async def start_logged_server(stream, **kw):
    log = make_logger("binder-ledger-test", stream=stream)
    server = BinderServer(zk_cache=fixture_cache(), dns_domain=DOMAIN,
                          datacenter_name="coal", host="127.0.0.1",
                          port=0, collector=MetricsCollector(), log=log,
                          query_log=True, **kw)
    await server.start()
    return server


def stage_sums(collector, part="sum"):
    """{stage: seconds or observations} of the stage histogram."""
    collector.fold()
    hist = collector.get("binder_query_stage_seconds")
    out = {}
    for key, cells in hist._counts.items():
        stage = dict(key)["stage"]
        out[stage] = (hist._sums.get(key, 0.0) if part == "sum"
                      else sum(cells))
    return out


def span(name):
    return fastio.io_stats()["spans"][name]


# -- the Python half: the fold of the C spans, the timing selector --

def test_the_selector_times_nothing_before_a_collector_is_installed():
    """A loop is made before its process has a collector: until
    ``install_loop_idle`` the selector waits untimed, and from then on
    every wait lands in the collector's ``loop-idle``."""
    collector = MetricsCollector()

    async def nap():
        selector = asyncio.get_running_loop().ledger_selector
        assert selector.observe is None
        await asyncio.sleep(0.02)
        assert collector.get("binder_query_stage_seconds") is None
        child = ledger.install_loop_idle(collector)
        assert selector.observe == child.observe
        await asyncio.sleep(0.02)

    ledger.run(nap())
    assert stage_sums(collector)["loop-idle"] >= 0.015


def test_span_fold_merges_deltas_once():
    collector = MetricsCollector()
    fold = ledger.SpanFold(collector, "udp-send")
    fold.fold([0, 2, 0], 0.003)
    fold.fold([0, 2, 0], 0.003)    # nothing new: nothing folded twice
    fold.fold([0, 2, 1], 0.007)
    assert sum(fold.child._cells) == 3
    assert stage_sums(collector)["udp-send"] == pytest.approx(0.007)
    assert fold.skipped == 0


def test_span_fold_skips_a_source_that_stepped_back():
    """A negative delta (a test's ``io_stats(True)``) restarts the
    baseline; it is never folded."""
    collector = MetricsCollector()
    fold = ledger.SpanFold(collector, "udp-recv")
    fold.fold([0, 5, 0], 0.5)
    fold.fold([0, 1, 0], 0.1)      # the source was reset in between
    assert fold.skipped == 1
    assert sum(fold.child._cells) == 5
    fold.fold([0, 3, 0], 0.3)      # and grows again from its new base
    assert sum(fold.child._cells) == 7
    assert stage_sums(collector)["udp-recv"] == pytest.approx(0.7)
    assert validate_exposition(collector.expose()) == []


def test_timing_selector_times_the_loops_wait():
    collector = MetricsCollector()

    async def nap():
        ledger.install_loop_idle(collector)
        t0 = time.monotonic()
        await asyncio.sleep(0.2)
        return time.monotonic() - t0

    napped = ledger.run(nap())
    assert stage_sums(collector, "count")["loop-idle"] >= 1
    assert 0.15 <= stage_sums(collector)["loop-idle"] <= napped + 0.05


def test_loop_idle_is_exported_in_the_stage_histogram():
    collector = MetricsCollector()

    async def nap():
        assert ledger.install_loop_idle(collector) is not None
        await asyncio.sleep(0.05)

    ledger.run(nap())
    assert stage_sums(collector, "count")["loop-idle"] >= 1
    assert 'stage="loop-idle"' in collector.expose()


def test_a_loop_of_another_make_exports_no_idle_span():
    collector = MetricsCollector()

    async def plain():
        return ledger.install_loop_idle(collector)

    assert asyncio.run(plain()) is None
    assert collector.get("binder_query_stage_seconds") is None


# -- the C half: the socket calls and the serve loop --

def bound_udp():
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.setblocking(False)
    return sock


@needs_native
def test_udp_recv_counts_an_eagain_call_of_recv_batch():
    fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
    sock = bound_udp()
    try:
        before, calls = span("udp-recv"), fastio.io_stats()["recv_calls"]
        assert fastio.recv_batch(sock.fileno(), 64) == []
        after = span("udp-recv")
        assert after["count"] == before["count"] + 1
        assert after["sum"] > before["sum"]
        assert sum(after["cells"]) == after["count"]
        # ... and it is no call that "returned any"
        assert fastio.io_stats()["recv_calls"] == calls
    finally:
        sock.close()


@needs_native
def test_udp_recv_counts_an_eagain_call_of_the_drain():
    fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
    cache = fastio.fastpath_new(16, 60000, [0.001, 1.0], [512.0])
    sock = bound_udp()
    try:
        recv, serve = span("udp-recv"), span("native-serve")
        assert fastio.fastpath_drain(cache, sock.fileno(), 1, 64) == (
            [], 0, 0, 0)
        assert span("udp-recv")["count"] == recv["count"] + 1
        # no datagram, no batch: the serve loop did not run
        assert span("native-serve")["count"] == serve["count"]
    finally:
        sock.close()


@needs_native
def test_send_batch_times_each_sendmmsg():
    fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
    rx, tx = bound_udp(), bound_udp()
    try:
        before = span("udp-send")
        sent = fastio.send_batch(tx.fileno(), [
            (b"x" * 20, rx.getsockname()) for _ in range(5)])
        after = span("udp-send")
        assert sent == 5
        assert after["count"] == before["count"] + 1
        assert after["sum"] > before["sum"]
        got = fastio.recv_batch(rx.fileno(), 64)
        assert len(got) == 5
    finally:
        rx.close()
        tx.close()


@needs_native
def test_the_same_grid_keeps_the_cells_and_another_restarts_them():
    fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
    sock = bound_udp()
    try:
        fastio.recv_batch(sock.fileno(), 64)
        count = span("udp-recv")["count"]
        assert count >= 1
        fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
        assert span("udp-recv")["count"] == count
        fastio.io_span_grid([0.001, 0.01])
        assert span("udp-recv") == {"sum": 0.0, "count": 0,
                                    "cells": [0, 0, 0]}
        with pytest.raises(ValueError):
            fastio.io_span_grid([0.01, 0.001])
    finally:
        fastio.io_span_grid(list(DEFAULT_STAGE_BUCKETS))
        sock.close()


# -- a served burst: leaves that add up, counters that agree --

def tcp_oneshot(port, wire):
    """One query over a connection of its own, as a truncation retry
    makes it: connect, send, read the answer, close."""
    with socket.create_connection(("127.0.0.1", port), timeout=2.0) as s:
        s.sendall(struct.pack(">H", len(wire)) + wire)
        buf = b""
        while len(buf) < 2 or len(buf) < 2 + int.from_bytes(buf[:2], "big"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return buf[2:]


def drive_burst(port, n, pause_s, tcp_port=None):
    """n A queries from a thread with blocking sockets: host names in
    turn, which the C lanes serve, and every fifth a name of its own
    that the zone lacks, which only the Python lanes can refuse; with
    *tcp_port* every seventh goes over a one-shot TCP connection;
    returns the answers received."""
    got = 0
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.settimeout(2.0)
    try:
        for i in range(n):
            name = f"nx{i}" if i % 5 == 4 else f"h{i % HOSTS}"
            wire = make_query(f"{name}.{DOMAIN}", Type.A,
                              qid=1 + i % 60000).encode()
            if tcp_port is not None and i % 7 == 6:
                got += 1 if tcp_oneshot(tcp_port, wire) else 0
                continue
            sock.sendto(wire, ("127.0.0.1", port))
            try:
                sock.recvfrom(4096)
                got += 1
            except socket.timeout:
                pass
            if pause_s:
                time.sleep(pause_s)
    finally:
        sock.close()
    return got


@needs_native
def test_leaf_spans_do_not_overlap_and_cover_the_wall_time():
    """Idle plus every named stage stays under the wall time of the
    burst (two overlapping spans would push it over) and within 25% of
    it: what is left is the loop's own Python."""
    async def run():
        server = await start_logged_server(io.StringIO())
        idle_fold = ledger.install_loop_idle(server.collector)
        assert idle_fold is not None
        try:
            await asyncio.sleep(0.05)
            base, t0 = stage_sums(server.collector), time.monotonic()
            got = await asyncio.get_running_loop().run_in_executor(
                None, drive_burst, server.udp_port, 600, 0.0005,
                server.tcp_port)
            wall = time.monotonic() - t0
            now = stage_sums(server.collector)
            return got, wall, {k: v - base.get(k, 0.0)
                               for k, v in now.items()}
        finally:
            await server.stop()

    got, wall, grew = ledger.run(run())
    assert got == 600
    for stage in ledger.LEAF_STAGES:
        assert grew.get(stage, 0.0) > 0.0, (stage, grew)
    overlay = ("await", "upstream", "upstream-rtt", "loop-wait")
    named = sum(v for k, v in grew.items() if k not in overlay)
    assert named <= wall * 1.001, (named, wall, grew)
    assert named >= 0.75 * wall, (named, wall, grew)


@needs_native
def test_socket_counters_agree_with_the_spans_and_the_lint_catalog():
    async def run():
        server = await start_logged_server(io.StringIO())
        ledger.install_loop_idle(server.collector)
        try:
            server.collector.fold()
            before = stage_sums(server.collector, "count")
            dgrams = server.collector.get("binder_udp_datagrams")
            was_in = dgrams.value({"dir": "in"})
            was_out = dgrams.value({"dir": "out"})
            for i in range(12):
                r = await udp_ask(server.udp_port, f"h{i % 3}.{DOMAIN}",
                                  Type.A, qid=100 + i)
                assert r.answers
            text = server.collector.expose()
            after = stage_sums(server.collector, "count")
            assert dgrams.value({"dir": "in"}) - was_in == 12
            assert dgrams.value({"dir": "out"}) - was_out == 12
            batches = server.collector.get("binder_udp_batch_size")
            assert batches.count() >= 1
            # a datagram at a time here: a recvmmsg and a sendmmsg each
            assert after["udp-recv"] - before["udp-recv"] >= 12
            assert after["udp-send"] - before["udp-send"] == 12
            assert after["native-serve"] - before["native-serve"] >= 1
            return text
        finally:
            await server.stop()

    text = ledger.run(run())
    assert validate_ledger_metrics(text) == []
    assert 'binder_udp_batch_size_bucket{le="1"}' in text


@needs_native
def test_a_negative_io_stats_delta_is_skipped_not_folded():
    async def run():
        server = await start_logged_server(io.StringIO())
        try:
            for i in range(6):
                await udp_ask(server.udp_port, f"h1.{DOMAIN}", Type.A,
                              qid=200 + i)
            server.collector.fold()
            dgrams = server.collector.get("binder_udp_datagrams")
            seen = dgrams.value({"dir": "in"})
            recv = stage_sums(server.collector, "count")["udp-recv"]
            assert seen >= 6
            fastio.io_stats(True)          # what test_hostile.py does
            await udp_ask(server.udp_port, f"h1.{DOMAIN}", Type.A, qid=300)
            server.collector.fold()        # the step back: skipped
            assert dgrams.value({"dir": "in"}) == seen
            assert stage_sums(server.collector,
                              "count")["udp-recv"] == recv
            assert server._io_folds["udp-recv"].skipped == 1
            await udp_ask(server.udp_port, f"h1.{DOMAIN}", Type.A, qid=301)
            server.collector.fold()        # and counting goes on
            assert dgrams.value({"dir": "in"}) == seen + 1
            assert validate_exposition(server.collector.expose()) == []
        finally:
            await server.stop()

    asyncio.run(run())


@needs_native
def test_tier_children_add_up_to_the_old_total():
    """One series became two: Python-lane hits are counted where they
    happen, native hits folded from C; their sum is what
    ``native_serve_share``'s subtraction reads."""
    async def run():
        server = await start_logged_server(io.StringIO(),
                                           zone_precompile=False)
        try:
            # zone table off: resolve, a Python hit (promotes), natives
            for i in range(6):
                await udp_ask(server.udp_port, f"h2.{DOMAIN}", Type.A,
                              qid=400 + i)
            text = server.collector.expose()
            hits = server.collector.get("binder_answer_cache_hits")
            python = hits.value({"tier": "python"})
            native = hits.value({"tier": "native"})
            assert python == server.answer_cache.stats()["hits"] == 1
            assert native == fastio.fastpath_stats(
                server._fastpath)["hits"] == 4
            assert hits.total() == python + native == 5
            assert hits.value() == 0            # no unlabelled series
            return text
        finally:
            await server.stop()

    text = asyncio.run(run())
    assert 'binder_answer_cache_hits{tier="python"} 1' in text
    assert 'binder_answer_cache_hits{tier="native"} 4' in text
    assert validate_exposition(text) == []


# -- the query log's two spans --

@needs_native
def test_log_write_times_the_ring_drain_and_counts_its_bytes():
    async def run():
        stream = io.StringIO()
        server = await start_logged_server(stream)
        try:
            start = len(stream.getvalue())
            for i in range(5):
                await udp_ask(server.udp_port, f"h3.{DOMAIN}", Type.A,
                              qid=500 + i)
            server._write_log()
            wrote = len(stream.getvalue()) - start
            counts = stage_sums(server.collector, "count")
            sums = stage_sums(server.collector)
            assert counts["log-write"] >= 1 and sums["log-write"] > 0
            assert counts["log-line"] == 0      # all five served in C
            nbytes = server.collector.get("binder_query_log_bytes")
            assert nbytes.total() == wrote > 0
            # an empty ring is no write
            n = counts["log-write"]
            server._write_log()
            assert stage_sums(server.collector,
                              "count")["log-write"] == n
        finally:
            await server.stop()

    asyncio.run(run())


def text_of(stream):
    """What reached a test's log stream: a StringIO's text, or the
    bytes under a text layer."""
    if isinstance(stream, io.StringIO):
        return stream.getvalue()
    return stream.buffer.getvalue().decode("utf-8")


def byte_stream():
    return log_ring_byte_stream()[0]


@pytest.mark.parametrize("make_stream", [io.StringIO, byte_stream],
                         ids=["logging", "direct"])
def test_log_line_times_a_python_lane_line_outside_its_timers(make_stream):
    """``log-line`` times a Python-lane line once, its render straight
    to bytes or its trip through ``log_event``; it reaches the
    histogram and the byte counter, never the line's own ``timers``."""
    async def run():
        stream = make_stream()
        server = await start_logged_server(stream, cache_size=0)
        try:
            start = len(text_of(stream))
            for i in range(4):
                await udp_ask(server.udp_port, f"h4.{DOMAIN}", Type.A,
                              qid=600 + i)
            lines = text_of(stream)[start:]
            counts = stage_sums(server.collector, "count")
            assert counts["log-line"] == 4
            assert counts["log-after"] == 4
            assert stage_sums(server.collector)["log-line"] > 0
            assert "log-line" not in lines and "log-after" in lines
            assert lines.count('"msg": "DNS query"') == 4
            nbytes = server.collector.get("binder_query_log_bytes")
            assert nbytes.total() == len(lines)
        finally:
            await server.stop()

    asyncio.run(run())


@needs_native
def test_log_lines_by_path_add_up_to_log_line_and_bytes_to_the_stream(
        monkeypatch):
    """Native lines, direct lines and one slow-query warning through
    ``logging`` on one stream: ``binder_query_log_lines{path}`` sums to
    ``log-line``'s count, ``log-write`` carried the first two kinds,
    and ``binder_query_log_bytes`` is the stream's growth."""
    import binder_tpu.server as binder_server

    async def run():
        stream = byte_stream()
        server = await start_logged_server(stream)
        try:
            start = len(text_of(stream))
            for i in range(5):          # the zone table answers in C
                await udp_ask(server.udp_port, f"h7.{DOMAIN}", Type.A,
                              qid=900 + i)
            for i in range(3):          # out of zone: the Python lanes
                await udp_ask(server.udp_port, f"nope{i}.example.com",
                              Type.A, qid=910 + i)
            monkeypatch.setattr(binder_server, "SLOW_QUERY_MS", -1.0)
            await udp_ask(server.udp_port, "slow.example.com", Type.A,
                          qid=920)
            monkeypatch.undo()
            server._write_log()
            lines = text_of(stream)[start:]
            by_path = server.collector.get("binder_query_log_lines")
            direct = by_path.value({"path": "direct"})
            logged = by_path.value({"path": "logging"})
            assert (direct, logged) == (3, 1)
            counts = stage_sums(server.collector, "count")
            assert counts["log-line"] == direct + logged
            assert lines.count('"msg": "DNS query"') == 9
            assert lines.count('"level": 40') == 1
            nbytes = server.collector.get("binder_query_log_bytes")
            assert nbytes.total() == len(lines)
            snap = server.io_introspect()
            assert snap["log_lines"] == 4 and snap["log_lines_direct"] == 3
            text = server.collector.expose()
            assert 'binder_query_log_lines{path="direct"} 3' in text
            assert 'binder_query_log_lines{path="logging"} 1' in text
        finally:
            await server.stop()

    asyncio.run(run())


# -- the stream lane's four spans, the lazy render --

#: what one one-shot leg observes: the accept and the EAGAIN that ends
#: its burst, the frame's recv and the EOF's, one send, one close
TCP_LEG_CALLS = {"tcp-accept": 2, "tcp-recv": 2, "tcp-send": 1,
                 "tcp-close": 1}
LEGS = 8


@pytest.fixture(scope="module")
def tcp_legs():
    """``LEGS`` one-shot TCP legs, one after the other, half of them for
    a name only the Python lanes can refuse (per-query stages) and half
    for a host (the bulk frame serve); what every stage grew by, in
    seconds and in observations, and the wall time beside."""
    def closes(server):
        return stage_sums(server.collector, "count").get("tcp-close", 0)

    async def run():
        server = await start_logged_server(io.StringIO())
        assert ledger.install_loop_idle(server.collector) is not None
        loop = asyncio.get_running_loop()
        try:
            await asyncio.sleep(0.05)
            sums = stage_sums(server.collector)
            counts = stage_sums(server.collector, "count")
            t0 = time.monotonic()
            for i in range(LEGS):
                name = f"nx{i}" if i % 2 else f"h{i}"
                assert await loop.run_in_executor(
                    None, tcp_oneshot, server.tcp_port, make_query(
                        f"{name}.{DOMAIN}", Type.A, qid=900 + i).encode())
                # the leg's EOF lands a turn later; the next leg starts
                # after it, so that every accept ends its own burst
                for _ in range(200):
                    if closes(server) - counts.get("tcp-close", 0) > i:
                        break
                    await asyncio.sleep(0.005)
            wall = time.monotonic() - t0
            return ({k: v - sums.get(k, 0.0) for k, v
                     in stage_sums(server.collector).items()},
                    {k: v - counts.get(k, 0) for k, v
                     in stage_sums(server.collector, "count").items()},
                    wall)
        finally:
            await server.stop()

    return ledger.run(run())


@pytest.mark.parametrize("stage", ledger.TCP_STAGES)
def test_a_tcp_span_observes_once_per_call(tcp_legs, stage):
    sums, counts, _wall = tcp_legs
    assert counts[stage] == TCP_LEG_CALLS[stage] * LEGS
    assert sums[stage] > 0.0


def test_the_tcp_spans_overlap_no_per_query_stage(tcp_legs):
    """The legs' Python-lane queries stamped their stages between the
    lane's crossings: idle, the four spans and every other stage stay
    under the wall time, which a span around the serve would pass."""
    sums, counts, wall = tcp_legs
    assert counts["log-after"] == LEGS // 2     # the refused names
    assert sums["store-lookup"] > 0.0
    overlay = ("await", "upstream", "upstream-rtt", "loop-wait")
    named = sum(v for k, v in sums.items() if k not in overlay)
    assert named <= wall * 1.001, (named, wall, sums)
    assert sum(sums[k] for k in ledger.TCP_STAGES) < wall - sums["loop-idle"]


def service_cache(members):
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/big", {
        "type": "service",
        "service": {"srvce": "_http", "proto": "_tcp", "port": 80}})
    for i in range(members):
        store.put_json(f"/com/foo/big/m{i}", {
            "type": "load_balancer",
            "load_balancer": {"address": f"10.9.{i // 250}.{i % 250 + 1}"}})
    store.start_session()
    return cache


@pytest.mark.parametrize("qname,qtype,members,lazy", [
    # an A set: one record a member
    (f"big.{DOMAIN}", Type.A, 64, False),
    (f"big.{DOMAIN}", Type.A, 65, True),
    # an SRV set with glue: two records a member
    (f"_http._tcp.big.{DOMAIN}", Type.SRV, 32, False),
    (f"_http._tcp.big.{DOMAIN}", Type.SRV, 33, True),
], ids=["A-64", "A-65", "SRV-64", "SRV-66"])
def test_lazy_render_fires_above_64_records_and_not_at_64(
        qname, qtype, members, lazy):
    """A set above ``engine.MAX_SET_RECORDS`` is rendered
    at query time under a stage of its own, in place of ``store-lookup``
    and ``pre-resp``; a set of 64 records keeps those two."""
    async def run():
        server = BinderServer(
            zk_cache=service_cache(members), dns_domain=DOMAIN,
            datacenter_name="coal", host="127.0.0.1", port=0,
            collector=MetricsCollector(), query_log=False,
            zone_precompile=False)
        await server.start()
        try:
            loop = asyncio.get_running_loop()
            wire = make_query(qname, qtype, qid=77).encode()
            answer = await loop.run_in_executor(
                None, tcp_oneshot, server.tcp_port, wire)
            return answer, stage_sums(server.collector, "count")
        finally:
            await server.stop()

    answer, counts = asyncio.run(run())
    assert int.from_bytes(answer[6:8], "big") == members
    if lazy:
        assert counts["lazy-render"] == 1
        assert "store-lookup" not in counts and "pre-resp" not in counts
    else:
        assert "lazy-render" not in counts
        assert counts["store-lookup"] == counts["pre-resp"] == 1


# -- stall instants on the shared clock --

def test_ring_keeps_a_forced_block_with_its_instant():
    async def run():
        dog = LoopLagWatchdog(interval=0.005)
        dog.start()
        await asyncio.sleep(0.03)
        start = time.monotonic()
        time.sleep(0.06)                       # the loop is held
        end = time.monotonic()
        await asyncio.sleep(0.03)
        dog.stop()
        return dog, start, end

    dog, start, end = asyncio.run(run())
    ring = dog.snapshot()["stalls"]
    # the instant is the late wake-up: at the block's end, on the clock
    # every process of the machine shares (a busy machine may add
    # stalls of its own beside the forced one)
    assert any(s["lag_s"] >= 0.05 and start <= s["t_mono"] <= end + 0.02
               for s in ring), ring
    assert dog.snapshot()["stall_events"] == 0      # under 0.25 s


def test_ring_leaves_out_what_is_under_50ms_and_caps_at_256():
    dog = LoopLagWatchdog(collector=MetricsCollector())
    dog._observe(0.049, 10.0)
    assert dog.snapshot()["stalls"] == []
    for i in range(STALL_RING_SIZE + 40):
        dog._observe(0.05 + i * 1e-4, 100.0 + i)
    ring = dog.snapshot()["stalls"]
    assert len(ring) == STALL_RING_SIZE == 256
    assert ring[0]["t_mono"] == 140.0 and ring[-1]["t_mono"] == 395.0
    assert dog.samples == STALL_RING_SIZE + 41     # the histogram's view


@needs_native
def test_status_carries_io_and_the_stall_ring():
    async def run():
        server = await start_logged_server(io.StringIO())
        dog = LoopLagWatchdog(collector=server.collector)
        dog._observe(0.07, time.monotonic())
        intro = Introspector(server=server, watchdog=dog,
                             collector=server.collector)
        try:
            for i in range(3):
                await udp_ask(server.udp_port, f"h5.{DOMAIN}", Type.A,
                              qid=700 + i)
            server._write_log()
            return intro.snapshot()
        finally:
            await server.stop()

    snap = asyncio.run(run())
    assert validate_status_snapshot(snap) == []
    assert snap["io"]["recv_datagrams"] >= 3
    assert snap["io"]["send_datagrams"] >= 3
    assert snap["io"]["recv_calls"] \
        >= snap["io"]["recv_empty"] + 3
    assert sum(snap["io"]["recv_batch_cells"]) >= 3
    assert snap["io"]["log_writes"] >= 1 and snap["io"]["log_bytes"] > 0
    assert [s["lag_s"] for s in snap["loop"]["stalls"]] == [0.07]
    # a snapshot whose ring lost its shape is refused
    snap["loop"]["stalls"] = 1
    assert any("loop.stalls" in e for e in validate_status_snapshot(snap))


def test_bstat_renders_the_io_line_and_the_ring():
    import importlib.machinery
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bin", "bstat")
    loader = importlib.machinery.SourceFileLoader("bstat", path)
    bstat = importlib.util.module_from_spec(
        importlib.util.spec_from_loader("bstat", loader))
    loader.exec_module(bstat)

    async def run():
        server = await start_logged_server(io.StringIO())
        dog = LoopLagWatchdog()
        dog._observe(0.3, time.monotonic())
        intro = Introspector(server=server, watchdog=dog)
        try:
            await udp_ask(server.udp_port, f"h6.{DOMAIN}", Type.A, qid=800)
            return intro.snapshot()
        finally:
            await server.stop()

    text = bstat.render(asyncio.run(run()))
    assert "io: recvmmsg" in text and "query log" in text
    assert "1 instant(s) of 50ms+ kept" in text


def test_scrape_thread_and_loop_fold_without_double_counting():
    """Scrapes run on their own threads beside the 1 Hz fold on the
    loop: two folds of one delta must count it once."""
    collector = MetricsCollector()
    fold = ledger.SpanFold(collector, "udp-recv")
    cells = [0] * (len(DEFAULT_STAGE_BUCKETS) + 1)
    cells[3] = 1000
    threads = [threading.Thread(target=fold.fold, args=(cells, 0.02))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(fold.child._cells) == 1000


# -- ISSUE 37: the event span, query-ingress, tcp-register, CPU seconds --

OVERLAY = ("await", "upstream", "upstream-rtt", "loop-wait")


def event_sums(collector, part="sum"):
    """{lane: seconds or observations} of ``binder_loop_event_seconds``."""
    hist = collector.get(ledger.METRIC_EVENT_HISTOGRAM)
    out = {}
    for key, cells in hist._counts.items():
        lane = dict(key)["lane"]
        out[lane] = (hist._sums.get(key, 0.0) if part == "sum"
                     else sum(cells))
    return out


def grew(now, was):
    return {k: v - was.get(k, 0) for k, v in now.items()}


def reading(collector):
    return (stage_sums(collector), stage_sums(collector, "count"),
            event_sums(collector), event_sums(collector, "count"))


async def settle(server, stage, want, base):
    """Wait until *stage* has grown by *want* observations over *base*
    (a leg's EOF lands a turn after its answer)."""
    for _ in range(400):
        if stage_sums(server.collector, "count").get(stage, 0) \
                - base.get(stage, 0) >= want:
            return
        await asyncio.sleep(0.005)
    raise AssertionError(f"{stage} never grew by {want}")


def test_event_series_and_new_leaves_exist_from_the_first_scrape():
    async def run():
        server = await start_logged_server(io.StringIO())
        try:
            return server.collector.expose()
        finally:
            await server.stop()

    text = ledger.run(run())
    for lane in ledger.EVENT_LANES:
        assert (f'binder_loop_event_seconds_count{{lane="{lane}"}}'
                in text), lane
    for stage in ("tcp-register", "query-ingress"):
        assert (f'binder_query_stage_seconds_count{{stage="{stage}"}}'
                in text), stage
    # the event span is a family of its own: an overlay among the
    # stages would be summed twice by every reader of that histogram
    assert 'stage="event' not in text and 'stage="udp"' not in text


@needs_native
@pytest.mark.parametrize("name,served_by", [("h3", "native-serve"),
                                            ("nx3", "store-lookup")])
def test_an_accept_event_holds_the_legs_first_read_once(name, served_by):
    """A one-shot leg is two ``tcp`` events, the accept's and the EOF
    read's: the first frame is read, served and answered inside the
    accept event (``TcpConn.start``) and observed there alone."""
    async def run():
        server = await start_logged_server(io.StringIO())
        ledger.install_loop_idle(server.collector)
        loop = asyncio.get_running_loop()
        try:
            await asyncio.sleep(0.05)
            sums, counts, ev, evn = reading(server.collector)
            assert await loop.run_in_executor(
                None, tcp_oneshot, server.tcp_port, make_query(
                    f"{name}.{DOMAIN}", Type.A, qid=77).encode())
            await settle(server, "tcp-close", 1, counts)
            now = reading(server.collector)
            return [grew(n, w) for n, w in zip(now, (sums, counts, ev, evn))]
        finally:
            await server.stop()

    sums, counts, ev, evn = ledger.run(run())
    assert evn["tcp"] == 2 and evn["udp"] == 0, evn
    assert counts["tcp-recv"] == 2 and counts["tcp-send"] == 1
    assert counts[served_by] == 1
    inside = sum(sums.get(s, 0.0) for s in
                 ledger.TCP_STAGES + ("tcp-register", served_by))
    assert ev["tcp"] >= inside > 0.0, (ev, sums)


@needs_native
def test_events_hold_their_leaves_and_busy_holds_the_events():
    """Over a UDP burst and then TCP legs on a real server: each lane's
    events hold the leaves observed inside them, the events together
    hold every leaf and per-query stage but ``loop-idle``, and the
    loop's busy time (wall less ``loop-idle``) holds the events.  A
    nested event or a leaf outside every event breaks one of these."""
    async def run():
        server = await start_logged_server(io.StringIO())
        ledger.install_loop_idle(server.collector)
        loop = asyncio.get_running_loop()
        try:
            await asyncio.sleep(0.05)
            base, t0 = reading(server.collector), time.monotonic()
            assert await loop.run_in_executor(
                None, drive_burst, server.udp_port, 300, 0.0005) == 300
            await asyncio.sleep(0.15)   # a turn of the log's flusher
            mid = reading(server.collector)
            counts = mid[1]
            for i in range(LEGS):
                name = f"nx{i}" if i % 2 else f"h{i}"
                assert await loop.run_in_executor(
                    None, tcp_oneshot, server.tcp_port, make_query(
                        f"{name}.{DOMAIN}", Type.A, qid=300 + i).encode())
            await settle(server, "tcp-close", LEGS, counts)
            end, wall = reading(server.collector), time.monotonic() - t0
            return ([grew(n, w) for n, w in zip(mid, base)],
                    [grew(n, w) for n, w in zip(end, mid)],
                    [grew(n, w) for n, w in zip(end, base)], wall)
        finally:
            await server.stop()

    udp, tcp, whole, wall = ledger.run(run())
    # the UDP phase: no TCP event, and the lane holds its leaves
    sums, counts, ev, evn = udp
    assert evn["tcp"] == 0 and evn["udp"] >= 1
    inside = sum(v for k, v in sums.items()
                 if k != "loop-idle" and k not in OVERLAY)
    assert ev["udp"] + ev["deferred"] >= inside > 0.0, (ev, sums)
    assert ev["udp"] >= sums["udp-recv"] + sums["udp-send"]
    # the TCP phase: no UDP event, two events a one-shot leg
    sums, counts, ev, evn = tcp
    assert evn["udp"] == 0 and evn["tcp"] == 2 * LEGS
    assert ev["tcp"] >= sum(sums[s] for s in ledger.TCP_STAGES) \
        + sums["tcp-register"]
    # the whole: busy >= events >= named
    sums, counts, ev, evn = whole
    events = sum(ev.values())
    named = sum(v for k, v in sums.items()
                if k != "loop-idle" and k not in OVERLAY)
    busy = wall - sums["loop-idle"]
    assert busy >= events >= named > 0.0, (busy, events, named)


class DropAll:
    """An RRL that drops (or slips) every UDP packet."""
    SEND, SLIP, DROP = 0, 1, 2

    def __init__(self, verdict):
        self.verdict = verdict

    def decide(self, addr):
        return self.verdict

    def slip_reply(self, data):
        return data[:2] + b"\x82\x00" + data[4:12]

    def note_tcp(self, addr):
        pass


def ingress_cases():
    query = make_query(f"nx1.{DOMAIN}", Type.A, qid=5).encode()
    host = make_query(f"h1.{DOMAIN}", Type.A, qid=6).encode()
    answer = bytearray(query)
    answer[2] |= 0x80       # QR=1: not a query
    return [
        # id, wire, protocol, fastpath_checked, rrl; what it becomes:
        # Python-lane queries, answers metered (C's too), wires sent
        ("python-lane-udp", query, "udp", False, None, 1, 1, 1),
        ("tcp-frame-the-bulk-serve-missed", query, "tcp", True, None,
         1, 1, 1),
        ("tcp-frame-served-by-serve-wire", host, "tcp", False, None,
         0, 1, 1),
        ("rrl-drop", query, "udp", False, DropAll(DropAll.DROP), 0, 0, 0),
        ("rrl-slip", query, "udp", False, DropAll(DropAll.SLIP), 0, 0, 1),
        ("malformed", b"\x00\x07garbage", "udp", False, None, 0, 0, 1),
        ("not-a-query", bytes(answer), "udp", False, None, 0, 0, 0),
    ]


@needs_native
@pytest.mark.parametrize(
    "wire,protocol,checked,rrl,queries,answers,sends",
    [c[1:] for c in ingress_cases()], ids=[c[0] for c in ingress_cases()])
def test_query_ingress_is_observed_once_a_packet_in_handle_raw(
        wire, protocol, checked, rrl, queries, answers, sends):
    """Every packet that reaches ``_handle_raw`` observes the leaf once,
    whether it becomes a query (then the per-query stages follow it) or
    ends there."""
    async def run():
        server = await start_logged_server(io.StringIO())
        try:
            if rrl is not None:
                server.engine.rrl = rrl
            sums, counts = reading(server.collector)[:2]
            latency = server.collector.get("binder_request_latency_seconds")
            was = latency.count({"type": "A"})
            out = []
            t0 = time.monotonic()
            server.engine._handle_raw(wire, ("192.0.2.9", 4000), protocol,
                                      out.append, fastpath_checked=checked)
            took = time.monotonic() - t0
            now = reading(server.collector)[:2]
            return (grew(now[0], sums), grew(now[1], counts), out, took,
                    latency.count({"type": "A"}) - was)
        finally:
            await server.stop()

    sums, counts, out, took, answered = ledger.run(run())
    assert counts["query-ingress"] == 1
    assert len(out) == sends
    # a query is stamped as before: its stages once each, the request
    # latency once, and ingress and stages together inside the call
    assert counts.get("log-after", 0) == queries
    assert answered == answers
    assert counts.get("store-lookup", 0) == queries
    named = sum(v for k, v in sums.items() if k != "loop-idle")
    assert 0.0 < sums["query-ingress"] <= named <= took


@needs_native
def test_a_datagram_the_drain_answered_observes_no_query_ingress():
    async def run():
        server = await start_logged_server(io.StringIO())
        try:
            await udp_ask(server.udp_port, f"h2.{DOMAIN}", Type.A, qid=1)
            counts = stage_sums(server.collector, "count")
            for i in range(6):
                r = await udp_ask(server.udp_port, f"h2.{DOMAIN}", Type.A,
                                  qid=10 + i)
                assert r.answers
            r = await udp_ask(server.udp_port, f"nx9.{DOMAIN}", Type.A, qid=9)
            assert not r.answers
            return grew(stage_sums(server.collector, "count"), counts)
        finally:
            await server.stop()

    counts = ledger.run(run())
    assert counts["native-serve"] >= 6
    assert counts["query-ingress"] == 1     # the refused name alone


def raw_tcp(port, payload, read=True):
    """A connection that sends *payload* as it is and stays until the
    server answers or closes."""
    with socket.create_connection(("127.0.0.1", port), timeout=2.0) as s:
        s.sendall(payload)
        return s.recv(65536) if read else b""


def framed(name, qid):
    wire = make_query(f"{name}.{DOMAIN}", Type.A, qid=qid).encode()
    return struct.pack(">H", len(wire)) + wire


@needs_native
@pytest.mark.parametrize("payload,registers,closes_itself", [
    (framed("h4", 41), 1, False),         # a one-shot leg: its reader
    (framed("h4", 42) + framed("nx4", 43), 1, False),   # two frames, one read
    (b"\x00\x00", 0, True),               # closed inside its accept
], ids=["one-shot", "pipelined-burst", "zero-length-frame"])
def test_tcp_register_counts_the_selector_changes_of_an_open_leg(
        payload, registers, closes_itself):
    """``tcp-register`` is the ``add_reader`` of a leg that is still
    open after its first serve (and a writer's registration after a
    short write); a connection closed inside its accept event registers
    nothing, and the unregistrations at the close are ``tcp-close``'s."""
    async def run():
        server = await start_logged_server(io.StringIO())
        loop = asyncio.get_running_loop()
        try:
            counts = stage_sums(server.collector, "count")
            await loop.run_in_executor(None, raw_tcp, server.tcp_port,
                                       payload, not closes_itself)
            await settle(server, "tcp-close", 1, counts)
            return grew(stage_sums(server.collector, "count"), counts)
        finally:
            await server.stop()

    counts = ledger.run(run())
    assert counts["tcp-register"] == registers
    assert counts["tcp-close"] == 1


def test_a_short_write_registers_and_unregisters_its_writer():
    """A write that goes short arms the writer (one ``tcp-register``)
    and draining it disarms it (another), both outside ``tcp-close``."""
    def read_all(sock, want):
        while want > 0:
            want -= len(sock.recv(1 << 20))

    async def run():
        engine = DnsServer(max_tcp_write_buffer=1 << 20)
        registered = []
        engine.span_register = registered.append
        a, b = socket.socketpair()
        try:
            a.setblocking(False)
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
            loop = asyncio.get_running_loop()
            conn = TcpConn(engine, a, ("127.0.0.1", 1), loop)
            conn.out.append(b"x" * (1 << 19))       # cannot go out whole
            conn.out_nframes = 1
            conn._flush()
            assert conn.writer_on and len(registered) == 1
            await loop.run_in_executor(None, read_all, b, 1 << 19)
            for _ in range(400):
                if not conn.writer_on:
                    break
                await asyncio.sleep(0.005)
            assert not conn.writer_on and len(registered) == 2
            conn.close()
            assert len(registered) == 2     # the close's are tcp-close's
        finally:
            a.close()
            b.close()

    asyncio.run(run())


def test_process_cpu_seconds_are_read_at_a_scrape_and_nowhere_else(
        monkeypatch):
    reads = []
    real = ledger.os.times

    def times():
        reads.append(1)
        return real()

    monkeypatch.setattr(ledger.os, "times", times)
    collector = MetricsCollector()
    ledger.install_process_cpu(collector)
    assert reads == []                      # installing reads nothing
    counter = collector.get(ledger.METRIC_PROCESS_CPU)
    first = collector.expose()
    assert len(reads) == 1
    user1 = counter.value({"mode": "user"})
    system1 = counter.value({"mode": "system"})
    assert validate_exposition(first) == []
    for mode in ("user", "system"):
        assert f'binder_process_cpu_seconds_total{{mode="{mode}"}}' in first
    t0 = time.process_time()
    while time.process_time() - t0 < 0.05:
        sum(range(1000))
    assert len(reads) == 1                  # burning CPU reads nothing
    collector.expose()
    assert len(reads) == 2
    assert counter.value({"mode": "user"}) >= user1 + 0.03
    assert counter.value({"mode": "system"}) >= system1
    assert user1 == pytest.approx(real()[0], abs=0.5)


@needs_native
def test_serving_reads_no_process_cpu(monkeypatch):
    """The CPU counter costs the served path nothing: a burst of
    queries makes no ``os.times()`` call; the scrape after it makes
    one."""
    reads = []
    real = ledger.os.times
    monkeypatch.setattr(ledger.os, "times",
                        lambda: reads.append(1) or real())

    async def run():
        server = await start_logged_server(io.StringIO())
        ledger.install_process_cpu(server.collector)
        try:
            got = await asyncio.get_running_loop().run_in_executor(
                None, drive_burst, server.udp_port, 60, 0.0, server.tcp_port)
            served = len(reads)
            server.collector.expose()
            return got, served, len(reads)
        finally:
            await server.stop()

    got, served, scraped = ledger.run(run())
    assert got == 60 and served == 0 and scraped == 1


def test_an_event_times_the_whole_callback_and_passes_its_result():
    collector = MetricsCollector()
    run = ledger.event(collector, "udp")

    def callback(a, b):
        time.sleep(0.01)
        return a + b

    assert run(callback, 2, 3) == 5
    with pytest.raises(ZeroDivisionError):
        run(lambda: 1 / 0)                  # observed all the same
    assert event_sums(collector, "count") == {"udp": 2}
    assert event_sums(collector)["udp"] >= 0.009
