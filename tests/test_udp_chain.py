"""Chained drains of one UDP socket, and the answers a full send buffer
costs (ISSUE 43); the chain's one log write (ISSUE 46).

A *drain* is the socket read (``fastpath_drain`` or, sampled or gated,
``recv_batch``) until a call brings fewer than 64, then the Python
lanes' answers in one ``send_batch``.  A drain that brought
``_UDP_CHAIN_MIN`` datagrams or more is followed by the next in the same
callback.  The chain ends at a drain that brought fewer, once the
callback has taken ``_UDP_BURST`` datagrams, on a socket error and on a
short send, which is retried once and whose rest is counted by lane
(``binder_udp_send_drops_total``).  Whatever ends it, the callback then
writes the query log once: every drain's lines in one write, after the
last drain's answers and before the loop is given back.

The reader runs over real sockets and the real extension: a loopback UDP
socket filled before the callback is called, every call of the
extension recorded in order on its way through; the send cases use an
``AF_UNIX`` datagram pair whose peer does not read.
"""
import asyncio
import importlib.machinery
import importlib.util
import io
import os
import socket
import types

import pytest

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns import server as dns_server
from binder_tpu.dns.server import DnsServer
from binder_tpu.introspect import Introspector
from binder_tpu.introspect.flight_recorder import FlightRecorder
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.policy.rrl import ResponseRateLimiter
from binder_tpu.server import BinderServer
from tests.test_fastpath import (ckey, make_cache, query_pkt,
                                 response_wire)
from tests.test_log_ring import (byte_stream, fixture_store, query_lines,
                                 start_logged_server)
from tools.lint import validate_ledger_metrics, validate_status_snapshot

try:
    from binder_tpu import _binderfastio as fastio
except ImportError:
    fastio = None

pytestmark = pytest.mark.skipif(
    fastio is None or not hasattr(fastio, "io_span_grid"),
    reason="native extension not built")

DOMAIN = "foo.com"
BURST = DnsServer._UDP_BURST
SAMPLE_EVERY = ResponseRateLimiter.FASTPATH_SAMPLE_EVERY
LANES = ("native", "python", "balancer")


class Recorded:
    """The extension's three calls of the reader, each passed on to the
    real one and written down in order: ``("fp"|"py", brought)``,
    ``("send", offered, taken)``; the engine's log write lands in the
    same list as ``("flush",)``.  ``on_send(i)`` runs after the i-th
    ``send_batch`` (``on_recv(i)`` after the i-th receive, for drains
    that C answered whole): where a test feeds the socket between two
    drains."""

    def __init__(self):
        self.calls = []
        self.sends = self.recvs = 0
        self.on_send = None
        self.on_recv = None
        self.recv_error_at = None       # index of the receive that fails
        self.recv_error = OSError(9, "scripted socket error")
        self.scripted = None            # recv_batch hands these out instead

    def receives(self):
        return [c for c in self.calls if c[0] in ("fp", "py")]

    def _maybe_fail(self, lane):
        if self.recv_error_at == len(self.receives()):
            self.calls.append((lane, "error"))
            raise self.recv_error

    def _received(self, lane, brought):
        self.calls.append((lane, brought))
        i, self.recvs = self.recvs, self.recvs + 1
        if self.on_recv is not None:
            self.on_recv(i)

    def recv_batch(self, fd, cap):
        assert cap == 64
        self._maybe_fail("py")
        if self.scripted is not None:
            msgs = self.scripted.pop(0) if self.scripted else []
        else:
            msgs = fastio.recv_batch(fd, cap)
        self._received("py", len(msgs))
        return msgs

    def fastpath_drain(self, fp, fd, gen, cap):
        assert cap == 64
        self._maybe_fail("fp")
        msgs, served, retried, dropped = fastio.fastpath_drain(
            fp, fd, gen, cap)
        self.last_send = (retried, dropped)
        self._received("fp", len(msgs) + served)
        return msgs, served, retried, dropped

    def send_batch(self, fd, out):
        taken = fastio.send_batch(fd, out)
        self.calls.append(("send", len(out), taken))
        i, self.sends = self.sends, self.sends + 1
        if self.on_send is not None:
            self.on_send(i)
        return taken

    def flush(self):
        self.calls.append(("flush",))


def loopback():
    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
    srv.bind(("127.0.0.1", 0))
    srv.setblocking(False)
    cli = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    cli.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
    cli.bind(("127.0.0.1", 0))
    cli.connect(srv.getsockname())
    cli.setblocking(False)
    return srv, cli


def feed(cli, n, tag=b"q"):
    for i in range(n):
        cli.send(tag + b"%04d" % i)


def waiting(sock):
    """Datagrams the socket holds now (they are taken)."""
    got = []
    while True:
        try:
            got.append(sock.recv(65535))
        except BlockingIOError:
            return got


def reader_over(monkeypatch, sock, lane, rrl=None):
    """An engine on its own whose batched reader runs over ``sock``
    through ``Recorded``.  Lane ``fp``: a native cache that holds
    nothing, so ``fastpath_drain`` surfaces every datagram; lane ``py``:
    no fast path, ``recv_batch``.  Every surfaced datagram is answered."""
    rec = Recorded()
    monkeypatch.setattr(dns_server, "_fastio", types.SimpleNamespace(
        recv_batch=rec.recv_batch, send_batch=rec.send_batch,
        fastpath_drain=rec.fastpath_drain))
    engine = DnsServer()
    engine.handled = []

    def handle_raw(data, addr, protocol, send):
        engine.handled.append((data, rrl.sample_cost if rrl else None))
        send(b"answer to " + data)

    engine._handle_raw = handle_raw
    engine.log_flush = rec.flush
    engine.rrl = rrl
    if lane == "fp":
        engine.fastpath = make_cache()
    return engine, engine._batched_udp_reader(sock), rec


def calls_of(drains, lane):
    """What a callback of these drains has to call, in order: each
    drain's receives and its one ``send_batch`` if it brought anything,
    then the callback's one log write."""
    want = []
    for recvs in drains:
        want += [(lane, n) for n in recvs]
        if sum(recvs):
            want.append(("send", sum(recvs), sum(recvs)))
    want.append(("flush",))
    return want


# -- the rule, by what the socket holds when the callback starts --

#: datagrams queued -> the drains of the first callback (the receives
#: of each), and what is left to the next callback
RULE = {
    1: ([[1]], 0),                  # one drain, no empty recvmmsg
    2: ([[2], [0]], 0),
    3: ([[3], [0]], 0),
    63: ([[63], [0]], 0),
    64: ([[64, 0], [0]], 0),        # a full batch is asked behind
    65: ([[64, 1], [0]], 0),
    127: ([[64, 63], [0]], 0),
    128: ([[64, 64]], 0),           # the burst: nothing is started
    129: ([[64, 64]], 1),
    300: ([[64, 64]], 172),
}


@pytest.mark.parametrize("lane", ["fp", "py"])
@pytest.mark.parametrize("queued", sorted(RULE))
def test_the_drains_of_a_callback_by_what_the_socket_holds(
        monkeypatch, queued, lane):
    drains, left = RULE[queued]
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, lane)
    feed(cli, queued)
    on_readable()
    assert rec.calls == calls_of(drains, lane)
    assert engine.udp_chained_drains == len(drains) - 1
    taken = sum(sum(d) for d in drains)
    assert len(engine.handled) == taken == queued - left
    # no recvmmsg was started once the callback had its burst
    before_each = [sum(n for _, n in rec.receives()[:i])
                   for i in range(len(rec.receives()))]
    assert all(b < BURST for b in before_each)
    # every answer is on the wire, every datagram not taken still queued
    assert len(waiting(cli)) == taken
    assert len(waiting(srv)) == left
    assert not engine.log_flush_owed
    srv.close()
    cli.close()


@pytest.mark.parametrize("lane", ["fp", "py"])
def test_a_lone_drains_calls_are_what_they_were(monkeypatch, lane):
    """A callback that holds one drain cannot see ISSUE 46: one receive,
    one ``send_batch``, one log write, in that order."""
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, lane)
    for _ in range(3):
        del rec.calls[:]
        feed(cli, 1)
        on_readable()
        assert rec.calls == [(lane, 1), ("send", 1, 1), ("flush",)]
        assert not engine.log_flush_owed
    assert engine.udp_chained_drains == 0
    assert len(waiting(cli)) == 3
    srv.close()
    cli.close()


def test_what_a_burst_leaves_is_the_next_callbacks(monkeypatch):
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp")
    feed(cli, 300)
    for drains in ([[64, 64]], [[64, 64]], [[44], [0]]):
        del rec.calls[:]
        on_readable()
        assert rec.calls == calls_of(drains, "fp")
    assert engine.udp_chained_drains == 1
    assert len(waiting(cli)) == 300
    srv.close()
    cli.close()


@pytest.mark.parametrize("lane", ["fp", "py"])
@pytest.mark.parametrize("fed,drains,left", [
    # a socket fed while each drain is served: the chain follows it
    # down to the drain that brings one
    ([3, 2, 1], [[3], [2], [1]], 0),
    ([2, 2, 2], [[2], [2], [2], [0]], 0),
    # ... and no further than the burst, counted over the whole chain
    ([60, 60, 60, 60], [[60], [60], [60]], 60),
    ([100, 28, 5], [[64, 36], [28]], 5),
    ([127, 2, 9], [[64, 63], [2]], 9),
], ids=["3-2-1", "2-2-2", "60x4", "100-28-5", "127-2-9"])
def test_a_chain_follows_the_socket_and_stops_at_the_burst(
        monkeypatch, fed, drains, left, lane):
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, lane)
    feed(cli, fed[0])
    rec.on_send = lambda i: i + 1 < len(fed) and feed(cli, fed[i + 1])
    on_readable()
    assert rec.calls == calls_of(drains, lane)
    assert engine.udp_chained_drains == len(drains) - 1
    assert len(waiting(srv)) == left
    srv.close()
    cli.close()


def test_one_packets_exception_costs_neither_the_chain_nor_the_drain(
        monkeypatch):
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp")
    answer = engine._handle_raw

    def handle_raw(data, addr, protocol, send):
        if data == b"q0001":
            raise RuntimeError("a bug on one query")
        answer(data, addr, protocol, send)

    engine._handle_raw = handle_raw
    on_readable = engine._batched_udp_reader(srv)
    feed(cli, 3)
    rec.on_send = lambda i: i == 0 and feed(cli, 2, b"r")
    on_readable()
    assert rec.calls == [("fp", 3), ("send", 2, 2),
                         ("fp", 2), ("send", 2, 2),
                         ("fp", 0), ("flush",)]
    assert sorted(waiting(cli)) == [
        b"answer to q0000", b"answer to q0002",
        b"answer to r0000", b"answer to r0001"]
    srv.close()
    cli.close()


@pytest.mark.parametrize("lane", ["fp", "py"])
def test_a_socket_error_ends_the_chain_and_the_one_log_write_follows_it(
        monkeypatch, lane):
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, lane)
    feed(cli, 3)
    rec.on_send = lambda i: i == 0 and feed(cli, 4)
    rec.recv_error_at = 1
    on_readable()
    assert rec.calls == [(lane, 3), ("send", 3, 3),
                         (lane, "error"), ("flush",)]
    assert not engine.log_flush_owed
    assert len(waiting(srv)) == 4       # the next callback's
    srv.close()
    cli.close()


@pytest.mark.parametrize("lane", ["fp", "py"])
def test_an_exception_that_escapes_a_drain_still_ends_in_the_log_write(
        monkeypatch, lane):
    """Not a socket's ``OSError``, which a drain takes as the chain's
    end, but a bug: it leaves ``on_readable`` as it came, behind the one
    write that carries the lines of the drains that served."""
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, lane)
    feed(cli, 3)
    rec.on_send = lambda i: i == 0 and feed(cli, 4)
    rec.recv_error_at = 1
    rec.recv_error = RuntimeError("a bug in the receive")
    with pytest.raises(RuntimeError, match="a bug in the receive"):
        on_readable()
    assert rec.calls == [(lane, 3), ("send", 3, 3),
                         (lane, "error"), ("flush",)]
    assert not engine.log_flush_owed
    assert len(waiting(cli)) == 3       # the first drain's answers left
    # the reader is whole: the next callback takes what the socket holds
    rec.recv_error_at = None
    del rec.calls[:]
    on_readable()
    assert rec.calls == calls_of([[4], [0]], lane)
    srv.close()
    cli.close()


# -- the limiter's duty cycle counts drains that brought datagrams --

def limiter():
    return ResponseRateLimiter(responses_per_second=1e9, burst=1e9)


def test_every_eighth_drain_of_a_chain_is_the_sampled_one(monkeypatch):
    srv, cli = loopback()
    rrl = limiter()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp", rrl)
    drains = 3 * SAMPLE_EVERY
    feed(cli, 2)
    rec.on_send = lambda i: i + 1 < drains and feed(cli, 2)
    on_readable()                       # 24 drains of 2, one of none
    assert rec.calls.count(("flush",)) == 1 and rec.calls[-1] == ("flush",)
    lanes = [lane for lane, n in rec.receives() if n]
    assert len(lanes) == drains and engine.udp_chained_drains == drains
    assert lanes == ["py" if (i + 1) % SAMPLE_EVERY == 0 else "fp"
                     for i in range(drains)]
    costs = [cost for _, cost in engine.handled]
    assert costs == [float(SAMPLE_EVERY) if (i // 2 + 1) % SAMPLE_EVERY == 0
                     else 1.0 for i in range(2 * drains)]
    srv.close()
    cli.close()


def test_every_eighth_drain_across_callbacks_is_the_sampled_one(
        monkeypatch):
    srv, cli = loopback()
    rrl = limiter()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp", rrl)
    for _ in range(2 * SAMPLE_EVERY + 3):
        feed(cli, 1)
        on_readable()                   # one drain of one, no chain
    assert [lane for lane, _ in rec.receives()] == [
        "py" if (i + 1) % SAMPLE_EVERY == 0 else "fp"
        for i in range(2 * SAMPLE_EVERY + 3)]
    assert engine.udp_chained_drains == 0
    srv.close()
    cli.close()


def test_an_empty_drain_leaves_the_limiters_tick_and_cost(monkeypatch):
    """Callbacks of two datagrams: each is a drain of two and a drain of
    none.  Were the empty ones counted, every fourth callback would be
    sampled and half the samples would find nothing."""
    srv, cli = loopback()
    rrl = limiter()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp", rrl)
    sampled = []
    for i in range(2 * SAMPLE_EVERY):
        del rec.calls[:]
        feed(cli, 2)
        on_readable()
        first, behind = rec.receives()
        assert first[1] == 2 and behind[1] == 0
        sampled.append(first[0] == "py")
        # the empty drain behind a sampled one is the native lanes'
        # again, and the one that would have been sampled and found
        # nothing leaves the cost at 1
        assert rrl.sample_cost == 1.0
    assert sampled == [(i + 1) % SAMPLE_EVERY == 0
                       for i in range(2 * SAMPLE_EVERY)]
    assert [cost for _, cost in engine.handled].count(
        float(SAMPLE_EVERY)) == 4
    srv.close()
    cli.close()


def test_a_gate_that_closes_in_a_sampled_drain_keeps_the_next_out_of_c(
        monkeypatch):
    srv, cli = loopback()
    rrl = limiter()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp", rrl)
    gate = [True]
    engine.fastpath_gate = lambda: gate[0]
    answer = engine._handle_raw

    def handle_raw(data, addr, protocol, send):
        if rrl.sample_cost > 1.0:
            gate[0] = False             # what hot() does to the gate
        answer(data, addr, protocol, send)

    engine._handle_raw = handle_raw
    on_readable = engine._batched_udp_reader(srv)
    drains = SAMPLE_EVERY + 2
    feed(cli, 2)
    rec.on_send = lambda i: i + 1 < drains and feed(cli, 2)
    on_readable()
    lanes = [lane for lane, n in rec.receives()]
    # seven in C, the sampled one, then Python for as long as the gate
    # stays shut: at the lone cost, and no tick is used up meanwhile
    assert lanes == ["fp"] * (SAMPLE_EVERY - 1) + ["py"] * 4
    costs = [cost for _, cost in engine.handled]
    assert costs == [1.0] * 14 + [float(SAMPLE_EVERY)] * 2 + [1.0] * 4
    gate[0] = True
    del rec.calls[:]
    feed(cli, 1)
    on_readable()
    assert rec.receives() == [("fp", 1)]
    srv.close()
    cli.close()


# -- a chain's answers, then its lines in one write: the real server --

def chained_server(monkeypatch, order, fed, fail_at=None):
    """A started, logged server whose extension calls, log write and
    ``on_readable`` each note ``(kind, n, answers the client holds,
    query lines on the stream)`` in ``order``.  The client asks
    ``fed[0]`` queries; each ``fastpath_drain`` that returns is followed
    by the next of ``fed``, so one callback chains them all.  The
    ``fail_at``-th ``fastpath_drain`` raises instead, which is no
    socket's error.  Returns what ``run()`` gives: the answers, the
    chained drains, the stream's lines."""

    async def run():
        stream, raw = byte_stream()
        cli = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        cli.setblocking(False)
        answers = []
        recvs = [0]

        def note(kind, n=None):
            answers.extend(waiting(cli))
            order.append((kind, n, len(answers), len(query_lines(raw))))

        def ask(n):
            for _ in range(n):
                cli.send(make_query("web.foo.com", Type.A,
                                    qid=len(order)).encode())

        def recv_batch(fd, cap):
            msgs = fastio.recv_batch(fd, cap)
            note("recv", len(msgs))
            return msgs

        def fastpath_drain(fp, fd, gen, cap):
            note("recv-starts")
            i, recvs[0] = recvs[0], recvs[0] + 1
            if i == fail_at:
                raise RuntimeError("a bug in the drain")
            got = fastio.fastpath_drain(fp, fd, gen, cap)
            note("recv", len(got[0]) + got[1])
            if i + 1 < len(fed):
                ask(fed[i + 1])
            return got

        monkeypatch.setattr(dns_server, "_fastio", types.SimpleNamespace(
            recv_batch=recv_batch, send_batch=fastio.send_batch,
            fastpath_drain=fastpath_drain))
        real_reader = DnsServer._batched_udp_reader

        def reader(self, sock):
            on_readable = real_reader(self, sock)

            def noted():
                try:
                    on_readable()
                finally:
                    note("returned")
            return noted

        monkeypatch.setattr(DnsServer, "_batched_udp_reader", reader)
        store, cache = fixture_store()
        server = await start_logged_server(cache, stream)
        write_log = server.engine.log_flush

        def flush():
            note("write-starts")
            write_log()
            note("written")

        try:
            server.engine.log_flush = flush
            cli.connect(("127.0.0.1", server.udp_port))
            ask(fed[0])
            want = sum(fed if fail_at is None else fed[:fail_at])
            for _ in range(200):
                await asyncio.sleep(0.01)
                answers.extend(waiting(cli))
                if len(answers) == want and any(
                        kind == "returned" for kind, *_ in order):
                    break
            return (answers, server.engine.udp_chained_drains,
                    query_lines(raw))
        finally:
            server.engine.log_flush = write_log
            await server.stop()
            cli.close()

    return asyncio.run(run())


def first_callback(order):
    """The notes up to the first return of ``on_readable``."""
    kinds = [kind for kind, *_ in order]
    return order[:kinds.index("returned") + 1]


def test_a_chains_answers_all_leave_before_its_one_log_write_and_its_lines_before_the_loop_is_given_back(
        monkeypatch):
    """The real ``send_batch`` / ``fastpath_drain`` and the real
    ``_write_log`` of a started server: no ``recvmmsg`` of the chain
    stands behind a log write; at the callback's one log write the
    client already holds every answer of every drain, the stream none
    of their lines; when ``on_readable`` returns the stream holds them
    all."""
    order = []
    fed = [3, 2, 1]
    answers, chained, lines = chained_server(monkeypatch, order, fed)
    assert len(answers) == len(lines) == sum(fed)
    assert all(Message.decode(a).rcode == Rcode.NOERROR for a in answers)
    assert chained == 2                 # one callback held all three
    callback = first_callback(order)
    seen = [(kind, n) for kind, n, _, _ in callback]
    assert seen == [("recv-starts", None), ("recv", 3),
                    ("recv-starts", None), ("recv", 2),
                    ("recv-starts", None), ("recv", 1),
                    ("write-starts", None), ("written", None),
                    ("returned", None)]
    done = 0
    for i, n in enumerate(fed):
        starts, brought = callback[2 * i:2 * i + 2]
        # the answers of the drains before are with the client at this
        # recvmmsg, which waited for no write: none of their lines is out
        assert starts[2:] == (done, 0)
        assert brought[3] == 0
        done += n
    write_starts, written, returned = callback[-3:]
    assert write_starts[2:] == (done, 0)
    assert written[2:] == returned[2:] == (done, done)


def test_an_exception_mid_chain_leaves_the_earlier_drains_lines_on_the_stream(
        monkeypatch):
    """The third drain of a chain raises what is no socket's error: the
    callback's ``finally`` still writes, and the lines of the two drains
    that served are on the stream when ``on_readable`` is left."""
    order = []
    fed = [3, 2, 4]
    answers, chained, lines = chained_server(monkeypatch, order, fed,
                                             fail_at=2)
    callback = first_callback(order)
    assert [(kind, n) for kind, n, _, _ in callback] == [
        ("recv-starts", None), ("recv", 3),
        ("recv-starts", None), ("recv", 2),
        ("recv-starts", None),
        ("write-starts", None), ("written", None), ("returned", None)]
    write_starts, written, returned = callback[-3:]
    assert write_starts[2:] == (5, 0)
    assert written[2:] == returned[2:] == (5, 5)
    assert sorted(ln["req_id"] for ln in lines[:5]) == sorted(
        Message.decode(a).id for a in answers[:5])


# -- sends: a full buffer is retried once, counted, and ends the chain --

def unix_pair():
    """``ours`` sends into a buffer of six 100-byte datagrams for as
    long as ``peer`` does not read."""
    ours, peer = socket.socketpair(socket.AF_UNIX, socket.SOCK_DGRAM)
    ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
    ours.setblocking(False)
    peer.setblocking(False)
    return ours, peer


def native_drops():
    return fastio.io_stats()["send_drops"]["native"]


def answering_cache():
    cache = make_cache()
    assert fastio.fastpath_put(cache, ckey(), 1, 1,
                               [response_wire(tag=b"T" * 70)])
    return cache


def test_fastpath_drain_retries_a_short_send_once_and_counts_the_rest():
    ours, peer = unix_pair()
    cache = answering_cache()
    for i in range(10):
        peer.send(query_pkt(qid=i))
    was = native_drops()
    sends = fastio.io_stats()["spans"]["udp-send"]["count"]
    misses, served, retried, dropped = fastio.fastpath_drain(
        cache, ours.fileno(), 1)
    assert misses == [] and served == 10
    got = waiting(peer)
    # what the buffer took is with the peer; the rest met EAGAIN, was
    # tried once more, and is counted under the native lane
    assert 0 < len(got) < 10
    assert retried == dropped == 10 - len(got)
    assert native_drops() - was == dropped
    # the sendmmsg that was short, the one that met EAGAIN, the retry
    assert fastio.io_stats()["spans"]["udp-send"]["count"] - sends == 3
    # with room again nothing is retried or dropped
    peer.send(query_pkt(qid=77))
    assert fastio.fastpath_drain(cache, ours.fileno(), 1) == ([], 1, 0, 0)
    assert native_drops() - was == dropped
    assert len(waiting(peer)) == 1
    ours.close()
    peer.close()


def test_the_native_lanes_short_send_ends_the_chain_and_the_next_callback_serves_on(
        monkeypatch):
    ours, peer = unix_pair()
    engine, on_readable, rec = reader_over(monkeypatch, ours, "fp")
    engine.fastpath = answering_cache()
    engine.fastpath_gen = lambda: 1
    engine.recorder = FlightRecorder(capacity=8)
    on_readable = engine._batched_udp_reader(ours)
    was = native_drops()
    for i in range(10):
        peer.send(query_pkt(qid=i))
    # a chain would find these behind the first drain, which C
    # answered whole (no send_batch of the reader's own)
    rec.on_recv = lambda i: i == 0 and [
        peer.send(query_pkt(qid=100 + k)) for k in range(3)]
    on_readable()
    retried, dropped = rec.last_send
    assert rec.calls == [("fp", 10), ("flush",)]    # ten brought: no chain
    assert engine.udp_chained_drains == 0
    assert retried == dropped > 0
    assert native_drops() - was == dropped
    assert engine.udp_send_drops == 0               # the Python lanes' own
    events = [e for e in engine.recorder.events()
              if e["type"] == "udp-send-drop"]
    assert [(e["lane"], e["dropped"]) for e in events] == [
        ("native", dropped)]
    assert len(waiting(peer)) == 10 - dropped
    # the peer has read: the next callback serves what was left, and on
    del rec.calls[:]
    on_readable()
    assert rec.calls == [("fp", 3), ("fp", 0), ("flush",)]
    assert len(waiting(peer)) == 3
    assert native_drops() - was == dropped
    ours.close()
    peer.close()


def test_send_batch_stops_at_a_full_buffer_and_says_how_far_it_got():
    ours, peer = unix_pair()
    out = [(b"a" * 100, None)] * 9
    taken = fastio.send_batch(ours.fileno(), out)
    assert 0 < taken < 9
    assert fastio.send_batch(ours.fileno(), out[taken:]) == 0
    assert len(waiting(peer)) == taken
    assert fastio.send_batch(ours.fileno(), out[taken:]) == 9 - taken
    ours.close()
    peer.close()


def test_the_python_lanes_short_send_is_retried_counted_and_ends_the_chain(
        monkeypatch):
    ours, peer = unix_pair()
    engine, on_readable, rec = reader_over(monkeypatch, ours, "py")
    engine.recorder = FlightRecorder(capacity=8)
    answer = b"a" * 100
    engine._handle_raw = lambda data, addr, protocol, send: send(answer)
    on_readable = engine._batched_udp_reader(ours)
    # the datagrams of a connected peer (the socket pair has no
    # address the extension's receive could name)
    rec.scripted = [[(b"q%d" % i, None) for i in range(9)],
                    [(b"r%d" % i, None) for i in range(3)]]
    on_readable()
    (_, offered, taken), (_, again, none) = [
        c for c in rec.calls if c[0] == "send"]
    assert offered == 9 and 0 < taken < 9
    assert (again, none) == (9 - taken, 0)          # the one retry
    assert rec.calls == [("py", 9), ("send", 9, taken),
                         ("send", 9 - taken, 0), ("flush",)]
    assert engine.udp_send_drops == 9 - taken
    assert engine.udp_chained_drains == 0           # nine brought: no chain
    assert [(e["lane"], e["dropped"]) for e in engine.recorder.events()
            if e["type"] == "udp-send-drop"] == [("python", 9 - taken)]
    assert waiting(peer) == [answer] * taken
    # the next callback serves on, and chains again
    del rec.calls[:]
    on_readable()
    assert rec.calls == [("py", 3), ("send", 3, 3),
                         ("py", 0), ("flush",)]
    assert engine.udp_send_drops == 9 - taken
    assert waiting(peer) == [answer] * 3
    ours.close()
    peer.close()


@pytest.mark.parametrize("retried,dropped", [(3, 0), (3, 3), (5, 2)])
def test_a_retry_that_went_through_still_ends_the_chain(
        monkeypatch, retried, dropped):
    """The buffer was full a moment ago: whether or not the retry got
    the rest out, the callback sends no more before the loop has
    turned."""
    srv, cli = loopback()
    engine, on_readable, rec = reader_over(monkeypatch, srv, "fp")
    engine.recorder = FlightRecorder(capacity=8)
    real = rec.fastpath_drain

    def fastpath_drain(fp, fd, gen, cap):
        msgs, served, _, _ = real(fp, fd, gen, cap)
        return msgs, served, retried, dropped

    monkeypatch.setattr(dns_server._fastio, "fastpath_drain",
                        fastpath_drain)
    on_readable = engine._batched_udp_reader(srv)
    feed(cli, 70)
    on_readable()
    # not even the drain's own second recvmmsg behind a full batch
    assert rec.calls == [("fp", 64), ("send", 64, 64), ("flush",)]
    assert engine.udp_chained_drains == 0
    events = [e for e in engine.recorder.events()
              if e["type"] == "udp-send-drop"]
    assert [e["dropped"] for e in events] == ([dropped] if dropped else [])
    assert len(waiting(srv)) == 6
    srv.close()
    cli.close()


def test_the_balancers_lane_counts_a_full_buffer_and_nothing_else():
    """``fastpath_serve_balancer`` answers hits on the balancer's own
    socket and counts what a full buffer makes it drop under its own
    lane (``io_stats`` names it); a send that fails for its destination
    is skipped as before and is no such drop."""
    ours, peer = unix_pair()
    cache = answering_cache()
    pkt = query_pkt(qid=5)
    hdr = bytes([1, 4, 0]) + bytes([127, 0, 0, 1]) + bytes(12) \
        + (5353).to_bytes(2, "big")
    frame = (len(hdr) + len(pkt)).to_bytes(4, "big") + hdr + pkt
    was = fastio.io_stats()["send_drops"]
    assert set(was) == {"native", "balancer"}
    # no peer address fits an AF_UNIX pair: every send fails for its
    # destination and is skipped, none for a full buffer
    consumed, served, misses = fastio.fastpath_serve_balancer(
        cache, frame * 9, 1, ours.fileno())
    assert (consumed, served, misses) == (9 * len(frame), 9, [])
    assert fastio.io_stats()["send_drops"] == was
    ours.close()
    peer.close()


def test_note_send_drops_counts_the_python_lane_and_limits_its_events():
    engine = DnsServer()
    engine.recorder = FlightRecorder(capacity=8)
    engine.note_send_drops("python", 0)
    assert engine.udp_send_drops == 0 and not engine.recorder.events()
    engine.note_send_drops("python", 3)
    engine.note_send_drops("native", 2)     # C has counted these itself
    engine.note_send_drops("python", 1)     # same window: no second event
    assert engine.udp_send_drops == 4
    events = [e for e in engine.recorder.events()
              if e["type"] == "udp-send-drop"]
    assert [(e["lane"], e["dropped"]) for e in events] == [("python", 3)]
    engine._send_drop_event_last -= engine.LATE_DROP_EVENT_WINDOW_S + 1
    engine.note_send_drops("native", 7)
    assert [e["lane"] for e in engine.recorder.events()
            if e["type"] == "udp-send-drop"] == ["python", "native"]


# -- the counters: exposition, /status, bstat, the lint pins --

def sample(text, name, **labels):
    for line in text.splitlines():
        if line.startswith(name) and all(
                f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.split()[-1])
    return None


def pin_errors(text):
    """The lint's findings about this PR's families (a server that never
    ran a loop lacks leaf stages the same validator asks for)."""
    return [e for e in validate_ledger_metrics(text)
            if "binder_udp_" in e]


def bare_server():
    store, cache = fixture_store()
    return BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                        collector=MetricsCollector(), cache_size=16)


def test_every_series_is_there_from_the_first_scrape():
    text = bare_server().collector.expose()
    assert pin_errors(text) == []
    for lane in LANES:
        assert sample(text, "binder_udp_send_drops_total", lane=lane) == 0
    assert sample(text, "binder_udp_chained_drains_total") == 0


def test_the_scrape_folds_each_lane_once(monkeypatch):
    server = bare_server()
    real = fastio.io_stats
    drops = {"native": 0, "balancer": 0}

    def io_stats(*args):
        return dict(real(*args), send_drops=dict(drops))

    monkeypatch.setattr(fastio, "io_stats", io_stats)
    server.collector.expose()
    server.engine.udp_chained_drains = 5
    server.engine.udp_send_drops = 2
    drops.update(native=3, balancer=4)
    for _ in range(2):                  # a second scrape adds nothing
        text = server.collector.expose()
        assert sample(text, "binder_udp_chained_drains_total") == 5
        assert [sample(text, "binder_udp_send_drops_total", lane=lane)
                for lane in LANES] == [3, 2, 4]
    # a C count that stepped back (a test's io_stats(True)) restarts
    # its baseline and is never folded as negative
    drops.update(native=1)
    text = server.collector.expose()
    assert sample(text, "binder_udp_send_drops_total", lane="native") == 3
    drops.update(native=2)
    text = server.collector.expose()
    assert sample(text, "binder_udp_send_drops_total", lane="native") == 4
    assert pin_errors(text) == []


@pytest.mark.parametrize("family,label", [
    ("binder_udp_chained_drains_total", None),
    ("binder_udp_send_drops_total", None),
    ("binder_udp_send_drops_total", "native"),
    ("binder_udp_send_drops_total", "python"),
    ("binder_udp_send_drops_total", "balancer"),
])
def test_the_lint_pins_the_families_and_the_lanes(family, label):
    text = bare_server().collector.expose()
    needle = family if label is None else f'lane="{label}"'
    cut = "\n".join(ln for ln in text.splitlines()
                    if needle not in ln) + "\n"
    assert any(family in e for e in pin_errors(cut))


def status_of_a_chain():
    async def run():
        store, cache = fixture_store()
        server = await start_logged_server(cache, io.StringIO())
        intro = Introspector(server=server, collector=server.collector)
        cli = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            cli.connect(("127.0.0.1", server.udp_port))
            cli.setblocking(False)
            for i in range(3):          # one drain of three, one of none
                cli.send(make_query("web.foo.com", Type.A, qid=i).encode())
            for _ in range(200):
                await asyncio.sleep(0.01)
                if server.engine.udp_chained_drains:
                    break
            server.engine.note_send_drops("python", 2)
            return intro.snapshot()
        finally:
            cli.close()
            await server.stop()

    return asyncio.run(run())


def test_status_carries_the_chain_and_the_drops():
    snap = status_of_a_chain()
    assert validate_status_snapshot(snap) == []
    assert snap["io"]["recv_chained"] == 1
    assert snap["io"]["send_drops"]["python"] == 2
    assert set(snap["io"]["send_drops"]) == set(LANES)
    # a snapshot that lost a lane is refused
    del snap["io"]["send_drops"]["native"]
    assert any("send_drops" in e for e in validate_status_snapshot(snap))
    del snap["io"]["recv_chained"]
    assert any("recv_chained" in e for e in validate_status_snapshot(snap))


def test_bstat_renders_the_chain_and_the_drops():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bin", "bstat")
    loader = importlib.machinery.SourceFileLoader("bstat", path)
    bstat = importlib.util.module_from_spec(
        importlib.util.spec_from_loader("bstat", loader))
    loader.exec_module(bstat)
    snap = status_of_a_chain()
    assert "1 chained drain(s)" in bstat.render(snap)
    snap["io"]["send_drops"] = {"native": 0, "python": 2, "balancer": 0}
    assert " / 2 (python 2) dropped at a full buffer" in bstat.render(snap)
    snap["io"]["send_drops"] = {"native": 5, "python": 2, "balancer": 1}
    assert " / 8 (native 5, python 2, balancer 1) dropped at a full " \
        "buffer" in bstat.render(snap)
    snap["io"]["send_drops"] = {lane: 0 for lane in LANES}
    assert " / 0 dropped at a full buffer" in bstat.render(snap)


# -- the socket asks for a send buffer beside its receive buffer --

def test_the_udp_socket_asks_for_both_buffers(monkeypatch):
    asked = []
    real = socket.socket.setsockopt

    def setsockopt(self, level, opt, value, *rest):
        asked.append((level, opt, value))
        return real(self, level, opt, value, *rest)

    monkeypatch.setattr(socket.socket, "setsockopt", setsockopt,
                        raising=False)

    async def run():
        engine = DnsServer()
        port = await engine.listen_udp("127.0.0.1", 0, announce=False)
        sock = engine._udp_socks[0][1]
        granted = sock.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF)
        engine.close_udp_listener(port)
        return granted

    granted = asyncio.run(run())
    for opt in (socket.SO_RCVBUF, socket.SO_SNDBUF):
        assert (socket.SOL_SOCKET, opt, 1 << 20) in asked
    with open("/proc/sys/net/core/wmem_max") as f:
        assert granted >= min(1 << 20, int(f.read()))


@pytest.mark.parametrize("refused", ["SO_SNDBUF", "SO_RCVBUF", "both"])
def test_a_refused_buffer_size_is_tolerated(monkeypatch, refused):
    real = socket.socket.setsockopt
    opts = {getattr(socket, name) for name in
            (("SO_SNDBUF", "SO_RCVBUF") if refused == "both"
             else (refused,))}

    def setsockopt(self, level, opt, value, *rest):
        if level == socket.SOL_SOCKET and opt in opts:
            raise OSError(1, "not permitted")
        return real(self, level, opt, value, *rest)

    monkeypatch.setattr(socket.socket, "setsockopt", setsockopt,
                        raising=False)

    async def run():
        engine = DnsServer()
        port = await engine.listen_udp("127.0.0.1", 0, announce=False)
        assert port > 0
        engine.close_udp_listener(port)

    asyncio.run(run())
