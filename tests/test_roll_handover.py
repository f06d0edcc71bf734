"""A shard's sockets outlive its incarnations, and a replacement reads them
only once it is filled (ISSUE 45).

Two levels.  *In one process*: two or three ``BinderServer`` objects over
descriptors of ONE bound UDP socket and TCP listener (``dup``: what a worker
inherits from its supervisor), with the startup walk of one of them held
back by the test, so the hand-over's instants can be driven one by one: the
incumbent stops reading (``quiesce``), a query arrives that nobody reads,
the replacement turns filled and answers it.  The parent's ``quiesce`` read
its socket to ``EAGAIN`` and closed it; a datagram the kernel queued after
that went with the socket.  *As processes*: a real two-shard group over a
zone large enough that the fill is chunked, rolled while a client asks at a
fixed pace from several sockets, every answer held to the plain engine
render (a server with every cache and table off, ``resolver/engine.py``)
and to ``benchmark/reference.py``; and a worker killed outright, respawned
onto the same sockets.  Every test has a time limit of its own (``LIMIT_S``
around its coroutine).
"""
import asyncio
import os
import socket
import struct
import sys
import time
import urllib.request

import pytest

from binder_tpu.dns import Message, Type, make_query
from binder_tpu.dns.server import bind_socket_pair
from binder_tpu.main import run as binder_run
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.shard import protocol
from binder_tpu.shard.replica import ReplicaStore
from binder_tpu.shard.supervisor import (ROLL_PHASES, ShardLink,
                                         ShardSupervisor)
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.store.fake import populate_synthetic
from tools.lint import validate_shard_metrics

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import dnswire  # noqa: E402
from reference import Zone, compare  # noqa: E402

DOMAIN = "roll.test"
LIMIT_S = 120.0
#: over ``BinderServer._FILL_INLINE_MAX`` (20,000 mirrored names), so a
#: worker's zone fill is a chunked walk
HOSTS = 20600
ZONE = Zone({"hosts": HOSTS, "racks": 0, "subtree": "zs",
             "services": {"count": 0, "srvce": "_http", "proto": "_tcp",
                          "port": 80, "rank_period": 20,
                          "classes": [{"name": "small",
                                       "members": [2, 6]}]},
             "chaos": {"writes": 0}}, DOMAIN, 45)


def limited(coro):
    async def run():
        return await asyncio.wait_for(coro, LIMIT_S)
    return asyncio.run(run())


def total(collector, name: str) -> float:
    collector.fold()
    return collector.get(name).total()


# -- in one process: the hand-over, instant by instant --

def small_store():
    store = FakeStore()
    populate_synthetic(store, DOMAIN, 64)
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    return cache


def host(i: int, hosts: int = 64) -> str:
    racks = max(1, min(1024, hosts // 512))
    return f"h{i:06d}.r{i % racks:04d}.zs.{DOMAIN}"


def address(i: int) -> str:
    return f"10.{i >> 16}.{(i >> 8) & 255}.{i & 255}"


class Pair:
    """One bound (UDP socket, TCP listener), as a supervisor holds a
    shard's; ``inherit()`` is what an incarnation of the shard gets."""

    def __init__(self):
        self.udp, self.tcp = bind_socket_pair("127.0.0.1", 0,
                                              reuse_port=True)
        self.port = self.udp.getsockname()[1]

    def inherit(self):
        return self.udp.dup(), self.tcp.dup()

    def close(self):
        self.udp.close()
        self.tcp.close()


async def incarnation(pair: Pair, read_when_filled: bool = False,
                      gate: asyncio.Event = None) -> BinderServer:
    """A server on the pair's sockets; with *gate*, its zone fill is a
    walk that ends when the test says so."""
    server = BinderServer(
        zk_cache=small_store(), dns_domain=DOMAIN, host="127.0.0.1",
        port=pair.port, collector=MetricsCollector(), query_log=False,
        sockets=pair.inherit(), read_when_filled=read_when_filled)
    if gate is not None:
        def fill():
            server._zone_fill_task = \
                asyncio.get_running_loop().create_task(gate.wait())
        server._zone_fill = fill
    await server.start()
    return server


async def udp_ask(port: int, i: int, wait: float):
    """One datagram from a socket of its own, no retry: the answer, or
    None after *wait* seconds; the socket stays open for ``udp_answer``."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setblocking(False)
    sock.connect(("127.0.0.1", port))
    sock.send(make_query(host(i), Type.A, qid=i + 1).encode())
    return sock, await udp_answer(sock, wait)


async def udp_answer(sock, wait: float):
    try:
        return await asyncio.wait_for(
            asyncio.get_running_loop().sock_recv(sock, 4096), wait)
    except asyncio.TimeoutError:
        return None


async def tcp_ask(port: int, i: int, wait: float):
    loop = asyncio.get_running_loop()
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setblocking(False)
    await loop.sock_connect(sock, ("127.0.0.1", port))
    wire = make_query(host(i), Type.A, qid=i + 1).encode()
    await loop.sock_sendall(sock, struct.pack(">H", len(wire)) + wire)
    return sock, await tcp_answer(sock, wait)


async def tcp_answer(sock, wait: float):
    data = await udp_answer(sock, wait)
    return None if data is None else data[2:]


def answers_host(data: bytes, i: int) -> bool:
    msg = Message.decode(data)
    return msg.id == i + 1 and [a.address for a in msg.answers] \
        == [address(i)]


@pytest.mark.parametrize("transport", ["udp", "tcp"])
def test_a_query_that_nobody_reads_at_the_hand_over_is_answered(transport):
    """The incumbent has stopped reading and the replacement does not
    read yet: the datagram (the connection) waits in the shard's socket
    (the listener's queue), which nobody closed, and the replacement
    answers it the moment it is filled.  The parent's ``quiesce`` closed
    both sockets here."""
    ask, answer = {"udp": (udp_ask, udp_answer),
                   "tcp": (tcp_ask, tcp_answer)}[transport]

    async def run():
        pair, gate = Pair(), asyncio.Event()
        old = await incarnation(pair)
        new = await incarnation(pair, read_when_filled=True, gate=gate)
        try:
            sock, data = await ask(pair.port, 3, 2.0)
            sock.close()
            assert answers_host(data, 3)        # the incumbent's
            assert await old.engine.quiesce(timeout=1.0) == 0
            # quiesce closed nothing: the incumbent's descriptors are open
            assert all(s.fileno() >= 0 for _, s in old.engine._udp_socks
                       + old.engine._tcp_listeners)
            sock, data = await ask(pair.port, 7, 0.4)
            assert data is None and not new.filled
            assert total(new.collector, "binder_requests_completed") == 0
            gate.set()
            data = await answer(sock, 2.0)
            sock.close()
            assert data is not None and answers_host(data, 7)
            assert new.filled
            assert total(new.collector, "binder_requests_completed") == 1
            assert total(new.collector, "binder_unfilled_serves_total") == 0
            assert total(old.collector, "binder_requests_completed") == 1
        finally:
            await old.stop()
            await new.stop()
            pair.close()

    limited(run())


def test_a_replacement_reads_nothing_before_it_is_filled():
    async def run():
        pair, gate = Pair(), asyncio.Event()
        old = await incarnation(pair)
        new = await incarnation(pair, read_when_filled=True, gate=gate)
        try:
            for i in range(24):
                sock, data = await udp_ask(pair.port, i, 2.0)
                sock.close()
                assert answers_host(data, i)
            sock, data = await tcp_ask(pair.port, 30, 2.0)
            sock.close()
            assert answers_host(data, 30)
            assert total(old.collector, "binder_requests_completed") == 25
            assert total(new.collector, "binder_requests_completed") == 0
            gate.set()
            await asyncio.sleep(0.05)
            assert new.filled
            # both read the shard's sockets now, until the incumbent goes
            await old.engine.quiesce(timeout=1.0)
            for i in range(8):
                sock, data = await udp_ask(pair.port, i, 2.0)
                sock.close()
                assert answers_host(data, i)
            assert total(new.collector, "binder_requests_completed") == 8
            assert total(new.collector, "binder_unfilled_serves_total") == 0
        finally:
            await old.stop()
            await new.stop()
            pair.close()

    limited(run())


def test_a_fresh_worker_serves_through_its_fill_and_counts_it():
    """Nobody else answers for a fresh (or respawned) shard: it reads from
    the start, and what it answered before ``filled`` is counted once."""
    async def run():
        pair, gate = Pair(), asyncio.Event()
        told = []
        fresh = await incarnation(pair, gate=gate)
        fresh.on_filled = lambda: told.append(fresh.filled)
        try:
            assert not fresh.filled
            for i in range(5):
                sock, data = await udp_ask(pair.port, i, 2.0)
                sock.close()
                assert answers_host(data, i)
            assert total(fresh.collector,
                         "binder_unfilled_serves_total") == 5
            gate.set()
            await asyncio.sleep(0.05)
            assert fresh.filled and told == [True]
            for i in range(3):
                sock, data = await udp_ask(pair.port, i, 2.0)
                sock.close()
            assert total(fresh.collector, "binder_requests_completed") == 8
            assert total(fresh.collector,
                         "binder_unfilled_serves_total") == 5
        finally:
            await fresh.stop()
            pair.close()

    limited(run())


def test_a_zone_that_fills_inline_is_filled_before_its_first_query():
    async def run():
        pair = Pair()
        server = await incarnation(pair, read_when_filled=True)
        try:
            assert server.filled
            sock, data = await udp_ask(pair.port, 1, 2.0)
            sock.close()
            assert answers_host(data, 1)
            assert total(server.collector,
                         "binder_unfilled_serves_total") == 0
        finally:
            await server.stop()
            pair.close()

    limited(run())


def test_a_server_of_its_own_binds_as_it_did():
    """Single-process mode (no ``sockets``): the pair is the server's own,
    bound at start and closed at stop."""
    async def run():
        server = BinderServer(
            zk_cache=small_store(), dns_domain=DOMAIN, host="127.0.0.1",
            port=0, collector=MetricsCollector(), query_log=False)
        await server.start()
        try:
            assert server.udp_port == server.tcp_port and server.filled
            sock, data = await udp_ask(server.udp_port, 2, 2.0)
            sock.close()
            assert answers_host(data, 2)
            (_, udp), = server.engine._udp_socks
        finally:
            await server.stop()
        assert udp.fileno() == -1

    limited(run())


# -- the frames, and the supervisor's account of a roll --

def test_the_new_frames_round_trip_and_the_replica_keeps_its_attach():
    frames = [protocol.attach_frame(7, 8, True),
              protocol.stats_frame(10.0, 3, 1, True, 2, filled=True),
              protocol.drained_frame(3, 1)]
    buf = bytearray(b"".join(protocol.encode_frame(f) for f in frames))
    assert protocol.decode_frames(buf) == frames and not buf
    assert frames[1]["filled"] is True
    assert protocol.stats_frame(0, 0, 0, True, 0)["filled"] is False
    a, b = socket.socketpair()
    try:
        replica = ReplicaStore(a, 0)
        replica._apply(frames[0])
        assert replica.attach == {"op": "attach", "udp_fd": 7, "tcp_fd": 8,
                                  "read_when_filled": True}
    finally:
        a.close()
        b.close()


class StubProc:
    pid = 0

    def __init__(self, alive: bool = True):
        self.rc = None if alive else 0

    def poll(self):
        return self.rc

    def terminate(self):
        self.rc = 0

    kill = terminate

    def wait(self, timeout=None):
        return self.rc


def bare_supervisor() -> ShardSupervisor:
    store = FakeStore()
    populate_synthetic(store, DOMAIN, 8)
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    return ShardSupervisor(
        options={"shards": 1, "host": "127.0.0.1", "port": 0,
                 "dnsDomain": DOMAIN, "queryLog": False},
        store=store, cache=cache, collector=MetricsCollector())


def stub_link(sup, worker_frames=()):
    """An in-process link whose worker end has already written
    *worker_frames* (and left)."""
    mine, theirs = socket.socketpair()
    mine.setblocking(False)
    link = ShardLink(0, StubProc(), mine)
    for frame in worker_frames:
        theirs.sendall(protocol.encode_frame(frame))
    theirs.close()
    return link


@pytest.mark.parametrize("frames, served_out, unserved", [
    # its last word: three held at SIGTERM, one still held at the deadline
    ([protocol.drained_frame(3, 1)], 2, 1),
    ([protocol.drained_frame(0, 0)], 0, 0),
    # killed at the deadline, no last word: what its last stats frame
    # held in flight is unserved
    ([protocol.stats_frame(9.0, 1, 1, True, 2, filled=True)], 0, 2),
    ([], 0, 0)])
def test_what_a_drained_incumbent_held_is_counted(frames, served_out,
                                                  unserved):
    async def run():
        sup = bare_supervisor()
        sup._loop = asyncio.get_running_loop()
        link = stub_link(sup, frames)
        await sup._drain_incumbent(link)
        assert link.closed
        assert (sup.roll_inflight, sup.roll_unserved) \
            == (served_out, unserved)
        c = sup.collector
        assert total(c, "binder_shard_roll_inflight_total") == served_out
        assert total(c, "binder_shard_roll_unserved_total") == unserved

    limited(run())


@pytest.mark.parametrize("stats, converges", [
    ({"ready": True, "filled": True}, True),
    ({"ready": True, "filled": False}, False),   # ready is not filled
    ({"ready": False, "filled": True}, False),
    (None, False)])
def test_a_roll_waits_for_filled_with_a_no_progress_window(stats,
                                                           converges):
    async def run():
        sup = bare_supervisor()
        sup.WORKER_QUIET_S = 0.3
        link = stub_link(sup)
        link.hello = {"pid": 1}
        link.stats = stats
        t0 = time.monotonic()
        reason = await sup._wait_converged(link, need_filled=True)
        if converges:
            assert reason is None
        else:
            assert "no progress" in reason
            assert time.monotonic() - t0 >= 0.3
        # a fresh start waits for hello alone, as it did
        assert await sup._wait_converged(link) is None
        link.sock.close()

    limited(run())


# -- as processes --

async def boot(tmpdir: str, shards: int, hosts: int):
    options = {
        "dnsDomain": DOMAIN, "datacenterName": "dc0",
        "host": "127.0.0.1", "port": 0, "queryLog": False,
        "expiry": 60000, "size": 10000,
        "store": {"backend": "fake",
                  "synthetic": {"hosts": hosts, "racks": 0,
                                "subtree": "zs"}},
        "shards": shards,
    }
    return await binder_run(options)


def worker_metrics(sup, shard: int) -> str:
    mport = sup.links[shard].hello["metrics_port"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/metrics", timeout=5) as r:
        return r.read().decode()


def metric(text: str, name: str, label: str = "") -> float:
    """The sum of a family's samples (those that carry *label*)."""
    return sum(float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
               if (line.startswith(name + "{") or line.startswith(name + " "))
               and label in line)


async def plain_server():
    """The plain engine render: a server over the same zone with every
    cache and table off, so each answer is ``resolver/engine.py``'s."""
    store = FakeStore()
    populate_synthetic(store, DOMAIN, HOSTS)
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    server = BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
        host="127.0.0.1", port=0, collector=MetricsCollector(),
        query_log=False, cache_size=0, zone_precompile=False)
    await server.start()
    return server


def test_a_group_rolled_under_a_paced_load_answers_every_query(tmp_path):
    """Two shards over 20,600 names, SIGHUP's roll while six sockets ask
    at a fixed pace with no retry: every query is answered, with the plain
    engine's bytes and the reference's records; both pids change; the
    supervisor's counters and the new workers' say what happened."""
    async def run():
        sup = await boot(str(tmp_path), 2, HOSTS)
        plain = await plain_server()
        loop = asyncio.get_running_loop()
        socks = []
        try:
            pids = {i: sup._pid(i) for i in range(2)}
            for _ in range(6):
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.setblocking(False)
                s.connect(("127.0.0.1", sup.udp_port))
                socks.append(s)
            asked = {}              # qid -> name
            got = {}                # qid -> wire
            rolling = True

            async def listen(s):
                while rolling or len(got) < len(asked):
                    try:
                        data = await asyncio.wait_for(
                            loop.sock_recv(s, 4096), 0.5)
                    except asyncio.TimeoutError:
                        continue
                    got[int.from_bytes(data[:2], "big")] = data

            listeners = [loop.create_task(listen(s)) for s in socks]

            async def pace():
                qid = 0
                while rolling:
                    qid += 1
                    name = ZONE.host_name((qid * 7919) % HOSTS)
                    asked[qid] = name
                    socks[qid % len(socks)].send(
                        make_query(name, Type.A, qid=qid).encode())
                    await asyncio.sleep(0.004)       # 250/s

            pacer = loop.create_task(pace())
            await asyncio.sleep(0.5)
            task = sup.request_roll()
            assert task is not None and await task
            await asyncio.sleep(0.5)
            rolling = False
            await pacer
            deadline = time.monotonic() + 5
            while len(got) < len(asked) and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            for t in listeners:
                t.cancel()
            assert len(asked) > 500
            assert sorted(set(asked) - set(got)) == []      # none lost
            # the plain engine's bytes (modulo the id) and the reference's
            # records, for every answer
            for qid, name in asked.items():
                out = []
                plain.engine._handle_raw(
                    make_query(name, Type.A, qid=qid).encode(),
                    ("127.0.0.9", 4242), "udp", out.append)
                assert got[qid] == out[0], name
                assert compare(dnswire.Answer(got[qid]), name, dnswire.A,
                               ZONE.expected(name, dnswire.A)) == [], name
            for i in range(2):
                assert sup._pid(i) not in (None, pids[i])
            assert sup.rolls == {0: 1, 1: 1} and sup.roll_aborts == 0
            text = sup.collector.expose()
            assert validate_shard_metrics(text) == []
            for phase in ROLL_PHASES:
                assert metric(text, "binder_shard_roll_phase_seconds_count",
                              f'phase="{phase}"') == 2, phase
            assert metric(text, "binder_shard_roll_unserved_total") == 0
            assert sup.roll_unserved == 0
            # the fill is a phase of its own: longer than the hand-over
            assert metric(text, "binder_shard_roll_phase_seconds_sum",
                          'phase="fill"') > 0
            snap = sup.snapshot()["shards"]
            assert snap["roll_unserved"] == 0
            assert all(w["filled"] for w in snap["workers"])
            # a replacement answered nothing before it was filled
            for i in range(2):
                scrape = worker_metrics(sup, i)
                assert metric(scrape,
                              "binder_unfilled_serves_total") == 0, i
                assert metric(scrape, "binder_requests_completed") > 0, i
            events = [e for e in sup.recorder.events()
                      if e.get("type") == "rolling-upgrade"]
            promoted = [e for e in events if e.get("phase") == "promote"]
            assert len(promoted) == 2
            assert all("attach_s" in e and "fill_s" in e for e in promoted)
        finally:
            for s in socks:
                s.close()
            await plain.stop()
            await sup.drain()

    limited(run())


def test_a_killed_worker_is_respawned_onto_the_same_sockets(tmp_path):
    """SIGKILL, no drain: the shard's sockets are the supervisor's, so
    what the kernel queues for the dead worker's share waits for the
    respawn, which reads the very same sockets (one inode) from hello on:
    queries sent while the shard was down, with no retry, are answered."""
    async def run():
        sup = await boot(str(tmp_path), 2, 64)
        socks = []
        try:
            udp, tcp = sup._socks[0]
            pid0 = sup._pid(0)
            assert sup.kill_shard(0) == pid0
            await asyncio.sleep(0.1)
            waiting = []
            for i in range(24):
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.setblocking(False)
                s.connect(("127.0.0.1", sup.udp_port))
                s.send(make_query(host(i), Type.A, qid=i + 1).encode())
                socks.append(s)
                waiting.append(udp_answer(s, 20.0))
            answers = await asyncio.gather(*waiting)
            assert all(a is not None and answers_host(a, i)
                       for i, a in enumerate(answers))
            deadline = time.monotonic() + 15
            while (sup.links.get(0) is None
                   or sup.links[0].hello is None) \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            new = sup._pid(0)
            assert new not in (None, pid0) and sup.respawns[0] == 1
            for sock in (udp, tcp):
                assert os.stat(f"/proc/{new}/fd/{sock.fileno()}").st_ino \
                    == os.fstat(sock.fileno()).st_ino
            assert (udp, tcp) == sup._socks[0]
        finally:
            for s in socks:
                s.close()
            await sup.drain()

    limited(run())
