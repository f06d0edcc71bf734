"""The post-churn path, held by name (ISSUE 49).

A store mutation has one refill: the synchronous drop of every cached
answer the name's tag reaches (Python answer cache, native answer cache,
zone table), then the zone drain's re-push of the name's zone entry
between loop passes.  The Python lanes' ladder is answer cache, then
resolver; nothing re-renders an answer ahead of its next query.

Held here over real sockets, for six shapes x two EDNS postures x two
transports: ask, mutate the store, ask twice more.  The old answer is
never seen after the mutation; both new answers are what a server with no
cache and no zone table (every answer a resolve) gives for the new data;
and with ``_binderfastio`` built the second is a native serve wherever
the zone table holds the shape, once the zone drain has run.
"""
import asyncio
import socket

import pytest

from binder_tpu.dns import Message, Type, make_query
from binder_tpu.introspect import Introspector
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.server import _fastio as fastio
from binder_tpu.store import FakeStore, MirrorCache
from tests.test_ledger import tcp_oneshot
from tools.lint import validate_status_snapshot

DOMAIN = "foo.com"


def put_host(store, label, addr, **extra):
    store.put_json(f"/com/foo/{label}",
                   dict({"type": "host", "host": {"address": addr}},
                        **extra))


def put_service(store, label, members, srvce="_pg", port=5432):
    store.put_json(f"/com/foo/{label}", {
        "type": "service",
        "service": {"srvce": srvce, "proto": "_tcp", "port": port}})
    for i in range(members):
        put_member(store, label, i, f"10.0.{len(label)}.{i + 1}")


def put_member(store, label, i, addr):
    # a 12-byte label: a member costs 73 bytes of an SRV set with glue,
    # so 512 bytes run out at 7 members
    store.put_json(f"/com/foo/{label}/pod-{i:03d}-aaaa", {
        "type": "load_balancer", "load_balancer": {"address": addr}})


#: shape -> (question, the zone before, the mutation, whether the zone
#: table holds the new answer)
SHAPES = {
    "host-a": (
        ("web.foo.com", Type.A),
        lambda s: put_host(s, "web", "10.1.2.3"),
        lambda s: put_host(s, "web", "10.9.9.9"), True),
    "ptr": (
        ("3.2.1.10.in-addr.arpa", Type.PTR),
        lambda s: put_host(s, "web", "10.1.2.3"),
        lambda s: put_host(s, "web", "10.1.2.3", ttl=55), True),
    "srv-3": (
        ("_pg._tcp.svc.foo.com", Type.SRV),
        lambda s: put_service(s, "svc", 3),
        lambda s: put_member(s, "svc", 0, "10.0.9.9"), True),
    # 7 members pass 512 bytes and so do 8: without an OPT the UDP
    # answer is a TC=1 header before and after
    "srv-tc512": (
        ("_pg._tcp.big.foo.com", Type.SRV),
        lambda s: put_service(s, "big", 7),
        lambda s: put_member(s, "big", 7, "10.0.9.8"), True),
    # the service registers under the asked labels: NXDOMAIN, then a set
    "nxdomain": (
        ("_http._tcp.svc.foo.com", Type.SRV),
        lambda s: put_service(s, "svc", 3),
        lambda s: put_service(s, "svc", 3, srvce="_http", port=80), True),
    # SRV on a host: NOERROR and the SOA, whose TTL is the record's
    "nodata": (
        ("_pg._tcp.web.foo.com", Type.SRV),
        lambda s: put_host(s, "web", "10.1.2.3", ttl=60),
        lambda s: put_host(s, "web", "10.1.2.3", ttl=45), False),
}
POSTURES = {"no-opt": None, "opt1232": 1232}


def seen(wire: bytes):
    """An answer without its id and without the order of its records:
    rotation is the server's to choose, and the zone table spells glue
    owners out where the encoder compresses them."""
    m = Message.decode(wire)

    def rec(r):
        return (type(r).__name__, r.name, r.ttl) + tuple(
            getattr(r, f, None) for f in ("address", "target", "port",
                                          "priority", "weight", "minimum"))
    return (m.rcode, m.tc, m.aa, m.rd, m.ra, len(m.questions),
            sorted(rec(r) for r in m.answers),
            sorted(rec(r) for r in m.authorities),
            sorted(rec(r) for r in m.additionals if hasattr(r, "address")),
            m.edns is not None)


def udp_oneshot(port, wire):
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.settimeout(5.0)
        s.sendto(wire, ("127.0.0.1", port))
        return s.recvfrom(65535)[0]


async def start(load, **kw):
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    load(store)
    store.start_session()
    # query_log off keeps the native lanes armed under a plain logger
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="dc0", host="127.0.0.1", port=0,
                          collector=MetricsCollector(), query_log=False,
                          **kw)
    await server.start()
    return store, server


def native_serves(server) -> int:
    if server._fastpath is None:
        return 0
    stats = fastio.fastpath_stats(server._fastpath)
    return stats["hits"] + stats["zone_hits"]


@pytest.mark.parametrize("transport", ["udp", "tcp"])
@pytest.mark.parametrize("posture", POSTURES)
@pytest.mark.parametrize("shape", SHAPES)
def test_after_a_mutation_the_new_answer_and_only_it(shape, posture,
                                                     transport):
    (qname, qtype), load, mutate, in_zone = SHAPES[shape]
    payload = POSTURES[posture]

    async def run():
        store, server = await start(load)
        # the engine alone: every answer a resolve
        ref_store, ref = await start(load, cache_size=0,
                                     zone_precompile=False)
        loop = asyncio.get_running_loop()

        async def ask(srv, qid):
            wire = make_query(qname, qtype, qid=qid, rd=True,
                              edns_payload=payload).encode()
            if transport == "udp":
                return await loop.run_in_executor(
                    None, udp_oneshot, srv.udp_port, wire)
            return await loop.run_in_executor(
                None, tcp_oneshot, srv.tcp_port, wire)

        try:
            old = await ask(server, 1)
            assert seen(old) == seen(await ask(ref, 1))
            mutate(store)
            mutate(ref_store)
            first = await ask(server, 2)
            # the zone drain has run once its dirty set is empty
            for _ in range(1000):
                if not server._zone_dirty:
                    break
                await asyncio.sleep(0)
            assert not server._zone_dirty
            before = native_serves(server)
            second = await ask(server, 3)
            by_c = native_serves(server) - before
            want = await ask(ref, 4)
            return old, first, second, want, by_c, \
                server._fastpath is not None
        finally:
            await server.stop()
            await ref.stop()

    old, first, second, want, by_c, native = asyncio.run(run())
    assert seen(first) == seen(second) == seen(want)
    truncated = bool(want[2] & 0x02)
    assert truncated == (shape == "srv-tc512" and transport == "udp"
                         and payload is None)
    if truncated:
        # a header, the question and no record: byte for byte, the id
        # apart (the old answer was such a header too)
        assert first[2:] == second[2:] == want[2:]
    else:
        assert seen(old) != seen(want)
    if native:
        # the zone table does not truncate: a datagram its entry does
        # not fit is the Python lanes', whose second sight is their
        # answer cache's
        assert by_c == (1 if in_zone and not truncated else 0)


def test_status_precompile_is_the_one_key_the_harness_reads():
    """``benchmark/run.py`` (``wait_settled``) reads
    ``status["precompile"]["seed_remaining"]`` of every worker and waits
    for 0; nothing else of the section is left (ROADMAP D13)."""
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    put_host(store, "web", "10.1.2.3")
    store.start_session()
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          collector=MetricsCollector(), query_log=False)
    snap = Introspector(server=server).snapshot()
    assert snap["precompile"] == {"seed_remaining": 0}
    assert validate_status_snapshot(snap) == []
    assert validate_status_snapshot(dict(snap, precompile={})) == [
        "precompile: missing 'seed_remaining'"]
