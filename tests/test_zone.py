"""Tests for zone precompilation (fpcore.h zone table).

The zone table serves finished answers for the dominant record shapes
(host A, PTR) inside the C UDP drain, filled from the store mirror at
server start and on every mutation — so even the FIRST query for a name
never surfaces to Python.  The reference resolves every cold name per
query (lib/server.js:136).

Layers here:
- differential: every zone-served response must be byte-identical to
  the same server's generic-path response (zonePrecompile off), id
  aside — the zone can never answer differently, only faster;
- coherence: store mutations re-point zone answers through the same
  tag-invalidation path as the caches; deletions fall back to Python;
- policy: shapes the zone table must leave to the Python lanes
  (service records, doubled dnsDomain suffixes, non-IN classes) are
  never zone-served.
"""
import asyncio

import pytest

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache

fastio = pytest.importorskip(
    "binder_tpu._binderfastio",
    reason="fastio extension not built (make -C native)")
if not hasattr(fastio, "fastpath_zone_put"):
    pytest.skip("fastio extension predates the zone table; rebuild",
                allow_module_level=True)

DOMAIN = "foo.com"


def fixture_store():
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/web",
                   {"type": "host", "host": {"address": "192.168.0.1"}})
    store.put_json("/com/foo/ttlhost",
                   {"type": "host", "ttl": 120,
                    "host": {"address": "10.9.9.9", "ttl": 77}})
    store.put_json("/com/foo/svc", {
        "type": "service",
        "service": {"srvce": "_pg", "proto": "_tcp", "port": 5432},
    })
    for i in range(2):
        store.put_json(f"/com/foo/svc/lb{i}",
                       {"type": "load_balancer",
                        "load_balancer": {"address": f"10.0.1.{i + 1}"}})
    store.start_session()
    return store, cache


async def start_server(cache, **kw):
    kw.setdefault("query_log", False)
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
            self.transport = transport
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


def zone_stats(server):
    return fastio.fastpath_stats(server._fastpath)


def _mixed_case(wire: bytes, lower: bytes, mixed: bytes) -> bytes:
    """Patch a query wire with true mixed-case qname bytes — make_query
    normalizes to lowercase, so dns0x20 shapes must be crafted at the
    wire level or the probe is vacuous."""
    assert lower in wire and lower.lower() == mixed.lower()
    return wire.replace(lower, mixed)


PROBES = [
    ("A no-edns", make_query("web.foo.com", Type.A, qid=1,
                             edns_payload=None).encode()),
    ("A rd", make_query("web.foo.com", Type.A, qid=2, rd=True,
                        edns_payload=None).encode()),
    ("A edns", make_query("web.foo.com", Type.A, qid=3,
                          edns_payload=1400).encode()),
    ("A 0x20", _mixed_case(
        make_query("web.foo.com", Type.A, qid=4).encode(),
        b"\x03web\x03foo\x03com", b"\x03WeB\x03fOo\x03CoM")),
    ("A ttl precedence", make_query("ttlhost.foo.com", Type.A,
                                    qid=5).encode()),
    ("PTR", make_query("1.0.168.192.in-addr.arpa", Type.PTR,
                       qid=6).encode()),
    ("PTR 0x20", _mixed_case(
        make_query("9.9.9.10.in-addr.arpa", Type.PTR, qid=7).encode(),
        b"\x07in-addr\x04arpa", b"\x07IN-aDdR\x04ArPa")),
]


class TestZoneDifferential:
    def test_zone_answers_equal_generic_and_never_reach_python(self):
        """Byte-differential: for every probe shape the zone-enabled
        server's FIRST response equals the zone-disabled server's, and
        it really came from the zone (zone_hits advanced, no Python
        resolve counted)."""
        async def run():
            _, cache_on = fixture_store()
            _, cache_off = fixture_store()
            on = await start_server(cache_on)
            off = await start_server(cache_off, zone_precompile=False)
            try:
                for label, wire in PROBES:
                    before = zone_stats(on)["zone_hits"]
                    got = await udp_ask_raw(on.udp_port, wire)
                    want = await udp_ask_raw(off.udp_port, wire)
                    assert got == want, label
                    assert zone_stats(on)["zone_hits"] == before + 1, \
                        (label, "expected a zone serve")
                    if "0x20" in label:
                        # the requester's exact mixed-case bytes echo
                        assert wire[12:24] in got, label
                # and the decoded answer is actually right
                r = Message.decode(
                    await udp_ask_raw(
                        on.udp_port,
                        make_query("web.foo.com", Type.A, qid=9).encode()))
                assert r.rcode == Rcode.NOERROR
                assert r.answers[0].address == "192.168.0.1"
                # deepest-object-wins TTL precedence baked in at push
                r = Message.decode(
                    await udp_ask_raw(
                        on.udp_port,
                        make_query("ttlhost.foo.com", Type.A,
                                   qid=10).encode()))
                assert r.answers[0].ttl == 77
            finally:
                await on.stop()
                await off.stop()

        asyncio.run(run())

    def test_shapes_the_zone_declines_are_not_zone_served(self):
        """Negative SRV shapes and missing names go through Python; the
        zone table must not touch them.  A type the engine declines by
        the type alone has no entry either: it is the type row's
        (tests/test_type_row.py), counted apart from the entries'."""
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache)
            try:
                probes = (
                    # SRV with the WRONG srvce/proto: NXDOMAIN (engine)
                    make_query("_wrong._tcp.svc.foo.com", Type.SRV,
                               qid=21),
                    # SRV on a non-service name we own: NODATA + SOA
                    make_query("_pg._tcp.web.foo.com", Type.SRV, qid=22),
                    make_query("absent.foo.com", Type.A, qid=23),
                )
                for q in probes:
                    before = zone_stats(server)["zone_hits"]
                    resp = Message.decode(
                        await udp_ask_raw(server.udp_port, q.encode()))
                    assert zone_stats(server)["zone_hits"] == before, \
                        q.questions[0]
                    assert resp.id == q.id
                before = zone_stats(server)
                q = make_query("web.foo.com", Type.AAAA, qid=24)
                resp = Message.decode(
                    await udp_ask_raw(server.udp_port, q.encode()))
                assert (resp.id, resp.rcode) == (q.id, Rcode.NOTIMP)
                after = zone_stats(server)
                assert after["zone_type_hits"] == \
                    before["zone_type_hits"] + 1
                assert after["zone_hits"] == before["zone_hits"] + 1
                assert after["zone_entries"] == before["zone_entries"]
            finally:
                await server.stop()

        asyncio.run(run())

    def test_srv_zone_served_content_equals_generic(self):
        """The registered SRV qname is precompiled — answers (per member
        per port, service TTL) and A additionals (member TTL) equal the
        generic path's in content; EDNS queries get the OPT appended as
        the last additional."""
        async def run():
            _, cache_on = fixture_store()
            _, cache_off = fixture_store()
            on = await start_server(cache_on)
            off = await start_server(cache_off, zone_precompile=False)
            try:
                def shape(r):
                    srv = sorted((a.name, a.ttl, a.priority, a.weight,
                                  a.port, a.target) for a in r.answers)
                    add = sorted((a.name, a.ttl, a.address)
                                 for a in r.additionals
                                 if hasattr(a, "address"))
                    return r.rcode, srv, add

                for qid, kw in ((31, {"edns_payload": None}),
                                (32, {"edns_payload": 1400})):
                    q = make_query("_pg._tcp.svc.foo.com", Type.SRV,
                                   qid=qid, **kw)
                    before = zone_stats(on)["zone_hits"]
                    got = Message.decode(
                        await udp_ask_raw(on.udp_port, q.encode()))
                    want = Message.decode(
                        await udp_ask_raw(off.udp_port, q.encode()))
                    assert zone_stats(on)["zone_hits"] == before + 1, kw
                    assert shape(got) == shape(want), kw
                    assert len(got.answers) == 2
                    assert {a.target for a in got.answers} == \
                        {"lb0.svc.foo.com", "lb1.svc.foo.com"}
            finally:
                await on.stop()
                await off.stop()

        asyncio.run(run())

    def test_srv_member_mutation_repoints_through_alien_table(self):
        """SRV entries are tagged with the service NODE name (not their
        qname): a member mutation's parent tag must drop and re-push
        them through the C side's alien-table scan."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("_pg._tcp.svc.foo.com", Type.SRV,
                               qid=41).encode()))
                assert len(r.answers) == 2
                store.put_json("/com/foo/svc/lb9",
                               {"type": "load_balancer",
                                "load_balancer": {"address": "10.0.1.9",
                                                  "ports": [100, 200]}})
                await asyncio.sleep(0)
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("_pg._tcp.svc.foo.com", Type.SRV,
                               qid=42).encode()))
                assert zone_stats(server)["zone_hits"] == before + 1
                # 2 original members (1 port each) + new member x2 ports
                assert len(r.answers) == 4
                ports = {a.port for a in r.answers
                         if a.target == "lb9.svc.foo.com"}
                assert ports == {100, 200}
            finally:
                await server.stop()

        asyncio.run(run())

    def test_service_a_rotation_zone_served(self):
        """Service plain-A answers come precompiled: full member set per
        answer (content equal to the generic path's), served natively,
        rotating so every member leads over repeated queries."""
        async def run():
            _, cache_on = fixture_store()
            _, cache_off = fixture_store()
            on = await start_server(cache_on)
            off = await start_server(cache_off, zone_precompile=False)
            try:
                def addrsets(r):
                    return sorted((a.address, a.ttl) for a in r.answers)

                want = Message.decode(await udp_ask_raw(
                    off.udp_port,
                    make_query("svc.foo.com", Type.A, qid=90).encode()))
                leads = set()
                for i in range(6):
                    before = zone_stats(on)["zone_hits"]
                    got = Message.decode(await udp_ask_raw(
                        on.udp_port,
                        make_query("svc.foo.com", Type.A,
                                   qid=91 + i).encode()))
                    assert zone_stats(on)["zone_hits"] == before + 1
                    assert got.rcode == Rcode.NOERROR
                    assert addrsets(got) == addrsets(want)
                    leads.add(got.answers[0].address)
                # both members lead at least once (cyclic rotation)
                assert leads == {"10.0.1.1", "10.0.1.2"}
            finally:
                await on.stop()
                await off.stop()

        asyncio.run(run())

    def test_service_member_mutation_repoints_rotation(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("svc.foo.com", Type.A, qid=95).encode()))
                assert {a.address for a in r.answers} == \
                    {"10.0.1.1", "10.0.1.2"}
                store.put_json("/com/foo/svc/lb2",
                               {"type": "load_balancer",
                                "load_balancer": {"address": "10.0.1.3"}})
                await asyncio.sleep(0)
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("svc.foo.com", Type.A, qid=96).encode()))
                assert {a.address for a in r.answers} == \
                    {"10.0.1.1", "10.0.1.2", "10.0.1.3"}
                assert zone_stats(server)["zone_hits"] == before + 1
            finally:
                await server.stop()

        asyncio.run(run())

    def test_service_min_ttl_matches_generic(self):
        """min(service-ttl, member-ttl) parity (lib/server.js:403-414)
        must be baked into the precompiled bodies."""
        async def run():
            store = FakeStore()
            cache = MirrorCache(store, DOMAIN)
            store.put_json("/com/foo/tsvc", {
                "type": "service", "ttl": 100,
                "service": {"srvce": "_x", "proto": "_tcp", "port": 1}})
            store.put_json("/com/foo/tsvc/m0",
                           {"type": "load_balancer", "ttl": 40,
                            "load_balancer": {"address": "10.3.0.1"}})
            store.put_json("/com/foo/tsvc/m1",
                           {"type": "load_balancer",
                            "load_balancer": {"address": "10.3.0.2"}})
            store.start_session()
            server = await start_server(cache)
            try:
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("tsvc.foo.com", Type.A, qid=97).encode()))
                assert zone_stats(server)["zone_hits"] == before + 1
                ttls = {a.address: a.ttl for a in r.answers}
                assert ttls == {"10.3.0.1": 40, "10.3.0.2": 100}
            finally:
                await server.stop()

        asyncio.run(run())

    def test_service_with_invalid_member_declines_to_python(self):
        """A structurally invalid member makes the generic path SERVFAIL
        mid-set; the zone must decline rather than answer differently."""
        async def run():
            store, cache = fixture_store()
            store.put_json("/com/foo/svc/bad",
                           {"type": "load_balancer",
                            "load_balancer": "not-a-dict"})
            server = await start_server(cache)
            try:
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("svc.foo.com", Type.A, qid=98).encode()))
                assert zone_stats(server)["zone_hits"] == before
                assert r.rcode == Rcode.SERVFAIL
            finally:
                await server.stop()

        asyncio.run(run())

    def test_doubled_suffix_policy_not_pushed(self):
        """Names the resolver REFUSES by suffix policy (doubled
        dnsDomain) must never be precompiled even if a store node
        exists at that domain."""
        async def run():
            store = FakeStore()
            cache = MirrorCache(store, DOMAIN)
            # a real znode whose domain is foo.com.foo.com
            store.put_json("/com/foo/com/foo",
                           {"type": "host",
                            "host": {"address": "10.1.2.3"}})
            store.start_session()
            server = await start_server(cache)
            try:
                q = make_query("foo.com.foo.com", Type.A, qid=31)
                resp = Message.decode(
                    await udp_ask_raw(server.udp_port, q.encode()))
                assert resp.rcode == Rcode.REFUSED
                assert zone_stats(server)["zone_hits"] == 0
            finally:
                await server.stop()

        asyncio.run(run())


class TestZoneCoherence:
    def test_mutation_repoints_zone_answer(self):
        """A store mutation must re-point the precompiled answer (drop
        via tag invalidation + fresh push from the same event) — and the
        NEW answer is still zone-served, not a Python fallback."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=41).encode()))
                assert r.answers[0].address == "192.168.0.1"

                store.put_json("/com/foo/web",
                               {"type": "host",
                                "host": {"address": "192.168.0.99"}})
                await asyncio.sleep(0)   # watch delivery (sync store)

                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=42).encode()))
                assert r.answers[0].address == "192.168.0.99"
                assert zone_stats(server)["zone_hits"] == before + 1

                # the reverse tree re-pointed too: old PTR gone, new live
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("99.0.168.192.in-addr.arpa", Type.PTR,
                               qid=43).encode()))
                assert r.rcode == Rcode.NOERROR
                assert r.answers[0].target == "web.foo.com"
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("1.0.168.192.in-addr.arpa", Type.PTR,
                               qid=44).encode()))
                assert r.rcode == Rcode.REFUSED
            finally:
                await server.stop()

        asyncio.run(run())

    def test_mutation_burst_bounded_drain_stays_fresh(self):
        """A burst of mutations larger than the zone drain batch (r5
        churn coalescing): answers must be FRESH immediately (the
        Python lanes' fallback while the name's re-push is still queued in
        the dirty set) and zone-served again once the bounded drain catches
        up — never stale in between."""
        async def run():
            store, cache = fixture_store()
            n = BinderServer._ZONE_DRAIN_BATCH * 2 + 10
            for i in range(n):
                store.put_json(f"/com/foo/h{i}",
                               {"type": "host",
                                "host": {"address": f"10.7.{i // 250}.{i % 250 + 1}"}})
            server = await start_server(cache)
            try:
                # mutate every host in one synchronous burst
                for i in range(n):
                    store.put_json(f"/com/foo/h{i}",
                                   {"type": "host",
                                    "host": {"address":
                                             f"10.8.{i // 250}.{i % 250 + 1}"}})
                assert len(server._zone_dirty) >= n
                # immediately (zero loop turns for the drain to run a
                # full catch-up): every answer must already be the NEW
                # address, whatever path serves it
                for i in (0, n // 2, n - 1):
                    r = Message.decode(await udp_ask_raw(
                        server.udp_port,
                        make_query(f"h{i}.foo.com", Type.A,
                                   qid=i).encode()))
                    assert r.answers[0].address == \
                        f"10.8.{i // 250}.{i % 250 + 1}"
                # let the bounded drain finish, then everything is
                # zone-served again
                for _ in range(10):
                    if not server._zone_dirty:
                        break
                    await asyncio.sleep(0)
                assert not server._zone_dirty
                assert not server._zone_drain_pending
                before = zone_stats(server)["zone_hits"]
                for i in (1, n - 2):
                    r = Message.decode(await udp_ask_raw(
                        server.udp_port,
                        make_query(f"h{i}.foo.com", Type.A,
                                   qid=1000 + i).encode()))
                    assert r.answers[0].address == \
                        f"10.8.{i // 250}.{i % 250 + 1}"
                assert zone_stats(server)["zone_hits"] == before + 2
            finally:
                await server.stop()

        asyncio.run(run())

    def test_deleted_node_falls_back_to_python_refused(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                store.delete("/com/foo/web")
                await asyncio.sleep(0)
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=51).encode()))
                assert r.rcode == Rcode.REFUSED
                assert zone_stats(server)["zone_hits"] == before
            finally:
                await server.stop()

        asyncio.run(run())

    def test_type_change_host_to_service_drops_zone_entry(self):
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                store.put_json("/com/foo/web", {
                    "type": "service",
                    "service": {"srvce": "_x", "proto": "_tcp",
                                "port": 1}})
                await asyncio.sleep(0)
                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=61).encode()))
                # service with no children: NODATA-ish per engine policy;
                # what matters here is the zone did NOT serve stale host
                assert zone_stats(server)["zone_hits"] == before
                assert not r.answers or \
                    r.answers[0].address != "192.168.0.1"
            finally:
                await server.stop()

        asyncio.run(run())

    def test_zone_precompile_off_serves_nothing_from_zone(self):
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache, zone_precompile=False)
            try:
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=71).encode()))
                assert r.answers[0].address == "192.168.0.1"
                assert zone_stats(server)["zone_hits"] == 0
                assert zone_stats(server)["zone_entries"] == 0
            finally:
                await server.stop()

        asyncio.run(run())

    def test_zone_serves_fold_into_metrics(self):
        """Zone serves surface in the Prometheus scrape: the per-qtype
        request counter advances and binder_zone_serves counts them."""
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache)
            try:
                for i in range(3):
                    await udp_ask_raw(
                        server.udp_port,
                        make_query("web.foo.com", Type.A,
                                   qid=80 + i).encode())
                text = server.collector.expose()
                assert 'binder_zone_serves_total 3' in text.replace(
                    "binder_zone_serves 3", "binder_zone_serves_total 3")
                # residency gauges expose the native tables' state:
                # fixture has web + ttlhost (A), their PTRs, svc A, SRV
                import re as _re
                m = _re.search(r"binder_zone_entries (\d+)", text)
                assert m and int(m.group(1)) >= 6, text[:400]
                assert _re.search(r"binder_zone_bytes [1-9]", text)
            finally:
                await server.stop()

        asyncio.run(run())


class TestZoneChurnSoak:
    def test_randomized_churn_read_your_writes(self):
        """Randomized mutation soak over the live UDP stack with the
        native path fully engaged: after every store mutation the next
        query for the touched name must reflect it — whether it is
        served from the zone, the caches, or Python.  Pins the
        drop-then-repush coherence of _on_store_invalidate under
        arbitrary interleavings (the single-shot repoint tests cannot
        reach orderings a random walk does)."""
        import random as _random

        async def run():
            rng = _random.Random(0x5A)
            store = FakeStore()
            cache = MirrorCache(store, DOMAIN)
            hosts = {f"h{i}": f"10.50.0.{i + 1}" for i in range(8)}
            for h, ip in hosts.items():
                store.put_json(f"/com/foo/{h}",
                               {"type": "host", "host": {"address": ip}})
            svc_members = {f"m{i}": f"10.51.0.{i + 1}" for i in range(3)}
            store.put_json("/com/foo/zsvc", {
                "type": "service",
                "service": {"srvce": "_z", "proto": "_tcp", "port": 9}})
            for m, ip in svc_members.items():
                store.put_json(f"/com/foo/zsvc/{m}",
                               {"type": "load_balancer",
                                "load_balancer": {"address": ip}})
            store.start_session()
            server = await start_server(cache)
            try:
                for step in range(120):
                    op = rng.randrange(4)
                    if op == 0:         # re-address a host
                        h = rng.choice(sorted(hosts))
                        hosts[h] = f"10.50.{rng.randrange(1, 200)}." \
                                   f"{rng.randrange(1, 200)}"
                        store.put_json(f"/com/foo/{h}",
                                       {"type": "host",
                                        "host": {"address": hosts[h]}})
                    elif op == 1 and len(hosts) > 2:   # delete a host
                        h = rng.choice(sorted(hosts))
                        del hosts[h]
                        store.delete(f"/com/foo/{h}")
                    elif op == 2:       # (re-)add a host
                        h = f"h{rng.randrange(12)}"
                        hosts[h] = f"10.50.{rng.randrange(1, 200)}." \
                                   f"{rng.randrange(1, 200)}"
                        store.put_json(f"/com/foo/{h}",
                                       {"type": "host",
                                        "host": {"address": hosts[h]}})
                    else:               # churn a service member
                        m = rng.choice(sorted(svc_members))
                        svc_members[m] = f"10.51.{rng.randrange(1, 200)}" \
                                         f".{rng.randrange(1, 200)}"
                        store.put_json(f"/com/foo/zsvc/{m}",
                                       {"type": "load_balancer",
                                        "load_balancer":
                                        {"address": svc_members[m]}})
                    await asyncio.sleep(0)   # watch delivery

                    # read-your-writes on a random live host
                    if hosts:
                        h = rng.choice(sorted(hosts))
                        r = Message.decode(await udp_ask_raw(
                            server.udp_port,
                            make_query(f"{h}.foo.com", Type.A,
                                       qid=step + 1).encode()))
                        assert r.rcode == Rcode.NOERROR, (step, h)
                        assert r.answers[0].address == hosts[h], (step, h)
                    # service plain-A and SRV reflect the member set
                    r = Message.decode(await udp_ask_raw(
                        server.udp_port,
                        make_query("zsvc.foo.com", Type.A,
                                   qid=1000 + step).encode()))
                    assert {a.address for a in r.answers} == \
                        set(svc_members.values()), step
                    r = Message.decode(await udp_ask_raw(
                        server.udp_port,
                        make_query("_z._tcp.zsvc.foo.com", Type.SRV,
                                   qid=2000 + step).encode()))
                    assert {a.address for a in r.additionals
                            if hasattr(a, "address")} == \
                        set(svc_members.values()), step

                # the soak must have exercised the native zone path
                # heavily, not just Python fallbacks
                assert zone_stats(server)["zone_hits"] > 200
            finally:
                await server.stop()

        asyncio.run(run())


class TestServeWireLanes:
    def test_tcp_lane_served_natively(self):
        """TCP queries for precompiled shapes are answered by
        fastpath_serve_wire without entering the Python resolver, with
        content equal to the zone-disabled server's TCP answer."""
        import struct as _struct

        async def tcp_ask_raw(port, wire):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write(_struct.pack(">H", len(wire)) + wire)
            await writer.drain()
            (length,) = _struct.unpack(">H",
                                       await reader.readexactly(2))
            data = await reader.readexactly(length)
            writer.close()
            await writer.wait_closed()
            return data

        async def run():
            _, cache_on = fixture_store()
            _, cache_off = fixture_store()
            on = await start_server(cache_on)
            off = await start_server(cache_off, zone_precompile=False)
            try:
                q = make_query("web.foo.com", Type.A, qid=61).encode()
                before = zone_stats(on)["zone_hits"]
                got = await tcp_ask_raw(on.tcp_port, q)
                want = await tcp_ask_raw(off.tcp_port, q)
                assert got == want
                assert zone_stats(on)["zone_hits"] == before + 1
                # SRV over TCP too (alien-table lookup through the
                # wire entry point)
                q = make_query("_pg._tcp.svc.foo.com", Type.SRV,
                               qid=62).encode()
                before = zone_stats(on)["zone_hits"]
                r = Message.decode(await tcp_ask_raw(on.tcp_port, q))
                assert r.rcode == Rcode.NOERROR and len(r.answers) == 2
                assert zone_stats(on)["zone_hits"] == before + 1
            finally:
                await on.stop()
                await off.stop()

        asyncio.run(run())

    def test_udp_lane_does_not_double_lookup(self):
        """Direct-UDP misses already checked the native path inside the
        drain; _handle_raw must not consult it again (lookups would
        double and skew the hit-rate metric)."""
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache)
            try:
                before = zone_stats(server)["lookups"]
                await udp_ask_raw(
                    server.udp_port,
                    make_query("absent.foo.com", Type.A, qid=71).encode())
                after = zone_stats(server)["lookups"]
                assert after == before + 1, (before, after)
            finally:
                await server.stop()

        asyncio.run(run())

    def test_tcp_gate_closed_stays_python(self):
        """With per-query logging on (the fastpath gate), TCP queries
        must surface to Python like everything else."""
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache, query_log=True)
            try:
                import struct as _struct
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.tcp_port)
                wire = make_query("web.foo.com", Type.A, qid=81).encode()
                writer.write(_struct.pack(">H", len(wire)) + wire)
                await writer.drain()
                (length,) = _struct.unpack(
                    ">H", await reader.readexactly(2))
                r = Message.decode(await reader.readexactly(length))
                writer.close()
                await writer.wait_closed()
                assert r.answers[0].address == "192.168.0.1"
                assert zone_stats(server)["zone_hits"] == 0
                assert zone_stats(server)["lookups"] == 0
            finally:
                await server.stop()

        asyncio.run(run())


class TestTruncationNotReplayedOverTcp:
    def test_tc_cached_udp_response_not_served_to_tcp(self):
        """An oversize answer set truncates for a no-EDNS UDP client
        (TC=1, answers emptied) and that TC wire lands in the native
        answer cache — correct for UDP repeats.  A TCP client asking
        the byte-identical question must still get the FULL answer set:
        the wire-serve entry declines truncated wires and Python (whose
        cache keys carry transport semantics) answers."""
        import struct as _struct

        async def run():
            store = FakeStore()
            cache = MirrorCache(store, DOMAIN)
            store.put_json("/com/foo/big", {
                "type": "service",
                "service": {"srvce": "_b", "proto": "_tcp", "port": 1}})
            n_members = 40          # 40 x 16B answers ≈ 640B > 512
            for i in range(n_members):
                store.put_json(f"/com/foo/big/m{i:02d}",
                               {"type": "load_balancer",
                                "load_balancer":
                                {"address": f"10.60.{i // 250}.{i + 1}"}})
            store.start_session()
            server = await start_server(cache)
            try:
                wire = make_query("big.foo.com", Type.A, qid=90,
                                  edns_payload=None).encode()
                # UDP: truncated (no EDNS ceiling), TC wire now cached
                # rotatable entries only complete (and push natively)
                # after the full variant set is collected — resolve
                # enough times for the TC wire to reach the C cache
                # one extra repeat: promotion to the C cache happens on
                # the completed entry's first hit (r5)
                for _ in range(9):
                    u = Message.decode(
                        await udp_ask_raw(server.udp_port, wire))
                    assert u.tc and not u.answers
                # the TC wire really is native-cached: the repeat UDP
                # query is a C hit and still TC (correct for UDP) —
                # without this, the TCP assertion below passes vacuously
                before = zone_stats(server)["hits"]
                u2 = Message.decode(
                    await udp_ask_raw(server.udp_port, wire))
                assert u2.tc
                assert zone_stats(server)["hits"] == before + 1
                # byte-identical question over TCP: full answer set
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", server.tcp_port)
                writer.write(_struct.pack(">H", len(wire)) + wire)
                await writer.drain()
                (length,) = _struct.unpack(
                    ">H", await reader.readexactly(2))
                t = Message.decode(await reader.readexactly(length))
                writer.close()
                await writer.wait_closed()
                assert not t.tc
                assert len(t.answers) == n_members, len(t.answers)
            finally:
                await server.stop()

        asyncio.run(run())


class TestZoneEpochRebuild:
    def test_session_rebuild_repoints_zone_via_epoch(self):
        """A (re)session rebuild bumps the mirror epoch: pre-rebuild
        zone entries must never serve again (lazy epoch drop), and the
        re-fired watch deliveries re-push fresh entries under the new
        epoch — queries stay correct across the whole transition, and
        post-rebuild serves are native again."""
        async def run():
            store, cache = fixture_store()
            server = await start_server(cache)
            try:
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=11).encode()))
                assert r.answers[0].address == "192.168.0.1"
                old_epoch = cache.epoch

                # mutate + rebuild back-to-back: the rebuild's re-fired
                # data deliveries must repopulate with CURRENT data
                store.put_json("/com/foo/web",
                               {"type": "host",
                                "host": {"address": "192.168.0.55"}})
                cache.rebuild()
                await asyncio.sleep(0)   # watch re-delivery (sync store)
                assert cache.epoch == old_epoch + 1

                before = zone_stats(server)["zone_hits"]
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("web.foo.com", Type.A, qid=12).encode()))
                assert r.answers[0].address == "192.168.0.55"
                # served natively under the NEW epoch, not via Python
                assert zone_stats(server)["zone_hits"] == before + 1
                # SRV (alien table) survived the transition too
                r = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("_pg._tcp.svc.foo.com", Type.SRV,
                               qid=13).encode()))
                assert r.rcode == Rcode.NOERROR and len(r.answers) == 2
            finally:
                await server.stop()

        asyncio.run(run())


class TestZoneDatabaseAndBalancerLane:
    def test_database_record_zone_served_differentially(self):
        """Database records (A from the primary URL's hostname,
        engine.resolve's database branch) precompile when the hostname
        is a canonical IPv4; a hostname that is NOT an address stays in
        Python (whatever it does there, the zone must not differ)."""
        async def run():
            def stores():
                store = FakeStore()
                cache = MirrorCache(store, DOMAIN)
                store.put_json("/com/foo/pg", {
                    "type": "database", "ttl": 45,
                    "database": {"primary":
                                 "tcp://10.4.4.4:5432/moray"}})
                store.put_json("/com/foo/pgname", {
                    "type": "database",
                    "database": {"primary":
                                 "tcp://pg.example.net:5432/moray"}})
                # non-string primary: must decline quietly, not
                # traceback through the mutation path (urlparse raises
                # AttributeError on non-str)
                store.put_json("/com/foo/pgbad", {
                    "type": "database", "database": {"primary": 45}})
                store.start_session()
                return cache

            on = await start_server(stores())
            off = await start_server(stores(), zone_precompile=False)
            try:
                wire = make_query("pg.foo.com", Type.A, qid=51).encode()
                before = zone_stats(on)["zone_hits"]
                got = await udp_ask_raw(on.udp_port, wire)
                want = await udp_ask_raw(off.udp_port, wire)
                assert got == want
                assert zone_stats(on)["zone_hits"] == before + 1
                r = Message.decode(got)
                assert r.answers[0].address == "10.4.4.4"
                assert r.answers[0].ttl == 45

                # non-IP primary hostname and non-string primary: never
                # precompiled; responses still agree with the generic
                # path
                for qid, name in ((52, "pgname.foo.com"),
                                  (53, "pgbad.foo.com")):
                    wire = make_query(name, Type.A, qid=qid).encode()
                    before = zone_stats(on)["zone_hits"]
                    got = await udp_ask_raw(on.udp_port, wire)
                    want = await udp_ask_raw(off.udp_port, wire)
                    assert got == want, name
                    assert zone_stats(on)["zone_hits"] == before, name
            finally:
                await on.stop()
                await off.stop()

        asyncio.run(run())

    def test_balancer_lane_zone_served(self):
        """Queries arriving over the balancer socket protocol (a
        balancer-fronted backend's only lane) are zone-served through
        the wire entry point without touching the Python resolver."""
        async def run():
            _, cache = fixture_store()
            server = await start_server(cache)
            try:
                out = []
                wire = make_query("web.foo.com", Type.A, qid=61).encode()
                before = zone_stats(server)["zone_hits"]
                server.engine._handle_raw(
                    wire, ("10.0.0.9", 5353), "balancer", out.append,
                    client_transport="udp")
                assert out, "no response emitted"
                assert zone_stats(server)["zone_hits"] == before + 1
                r = Message.decode(out[0])
                assert r.id == 61
                assert r.answers[0].address == "192.168.0.1"
            finally:
                await server.stop()

        asyncio.run(run())
