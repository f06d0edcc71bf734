"""What the Python lanes answer after a store mutation.

A datagram the native lanes did not answer takes ``_on_query``: answer
cache, then resolver.  A mirrored mutation drops the cached answers of
the names it touched, synchronously, so:

- the ask after a mutation is a resolve of the new data (read your
  writes, under sustained churn and across session flaps too), the one
  after that an answer-cache hit of the same bytes, and an unmutated
  neighbour keeps its cached answer;
- what is served, from the cache or by a resolve, is byte for byte what a
  server with no cache encodes (modulo the 16-bit id), in both EDNS
  postures, with RD set, for negative answers, and for every rotation
  of a service's set;
- negative answers (NXDOMAIN / NODATA) are cached with their own
  accounting and die with their dependency tag; SERVFAIL is never
  cached.

``tests/test_mutation_refill.py`` holds the same over real sockets, with
the native lanes and the zone drain.
"""
import asyncio

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns.query import QueryCtx
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache

DOMAIN = "foo.com"
SVC = "/com/foo/svc"


def build(**kw):
    """Server over a fake store; fixtures are loaded AFTER construction
    so every put_json is a live mutation event, delivered synchronously
    (no loop: the drops and the zone refresh run inline)."""
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    server = BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
        collector=MetricsCollector(), query_log=False, **kw)
    return store, cache, server


def ask(server, name, qtype, rd=False, edns=1232, qid=7):
    sent = []
    req = make_query(name, qtype, qid=qid, rd=rd, edns_payload=edns)
    q = QueryCtx(req, ("127.0.0.1", 5353), "udp", sent.append)
    pending = server._on_query(q)
    assert pending is None
    assert len(sent) == 1, "server must respond exactly once"
    return Message.decode(sent[0]), sent[0], q


def put_host(store, path, addr, **extra):
    rec = {"type": "host", "host": {"address": addr}}
    rec.update(extra)
    store.put_json(path, rec)


def put_service(store, n_members=3):
    store.put_json(SVC, {"type": "service",
                         "service": {"srvce": "_pg", "proto": "_tcp",
                                     "port": 5432}})
    for i in range(n_members):
        store.put_json(f"{SVC}/lb{i}",
                       {"type": "load_balancer",
                        "load_balancer": {"address": f"10.0.1.{i + 1}"}})


def count_engine(server):
    calls = {"n": 0}
    inner = server.resolver.handle

    def counting(query):
        calls["n"] += 1
        return inner(query)
    server.resolver.handle = counting
    return calls


class _Rotate:
    """``resolver.rng`` whose shuffle rotates left: by ``k`` every time,
    or (``k`` None) by one more each call, so that successive resolves
    give successive rotations."""

    def __init__(self, k=None):
        self.k, self.calls = k, 0

    def shuffle(self, lst):
        k = (self.calls if self.k is None else self.k) % len(lst)
        self.calls += 1
        lst[:] = lst[k:] + lst[:k]


class TestMutationServesNewData:
    """Ask (a resolve, cached), mutate, ask twice: the first is a
    resolve of the new data, never the dropped answer; the second is the
    answer cache's, the same bytes."""

    def after_mutation(self, server, name, qtype, calls):
        before = calls["n"]
        r, wire, q = ask(server, name, qtype, qid=2)
        assert calls["n"] == before + 1 and "cached" not in q.log_ctx
        r2, wire2, q2 = ask(server, name, qtype, qid=2)
        assert calls["n"] == before + 1 and q2.log_ctx["cached"] is True
        assert wire2 == wire
        return r

    def test_mutation_serves_new_host_answer(self):
        store, cache, server = build()
        calls = count_engine(server)
        put_host(store, "/com/foo/web", "10.1.2.3")
        ask(server, "web.foo.com", Type.A, qid=1)
        put_host(store, "/com/foo/web", "10.9.9.9")       # mutation
        r = self.after_mutation(server, "web.foo.com", Type.A, calls)
        assert r.rcode == Rcode.NOERROR
        assert [a.address for a in r.answers] == ["10.9.9.9"]

    def test_mutation_serves_new_ptr(self):
        store, cache, server = build()
        calls = count_engine(server)
        put_host(store, "/com/foo/web", "10.1.2.3")
        ask(server, "3.2.1.10.in-addr.arpa", Type.PTR, qid=1)
        # address unchanged, record rewritten (ttl added): the reverse
        # shape's entry drops with its tag
        put_host(store, "/com/foo/web", "10.1.2.3", ttl=55)
        r = self.after_mutation(server, "3.2.1.10.in-addr.arpa",
                                Type.PTR, calls)
        assert r.answers[0].target == "web.foo.com"
        assert r.answers[0].ttl == 55

    def test_mutation_serves_new_srv(self):
        store, cache, server = build()
        put_service(store)
        ask(server, "_pg._tcp.svc.foo.com", Type.SRV, qid=1)
        store.put_json(f"{SVC}/lb0",
                       {"type": "load_balancer",
                        "load_balancer": {"address": "10.0.9.9"}})
        # a rotatable set: every sight is a resolve until the entry
        # holds its eight variants, and each carries the new member
        for qid in (2, 3):
            r, _, q = ask(server, "_pg._tcp.svc.foo.com", Type.SRV,
                          qid=qid)
            assert r.rcode == Rcode.NOERROR and "cached" not in q.log_ctx
            assert len(r.answers) == 3 and all(a.port == 5432
                                               for a in r.answers)
            addl = {a.name: a.address for a in r.additionals
                    if hasattr(a, "address")}
            assert addl["lb0.svc.foo.com"] == "10.0.9.9"


class TestChurn:
    def test_read_your_writes_under_churn(self):
        store, cache, server = build()
        calls = count_engine(server)
        put_host(store, "/com/foo/web", "10.0.0.1")
        put_host(store, "/com/foo/stable", "10.7.7.7")
        ask(server, "web.foo.com", Type.A, qid=1)
        ask(server, "stable.foo.com", Type.A, qid=1)
        assert calls["n"] == 2
        for i in range(2, 60):
            addr = f"10.0.{i % 250}.{i % 250}"
            put_host(store, "/com/foo/web", addr)
            r, _, q = ask(server, "web.foo.com", Type.A, qid=i)
            # the mutation's drop was synchronous: the next query
            # resolves the NEW address
            assert [a.address for a in r.answers] == [addr]
            assert "cached" not in q.log_ctx
            # the unmutated neighbor keeps its cached answer (per-name
            # selectivity)
            r2, _, q2 = ask(server, "stable.foo.com", Type.A, qid=i)
            assert [a.address for a in r2.answers] == ["10.7.7.7"]
            assert q2.log_ctx["cached"] is True
        assert calls["n"] == 2 + 58

    def test_dropped_negative_shape_resolved_anew(self):
        store, cache, server = build()
        put_service(store)
        calls = count_engine(server)
        # a concrete negative qname a client actually asked
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV)
        assert r.rcode == Rcode.NXDOMAIN
        # churn the service: the cached negative dies with its tag and
        # the next ask decides again, on the new data
        store.put_json(SVC, {"type": "service",
                             "service": {"srvce": "_pg", "proto": "_tcp",
                                         "port": 5433}})
        r, _, q = ask(server, "_http._tcp.svc.foo.com", Type.SRV, qid=9)
        assert r.rcode == Rcode.NXDOMAIN and "cached" not in q.log_ctx
        r, _, q = ask(server, "_http._tcp.svc.foo.com", Type.SRV, qid=9)
        assert r.rcode == Rcode.NXDOMAIN and q.log_ctx["cached"] is True
        assert calls["n"] == 2


class TestWireParity:
    """Served wires must be byte-for-byte what a server with no cache
    encodes (modulo the 16-bit id and the rotation variant — here both
    are pinned: same qid, rng stubbed to a known rotation), on the first
    sight (a resolve) and on the second (the answer cache's)."""

    def fixture_pair(self, load, k=0):
        s1, c1, served = build()
        s2, c2, engine = build(cache_size=0)
        load(s1)
        load(s2)
        served.resolver.rng, engine.resolver.rng = _Rotate(k), _Rotate(k)
        return served, engine

    def assert_parity(self, name, qtype, load, edns=1232, rd=False,
                      perturb=None):
        """``perturb``: ask once, mutate, and restore the canonical
        fixture (a second mutation) before the comparison, so that the
        served wire is the one made after a drop."""
        served, engine = self.fixture_pair(load)
        if perturb is not None:
            s1 = served.zk_cache.store
            ask(served, name, qtype, qid=99, edns=edns, rd=rd)
            perturb(s1)
            load(s1)
        _, want, _q = ask(engine, name, qtype, qid=3, edns=edns, rd=rd)
        _, first, q1 = ask(served, name, qtype, qid=3, edns=edns, rd=rd)
        _, second, q2 = ask(served, name, qtype, qid=3, edns=edns, rd=rd)
        assert "cached" not in q1.log_ctx and q2.log_ctx["cached"] is True
        assert first == second == want

    def test_host_a_parity(self):
        load = lambda s: put_host(s, "/com/foo/web", "10.1.2.3", ttl=77)
        touch = lambda s: put_host(s, "/com/foo/web", "10.9.9.9", ttl=77)
        self.assert_parity("web.foo.com", Type.A, load)
        self.assert_parity("web.foo.com", Type.A, load, edns=None)
        self.assert_parity("web.foo.com", Type.A, load, rd=True)
        self.assert_parity("web.foo.com", Type.A, load, perturb=touch)

    def test_database_parity(self):
        self.assert_parity("pg.foo.com", Type.A, lambda s: s.put_json(
            "/com/foo/pg",
            {"type": "database",
             "database": {"primary": "tcp://10.99.99.14:5432/x"}}))

    def test_ptr_parity(self):
        self.assert_parity(
            "3.2.1.10.in-addr.arpa", Type.PTR,
            lambda s: put_host(s, "/com/foo/web", "10.1.2.3"))

    def test_nodata_soa_parity(self):
        load = lambda s: put_host(s, "/com/foo/web", "10.1.2.3", ttl=60)
        touch = lambda s: put_host(s, "/com/foo/web", "10.9.9.9",
                                   ttl=60)
        self.assert_parity("_pg._tcp.web.foo.com", Type.SRV, load,
                           perturb=touch)
        self.assert_parity("_pg._tcp.web.foo.com", Type.SRV, load,
                           edns=None, perturb=touch)

    def test_nxdomain_parity(self):
        self.assert_parity(
            "_http._udp.svc.foo.com", Type.SRV, put_service,
            perturb=lambda s: s.put_json(
                SVC, {"type": "service",
                      "service": {"srvce": "_pg", "proto": "_tcp",
                                  "port": 5433}}))

    def assert_rotation_parity(self, name, qtype):
        for k in range(3):
            served, engine = self.fixture_pair(put_service, k)
            for edns in (1232, None):
                _, got, _q = ask(served, name, qtype, qid=3, edns=edns)
                _, want, _q = ask(engine, name, qtype, qid=3, edns=edns)
                assert got == want, (k, edns)

    def test_rotation_variant_parity_plain_a(self):
        self.assert_rotation_parity("svc.foo.com", Type.A)

    def test_rotation_variant_parity_srv(self):
        self.assert_rotation_parity("_pg._tcp.svc.foo.com", Type.SRV)

    def test_all_variants_cover_member_set(self):
        store, cache, server = build()
        put_service(store)
        server.resolver.rng = _Rotate()
        calls = count_engine(server)
        leads = []
        for i in range(12):
            r, _, _q = ask(server, "svc.foo.com", Type.A, qid=i + 1)
            assert sorted(a.address for a in r.answers) == \
                ["10.0.1.1", "10.0.1.2", "10.0.1.3"]
            leads.append(r.answers[0].address)
        # eight resolves collect eight rotations; the hits after them
        # cycle through what was collected: round-robin either way
        assert calls["n"] == 8
        for part in (leads[:8], leads[8:]):
            assert all(len(set(part[i:i + 3])) == 3
                       for i in range(len(part) - 2)), leads


class TestNegativeCaching:
    def test_nxdomain_cached_with_accounting(self):
        store, cache, server = build()
        put_service(store)
        calls = count_engine(server)
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV,
                       qid=1)
        assert r.rcode == Rcode.NXDOMAIN
        r, _, q = ask(server, "_http._tcp.svc.foo.com", Type.SRV, qid=2)
        assert r.rcode == Rcode.NXDOMAIN
        assert calls["n"] == 1, "repeat negative must not hit the engine"
        assert server.answer_cache.stats()["neg_hits"] == 1

    def test_nodata_cached(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        calls = count_engine(server)
        for qid in (1, 2):
            r, _, _q = ask(server, "_pg._tcp.web.foo.com", Type.SRV,
                           qid=qid)
            assert r.rcode == Rcode.NOERROR and not r.answers
            assert r.authorities
        assert calls["n"] == 1

    def test_negative_invalidated_by_its_tag(self):
        store, cache, server = build()
        put_service(store)
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV,
                       qid=1)
        assert r.rcode == Rcode.NXDOMAIN
        # the service re-registers under the asked name: the cached
        # negative must die with its dependency tag
        store.put_json(SVC, {"type": "service",
                             "service": {"srvce": "_http",
                                         "proto": "_tcp", "port": 80}})
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV,
                       qid=2)
        assert r.rcode == Rcode.NOERROR and r.answers

    def test_servfail_never_cached(self):
        store, cache, server = build()
        store.put_json("/com/foo/junk", {"type": "host"})
        calls = count_engine(server)
        for qid in (1, 2, 3):
            r, _, _q = ask(server, "junk.foo.com", Type.A, qid=qid)
            assert r.rcode == Rcode.SERVFAIL
        assert calls["n"] == 3, "every SERVFAIL must re-check the store"


class TestSessionFlap:
    """ZK session *flapping* (ISSUE 4 satellite): loss and immediate
    re-establishment while a name churns keeps read-your-writes."""

    def test_flap_with_expire_session_keeps_read_your_writes(self):
        async def run():
            store, cache, server = build()
            put_host(store, "/com/foo/flap", "10.6.0.1")
            ask(server, "flap.foo.com", Type.A, qid=1)
            for cycle in range(6):
                store.expire_session()   # loss + immediate re-establish
                put_host(store, "/com/foo/flap", f"10.6.0.{cycle + 2}")
                for _ in range(1000):
                    if not cache.rebuild_pending():
                        break
                    await asyncio.sleep(0)
                r, _, _q = ask(server, "flap.foo.com", Type.A,
                               qid=cycle + 10)
                assert [a.address for a in r.answers] \
                    == [f"10.6.0.{cycle + 2}"]

        asyncio.run(run())
