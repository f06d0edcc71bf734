"""Mutation-time answer precompilation (resolver/precompile.py).

Pins the tentpole properties of the precompiled answer layer:

- a store mutation re-renders the affected names' answers and installs
  them, so the post-churn query is a compiled-table probe + ID/flags
  patch (``log_ctx["precompiled"]``), never an engine resolve;
- invalidate-then-reinstall under sustained churn keeps read-your-writes
  (the drop is synchronous, the re-render immediate on the inline path);
- precompiled wires are byte-for-byte what the engine would encode —
  including every round-robin rotation variant, SRV answer+additional
  sections, negative answers, and both EDNS postures (modulo the 16-bit
  id, which is patched per query);
- a watch storm that outruns the bounded work queue SHEDS (metrics +
  flight-recorder event) and those names degrade to today's lazy
  resolution — correct answers, just slower;
- negative answers (NXDOMAIN / NODATA) are cached with their own
  accounting; SERVFAIL is never cached or compiled;
- the ``binder_precompile_*`` metric family is pinned by
  ``tools/lint.py validate_precompile_metrics`` against the real
  exposition text.
"""
import asyncio
import importlib.machinery
import importlib.util
import os

import pytest

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns.query import QueryCtx
from binder_tpu.introspect import FlightRecorder, Introspector
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.resolver.precompile import Precompiler
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache

from tools.lint import (validate_precompile_metrics,
                        validate_status_snapshot)

DOMAIN = "foo.com"
SVC = "/com/foo/svc"


def build(precompile=True, recorder=None, **kw):
    """Server over a fake store; fixtures are loaded AFTER construction
    so every put_json is a live mutation event (the precompiler's input),
    delivered synchronously (no loop -> inline compile)."""
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN, recorder=recorder)
    store.start_session()
    server = BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
        collector=MetricsCollector(), query_log=False,
        answer_precompile=precompile, flight_recorder=recorder, **kw)
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


def forbid_engine(server):
    """Any resolve past the compiled table is a test failure."""
    def boom(_query):
        raise AssertionError("engine consulted; precompiled layer missed")
    server.resolver.handle = boom


class TestMutationInstalls:
    """Mutation-path re-rendering is EVIDENCE-BASED: the shapes a
    mutation's invalidation actually dropped (things being served) are
    re-rendered eagerly; churn on unqueried names costs nothing.  The
    startup seed covers the cold mirror.  So the pattern here is:
    prime (one lazy query), mutate, then the engine is forbidden."""

    def test_mutation_recompiles_served_host_answer(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        ask(server, "web.foo.com", Type.A, qid=1)         # evidence
        put_host(store, "/com/foo/web", "10.9.9.9")       # mutation
        forbid_engine(server)
        r, _, q = ask(server, "web.foo.com", Type.A, qid=2)
        assert r.rcode == Rcode.NOERROR
        assert [a.address for a in r.answers] == ["10.9.9.9"]
        assert q.log_ctx.get("precompiled") is True

    def test_unqueried_churn_compiles_nothing(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        for i in range(5):
            put_host(store, "/com/foo/web", f"10.1.2.{i + 4}")
        assert server._precompiler.compiled == 0
        assert server.answer_cache.stats()["compiled_entries"] == 0

    def test_mutation_recompiles_served_ptr(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        ask(server, "3.2.1.10.in-addr.arpa", Type.PTR, qid=1)
        # address unchanged, record rewritten (ttl added): the reverse
        # shape's per-key entry drops and is re-rendered
        put_host(store, "/com/foo/web", "10.1.2.3", ttl=55)
        forbid_engine(server)
        r, _, q = ask(server, "3.2.1.10.in-addr.arpa", Type.PTR, qid=2)
        assert r.answers[0].target == "web.foo.com"
        assert r.answers[0].ttl == 55
        assert q.log_ctx.get("precompiled") is True

    def test_mutation_recompiles_served_srv(self):
        store, cache, server = build()
        put_service(store)
        ask(server, "_pg._tcp.svc.foo.com", Type.SRV, qid=1)
        store.put_json(f"{SVC}/lb0",
                       {"type": "load_balancer",
                        "load_balancer": {"address": "10.0.9.9"}})
        forbid_engine(server)
        r, _, q = ask(server, "_pg._tcp.svc.foo.com", Type.SRV, qid=2)
        assert r.rcode == Rcode.NOERROR
        assert len(r.answers) == 3 and all(a.port == 5432
                                           for a in r.answers)
        addl = {a.name: a.address for a in r.additionals
                if hasattr(a, "address")}
        assert addl["lb0.svc.foo.com"] == "10.0.9.9"
        assert q.log_ctx.get("precompiled") is True

    def test_seed_mirror_compiles_preexisting_names(self):
        # fixture loaded BEFORE the server subscribed: only the startup
        # seed can compile it (the _zone_fill analog)
        store = FakeStore()
        cache = MirrorCache(store, DOMAIN)
        store.start_session()
        put_host(store, "/com/foo/old", "10.9.9.9")
        server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                              datacenter_name="dc0",
                              collector=MetricsCollector(),
                              query_log=False, answer_precompile=True)
        server._precompiler.seed_mirror()
        forbid_engine(server)
        r, _, q = ask(server, "old.foo.com", Type.A)
        assert [a.address for a in r.answers] == ["10.9.9.9"]
        assert q.log_ctx.get("precompiled") is True
        # the reverse shape seeded too
        r, _, _q = ask(server, "9.9.9.10.in-addr.arpa", Type.PTR)
        assert r.answers[0].target == "old.foo.com"

    def test_servfail_shape_never_compiled(self):
        store, cache, server = build()
        store.put_json("/com/foo/junk", {"type": "host"})  # no sub-object
        pc = server._precompiler
        pc.seed_mirror()
        assert pc.declined > 0
        assert server.answer_cache.stats()["compiled_entries"] == 0
        r, _, q = ask(server, "junk.foo.com", Type.A)
        assert r.rcode == Rcode.SERVFAIL
        assert "precompiled" not in q.log_ctx
        # and the SERVFAIL was not cached either (the absolute rule)
        assert server.answer_cache.stats()["entries"] == 0

    def test_recursion_miss_not_compiled(self):
        class _Rec:
            pass
        store, cache, server = build(recursion=_Rec())
        put_host(store, "/com/foo/web", "10.1.2.3")
        store.rmr("/com/foo/web")
        # the deleted name's answer is RD-dependent now (REFUSED vs
        # cross-DC forward): only the lazy path may decide
        assert server.answer_cache.get_compiled(
            Type.A, "web.foo.com", cache.epoch) is None


class _Unshuffled:
    """``resolver.rng`` that leaves a set in plan order: the rotation
    the precompiler renders as variant 0."""

    def shuffle(self, lst):
        pass


def load_host_zone(store):
    for i in range(12):
        put_host(store, f"/com/foo/h{i:02d}", f"10.2.0.{i + 1}")


def load_service_zone(store):
    for s in range(4):
        base = f"/com/foo/svc{s}"
        store.put_json(base, {"type": "service",
                              "service": {"srvce": "_pg", "proto": "_tcp",
                                          "port": 5432}})
        for m in range(3):
            store.put_json(f"{base}/lb{m}", {
                "type": "load_balancer",
                "load_balancer": {"address": f"10.3.{s}.{m + 1}"}})


#: zone -> (its load, the shapes its mirror answers: an A and a PTR a
#: host or member, an A and an SRV a service; the most one name holds)
SEED_ZONES = {"hosts": (load_host_zone, 12 * 2, 2),
              "services": (load_service_zone, 4 * 2 + 12 * 2, 2)}


class TestBoundedSeed:
    """The startup seed renders what its table keeps (ISSUE 36): a
    mirror whose shapes fit the compiled table is seeded whole, as it
    always was; of a larger one the seed fills the table and counts the
    rest, instead of rendering every shape to keep the last
    ``compiled_size``."""

    def seeded_pair(self, zone, **kw):
        load, shapes, widest = SEED_ZONES[zone]
        _s1, _c1, server = build(**kw)
        _s2, _c2, engine = build(precompile=False)
        load(server.zk_cache.store)
        load(engine.zk_cache.store)
        server.resolver.rng = engine.resolver.rng = _Unshuffled()
        return server, engine, shapes, widest

    def assert_seeded_wires_are_the_engines(self, server, engine):
        """Every shape in the table serves, with the engine forbidden,
        the bytes the generic path gives; every shape of the mirror
        left out of it is still answered, by a resolve, with the same
        bytes."""
        table = set(server.answer_cache._compiled)
        resolve = server.resolver.handle
        left_out = 0
        for domain in list(server.zk_cache.nodes):
            for qtype, qname in server._precompiler.seed_shapes(domain):
                kept = (qtype, qname) in table
                left_out += not kept
                if kept:
                    forbid_engine(server)
                else:
                    server.resolver.handle = resolve
                _, wire, q = ask(server, qname, qtype, qid=11)
                _, want, _q = ask(engine, qname, qtype, qid=11)
                assert wire == want, (qtype, qname)
                assert bool(q.log_ctx.get("precompiled")) is kept
        server.resolver.handle = resolve
        return left_out

    @pytest.mark.parametrize("walk", ["inline", "chunked"])
    @pytest.mark.parametrize("zone", sorted(SEED_ZONES))
    def test_a_table_smaller_than_the_mirror_is_filled_and_no_more(
            self, zone, walk, monkeypatch):
        """Inline, and from the background task a mirror above
        ``SEED_INLINE_MAX`` seeds from under a loop: the task ends and
        ``seed_remaining`` reaches 0 (what ``wait_settled`` and
        ``chip_smoke.py`` wait for) with the table full and the rest
        counted."""
        size = 10
        server, engine, shapes, widest = self.seeded_pair(
            zone, precompile_size=size)
        pc = server._precompiler
        if walk == "inline":
            pc.seed_mirror()
        else:
            monkeypatch.setattr(Precompiler, "SEED_INLINE_MAX", 3)

            async def run():
                pc.seed_mirror()
                assert pc._seed_task is not None, "the walk ran inline"
                await asyncio.wait_for(pc._seed_task, timeout=30)

            asyncio.run(run())
        intro = pc.introspect()
        assert server.answer_cache.stats()["compiled_entries"] == size
        assert server.answer_cache.compiled_full()
        # the bound, give or take the shapes of the one name in hand
        assert size <= pc.compiled < size + widest
        assert f"binder_precompile_compiled {pc.compiled}\n" \
            in server.collector.expose()
        assert intro["seed_remaining"] == 0
        assert intro["seeded"] == pc.compiled
        assert intro["seeded"] + intro["seed_skipped"] == shapes
        assert intro["declined"] == 0
        left_out = self.assert_seeded_wires_are_the_engines(server,
                                                            engine)
        assert left_out == shapes - size

    @pytest.mark.parametrize("zone", sorted(SEED_ZONES))
    def test_a_table_that_holds_the_mirror_is_seeded_whole(self, zone):
        server, engine, shapes, _widest = self.seeded_pair(zone)
        pc = server._precompiler
        pc.seed_mirror()
        intro = pc.introspect()
        assert intro["seeded"] == intro["compiled"] == shapes
        assert intro["seed_skipped"] == 0
        assert intro["seed_remaining"] == 0
        assert server.answer_cache.stats()["compiled_entries"] == shapes
        assert not server.answer_cache.compiled_full()
        assert self.assert_seeded_wires_are_the_engines(server,
                                                        engine) == 0

    @pytest.mark.parametrize("zone", sorted(SEED_ZONES))
    def test_a_table_of_none_seeds_nothing(self, zone):
        server, engine, shapes, _widest = self.seeded_pair(
            zone, precompile_size=0)
        pc = server._precompiler
        pc.seed_mirror()
        intro = pc.introspect()
        assert (intro["seeded"], intro["compiled"]) == (0, 0)
        assert intro["seed_skipped"] == shapes
        assert self.assert_seeded_wires_are_the_engines(
            server, engine) == shapes


class TestChurn:
    def test_invalidated_then_reinstalled_under_churn(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.0.0.1")
        put_host(store, "/com/foo/stable", "10.7.7.7")
        # serving evidence: one lazy query each
        ask(server, "web.foo.com", Type.A, qid=1)
        ask(server, "stable.foo.com", Type.A, qid=1)
        for i in range(2, 60):
            addr = f"10.0.{i % 250}.{i % 250}"
            put_host(store, "/com/foo/web", addr)
            r, _, q = ask(server, "web.foo.com", Type.A, qid=i)
            # read-your-writes through the compiled path: the mutation's
            # drop was synchronous and the re-render immediate, so the
            # post-churn query serves the NEW address, precompiled
            assert [a.address for a in r.answers] == [addr]
            assert q.log_ctx.get("precompiled") is True
            # the unmutated neighbor keeps serving (per-name selectivity)
            r2, _, _q2 = ask(server, "stable.foo.com", Type.A, qid=i)
            assert [a.address for a in r2.answers] == ["10.7.7.7"]

    def test_dropped_negative_shape_reinstalled(self):
        store, cache, server = build()
        put_service(store)
        # a concrete negative qname a client actually asked: cached by
        # the query path with its question identity (qkey)
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV)
        assert r.rcode == Rcode.NXDOMAIN
        # churn the service: the dropped key's identity rides to the
        # precompiler, which re-renders the negative eagerly
        store.put_json(SVC, {"type": "service",
                             "service": {"srvce": "_pg", "proto": "_tcp",
                                         "port": 5433}})
        forbid_engine(server)
        r, _, q = ask(server, "_http._tcp.svc.foo.com", Type.SRV, qid=9)
        assert r.rcode == Rcode.NXDOMAIN
        assert q.log_ctx.get("precompiled") is True


class TestWireParity:
    """Precompiled wires must be byte-for-byte what the engine encodes
    (modulo the 16-bit id and the rotation variant — here both are
    pinned: same qid, rng stubbed to a known rotation)."""

    def fixture_pair(self, load):
        s1, c1, srv1 = build(precompile=True)
        s2, c2, srv2 = build(precompile=False)
        load(s1)
        load(s2)
        srv1._precompiler.seed_mirror()   # the cold-start walk
        return srv1, srv2

    def assert_parity(self, name, qtype, load, edns=1232, rd=False,
                      prime=False, perturb=None):
        """``prime=True`` for shapes only reachable through the
        dropped-key path (concrete negative qnames): ask once lazily so
        the question identity is cached, then mutate so the
        invalidation hands it to the precompiler for re-render."""
        srv_pre, srv_eng = self.fixture_pair(load)
        if prime:
            s1 = srv_pre.zk_cache.store
            ask(srv_pre, name, qtype, qid=99, edns=edns, rd=rd)
            # a REAL mutation (identical re-puts no longer invalidate:
            # unchanged data cannot change answers), restored to the
            # canonical fixture so the parity comparison holds
            if perturb is None:
                perturb = lambda s: s.put_json(  # noqa: E731
                    SVC, {"type": "service",
                          "service": {"srvce": "_pg", "proto": "_tcp",
                                      "port": 5433}})
            perturb(s1)
            load(s1)                    # restore == second mutation
        forbid_engine(srv_pre)
        _, wire_pre, q = ask(srv_pre, name, qtype, qid=3, edns=edns,
                             rd=rd)
        assert q.log_ctx.get("precompiled") is True
        _, wire_eng, _q = ask(srv_eng, name, qtype, qid=3, edns=edns,
                              rd=rd)
        assert wire_pre == wire_eng

    def test_host_a_parity(self):
        load = lambda s: put_host(s, "/com/foo/web", "10.1.2.3", ttl=77)
        self.assert_parity("web.foo.com", Type.A, load)
        self.assert_parity("web.foo.com", Type.A, load, edns=None)
        self.assert_parity("web.foo.com", Type.A, load, rd=True)

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
                           prime=True, perturb=touch)
        self.assert_parity("_pg._tcp.web.foo.com", Type.SRV, load,
                           edns=None, prime=True, perturb=touch)

    def test_nxdomain_parity(self):
        self.assert_parity("_http._udp.svc.foo.com", Type.SRV,
                           put_service, prime=True)

    class _RotRng:
        """shuffle() = rotate left by k — the cyclic variant the
        precompiler renders as variant k."""

        def __init__(self, k):
            self.k = k

        def shuffle(self, lst):
            k = self.k % len(lst) if lst else 0
            lst[:] = lst[k:] + lst[:k]

    def test_rotation_variant_parity_plain_a(self):
        for k in range(3):
            srv_pre, srv_eng = self.fixture_pair(put_service)
            srv_eng.resolver.rng = self._RotRng(k)
            forbid_engine(srv_pre)
            # compiled serves rotate 0,1,2,... — advance to variant k
            for i in range(k):
                ask(srv_pre, "svc.foo.com", Type.A, qid=50 + i)
            _, wire_pre, q = ask(srv_pre, "svc.foo.com", Type.A, qid=3)
            assert q.log_ctx.get("precompiled") is True
            _, wire_eng, _q = ask(srv_eng, "svc.foo.com", Type.A, qid=3)
            assert wire_pre == wire_eng

    def test_rotation_variant_parity_srv(self):
        for k in range(3):
            srv_pre, srv_eng = self.fixture_pair(put_service)
            srv_eng.resolver.rng = self._RotRng(k)
            forbid_engine(srv_pre)
            for i in range(k):
                ask(srv_pre, "_pg._tcp.svc.foo.com", Type.SRV,
                    qid=50 + i)
            _, wire_pre, q = ask(srv_pre, "_pg._tcp.svc.foo.com",
                                 Type.SRV, qid=3)
            assert q.log_ctx.get("precompiled") is True
            _, wire_eng, _q = ask(srv_eng, "_pg._tcp.svc.foo.com",
                                  Type.SRV, qid=3)
            assert wire_pre == wire_eng

    def test_all_variants_cover_member_set(self):
        store, cache, server = build()
        put_service(store)
        server._precompiler.seed_mirror()
        forbid_engine(server)
        firsts = set()
        for i in range(3):
            r, _, _q = ask(server, "svc.foo.com", Type.A, qid=i + 1)
            assert sorted(a.address for a in r.answers) == \
                ["10.0.1.1", "10.0.1.2", "10.0.1.3"]
            firsts.add(r.answers[0].address)
        # round-robin: consecutive serves lead with different members
        assert len(firsts) == 3


class TestStormShedding:
    def test_storm_sheds_to_lazy(self):
        recorder = FlightRecorder(capacity=64)

        async def run():
            store, cache, server = build(recorder=recorder)
            pc = server._precompiler
            # instance shadow of the bound (the cap too: the effective
            # bound scales with zone size up to MAX_PENDING_CAP)
            pc.MAX_PENDING = pc.MAX_PENDING_CAP = 4
            # 40 served names (the evidence that makes their mutations
            # re-render work)
            for i in range(40):
                put_host(store, f"/com/foo/s{i}", f"10.1.0.{i + 1}")
                ask(server, f"s{i}.foo.com", Type.A, qid=i + 1)
            await asyncio.sleep(0)
            # storm: every served name mutated within one loop pass (no
            # drain runs in between) — far more work than the queue
            # admits
            for i in range(40):
                put_host(store, f"/com/foo/s{i}", f"10.2.0.{i + 1}")
            assert pc.shed > 0
            assert len(pc._pending) <= pc.MAX_PENDING
            # lazy fallback: a shed name still answers correctly (the
            # engine path), just without the precompiled serve
            r, _, q = ask(server, "s39.foo.com", Type.A, qid=99)
            assert r.rcode == Rcode.NOERROR
            assert [a.address for a in r.answers] == ["10.2.0.40"]
            # draining the queue compiles what was admitted
            while pc._pending:
                await asyncio.sleep(0)
            assert pc.compiled > 0
            return server

        asyncio.run(run())
        events = [e for e in recorder.events()
                  if e["type"] == "precompile-shed"]
        assert events, "shedding must leave flight-recorder evidence"
        assert events[0]["shed"] > 0

    def test_shed_then_requeued_on_next_mutation(self):
        async def run():
            store, cache, server = build()
            pc = server._precompiler
            for i in range(10):
                put_host(store, f"/com/foo/b{i}", f"10.2.0.{i + 1}")
                ask(server, f"b{i}.foo.com", Type.A, qid=i + 1)
            await asyncio.sleep(0)
            pc.MAX_PENDING = pc.MAX_PENDING_CAP = 2
            for i in range(10):
                put_host(store, f"/com/foo/b{i}", f"10.3.0.{i + 1}")
            assert pc.shed > 0
            while pc._pending:
                await asyncio.sleep(0)
            # a fresh mutation of a (possibly shed) name re-renders it
            # normally once the storm is over and the bound is back
            pc.MAX_PENDING = type(pc).MAX_PENDING
            pc.MAX_PENDING_CAP = type(pc).MAX_PENDING_CAP
            ask(server, "b9.foo.com", Type.A, qid=90)   # evidence again
            put_host(store, "/com/foo/b9", "10.2.9.9")
            while pc._pending:
                await asyncio.sleep(0)
            forbid_engine(server)
            r, _, q = ask(server, "b9.foo.com", Type.A, qid=91)
            assert [a.address for a in r.answers] == ["10.2.9.9"]
            assert q.log_ctx.get("precompiled") is True

        asyncio.run(run())


class TestNegativeCaching:
    def count_engine(self, server):
        calls = {"n": 0}
        inner = server.resolver.handle

        def counting(query):
            calls["n"] += 1
            return inner(query)
        server.resolver.handle = counting
        return calls

    def test_nxdomain_cached_with_accounting(self):
        store, cache, server = build(precompile=False)
        put_service(store)
        calls = self.count_engine(server)
        r, _, _q = ask(server, "_http._tcp.svc.foo.com", Type.SRV,
                       qid=1)
        assert r.rcode == Rcode.NXDOMAIN
        r, _, q = ask(server, "_http._tcp.svc.foo.com", Type.SRV, qid=2)
        assert r.rcode == Rcode.NXDOMAIN
        assert calls["n"] == 1, "repeat negative must not hit the engine"
        assert server.answer_cache.stats()["neg_hits"] == 1

    def test_nodata_cached(self):
        store, cache, server = build(precompile=False)
        put_host(store, "/com/foo/web", "10.1.2.3")
        calls = self.count_engine(server)
        for qid in (1, 2):
            r, _, _q = ask(server, "_pg._tcp.web.foo.com", Type.SRV,
                           qid=qid)
            assert r.rcode == Rcode.NOERROR and not r.answers
            assert r.authorities
        assert calls["n"] == 1

    def test_negative_invalidated_by_its_tag(self):
        store, cache, server = build(precompile=False)
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
        store, cache, server = build(precompile=False)
        store.put_json("/com/foo/junk", {"type": "host"})
        calls = self.count_engine(server)
        for qid in (1, 2, 3):
            r, _, _q = ask(server, "junk.foo.com", Type.A, qid=qid)
            assert r.rcode == Rcode.SERVFAIL
        assert calls["n"] == 3, "every SERVFAIL must re-check the store"


class TestMetrics:
    def test_precompile_exposition_validates(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        ask(server, "web.foo.com", Type.A)
        text = server.collector.expose()
        assert validate_precompile_metrics(text) == []
        assert "binder_precompile_compiled" in text
        assert "binder_precompile_serves" in text

    def test_validator_rejects_missing_family(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        text = server.collector.expose()
        broken = "\n".join(
            ln for ln in text.splitlines()
            if "binder_precompile_shed" not in ln) + "\n"
        assert any("binder_precompile_shed" in e
                   for e in validate_precompile_metrics(broken))

    def test_status_schema_and_bstat_hold_the_seed_fields(self):
        store, cache, server = build(precompile_size=2)
        put_host(store, "/com/foo/web", "10.1.2.3")
        put_host(store, "/com/foo/db", "10.1.2.4")
        server._precompiler.seed_mirror()
        snap = Introspector(server=server).snapshot()
        assert validate_status_snapshot(snap) == []
        pc = snap["precompile"]
        assert (pc["seeded"], pc["seed_skipped"]) == (2, 2)
        for key in ("seeded", "seed_skipped"):
            cut = dict(snap, precompile={k: v for k, v in pc.items()
                                         if k != key})
            assert validate_status_snapshot(cut) == [
                f"precompile: missing {key!r}"]
        loader = importlib.machinery.SourceFileLoader(
            "bstat", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "bin", "bstat"))
        bstat = importlib.util.module_from_spec(
            importlib.util.spec_from_loader("bstat", loader))
        loader.exec_module(bstat)
        line = next(ln for ln in bstat.render(snap).splitlines()
                    if ln.startswith("precompile:"))
        assert "seed 2 shape(s), 2 past the table's capacity" in line

    def test_introspect_section(self):
        store, cache, server = build()
        put_host(store, "/com/foo/web", "10.1.2.3")
        server._precompiler.seed_mirror()
        pc = server._precompiler.introspect()
        assert pc["compiled"] >= 1
        assert pc["queue_depth"] == 0
        assert pc["max_pending"] > 0


class TestSessionFlapSoak:
    """ZK session *flapping* (ISSUE 4 satellite): rapid
    connected -> degraded -> connected cycles while names churn must
    not leak precompile work — every cycle's queue drains back to
    empty, shed work is bounded by MAX_PENDING, and the compiled table
    still serves the final state."""

    def test_flap_cycles_leave_no_queue_leak(self):
        async def run():
            store, cache, server = build()
            pc = server._precompiler
            for i in range(12):
                put_host(store, f"/com/foo/f{i}", f"10.4.0.{i + 1}")
                ask(server, f"f{i}.foo.com", Type.A, qid=i + 1)
            await asyncio.sleep(0)
            for cycle in range(8):
                store.lose_session()
                # mutations while dark are not mirrored (no watch
                # events) — nothing may enqueue
                depth_dark = len(pc._pending)
                store.start_session()     # rebind storms the watchers
                for i in range(12):
                    put_host(store, f"/com/foo/f{i}",
                             f"10.5.{cycle}.{i + 1}")
                assert len(pc._pending) <= pc.MAX_PENDING
                # drain completely between flaps: a leak would show as
                # monotonic queue growth across cycles
                for _ in range(1000):
                    if not pc._pending:
                        break
                    await asyncio.sleep(0)
                assert not pc._pending, \
                    f"queue leaked {len(pc._pending)} items " \
                    f"(cycle {cycle}, dark depth {depth_dark})"
            # post-flap: the final addresses serve (precompiled or
            # lazily — correctness first), and the queue is at rest
            r, _, q = ask(server, "f11.foo.com", Type.A, qid=99)
            assert r.rcode == Rcode.NOERROR
            assert [a.address for a in r.answers] == ["10.5.7.12"]
            assert pc.introspect()["queue_depth"] == 0

        asyncio.run(run())

    def test_flap_with_expire_session_keeps_read_your_writes(self):
        async def run():
            store, cache, server = build()
            pc = server._precompiler
            put_host(store, "/com/foo/flap", "10.6.0.1")
            ask(server, "flap.foo.com", Type.A, qid=1)
            for cycle in range(6):
                store.expire_session()   # loss + immediate re-establish
                put_host(store, "/com/foo/flap", f"10.6.0.{cycle + 2}")
                for _ in range(1000):
                    if not pc._pending:
                        break
                    await asyncio.sleep(0)
                r, _, _q = ask(server, "flap.foo.com", Type.A,
                               qid=cycle + 10)
                assert [a.address for a in r.answers] \
                    == [f"10.6.0.{cycle + 2}"]
            assert not pc._pending

        asyncio.run(run())
