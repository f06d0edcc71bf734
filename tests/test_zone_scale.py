"""Zone-scale representation tests (ISSUE 7): interned-name pool,
compact node records, chunked session rebuild, scale-aware
backpressure, the late-drop counter, and the binder_mirror_* metric
family pins.

The heavyweight end-to-end figures (RSS/name, 1M-name serving) live in
`make zone-smoke` and `tools/zone_probe.py`; these tests pin the
MECHANISMS at sizes tier-1 can afford.
"""
import asyncio
import json
import time

from binder_tpu.dns.server import DnsServer
from binder_tpu.introspect import FlightRecorder
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.store.fake import populate_synthetic
from binder_tpu.store.names import (
    NamePool,
    compact_record,
    expand_record,
    rec_parts,
)

from tools.lint import validate_mirror_metrics  # noqa: E402
from tools.zone_probe import Harness, host_name, host_path  # noqa: E402

DOMAIN = "foo.com"


def make_cache(**kw):
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN, **kw)
    return store, cache


class TestNamePool:
    def test_interning_returns_one_canonical_object(self):
        pool = NamePool()
        a = pool.intern("host-a.foo.com")
        b = pool.intern("host-" + "a.foo.com")
        assert a is b
        assert pool.hits == 1

    def test_bytes_interning(self):
        pool = NamePool()
        a = pool.intern_bytes(b"\x03foo\x00")
        b = pool.intern_bytes(bytes(b"\x03foo\x00"))
        assert a is b

    def test_sweep_drops_dead_entries(self):
        pool = NamePool()
        keep = pool.intern("live-name.example")
        for i in range(100):
            pool.intern(f"dead-{i}.example")
        dropped = pool.sweep()
        assert dropped >= 100
        # the live name survived (we still hold a reference)
        assert pool.intern("live-name.example") is keep

    def test_stats_shape(self):
        pool = NamePool()
        pool.intern("x.example")
        st = pool.stats()
        for key in ("interned", "interned_str", "interned_bytes",
                    "hits", "sweeps"):
            assert key in st


class TestCompactRecord:
    CASES = [
        {"type": "host", "host": {"address": "10.0.0.1"}},
        {"type": "load_balancer",
         "load_balancer": {"address": "10.0.0.2", "ttl": 5}},
        {"type": "host", "host": {"address": "10.0.0.3"}, "ttl": 60},
        {"type": "rr_host",
         "rr_host": {"address": "10.9.9.9", "ttl": 1}, "ttl": 2},
    ]

    def test_host_shapes_compact_and_round_trip(self):
        for case in self.CASES:
            rec = compact_record(json.loads(json.dumps(case)))
            assert type(rec) is tuple, case
            assert expand_record(rec) == case
            rtype, addr, ttl, sttl = rec_parts(rec)
            assert rtype == case["type"]
            assert addr == case[case["type"]]["address"]

    def test_ttl_less_shape_packs_to_pair(self):
        rec = compact_record({"type": "host",
                              "host": {"address": "10.0.0.1"}})
        assert len(rec) == 2

    def test_non_host_shapes_stay_dicts(self):
        for case in (
            {"type": "service",
             "service": {"srvce": "_h", "proto": "_t", "port": 1}},
            {"type": "database", "database": {"primary": "tcp://x/"}},
            # host-like but with an extra field that must round-trip
            {"type": "host", "host": {"address": "10.0.0.1"},
             "extra": 1},
            {"type": "host",
             "host": {"address": "10.0.0.1", "ports": [1]}},
            # non-string address
            {"type": "host", "host": {"address": 42}},
        ):
            rec = compact_record(json.loads(json.dumps(case)))
            assert type(rec) is dict, case
            assert rec == case

    def test_lists_and_null_pass_through(self):
        assert compact_record(None) is None
        assert compact_record([1, 2]) == [1, 2]


class TestCompactMirror:
    def test_node_rec_is_tuple_for_hosts(self):
        store, cache = make_cache()
        store.start_session()
        store.put_json("/com/foo/web",
                       {"type": "host", "host": {"address": "10.0.0.5"}})
        node = cache.lookup("web.foo.com")
        assert type(node.rec) is tuple
        # the data property reconstructs the parsed-JSON shape
        assert node.data == {"type": "host",
                             "host": {"address": "10.0.0.5"}}
        assert node.ip == "10.0.0.5"
        assert node.name == "web"
        assert node.path == "/com/foo/web"
        # leaves allocate no kids container
        assert node.kids is None

    def test_children_resolve_through_node_index(self):
        store, cache = make_cache()
        store.start_session()
        store.put_json("/com/foo/svc", {
            "type": "service",
            "service": {"srvce": "_http", "proto": "_tcp", "port": 80}})
        for i in range(3):
            store.put_json(f"/com/foo/svc/h{i}",
                           {"type": "load_balancer",
                            "load_balancer": {"address": f"10.0.1.{i}"}})
        node = cache.lookup("svc.foo.com")
        assert sorted(k.name for k in node.children) == ["h0", "h1", "h2"]
        assert all(type(k.rec) is tuple for k in node.children)

    def test_canon_returns_mirror_domain_object(self):
        store, cache = make_cache()
        store.start_session()
        store.put_json("/com/foo/web",
                       {"type": "host", "host": {"address": "10.0.0.5"}})
        node = cache.lookup("web.foo.com")
        # a query-decoded copy of the name canonicalizes to THE object
        copy = "web" + ".foo.com"
        assert copy is not node.domain
        assert cache.canon(copy) is node.domain


class TestChunkedRebuild:
    def _zone(self, n):
        store = FakeStore()
        populate_synthetic(store, DOMAIN, n, racks=4)
        cache = MirrorCache(store, DOMAIN)
        return store, cache

    def test_inline_rebuild_without_loop(self):
        store, cache = self._zone(500)
        store.start_session()
        assert cache.rebuild_pending() == 0
        epoch0 = cache.epoch
        store.expire_session()
        # no loop: drained inline to completion, one epoch bump
        assert cache.rebuild_pending() == 0
        assert cache.epoch == epoch0 + 1
        assert cache.lookup(host_name_under(DOMAIN, 7, 4)) is not None
        assert cache.last_rebuild_duration_s is not None

    def test_chunked_rebuild_serves_throughout(self):
        async def run():
            store, cache = self._zone(4000)
            store.start_session()       # initial build (new subtree)
            name = host_name_under(DOMAIN, 123, 4)
            assert cache.lookup(name) is not None
            epoch0 = cache.epoch
            chunks0 = cache.rebuild_chunks
            store.expire_session()
            # the walk is in flight: pending nodes remain after the
            # inline first chunk, and serving continues underneath
            assert cache.rebuild_pending() > 0
            assert cache.epoch == epoch0 + 1
            served = 0
            while cache.rebuild_pending():
                node = cache.lookup(name)
                assert node is not None, "lookup went dark mid-rebuild"
                assert node.ip is not None
                served += 1
                await asyncio.sleep(0.001)
            assert served > 0
            assert cache.rebuild_chunks - chunks0 > 1
            assert cache.epoch == epoch0 + 1   # ONE bump per rebuild
            assert cache.lookup(name).data["host"]["address"]
            return cache

        asyncio.run(run())

    def test_rebuild_superseded_by_newer_session(self):
        async def run():
            store, cache = self._zone(3000)
            store.start_session()
            store.expire_session()
            assert cache.rebuild_pending() > 0
            epoch1 = cache.epoch
            store.expire_session()      # churn mid-rebuild: restart walk
            assert cache.epoch == epoch1 + 1
            while cache.rebuild_pending():
                await asyncio.sleep(0.001)
            # converged: data intact after the doubled rebuild
            assert cache.lookup(
                host_name_under(DOMAIN, 42, 4)).ip is not None

        asyncio.run(run())

    def test_mutation_latency_independent_of_zone_size(self):
        """O(delta) pin: p50 single-name mutation latency at 20x the
        zone size stays within a small factor (an O(zone) path would
        scale ~20x)."""
        def measure(n):
            store = FakeStore()
            populate_synthetic(store, "bench.zone", n)
            cache = MirrorCache(store, "bench.zone")
            store.start_session()
            h = Harness(cache)
            racks = max(1, min(1024, n // 512))
            lats = []
            for j in range(60):
                i = (j * max(1, n // 60)) % n
                h.prime(host_name(i, racks))
                body = json.dumps(
                    {"type": "host",
                     "host": {"address": f"10.77.{j // 250}.{j % 250}"}}
                ).encode()
                t0 = time.perf_counter()
                store.set_data(host_path(i, racks), body)
                lats.append(time.perf_counter() - t0)
            lats.sort()
            return lats[len(lats) // 2]

        small = measure(1000)
        large = measure(20000)
        assert large / small < 6.0, (small, large)


def host_name_under(domain: str, i: int, racks: int) -> str:
    return f"h{i:06d}.r{i % racks:04d}.zs.{domain}"


class TestLateDropAccounting:
    def test_counter_and_flight_event(self):
        recorder = FlightRecorder(capacity=16)
        collector = MetricsCollector()
        counter = collector.counter("binder_udp_late_drops_total",
                                    "test")
        srv = DnsServer()
        srv.recorder = recorder
        srv.late_drop_counter = counter.labelled()
        srv.note_late_drops(3)
        srv.note_late_drops(2)          # same window: no second event
        assert srv.udp_late_drops == 5
        assert counter.total() == 5
        events = [e for e in recorder.events()
                  if e["type"] == "udp-late-drop"]
        assert len(events) == 1
        assert events[0]["dropped"] == 3
        assert events[0]["total"] == 3
        # a later window records again
        srv._late_drop_event_last -= srv.LATE_DROP_EVENT_WINDOW_S + 1
        srv.note_late_drops(1)
        events = [e for e in recorder.events()
                  if e["type"] == "udp-late-drop"]
        assert len(events) == 2
        assert events[-1]["total"] == 6

    def test_zero_is_a_noop(self):
        srv = DnsServer()
        srv.note_late_drops(0)
        assert srv.udp_late_drops == 0


class TestMirrorMetricsExposition:
    def test_server_scrape_passes_mirror_validator(self):
        collector = MetricsCollector()
        store = FakeStore()
        store.put_json("/com/foo/web",
                       {"type": "host", "host": {"address": "10.0.0.1"}})
        cache = MirrorCache(store, DOMAIN, collector=collector)
        store.start_session()
        BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                     collector=collector, cache_size=16)
        errs = validate_mirror_metrics(collector.expose())
        assert errs == []

    def test_validator_rejects_missing_family(self):
        collector = MetricsCollector()
        store = FakeStore()
        MirrorCache(store, DOMAIN, collector=collector)
        # no server: the late-drop counter family is absent
        errs = validate_mirror_metrics(collector.expose())
        assert any("binder_udp_late_drops_total" in e for e in errs)

    def test_rebuild_metrics_move(self):
        collector = MetricsCollector()
        store = FakeStore()
        populate_synthetic(store, DOMAIN, 1000, racks=2)
        cache = MirrorCache(store, DOMAIN, collector=collector)
        store.start_session()
        store.expire_session()
        text = collector.expose()
        assert "binder_mirror_names" in text
        chunks = [line for line in text.splitlines()
                  if line.startswith("binder_mirror_rebuild_chunks")]
        assert chunks and float(chunks[0].split()[-1]) >= 1.0


class TestSharedWatchScaling:
    """ROADMAP 3b: with the mirror index offered via ``bind_source``,
    the real-ZK owner registers ONE wire watch per host leaf (the data
    watch) and children watches only where children can exist, so the
    ensemble-side watch table — and the session re-establishment
    chatter — scales with directories, not names."""

    N_HOSTS = 40
    N_SVC = 4
    N_LB = 3

    HOST = {"type": "host", "host": {"address": "10.3.0.1"}}
    SVC = {"type": "service",
           "service": {"srvce": "_http", "proto": "_tcp", "port": 80}}
    LB = {"type": "load_balancer",
          "load_balancer": {"address": "10.4.0.1"}}

    def test_watch_table_scales_with_directories_not_names(self):
        from binder_tpu.store.zk_client import ZKClient
        from binder_tpu.store.zk_testserver import ZKTestServer

        async def wait_for(pred, timeout=8.0):
            deadline = asyncio.get_running_loop().time() + timeout
            while asyncio.get_running_loop().time() < deadline:
                if pred():
                    return True
                await asyncio.sleep(0.01)
            return False

        async def run():
            server = ZKTestServer()
            await server.start()
            writer = ZKClient("127.0.0.1", port=server.port,
                              session_timeout_ms=4000)
            client = None
            try:
                assert await wait_for(writer.is_connected)
                for i in range(self.N_HOSTS):
                    await writer.mkdirp(f"/com/foo/h{i:03d}",
                                        json.dumps(self.HOST).encode())
                for s in range(self.N_SVC):
                    await writer.mkdirp(f"/com/foo/svc{s}",
                                        json.dumps(self.SVC).encode())
                    for j in range(self.N_LB):
                        await writer.mkdirp(f"/com/foo/svc{s}/lb{j}",
                                            json.dumps(self.LB).encode())

                client = ZKClient("127.0.0.1", port=server.port,
                                  session_timeout_ms=4000)
                cache = MirrorCache(client, DOMAIN)
                assert client._shared_nodes is cache.nodes  # mode is on
                client.start()

                total = 1 + self.N_HOSTS + self.N_SVC * (1 + self.N_LB)
                assert await wait_for(lambda: len(cache.nodes) == total)
                state = server.state
                sid = client._session_id

                def mine(table):
                    return {p for p, sids in table.items() if sid in sids}

                # one data watch per mirrored znode...
                assert await wait_for(
                    lambda: len(mine(state.data_watches)) == total)
                # ...but children watches ONLY on the root and the
                # service containers — none of the 52 host/lb leaves
                dirs = {"/com/foo"} | {f"/com/foo/svc{s}"
                                       for s in range(self.N_SVC)}
                assert mine(state.child_watches) == dirs
                assert len(dirs) * 8 < total  # the scaling claim itself

                # liveness is not traded away: every mutation class the
                # per-path watchers caught still flows to the mirror.
                await writer.mkdirp("/com/foo/hnew",
                                    json.dumps(self.HOST).encode())
                assert await wait_for(
                    lambda: cache.lookup("hnew.foo.com") is not None)
                await writer.set_data(
                    "/com/foo/svc0/lb0",
                    b'{"type": "load_balancer", '
                    b'"load_balancer": {"address": "10.4.9.9"}}')
                assert await wait_for(
                    lambda: cache.lookup("lb0.svc0.foo.com").ip
                    == "10.4.9.9")
                # a child appearing under an EXISTING container
                await writer.mkdirp("/com/foo/svc1/lbnew",
                                    json.dumps(self.LB).encode())
                assert await wait_for(
                    lambda: cache.lookup("lbnew.svc1.foo.com") is not None)
                # the leaf->parent case the container rule exists for:
                # a service created EMPTY gains its first child later
                await writer.mkdirp("/com/foo/svc9",
                                    json.dumps(self.SVC).encode())
                assert await wait_for(
                    lambda: cache.lookup("svc9.foo.com") is not None)
                await writer.mkdirp("/com/foo/svc9/lb0",
                                    json.dumps(self.LB).encode())
                assert await wait_for(
                    lambda: cache.lookup("lb0.svc9.foo.com") is not None)

                # session re-establishment re-registers the same SCALED
                # shape (historically this was the 2x-per-node storm)
                total += 4          # hnew, svc9, svc9/lb0, svc1/lbnew
                dirs |= {"/com/foo/svc9"}
                server.expire_session(client._session_id)
                assert await wait_for(
                    lambda: client.is_connected()
                    and client._session_id != sid)
                sid = client._session_id
                assert await wait_for(
                    lambda: len(mine(state.data_watches)) == total)
                assert await wait_for(
                    lambda: mine(state.child_watches) == dirs)
                assert len(cache.nodes) == total
            finally:
                if client is not None:
                    client.close()
                writer.close()
                await server.stop()

        asyncio.run(run())


class TestScaleAwareBackpressure:
    def test_served_answers_match_engine_at_scale(self):
        store = FakeStore()
        n = 3000
        populate_synthetic(store, "bench.zone", n)
        cache = MirrorCache(store, "bench.zone")
        store.start_session()
        h = Harness(cache)
        racks = max(1, min(1024, n // 512))
        for i in (0, n // 2, n - 1):
            name = host_name(i, racks)
            old = h.prime(name)
            assert h.served_wire(name) == old == h.engine_wire(name)
            # and across a mutation: the cached answer is dropped, the
            # next ask is a resolve of the new data
            store.set_data(host_path(i, racks),
                           b'{"type": "host", '
                           b'"host": {"address": "10.99.0.1"}}')
            assert h.served_wire(name) == h.engine_wire(name) != old

    def test_ptr_follows_compact_representation(self):
        store = FakeStore()
        populate_synthetic(store, "bench.zone", 600)
        cache = MirrorCache(store, "bench.zone")
        store.start_session()
        h = Harness(cache)
        racks = max(1, min(1024, 600 // 512))
        name = host_name(0, racks)
        node = cache.lookup(name)
        rev = cache.reverse_lookup(node.ip)
        assert rev is node
        plan = h.resolver.plan_ptr(
            ".".join(reversed(node.ip.split("."))) + ".in-addr.arpa")
        assert plan.groups and plan.groups[0][0][0].target == name
