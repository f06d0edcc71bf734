"""Fault-injection harness + graceful-degradation policy engine.

What this pins down end to end (docs/degradation.md):

- FaultPlan DSL parses (and rejects garbage loudly); ChaosUpstream
  applies loss / delay / duplication / truncation / dead-peer faults;
- circuit breakers: threshold opens, backoff + half-open probing
  closes on recovery, and — the satellite guarantee — a dead peer
  adds <100 ms per query once its breaker is open;
- hedged dispatch beats the serial timeout for a silent-but-unopened
  peer;
- the stale-serve state machine: fresh -> stale-serving (TTL clamp)
  -> stale-exhausted (withheld per config) -> fresh again, with cache
  flushes at every edge and binder_degraded_state tracking;
- overload admission: in-flight oldest-shed answers (REFUSED, never a
  hang, never double-metered) and per-client recursion token buckets;
- validate_degradation_metrics passes against a live scrape (and
  catches removals);
- the chaos soak: scripted ZK-session loss + upstream packet loss
  under continuous queries — answers stay correct-or-refused, nothing
  staler than the cap is served, and the system re-converges
  (binder_degraded_state back to 0, breakers closed, mirror advances).
"""
import asyncio
import time

import pytest

from binder_tpu.chaos import ChaosDriver, ChaosUpstream, FaultPlan
from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.policy import (AdmissionControl, CircuitBreaker,
                               DegradationPolicy, PeerBreakers)
from binder_tpu.introspect import FlightRecorder
from binder_tpu.recursion import Recursion, StaticResolverSource
from binder_tpu.recursion.client import DnsClient
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from tools.lint import (validate_degradation_metrics,
                        validate_status_snapshot)

DOMAIN = "foo.com"


def make_fixture(recorder=None, collector=None, hosts=None):
    store = FakeStore(recorder=recorder)
    cache = MirrorCache(store, DOMAIN, collector=collector,
                        recorder=recorder)
    for name, addr in (hosts or {"web": "10.0.0.1"}).items():
        store.put_json(f"/com/foo/{name}",
                       {"type": "host", "host": {"address": addr}})
    store.start_session()
    return store, cache


async def start_server(recorder=None, collector=None, recursion=None,
                       hosts=None, **kw):
    store, cache = make_fixture(recorder=recorder, collector=collector,
                                hosts=hosts)
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="dc0", host="127.0.0.1",
                          port=0, collector=collector or MetricsCollector(),
                          query_log=False, flight_recorder=recorder,
                          recursion=recursion, **kw)
    await server.start()
    return server, store


async def udp_ask(port, name, qtype, qid=1, rd=False, edns=1232,
                  timeout=5.0):
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    class Proto(asyncio.DatagramProtocol):
        def connection_made(self, transport):
            transport.sendto(make_query(name, qtype, qid=qid, rd=rd,
                                        edns_payload=edns).encode())

        def datagram_received(self, data, addr):
            if not fut.done():
                fut.set_result(data)

    transport, _ = await loop.create_datagram_endpoint(
        Proto, remote_addr=("127.0.0.1", port))
    try:
        data = await asyncio.wait_for(fut, timeout)
    finally:
        transport.close()
    return Message.decode(data)


# ---------------------------------------------------------------------------
# FaultPlan DSL + ChaosUpstream


class TestFaultPlan:
    def test_parse_full_spec(self):
        plan = FaultPlan.parse("""
            # chaos script
            at 0.5 lose-session
            at 1.0 watch-storm n=600
            at 1.5 loop-stall ms=120
            at 2.0 upstream loss=0.3 delay_ms=40 dup=0.05
            at 3.0 restore-session; at 4.0 upstream clear
        """)
        assert [a for _t, a, _k in plan.timeline] == [
            "lose-session", "watch-storm", "loop-stall", "upstream",
            "restore-session", "upstream"]
        assert plan.duration == 4.0
        t, action, kw = plan.timeline[3]
        assert (t, action) == (2.0, "upstream")
        assert kw == {"loss": 0.3, "delay_ms": 40, "dup": 0.05}

    def test_parse_rejects_garbage(self):
        for bad in ("lose-session", "at x lose-session",
                    "at 1 warp-core-breach", "at 1 upstream loss"):
            with pytest.raises(ValueError):
                FaultPlan.parse(bad)

    def test_same_seed_same_decisions(self):
        a, b = FaultPlan(seed=7), FaultPlan(seed=7)
        assert [a.rng.random() for _ in range(20)] \
            == [b.rng.random() for _ in range(20)]

    def test_driver_applies_session_and_storm(self):
        recorder = FlightRecorder()
        store, cache = make_fixture(recorder=recorder)
        writes = []
        drv = ChaosDriver(FaultPlan(), store=store,
                          mutate=lambda i: writes.append(i),
                          recorder=recorder)
        drv.apply("lose-session", {})
        assert store.session_state() == "degraded"
        drv.apply("watch-storm", {"n": 5})
        assert writes == [0, 1, 2, 3, 4]
        drv.apply("restore-session", {})
        assert store.session_state() == "connected"
        kinds = [e["type"] for e in recorder.events()]
        assert kinds.count("chaos-inject") == 3


class TestChaosUpstream:
    def run(self, coro):
        return asyncio.run(coro)

    def test_serves_then_faults(self):
        async def go():
            plan = FaultPlan(seed=1)
            up = ChaosUpstream(plan, hosts={"w.remote.foo.com":
                                            "10.9.0.1"})
            port = await up.start()
            client = DnsClient(timeout=0.3)
            try:
                # healthy: answers with the mapped address
                recs = await client.lookup("w.remote.foo.com", Type.A,
                                           [f"127.0.0.1:{port}"])
                assert [r.address for r in recs] == ["10.9.0.1"]
                # dead: every packet dropped -> UpstreamError
                plan.upstream.set(dead=1)
                from binder_tpu.recursion.client import UpstreamError
                with pytest.raises(UpstreamError):
                    await client.lookup("w.remote.foo.com", Type.A,
                                        [f"127.0.0.1:{port}"])
                assert up.dropped >= 1
                # truncation: UDP answers TC=1, TCP retry serves it
                plan.upstream.set(clear=True, truncate=1)
                recs = await client.lookup("w.remote.foo.com", Type.A,
                                           [f"127.0.0.1:{port}"])
                assert [r.address for r in recs] == ["10.9.0.1"]
                assert up.truncated >= 1
                # delay: the answer arrives, late
                plan.upstream.set(clear=True, delay_ms=80)
                t0 = time.monotonic()
                await client.lookup("w.remote.foo.com", Type.A,
                                    [f"127.0.0.1:{port}"])
                assert time.monotonic() - t0 >= 0.07
                assert up.delayed >= 1
            finally:
                client.close()
                await up.stop()

        self.run(go())


    def test_a_drawn_port_taken_on_tcp_is_redrawn(self):
        """The kernel draws the UDP port and TCP shares its number: where
        another socket holds that number on TCP (a client's, in
        TIME_WAIT) the pair is drawn again, as ``BinderServer.start``
        does; a port the caller fixed is not."""
        async def go():
            import socket
            blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            blocker.bind(("127.0.0.1", 0))
            blocker.listen(1)
            taken = blocker.getsockname()[1]
            real = asyncio.start_server
            ports = []

            async def first_draw_collides(cb, host, port, **kw):
                ports.append(port)
                return await real(cb, host,
                                  taken if len(ports) == 1 else port, **kw)

            asyncio.start_server = first_draw_collides
            up = ChaosUpstream(FaultPlan(), hosts={"w.foo.com": "10.1.1.1"})
            try:
                port = await up.start()
                client = DnsClient(timeout=0.5)
                try:
                    recs = await client.lookup("w.foo.com", Type.A,
                                               [f"127.0.0.1:{port}"])
                finally:
                    client.close()
                await up.stop()
                fixed = ChaosUpstream(FaultPlan(), hosts={})
                asyncio.start_server = real
                with pytest.raises(OSError):
                    await fixed.start(port=taken)
                assert fixed._udp_transport is None
            finally:
                asyncio.start_server = real
                blocker.close()
            return ports, port, [r.address for r in recs]

        ports, port, addresses = self.run(go())
        assert len(ports) == 2 and port == ports[1]
        assert addresses == ["10.1.1.1"]


# ---------------------------------------------------------------------------
# circuit breakers + hedging


class TestCircuitBreaker:
    def test_threshold_backoff_halfopen_close(self):
        b = CircuitBreaker("p")
        now = 100.0
        for _ in range(CircuitBreaker.FAILURE_THRESHOLD - 1):
            b.record_failure(now)
        assert b.state == "closed"
        b.record_failure(now)
        assert b.state == "open"
        # jittered backoff within [0.5x, 1x] of the base
        assert now + 0.5 * b.BACKOFF_BASE <= b.open_until \
            <= now + b.BACKOFF_BASE
        assert not b.allow(now)
        # backoff elapsed: exactly one probe per interval
        t1 = b.open_until + 0.01
        assert b.allow(t1)
        assert b.state == "half-open"
        assert not b.allow(t1 + 0.1)
        # failed probe: re-opens with doubled backoff
        b.record_failure(t1)
        assert b.state == "open"
        assert b.open_until - t1 >= 0.5 * 2 * b.BACKOFF_BASE
        # successful probe closes and resets
        t2 = b.open_until + 0.01
        assert b.allow(t2)
        b.record_success(0.005)
        assert b.state == "closed"
        assert b.allow(t2)

    def test_registry_filter_and_metrics(self):
        collector = MetricsCollector()
        reg = PeerBreakers(collector=collector)
        for _ in range(3):
            reg.record("dead:53", False)
        reg.record("live:53", True, 0.004)
        assert reg.get("dead:53").state == "open"
        assert reg.filter(["dead:53", "live:53"]) == ["live:53"]
        assert reg.open_count() == 1
        g = collector.get("binder_breaker_state")
        assert g.value({"peer": "dead:53"}) == 2.0
        assert g.value({"peer": "(max)"}) == 2.0
        assert reg.hedge_delay("live:53") >= PeerBreakers.HEDGE_FLOOR

    def test_rcode_error_is_a_live_peer(self):
        reg = PeerBreakers()
        for _ in range(10):
            reg.record("p:53", True)    # REFUSED et al. = responses
        assert reg.get("p:53").state == "closed"


def _blackhole_upstream():
    """A ChaosUpstream with every packet dropped: silence, no ICMP —
    the worst-case dead peer."""
    plan = FaultPlan(seed=3)
    plan.upstream.set(dead=1)
    return ChaosUpstream(plan, hosts={})


class TestDeadPeerLatency:
    """The satellite pin: a dead first resolver must cost <100 ms per
    query once its breaker is open (it cost the full 3 s timeout per
    query in the reference)."""

    def test_open_breaker_bounds_dead_peer_cost(self):
        async def go():
            dead = _blackhole_upstream()
            dead_port = await dead.start()
            live = ChaosUpstream(FaultPlan(),
                                 hosts={"w.foo.com": "10.1.1.1"})
            live_port = await live.start()
            breakers = PeerBreakers()
            client = DnsClient(timeout=0.1, breakers=breakers)
            ups = [f"127.0.0.1:{dead_port}", f"127.0.0.1:{live_port}"]
            try:
                # warm-up queries: each one times the dead peer out
                # (recorded via the future's outcome callback even when
                # a hedged winner cancels the task) until its breaker
                # opens
                for _ in range(6):
                    recs = await client.lookup("w.foo.com", Type.A, ups)
                    assert [r.address for r in recs] == ["10.1.1.1"]
                    await asyncio.sleep(0.12)   # let the sweep settle
                    if breakers.get(ups[0]).state == "open":
                        break
                assert breakers.get(ups[0]).state == "open"
                # the pin: with the breaker open the dead peer adds
                # <100 ms (it is skipped outright)
                t0 = time.monotonic()
                recs = await client.lookup("w.foo.com", Type.A, ups)
                elapsed = time.monotonic() - t0
                assert [r.address for r in recs] == ["10.1.1.1"]
                assert elapsed < 0.1, f"dead peer cost {elapsed:.3f}s " \
                    "with its breaker open"
            finally:
                client.close()
                await dead.stop()
                await live.stop()

        asyncio.run(go())

    def test_all_open_fails_fast_not_hangs(self):
        async def go():
            breakers = PeerBreakers()
            for _ in range(3):
                breakers.record("192.0.2.1:53", False)
            client = DnsClient(timeout=3.0, breakers=breakers)
            from binder_tpu.recursion.client import UpstreamError
            t0 = time.monotonic()
            try:
                with pytest.raises(UpstreamError):
                    await client.lookup_raw("x.foo.com", Type.A,
                                            ["192.0.2.1:53"])
            finally:
                client.close()
            assert time.monotonic() - t0 < 0.1

        asyncio.run(go())

    def test_hedge_beats_slow_peer(self):
        """A silent (not yet broken) first peer costs one hedge
        stagger, not the full timeout."""
        async def go():
            slow_plan = FaultPlan()
            slow_plan.upstream.set(delay_ms=2000)
            slow = ChaosUpstream(slow_plan, hosts={"w.foo.com": "10.2.2.2"})
            slow_port = await slow.start()
            live = ChaosUpstream(FaultPlan(),
                                 hosts={"w.foo.com": "10.1.1.1"})
            live_port = await live.start()
            breakers = PeerBreakers()
            client = DnsClient(timeout=3.0, concurrency=1,
                               breakers=breakers)
            try:
                t0 = time.monotonic()
                recs = await client.lookup(
                    "w.foo.com", Type.A,
                    [f"127.0.0.1:{slow_port}", f"127.0.0.1:{live_port}"])
                elapsed = time.monotonic() - t0
                assert [r.address for r in recs] == ["10.1.1.1"]
                # hedge default 0.25s + scheduling; far under the 2s
                # the slow peer (or the 3s timeout) would cost
                assert elapsed < 1.0
            finally:
                client.close()
                await slow.stop()
                await live.stop()

        asyncio.run(go())


# ---------------------------------------------------------------------------
# stale-serve degradation policy


class TestDegradationPolicy:
    def test_state_machine_and_metrics(self):
        collector = MetricsCollector()
        recorder = FlightRecorder()
        store, cache = make_fixture(recorder=recorder)
        pol = DegradationPolicy(store=store, zk_cache=cache,
                                max_staleness_s=0.15,
                                collector=collector, recorder=recorder)
        seen = []
        pol.on_transition(lambda old, new: seen.append((old, new)))
        assert pol.mode() == "fresh"
        store.lose_session()
        assert pol.mode() == "stale-serving"
        time.sleep(0.2)
        assert pol.mode() == "stale-exhausted"
        store.start_session()
        assert pol.mode() == "fresh"
        assert seen == [("fresh", "stale-serving"),
                        ("stale-serving", "stale-exhausted"),
                        ("stale-exhausted", "fresh")]
        kinds = [e["type"] for e in recorder.events()]
        assert kinds.count("degraded-transition") == 3
        snap = pol.introspect()
        assert snap["state"] == "fresh"
        assert len(snap["transitions"]) == 3

    def test_stale_serving_clamps_ttl(self):
        async def go():
            server, store = await start_server(
                degradation={"maxStalenessSeconds": 30.0,
                             "staleTtlClampSeconds": 5})
            store.put_json("/com/foo/slow",
                           {"type": "host", "ttl": 3600,
                            "host": {"address": "10.0.0.9"}})
            try:
                msg = await udp_ask(server.udp_port, "slow.foo.com",
                                    Type.A)
                assert msg.answers[0].ttl == 3600
                epoch_before = server.zk_cache.epoch
                store.lose_session()
                msg = await udp_ask(server.udp_port, "slow.foo.com",
                                    Type.A)
                assert msg.rcode == Rcode.NOERROR
                assert msg.answers[0].address == "10.0.0.9"
                assert msg.answers[0].ttl == 5          # clamped
                # the transition flushed every cached lane
                assert server.zk_cache.epoch > epoch_before
                assert server._policy.stale_served >= 1
            finally:
                await server.stop()

        asyncio.run(go())

    def test_exhausted_servfail_and_nodata(self):
        async def go():
            for action, want in (("servfail", Rcode.SERVFAIL),
                                 ("nodata", Rcode.NOERROR)):
                server, store = await start_server(
                    degradation={"maxStalenessSeconds": 0.05,
                                 "exhaustedAction": action})
                try:
                    store.lose_session()
                    await asyncio.sleep(0.1)
                    msg = await udp_ask(server.udp_port, "web.foo.com",
                                        Type.A)
                    assert msg.rcode == want
                    assert msg.answers == []
                    if action == "nodata":
                        assert msg.authorities, "NODATA must carry SOA"
                    # recovery: session back -> fresh data served again
                    store.start_session()
                    msg = await udp_ask(server.udp_port, "web.foo.com",
                                        Type.A)
                    assert msg.rcode == Rcode.NOERROR
                    assert msg.answers[0].address == "10.0.0.1"
                finally:
                    await server.stop()

        asyncio.run(go())

    def test_cached_answers_do_not_outlive_the_cap(self):
        """The cap covers the cached lanes: an answer cached while
        fresh must not be served once the policy is exhausted."""
        async def go():
            server, store = await start_server(
                degradation={"maxStalenessSeconds": 0.05})
            try:
                # populate the per-key answer cache while fresh
                for _ in range(2):
                    msg = await udp_ask(server.udp_port, "web.foo.com",
                                        Type.A)
                    assert msg.rcode == Rcode.NOERROR
                store.lose_session()
                await asyncio.sleep(0.1)
                msg = await udp_ask(server.udp_port, "web.foo.com",
                                    Type.A)
                assert msg.rcode == Rcode.SERVFAIL
                assert msg.answers == []
            finally:
                await server.stop()

        asyncio.run(go())


# ---------------------------------------------------------------------------
# overload admission control


class TestAdmission:
    def test_inflight_oldest_shed(self):
        async def go():
            server, store = await start_server(
                admission={"maxInflight": 4})
            # park every query in a never-completing handler
            release = asyncio.Event()

            def slow_handle(query):
                async def wait():
                    await release.wait()
                    query.set_error(Rcode.REFUSED)
                    query.respond()
                return wait()

            server.resolver.handle = slow_handle
            server.engine.fastpath = None
            try:
                loop = asyncio.get_running_loop()
                answers = [loop.create_future() for _ in range(6)]

                class Proto(asyncio.DatagramProtocol):
                    def __init__(self, i):
                        self.i = i

                    def connection_made(self, transport):
                        transport.sendto(make_query(
                            f"q{self.i}.foo.com", Type.A,
                            qid=self.i + 1).encode())

                    def datagram_received(self, data, addr):
                        if not answers[self.i].done():
                            answers[self.i].set_result(data)

                transports = []
                for i in range(6):
                    tr, _ = await loop.create_datagram_endpoint(
                        lambda i=i: Proto(i),
                        remote_addr=("127.0.0.1", server.udp_port))
                    transports.append(tr)
                    await asyncio.sleep(0.01)
                # 6 in flight with cap 4: the two OLDEST were shed with
                # an immediate REFUSED; the newest 4 still hang
                shed = await asyncio.wait_for(
                    asyncio.gather(answers[0], answers[1]), 2.0)
                for wire in shed:
                    msg = Message.decode(wire)
                    assert msg.rcode == Rcode.REFUSED
                assert len(server.engine.inflight) == 4
                adm = server._admission
                assert adm.shed_counts["inflight-overflow"] == 2
                release.set()
                await asyncio.sleep(0.05)
                for tr in transports:
                    tr.close()
            finally:
                await server.stop()

        asyncio.run(go())

    def test_recursion_token_bucket(self):
        adm = AdmissionControl(recursion_rate=1000.0, recursion_burst=3)
        assert all(adm.allow_recursion("10.0.0.1") for _ in range(3))
        assert not adm.allow_recursion("10.0.0.1")
        # other clients are unaffected
        assert adm.allow_recursion("10.0.0.2")
        assert adm.shed_counts["recursion-ratelimit"] == 1

    def test_recursion_shed_is_wellformed_refused(self):
        async def go():
            # recursion configured, bucket of burst 1: the second RD
            # miss from one client is REFUSED without upstream work
            store, cache = make_fixture()
            recursion = Recursion(
                zk_cache=cache, dns_domain=DOMAIN,
                datacenter_name="dc0",
                source=StaticResolverSource({"remote":
                                             ["192.0.2.9:53"]}))
            await recursion.wait_ready()
            server = BinderServer(
                zk_cache=cache, dns_domain=DOMAIN,
                datacenter_name="dc0", host="127.0.0.1", port=0,
                collector=MetricsCollector(), query_log=False,
                recursion=recursion,
                admission={"recursionRate": 0.001, "recursionBurst": 1})
            await server.start()
            try:
                t0 = time.monotonic()
                # burst 1: first forward goes upstream (dead peer -> its
                # own slow path), so spend the token with a query that
                # can't linger — use a name in a DC we don't know
                msg = await udp_ask(server.udp_port,
                                    "w.nodc.foo.com", Type.A, rd=True)
                assert msg.rcode == Rcode.REFUSED
                msg = await udp_ask(server.udp_port,
                                    "w.nodc.foo.com", Type.A, rd=True)
                assert msg.rcode == Rcode.REFUSED
                assert time.monotonic() - t0 < 2.0
                assert server._admission.shed_counts[
                    "recursion-ratelimit"] >= 1
            finally:
                await server.stop()
                await recursion.close()

        asyncio.run(go())


# ---------------------------------------------------------------------------
# metrics + status pins


class TestDegradationMetrics:
    def _full_stack_scrape(self):
        async def go():
            collector = MetricsCollector()
            recorder = FlightRecorder()
            store, cache = make_fixture(recorder=recorder,
                                        collector=collector)
            recursion = Recursion(
                zk_cache=cache, dns_domain=DOMAIN,
                datacenter_name="dc0",
                source=StaticResolverSource({}),
                collector=collector, recorder=recorder)
            await recursion.wait_ready()
            server = BinderServer(
                zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
                host="127.0.0.1", port=0, collector=collector,
                query_log=False, flight_recorder=recorder,
                recursion=recursion,
                degradation={}, admission={})
            await server.start()
            try:
                return collector.expose(), server
            finally:
                await server.stop()
                await recursion.close()

        return asyncio.run(go())

    def test_scrape_passes_validator(self):
        text, _server = self._full_stack_scrape()
        assert validate_degradation_metrics(text) == []

    def test_validator_catches_removals(self):
        text, _server = self._full_stack_scrape()
        # strip one family entirely: must fail
        gutted = "\n".join(l for l in text.splitlines()
                           if "binder_degraded_state" not in l) + "\n"
        errs = validate_degradation_metrics(gutted)
        assert any("binder_degraded_state" in e for e in errs)
        # strip one pinned label series: must fail
        gutted = "\n".join(
            l for l in text.splitlines()
            if 'reason="inflight-overflow"' not in l) + "\n"
        errs = validate_degradation_metrics(gutted)
        assert any("inflight-overflow" in e for e in errs)

    def test_status_snapshot_carries_policy_section(self):
        async def go():
            from binder_tpu.introspect import Introspector
            collector = MetricsCollector()
            server, store = await start_server(
                collector=collector,
                degradation={}, admission={})
            try:
                intro = Introspector(server=server)
                snap = intro.snapshot()
                assert validate_status_snapshot(snap) == []
                pol = snap["policy"]
                assert pol["degradation"]["state"] == "fresh"
                assert pol["admission"]["max_inflight"] == 512
                store.lose_session()
                snap = intro.snapshot()
                assert snap["policy"]["degradation"]["state"] \
                    == "stale-serving"
            finally:
                await server.stop()

        asyncio.run(go())


# ---------------------------------------------------------------------------
# the chaos soak (acceptance criterion)


class TestChaosSoak:
    SOAK_SECONDS = 3.0

    def test_soak_under_session_loss_and_packet_loss(self):
        asyncio.run(self._soak())

    async def _soak(self):
        collector = MetricsCollector()
        recorder = FlightRecorder()
        store, cache = make_fixture(
            recorder=recorder, collector=collector,
            hosts={f"w{i}": f"10.0.1.{i + 1}" for i in range(8)})

        # recursion upstream with scripted packet loss
        plan = FaultPlan(seed=42)
        upstream = ChaosUpstream(
            plan, hosts={"w.remote.foo.com": "10.8.0.1"})
        up_port = await upstream.start()
        recursion = Recursion(
            zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
            source=StaticResolverSource(
                {"remote": [f"127.0.0.1:{up_port}"]}),
            client=DnsClient(timeout=0.25),
            collector=collector, recorder=recorder)
        await recursion.wait_ready()

        max_staleness = 0.8
        server = BinderServer(
            zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
            host="127.0.0.1", port=0, collector=collector,
            query_log=False, flight_recorder=recorder,
            recursion=recursion,
            degradation={"maxStalenessSeconds": max_staleness,
                         "staleTtlClampSeconds": 3},
            admission={"maxInflight": 64})
        await server.start()

        # scripted faults: upstream loss early, session killed
        # mid-churn, both healed before the end
        soak_plan = FaultPlan(seed=7) \
            .at(0.3, "upstream", loss=0.4) \
            .at(0.6, "lose-session") \
            .at(0.7, "watch-storm", n=50) \
            .at(2.0, "restore-session") \
            .at(2.2, "upstream", clear=True)
        # the upstream faults must act on the UPSTREAM's plan
        soak_plan.upstream = plan.upstream

        def mutate(i):
            store.put_json(f"/com/foo/churn{i % 4}",
                           {"type": "host",
                            "host": {"address": f"10.7.0.{i % 200 + 1}"}})

        driver = ChaosDriver(soak_plan, store=store, mutate=mutate,
                             recorder=recorder)
        chaos_task = driver.start()

        pol = server._policy
        stats = {"ok": 0, "refused": 0, "servfail": 0, "stale": 0}
        t_end = asyncio.get_running_loop().time() + self.SOAK_SECONDS
        i = 0
        try:
            while asyncio.get_running_loop().time() < t_end:
                name = f"w{i % 8}.foo.com"
                rd = i % 5 == 0
                if rd:
                    name = "w.remote.foo.com"
                i += 1
                try:
                    msg = await udp_ask(server.udp_port, name, Type.A,
                                        qid=(i % 0xFFFF) + 1, rd=rd,
                                        timeout=1.0)
                except asyncio.TimeoutError:
                    # recursion forwards may legitimately exceed the
                    # ask window under 40% loss; local queries may not
                    assert rd, f"local query for {name} hung"
                    continue
                mode = pol.mode()
                if msg.rcode == Rcode.NOERROR and msg.answers:
                    # INVARIANT: data answers only while fresh or
                    # within the staleness cap — and stale answers are
                    # clamped
                    assert mode in ("fresh", "stale-serving")
                    if mode == "stale-serving" and not rd:
                        assert all(a.ttl <= 3 for a in msg.answers)
                        stats["stale"] += 1
                    ds = getattr(store, "disconnected_seconds")()
                    if ds is not None and not rd:
                        assert ds <= max_staleness + 0.5, \
                            "served staler than the cap"
                    stats["ok"] += 1
                elif msg.rcode == Rcode.REFUSED:
                    stats["refused"] += 1
                elif msg.rcode == Rcode.SERVFAIL:
                    # only legitimate while exhausted (or store down)
                    stats["servfail"] += 1
                await asyncio.sleep(0.01)

            await asyncio.wait_for(chaos_task, 5.0)
            # every phase actually exercised
            assert stats["ok"] > 0
            assert stats["stale"] > 0, "stale-serving window not observed"
            assert stats["servfail"] > 0, "exhausted window not observed"

            # RE-CONVERGENCE: session is back -> fresh, serving, and
            # every degradation signal returns to rest
            gen_before = cache.gen
            store.put_json("/com/foo/w0",
                           {"type": "host",
                            "host": {"address": "10.0.1.99"}})
            assert cache.gen > gen_before, "mirror gen must advance"
            for _ in range(50):
                if pol.mode() == "fresh":
                    break
                await asyncio.sleep(0.05)
            assert pol.mode() == "fresh"
            assert collector.get("binder_degraded_state").value() == 0.0
            msg = await udp_ask(server.udp_port, "w0.foo.com", Type.A,
                                qid=9999)
            assert msg.rcode == Rcode.NOERROR
            assert msg.answers[0].address == "10.0.1.99"
            assert recursion.breakers.open_count() == 0
            # the flight recorder kept the story
            kinds = {e["type"] for e in recorder.events()}
            assert "chaos-inject" in kinds
            assert "degraded-transition" in kinds
        finally:
            await server.stop()
            await recursion.close()
            await upstream.stop()


class TestChaosSmokeHarness:
    """`make chaos-smoke`'s harness, run short: tier-1 proves the
    EXACT script the 30 s make target runs (same invariants, same
    FaultPlan shape) — the smoke can never rot unnoticed."""

    def test_smoke_harness_short(self):
        import tools.chaos_smoke as cs
        stats = cs.run_smoke(duration=3.0)
        assert stats["ok"] > 0
        assert stats["stale"] > 0
        assert stats["servfail"] > 0
        assert stats["flight_events"].get("chaos-inject", 0) >= 6
        assert stats["flight_events"].get("degraded-transition", 0) >= 3


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
