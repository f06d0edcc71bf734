"""Serving-plane verification + propagation tracing (ISSUE 16).

What this pins down:

- the incremental checker catches each scripted corruption through the
  invariant that owns it: a dropped reverse entry (ptr-coherence), a
  missing service member (dangling-srv), and a skewed mutation log
  (replica-digest);
- a violation is surfaced everywhere at once: flight-recorder event,
  ``binder_verify_violations_total`` counter, and the ``/status``
  verify section — and ``validate_verify_metrics`` /
  ``validate_status_snapshot`` hold throughout;
- the delta queue sheds (counted, never unbounded) past MAX_QUEUE;
- the propagation tracer: distinct trace ids per store event, handed-
  down contexts consumed exactly once, stage latencies folded into the
  introspected p50/p99, and the mutation->mirror->native-install
  chain observed end to end through a live server;
- replica-digest mechanics: the rolling digest is deterministic over
  the replicated substance and blind to trace freight; a replica
  flags a divergence exactly once and resyncs; digests stay in parity
  across a snapshot re-attach (the shard-kill/respawn path);
- the audit stays inside its time budget per slice at a 20k-name zone
  (100k behind an env gate) — the checker must never become the loop
  stall it exists to detect;
- the chaos DSL parses the verify-plane actions (string selectors
  included) and the driver dispatches them to the verify target.
"""
import asyncio
import importlib.machinery
import importlib.util
import os
import socket
import time

import pytest

from binder_tpu.chaos import ChaosDriver, FaultPlan
from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.introspect import FlightRecorder, Introspector
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.shard import ReplicaStore, protocol
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.store.cache import domain_to_path
from binder_tpu.store.fake import populate_synthetic
from binder_tpu.verify import PropagationTracer, Verifier
from tools.lint import validate_status_snapshot, validate_verify_metrics

DOMAIN = "verify.unit"


def make_fixture(recorder=None, collector=None):
    """8 hosts, one service with 3 members — every invariant has
    something to bite on."""
    store = FakeStore(recorder=recorder)
    cache = MirrorCache(store, DOMAIN, collector=collector,
                        recorder=recorder)
    for i in range(8):
        store.put_json(domain_to_path(f"w{i}.{DOMAIN}"),
                       {"type": "host",
                        "host": {"address": f"10.77.0.{i + 1}"}})
    store.put_json(domain_to_path(f"svc.{DOMAIN}"),
                   {"type": "service",
                    "service": {"srvce": "_http", "proto": "_tcp",
                                "port": 80}})
    for i in range(3):
        store.put_json(domain_to_path(f"m{i}.svc.{DOMAIN}"),
                       {"type": "host",
                        "host": {"address": f"10.77.9.{i + 1}"}})
    store.start_session()
    return store, cache


async def start_server(recorder, collector, **kw):
    store, cache = make_fixture(recorder=recorder, collector=collector)
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="dc0", host="127.0.0.1",
                          port=0, collector=collector,
                          query_log=kw.pop("query_log", False),
                          flight_recorder=recorder,
                          verify={"auditIntervalSeconds": 0.05}, **kw)
    await server.start()
    return server, store


async def udp_ask(port, name, qtype, qid=1):
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    class Proto(asyncio.DatagramProtocol):
        def connection_made(self, transport):
            transport.sendto(make_query(name, qtype, qid=qid).encode())

        def datagram_received(self, data, addr):
            if not fut.done():
                fut.set_result(data)

    transport, _ = await loop.create_datagram_endpoint(
        Proto, remote_addr=("127.0.0.1", port))
    try:
        return Message.decode(await asyncio.wait_for(fut, 5.0))
    finally:
        transport.close()


# -- the incremental checker (no loop: enqueue drains inline) --

class TestIncrementalChecker:
    def test_clean_zone_checks_without_violations(self):
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache)
        vf.enqueue_tags(list(cache.nodes))
        assert sum(vf.checks.values()) > 0
        assert sum(vf.violations.values()) == 0

    def test_dropped_reverse_entry_is_ptr_coherence(self):
        recorder = FlightRecorder(capacity=64)
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache, recorder=recorder)
        ip = "10.77.0.3"
        assert cache.rev_lookup.pop(ip) is not None
        vf.enqueue_tags([f"w2.{DOMAIN}"])
        assert vf.violations["ptr-coherence"] == 1
        ev = [e for e in recorder.events()
              if e["type"] == "verify-violation"]
        assert ev and ev[-1]["invariant"] == "ptr-coherence"
        assert ev[-1]["ip"] == ip

    def test_reverse_name_tag_checks_the_reverse_side(self):
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache)
        # corrupt the map: reverse entry points at a node the mirror
        # no longer carries
        node = cache.rev_lookup["10.77.0.1"]
        del cache.nodes[node.domain]
        vf.enqueue_tags(["1.0.77.10.in-addr.arpa"])
        assert vf.violations["ptr-coherence"] == 1

    def test_missing_service_member_is_dangling_srv(self):
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache)
        del cache.nodes[f"m1.svc.{DOMAIN}"]
        vf.enqueue_tags([f"svc.{DOMAIN}"])
        assert vf.violations["dangling-srv"] == 1

    def test_queue_sheds_past_cap_and_counts(self):
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache)
        n = vf.MAX_QUEUE + 500

        class _Tags:
            """Generator-shaped tag feed: shed must not require a
            materialized list."""
            def __iter__(self):
                return (f"ghost{i}.{DOMAIN}" for i in range(n))

        vf.enqueue_tags(_Tags())
        assert vf.skipped["queue-shed"] == 500

    def test_note_digest_counts_and_violates(self):
        recorder = FlightRecorder(capacity=64)
        _, cache = make_fixture()
        vf = Verifier(zk_cache=cache, recorder=recorder)
        vf.note_digest(7, True)
        vf.note_digest(8, False, have="aaaa", want="bbbb")
        assert vf.checks["replica-digest"] == 2
        assert vf.violations["replica-digest"] == 1
        ev = [e for e in recorder.events()
              if e["type"] == "verify-violation"]
        assert ev[-1]["generation"] == 8
        assert ev[-1]["have"] == "aaaa"


# -- the audit's find + the full surfacing round trip --

class TestViolationRoundTrip:
    def test_drop_reverse_to_flight_metrics_status(self):
        async def go():
            recorder = FlightRecorder(capacity=256)
            collector = MetricsCollector()
            server, store = await start_server(recorder, collector)
            vf = server._verify
            try:
                # no invalidation fires: only the audit can find it
                ip = server.drop_reverse()
                assert ip is not None
                vf.audit_cycle()
                assert vf.violations["ptr-coherence"] >= 1

                # flight recorder
                ev = [e for e in recorder.events()
                      if e["type"] == "verify-violation"]
                assert any(e["invariant"] == "ptr-coherence"
                           for e in ev)
                # metrics: counter advanced, full family validates
                text = collector.expose()
                assert 'invariant="ptr-coherence"' in text
                assert validate_verify_metrics(text) == []
                # /status: section present, snapshot schema holds
                intro = Introspector(server=server, recorder=recorder,
                                     name="t")
                intro.set_loop(asyncio.get_running_loop())
                snap = intro.snapshot()
                assert validate_status_snapshot(snap) == []
                sec = snap["verify"]
                assert sec["violations"]["ptr-coherence"] >= 1
                assert any(v["invariant"] == "ptr-coherence"
                           for v in sec["recent_violations"])
                # and the operator CLI renders it loudly
                loader = importlib.machinery.SourceFileLoader(
                    "bstat_cli", os.path.join(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))),
                        "bin", "bstat"))
                spec = importlib.util.spec_from_loader(
                    "bstat_cli", loader)
                bstat = importlib.util.module_from_spec(spec)
                loader.exec_module(bstat)
                out = bstat.render(snap)
                assert "VIOLATION ptr-coherence" in out
            finally:
                await server.stop()

        asyncio.run(go())


# -- propagation tracing --

class TestPropagationTracer:
    def test_distinct_ids_per_store_event(self):
        tr = PropagationTracer()
        tr.on_store_event(1)
        a = tr.current[0]
        tr.on_store_event(2)
        b = tr.current[0]
        assert a != b

    def test_observe_without_context_is_noop(self):
        tr = PropagationTracer()
        tr.observe("mirror-apply")
        assert tr.observed == 0

    def test_inherited_context_consumed_exactly_once(self):
        tr = PropagationTracer()
        tr.inherit("m1-aa", time.monotonic() - 0.5)
        tr.on_store_event(3)
        assert tr.current[0] == "m1-aa"
        tr.on_store_event(4)
        assert tr.current[0] != "m1-aa"
        # malformed handed-down fields never become a context
        tr.inherit(None, "not-a-time")
        tr.on_store_event(5)
        assert tr.current[0] != "m1-aa"

    def test_stage_latencies_fold_into_introspection(self):
        tr = PropagationTracer()
        tr.inherit("m1-bb", time.monotonic() - 0.25)
        tr.on_store_event(1)
        tr.observe("mirror-apply")
        tr.observe("replica-apply")
        snap = tr.introspect()
        assert snap["observed"] == 2
        st = snap["stages"]["mirror-apply"]
        assert st["count"] == 1
        assert 0.2 < st["p50_seconds"] < 5.0
        slow = snap["slowest"]
        assert slow and slow[0]["trace"] == "m1-bb"

    def test_mutation_to_install_traced_through_live_server(self):
        async def go():
            recorder = FlightRecorder(capacity=256)
            collector = MetricsCollector()
            server, store = await start_server(recorder, collector)
            vf = server._verify
            try:
                msg = await udp_ask(server.udp_port, f"w1.{DOMAIN}",
                                    Type.A)
                assert msg.rcode == Rcode.NOERROR
                store.put_json(domain_to_path(f"w1.{DOMAIN}"),
                               {"type": "host",
                                "host": {"address": "10.77.0.99"}})
                # deadline poll, not a fixed sleep: the zone drain
                # re-pushes in bounded batches between loop passes
                want = ("mirror-apply", "native-install")
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    prop = vf.introspect()["propagation"]
                    if all(prop["stages"][s]["count"] >= 1
                           for s in want):
                        break
                    await asyncio.sleep(0.02)
                for stage in want:
                    assert prop["stages"][stage]["count"] >= 1, stage
                assert prop["observed"] >= 2
            finally:
                await server.stop()

        asyncio.run(go())


# -- replica-digest mechanics --

class TestReplicaDigest:
    def _node_frame(self, name, addr, tr=None, t0=None):
        return protocol.node_frame(
            f"{name}.{DOMAIN}",
            {"type": "host", "host": {"address": addr}}, tr, t0)

    def test_digest_deterministic_and_blind_to_trace_freight(self):
        f1 = self._node_frame("x", "10.1.1.1")
        f2 = self._node_frame("x", "10.1.1.1", "m1-ff", 123.25)
        f3 = self._node_frame("x", "10.1.1.2")
        assert protocol.delta_digest("0", f1) \
            == protocol.delta_digest("0", f2)
        assert protocol.delta_digest("0", f1) \
            != protocol.delta_digest("0", f3)
        # chaining is order-sensitive (it is a log digest, not a set)
        ab = protocol.delta_digest(protocol.delta_digest("0", f1), f3)
        ba = protocol.delta_digest(protocol.delta_digest("0", f3), f1)
        assert ab != ba

    def test_replica_flags_divergence_once_and_resyncs(self):
        sup_end, worker_end = socket.socketpair()
        try:
            replica = ReplicaStore(worker_end, 0)
            replica._dg = "0"           # as armed at snap-end
            outcomes = []
            replica.on_digest = lambda gen, ok, have, want: \
                outcomes.append((gen, ok))

            f = self._node_frame("y", "10.2.2.2")
            replica._apply(f)
            good = protocol.delta_digest("0", f)
            replica._apply(protocol.digest_frame(1, good))
            assert outcomes == [(1, True)]

            # owner claims a digest we never saw the frames for
            replica._apply(protocol.digest_frame(2, "feedbeefdead0000"))
            assert outcomes[-1] == (2, False)
            # resynced to the owner's roll: no cascade next frame
            assert replica._dg == "feedbeefdead0000"
            # the mismatch went up-channel as a digest report
            sup_end.settimeout(5.0)
            frames = protocol.decode_frames(
                bytearray(sup_end.recv(65536)))
            reports = [fr for fr in frames
                       if fr.get("op") == "digest-report"]
            assert len(reports) == 1
            assert reports[0]["ok"] is False
            assert reports[0]["want"] == "feedbeefdead0000"
        finally:
            sup_end.close()
            worker_end.close()

    def test_delta_frame_trace_feeds_replica_tracer(self):
        """The worker-side half of _wire_shard_worker: the replica
        stages the frame's handed-down context, the mirror's bump_gen
        consumes it, and replica-apply reports against the OWNER's
        t0."""
        sup_end, worker_end = socket.socketpair()
        try:
            replica = ReplicaStore(worker_end, 0)
            tracer = PropagationTracer()
            replica.tracer = tracer
            cache = MirrorCache(replica, DOMAIN)
            cache.tracer = tracer
            replica.start_session()
            # untraced create first: a node CREATE fires the parent's
            # children-watch too (two store events — the second would
            # clobber the inherited context with a fresh one); the
            # traced hot-churn flow is an UPDATE on an existing node,
            # which fires exactly one
            replica._apply(self._node_frame("z", "10.3.3.1"))
            replica._apply(self._node_frame(
                "z", "10.3.3.3", "m9-01", time.monotonic() - 0.1))
            snap = tracer.introspect()
            assert snap["stages"]["replica-apply"]["count"] >= 1
            traced = [s for s in snap["slowest"]
                      if s["trace"] == "m9-01"]
            # end-to-end latency: against the owner's 0.1s-old t0
            assert traced and traced[0]["seconds"] > 0.05
            assert cache.lookup(f"z.{DOMAIN}").data["host"][
                "address"] == "10.3.3.3"
        finally:
            sup_end.close()
            worker_end.close()

    def test_parity_across_snapshot_reattach(self):
        """The shard-kill/respawn path: a replica that re-attaches via
        a fresh snapshot re-arms at "0" alongside the owner's roll, so
        digests agree again — divergence cannot outlive a respawn."""
        from binder_tpu.shard.supervisor import ShardLink, ShardSupervisor

        class _StubProc:
            pid = 0

            def poll(self):
                return None

        async def run():
            store, cache = make_fixture()
            sup = ShardSupervisor(
                options={"shards": 1, "host": "127.0.0.1", "port": 0,
                         "dnsDomain": DOMAIN},
                store=store, cache=cache, collector=MetricsCollector())
            sup._loop = asyncio.get_running_loop()

            def attach(shard):
                sup_end, worker_end = socket.socketpair()
                sup_end.setblocking(False)
                link = ShardLink(shard, _StubProc(), sup_end)
                sup.links[shard] = link
                sup._send_snapshot(link)
                replica = ReplicaStore(worker_end, shard)
                while link.snap_queue is not None:
                    sup._pump_snapshot(link)
                replica.read_snapshot(timeout=30.0)
                return link, replica

            def drain_until(replica, done):
                replica._sock.settimeout(5.0)
                while not done():
                    for frame in replica._recv_frames():
                        replica._apply(frame)

            link, replica = attach(0)
            outcomes = []
            replica.on_digest = lambda gen, ok, have, want: \
                outcomes.append(ok)
            assert replica._dg == "0" and link.dg == "0"

            store.put_json(domain_to_path(f"w0.{DOMAIN}"),
                           {"type": "host",
                            "host": {"address": "10.77.0.201"}})
            drain_until(replica, lambda: outcomes)
            assert outcomes and all(outcomes)
            assert replica._dg == link.dg != "0"

            # kill + respawn: fresh link, fresh snapshot, fresh roll
            sup._close_link(link)
            del sup.links[0]
            replica.close()
            link2, replica2 = attach(0)
            outcomes2 = []
            replica2.on_digest = lambda gen, ok, have, want: \
                outcomes2.append(ok)
            assert replica2._dg == "0" and link2.dg == "0"
            assert replica2.exists(
                domain_to_path(f"w0.{DOMAIN}"))
            store.put_json(domain_to_path(f"w0.{DOMAIN}"),
                           {"type": "host",
                            "host": {"address": "10.77.0.202"}})
            drain_until(replica2, lambda: outcomes2)
            assert outcomes2 and all(outcomes2)
            assert replica2._dg == link2.dg
            sup._close_link(link2)
            replica2.close()

        asyncio.run(run())


# -- the sampled audit at zone scale --

def _audit_scale(names, budget_factor):
    store = FakeStore()
    populate_synthetic(store, DOMAIN, names)
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    vf = Verifier(zk_cache=cache, config={"auditSample": 4})
    worst = 0.0
    passes0 = vf.audit_passes
    while vf.audit_passes == passes0 or vf._audit_work:
        t0 = time.perf_counter()
        vf.audit_slice()
        worst = max(worst, time.perf_counter() - t0)
    assert vf.audit_passes == passes0 + 1
    assert sum(vf.violations.values()) == 0
    assert vf.checks["ptr-coherence"] > 0
    # each slice must stay well under the loop-lag watchdog's 250 ms
    # stall threshold — the 2 ms budget plus one refill's list() over
    # the node index; the factor absorbs CI-box jitter
    assert worst < 0.25 * budget_factor, worst
    return worst


class TestAuditScale:
    def test_20k_zone_slices_stay_inside_budget(self):
        _audit_scale(20000, budget_factor=0.5)

    @pytest.mark.skipif(
        "BINDER_VERIFY_SCALE" not in os.environ,
        reason="set BINDER_VERIFY_SCALE=1 for the 100k audit tier")
    def test_100k_zone_slices_stay_inside_budget(self):
        _audit_scale(100000, budget_factor=1.0)


# -- chaos DSL: the verify-plane actions --

class TestChaosVerifyActions:
    def test_parse_actions_with_string_selectors(self):
        plan = FaultPlan.parse(
            "at 0.5 rrl-flood qname=web.foo.com\n"
            "at 1.0 drop-reverse ip=10.0.0.1\n"
            "at 1.5 skew-replica shard=0 frames=2\n"
            "at 2.0 drop-reverse")
        acts = [(t, a, kw) for t, a, kw in plan.timeline]
        assert acts[0] == (0.5, "rrl-flood", {"qname": "web.foo.com"})
        assert acts[1] == (1.0, "drop-reverse", {"ip": "10.0.0.1"})
        assert acts[2] == (1.5, "skew-replica",
                           {"shard": 0, "frames": 2})
        assert acts[3] == (2.0, "drop-reverse", {})

    def test_parse_rejects_empty_selector(self):
        with pytest.raises(ValueError):
            FaultPlan.parse("at 1 drop-reverse ip=")

    def test_driver_dispatches_to_verify_target(self):
        calls = []

        class Target:
            def drop_reverse(self, ip=None):
                calls.append(("drop", ip))
                return ip

            def skew_replica(self, shard=-1, frames=1):
                calls.append(("skew", shard, frames))
                return shard

        plan = (FaultPlan()
                .at(0.0, "drop-reverse", ip="10.9.9.9")
                .at(0.0, "skew-replica", shard=1, frames=3))
        recorder = FlightRecorder(capacity=64)
        driver = ChaosDriver(plan, verify_target=Target(),
                             recorder=recorder)
        asyncio.run(driver.run())
        assert ("drop", "10.9.9.9") in calls
        assert ("skew", 1, 3) in calls
        injected = [e for e in recorder.events()
                    if e["type"] == "chaos-inject"]
        assert len(injected) == 2

    def test_missing_target_or_hook_is_skipped_not_fatal(self):
        plan = FaultPlan().at(0.0, "drop-reverse")
        asyncio.run(ChaosDriver(plan).run())
        asyncio.run(ChaosDriver(plan, verify_target=object()).run())
