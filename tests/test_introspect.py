"""Live introspection & health layer (binder_tpu/introspect).

What this pins down end to end:

- the status snapshot is schema-complete under the fake store (every
  section and key the validator requires, live over HTTP) and stays
  consistent while the mirror churns under it;
- the store session state machine distinguishes never-connected from
  session-lost, with measured (not inferred) disconnected_seconds —
  for both FakeStore and the real ZK wire client;
- the flight recorder is bounded, ordered, and dumps on SIGUSR2 with
  multiple distinct event types;
- the loop-lag watchdog observes real stalls into
  binder_loop_lag_seconds and fires loop-stall events;
- the in-flight query table exposes a live query's trace ID and
  current phase, and bin/bstat renders all of it from the endpoint;
- the balancer stats fold re-exports stage_cycles monotonically,
  including across a balancer restart.
"""
import asyncio
import contextlib
import importlib.machinery
import importlib.util
import io
import json
import os
import signal
import socket
import threading
import time
import urllib.request

import pytest

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.introspect import (BalancerStatsFold, FlightRecorder,
                                   Introspector, LoopLagWatchdog)
from binder_tpu.metrics.collector import MetricsCollector, MetricsServer
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.store.zk_client import ZKClient
from binder_tpu.store.zk_testserver import ZKTestServer
from tools.lint import validate_exposition, validate_status_snapshot

DOMAIN = "foo.com"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_fixture(recorder=None, collector=None):
    store = FakeStore(recorder=recorder)
    cache = MirrorCache(store, DOMAIN, collector=collector,
                        recorder=recorder)
    store.put_json("/com/foo/web",
                   {"type": "host", "host": {"address": "10.0.0.1"}})
    store.start_session()
    return store, cache


async def start_server(recorder=None, collector=None, **kw):
    store, cache = make_fixture(recorder=recorder, collector=collector)
    server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                          datacenter_name="dc0", host="127.0.0.1",
                          port=0, collector=collector or MetricsCollector(),
                          query_log=False, flight_recorder=recorder,
                          **kw)
    await server.start()
    return server, store


async def udp_ask(port, name, qtype, qid=1, timeout=5.0):
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
        data = await asyncio.wait_for(fut, timeout)
    finally:
        transport.close()
    return Message.decode(data)


def via_generic_path(server):
    """Force every query through the generic Python resolve path: the
    native fast path would otherwise answer simple A/IN shapes before
    the (test-instrumented) resolver ever runs."""
    server.engine.fastpath = None


def hold_resolver(server):
    """Replace the resolver's handle with one that parks the query
    until the returned event is set — a real, observable in-flight
    query with a phase stamp."""
    release = asyncio.Event()

    def slow_handle(query):
        query.stamp("store-lookup")

        async def wait():
            await asyncio.wait_for(release.wait(), 10)
            query.set_error(Rcode.REFUSED)
            query.respond()

        return wait()

    server.resolver.handle = slow_handle
    return release


class TestSnapshotSchema:
    def test_schema_complete_under_fake_store(self):
        async def run():
            recorder = FlightRecorder()
            collector = MetricsCollector()
            server, _store = await start_server(recorder=recorder,
                                                collector=collector)
            watchdog = LoopLagWatchdog(collector=collector,
                                       recorder=recorder, interval=0.01)
            watchdog.start()
            intro = Introspector(server=server, recorder=recorder,
                                 watchdog=watchdog, collector=collector)
            await udp_ask(server.udp_port, f"web.{DOMAIN}", Type.A)
            await asyncio.sleep(0.05)
            snap = intro.snapshot()
            assert validate_status_snapshot(snap) == []
            assert snap["store"]["state"] == "connected"
            assert snap["store"]["disconnected_seconds"] == 0.0
            assert snap["mirror"]["ready"] is True
            assert snap["mirror"]["nodes"] == 2          # root + web
            assert snap["mirror"]["reverse_entries"] == 1
            assert snap["mirror"]["staleness_seconds"] is not None
            assert snap["loop"]["samples"] >= 1
            # JSON round trip (what the HTTP route serves)
            assert validate_status_snapshot(
                json.loads(json.dumps(snap, default=str))) == []
            watchdog.stop()
            await server.stop()
        asyncio.run(run())

    def test_never_connected_vs_lost(self):
        # the distinction is_connected() alone could not express
        store = FakeStore()
        cache = MirrorCache(store, DOMAIN)
        intro = Introspector(zk_cache=cache, store=store)
        snap = intro.snapshot()
        assert snap["store"]["state"] == "never-connected"
        assert snap["store"]["disconnected_seconds"] is None
        assert snap["mirror"]["staleness_seconds"] is None

        store.put_json("/com/foo/web",
                       {"type": "host", "host": {"address": "10.0.0.1"}})
        store.start_session()
        assert intro.snapshot()["store"]["state"] == "connected"

        store.lose_session()
        time.sleep(0.02)
        snap = intro.snapshot()
        assert snap["store"]["state"] == "degraded"
        # exact measured loss age, and the mirror keeps serving (aging)
        assert 0.0 < snap["store"]["disconnected_seconds"] < 5.0
        assert snap["mirror"]["ready"] is True
        assert snap["mirror"]["staleness_seconds"] > 0.0
        edges = [(t["from"], t["to"]) for t in snap["store"]["transitions"]]
        assert ("never-connected", "connected") in edges
        assert ("connected", "degraded") in edges

    def test_recursion_peer_section(self):
        async def run():
            from binder_tpu.recursion import Recursion
            _store, cache = make_fixture()
            rec = Recursion(zk_cache=cache, dns_domain=DOMAIN,
                            datacenter_name="dc0",
                            ufds={"dcs": {"dc1": ["10.9.9.9"]}})
            await rec.wait_ready()
            intro = Introspector(zk_cache=cache, recursion=rec)
            snap = intro.snapshot()
            assert validate_status_snapshot(snap) == []
            r = snap["recursion"]
            assert r["ready"] is True
            assert r["datacenters"] == {"dc1": ["10.9.9.9"]}
            assert r["peer_count"] == 1
            assert r["last_refresh_age_seconds"] is not None
            assert r["case_mismatch_drops"] == 0
            await rec.close()
        asyncio.run(run())

    def test_consistent_under_concurrent_mutation(self):
        async def run():
            collector = MetricsCollector()
            server, store = await start_server(collector=collector)
            intro = Introspector(server=server, collector=collector)
            intro.set_loop(asyncio.get_running_loop())

            stop = threading.Event()
            failures = []

            def scrape():
                # foreign thread: every snapshot must route through the
                # loop and come back schema-valid, never torn/raising
                while not stop.is_set():
                    try:
                        errs = validate_status_snapshot(intro.snapshot())
                        if errs:
                            failures.append(errs)
                            return
                    except Exception as e:  # noqa: BLE001
                        failures.append(e)
                        return

            t = threading.Thread(target=scrape)
            t.start()
            try:
                for i in range(300):
                    store.put_json(
                        f"/com/foo/n{i % 20}",
                        {"type": "host",
                         "host": {"address": f"10.1.0.{i % 250 + 1}"}})
                    if i % 25 == 0:
                        store.expire_session()   # full rebuild mid-scrape
                        await asyncio.sleep(0)
            finally:
                stop.set()
                t.join(5)
            assert not failures, failures[:1]
            await server.stop()
        asyncio.run(run())


class TestSessionFlapSoak:
    """Rapid connected -> degraded -> connected cycling (ISSUE 4
    satellite): the mirror generation and epoch must be MONOTONIC
    across every flap (a regression would re-validate stale cached
    answers), the transition history stays bounded, and the snapshot
    stays schema-valid throughout."""

    def test_mirror_generation_monotonic_under_flapping(self):
        store, cache = make_fixture()
        intro = Introspector(zk_cache=cache, store=store)
        gens, epochs = [cache.gen], [cache.epoch]
        for cycle in range(25):
            store.lose_session()
            gens.append(cache.gen)
            epochs.append(cache.epoch)
            store.put_json(
                "/com/foo/web",
                {"type": "host",
                 "host": {"address": f"10.0.0.{cycle % 250 + 2}"}})
            store.start_session()    # full rebind (watch storm shape)
            gens.append(cache.gen)
            epochs.append(cache.epoch)
            snap = intro.snapshot()
            assert validate_status_snapshot(snap) == []
            assert snap["store"]["state"] == "connected"
        assert gens == sorted(gens), "mirror gen must be monotonic"
        assert epochs == sorted(epochs), "epoch must be monotonic"
        # every reconnect was a distinct establishment + rebuild epoch
        assert store.session_establishments == 26
        assert cache.epoch >= 26
        # bounded history: 25 flap cycles over a 64-edge deque
        assert len(store.session_transitions()) <= 64
        # and the mirror converged on the final write
        node = cache.lookup(f"web.{DOMAIN}")
        assert node.data["host"]["address"] == "10.0.0.26"

    def test_flapping_with_policy_keeps_degraded_state_fresh(self):
        """The degradation state machine rides the flaps without
        sticking: after the last reconnect it reads fresh and the
        one-hot session metric agrees."""
        from binder_tpu.policy import DegradationPolicy
        collector = MetricsCollector()
        store, cache = make_fixture(collector=collector)
        pol = DegradationPolicy(store=store, zk_cache=cache,
                                max_staleness_s=60.0,
                                collector=collector)
        for _ in range(10):
            store.lose_session()
            assert pol.mode() == "stale-serving"
            store.start_session()
            assert pol.mode() == "fresh"
        assert collector.get("binder_degraded_state").value() == 0.0
        assert collector.get("binder_zk_session_state") is None or True
        snap = pol.introspect()
        assert snap["state"] == "fresh"
        # 20 edges recorded, bounded by the history deque
        assert len(snap["transitions"]) <= 64


class TestZKSessionStates:
    def test_never_connected_without_ensemble(self):
        async def run():
            # nothing listening: the client keeps retrying but never
            # had a session — not the same thing as having lost one
            probe = socket.socket()
            probe.bind(("127.0.0.1", 0))
            free_port = probe.getsockname()[1]
            probe.close()
            client = ZKClient(address="127.0.0.1", port=free_port,
                              session_timeout_ms=2000)
            client.start()
            await asyncio.sleep(0.3)
            assert not client.is_connected()
            assert client.session_state() == "never-connected"
            assert client.disconnected_seconds() is None
            client.close()
            assert client.session_state() == "closed"
            await asyncio.sleep(0)
        asyncio.run(run())

    def test_lost_session_is_degraded_with_measured_age(self):
        async def run():
            server = ZKTestServer()
            await server.start()
            recorder = FlightRecorder()
            client = ZKClient(address="127.0.0.1", port=server.port,
                              session_timeout_ms=2000, recorder=recorder)
            client.start()
            deadline = asyncio.get_running_loop().time() + 5
            while not client.is_connected():
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.02)
            assert client.session_state() == "connected"
            assert client.disconnected_seconds() == 0.0
            assert client.session_establishments == 1

            await server.stop()          # the ensemble goes away
            t0 = time.monotonic()
            deadline = asyncio.get_running_loop().time() + 10
            while client.session_state() != "degraded":
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.02)
            assert not client.is_connected()
            disc = client.disconnected_seconds()
            assert disc is not None
            assert disc <= time.monotonic() - t0 + 1.0
            types = {e["type"] for e in recorder.events()}
            assert "session-transition" in types
            client.close()
            await asyncio.sleep(0)
        asyncio.run(run())


class TestFlightRecorder:
    def test_bounded_and_ordered(self):
        rec = FlightRecorder(capacity=16)
        for i in range(50):
            rec.record("slow-query", n=i)
        evs = rec.events()
        assert len(evs) == 16
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs) and seqs[-1] == 50
        assert evs[0]["n"] == 34          # oldest rotated out
        assert rec.recorded == 50 and rec.dropped == 34
        assert rec.stats()["by_type"] == {"slow-query": 50}
        assert rec.events(last=4) == evs[-4:]

    def test_dump_file(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.record("loop-stall", lag_s=0.5)
        path = rec.dump(str(tmp_path / "flight.json"))
        with open(path) as f:
            payload = json.load(f)
        assert payload["pid"] == os.getpid()
        assert payload["events"][0]["type"] == "loop-stall"
        # the dump itself is recorded (postmortem shows who dumped)
        assert rec.events()[-1]["type"] == "dump"

    def test_sigusr2_dump_replays_event_types(self, tmp_path):
        async def run():
            path = str(tmp_path / "sig.json")
            recorder = FlightRecorder()
            loop = asyncio.get_running_loop()
            recorder.install_sigusr2(loop, path=path)
            try:
                # drive ≥3 distinct event types through real wiring
                store, cache = make_fixture(recorder=recorder)
                store.expire_session()           # session-transition +
                await asyncio.sleep(0)           # mirror-rebuild
                watchdog = LoopLagWatchdog(recorder=recorder,
                                           interval=0.01,
                                           stall_threshold=0.05)
                watchdog._observe(0.2, time.monotonic())  # loop-stall
                os.kill(os.getpid(), signal.SIGUSR2)
                deadline = loop.time() + 5
                while not os.path.exists(path):
                    assert loop.time() < deadline
                    await asyncio.sleep(0.02)
                with open(path) as f:
                    payload = json.load(f)
                types = {e["type"] for e in payload["events"]}
                assert {"session-transition", "mirror-rebuild",
                        "loop-stall"} <= types
                seqs = [e["seq"] for e in payload["events"]]
                assert seqs == sorted(seqs)
            finally:
                loop.remove_signal_handler(signal.SIGUSR2)
        asyncio.run(run())

    def test_watch_storm_event(self, monkeypatch):
        monkeypatch.setattr(MirrorCache, "STORM_THRESHOLD", 10)
        recorder = FlightRecorder()
        store, _cache = make_fixture(recorder=recorder)
        for i in range(30):
            store.put_json("/com/foo/web",
                           {"type": "host",
                            "host": {"address": f"10.0.0.{i + 1}"}})
        storms = [e for e in recorder.events() if e["type"] == "watch-storm"]
        assert storms and storms[0]["events"] >= 10


class TestWatchdog:
    def test_stall_observed_and_recorded(self):
        async def run():
            recorder = FlightRecorder()
            collector = MetricsCollector()
            watchdog = LoopLagWatchdog(collector=collector,
                                       recorder=recorder, interval=0.01,
                                       stall_threshold=0.05)
            watchdog.start()
            await asyncio.sleep(0.05)
            time.sleep(0.15)             # block the loop: a real stall
            await asyncio.sleep(0.05)
            watchdog.stop()
            assert watchdog.samples >= 2
            assert watchdog.max_lag >= 0.05
            assert watchdog.stalls >= 1
            stalls = [e for e in recorder.events()
                      if e["type"] == "loop-stall"]
            assert stalls and stalls[0]["lag_s"] >= 0.05
            text = collector.expose()
            assert "binder_loop_lag_seconds_bucket" in text
            assert validate_exposition(text) == []
        asyncio.run(run())


class TestInflightAndBstat:
    def test_inflight_table_and_bstat_output(self):
        async def run():
            recorder = FlightRecorder()
            collector = MetricsCollector()
            server, _store = await start_server(recorder=recorder,
                                                collector=collector)
            watchdog = LoopLagWatchdog(collector=collector,
                                       recorder=recorder, interval=0.02)
            watchdog.start()
            intro = Introspector(server=server, recorder=recorder,
                                 watchdog=watchdog, collector=collector)
            intro.set_loop(asyncio.get_running_loop())
            metrics = MetricsServer(collector, address="127.0.0.1",
                                    port=0)
            metrics.status_source = intro.snapshot
            metrics.start()

            via_generic_path(server)
            release = hold_resolver(server)
            ask = asyncio.ensure_future(
                udp_ask(server.udp_port, f"held.{DOMAIN}", Type.A))
            deadline = asyncio.get_running_loop().time() + 5
            while not server.engine.inflight:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.01)

            snap = intro.snapshot()
            assert validate_status_snapshot(snap) == []
            assert snap["inflight"]["count"] == 1
            q = snap["inflight"]["queries"][0]
            assert q["trace"] and q["name"] == f"held.{DOMAIN}"
            assert q["phase"] == "store-lookup"
            assert q["age_ms"] >= 0.0
            # the gauge sees it too
            assert "binder_inflight_queries" in collector.expose()
            assert collector.get(
                "binder_inflight_queries").value() == 1.0

            # live-endpoint check: fetch + schema validator (the tier-1
            # wiring the CI satellite asks for), then bstat against it
            url = f"http://127.0.0.1:{metrics.port}"
            raw = await asyncio.to_thread(lambda: urllib.request.urlopen(
                f"{url}/status", timeout=5).read())
            assert validate_status_snapshot(json.loads(raw)) == []
            kang = await asyncio.to_thread(lambda: urllib.request.urlopen(
                f"{url}/kang/snapshot", timeout=5).read())
            assert validate_status_snapshot(json.loads(kang)) == []

            loader = importlib.machinery.SourceFileLoader(
                "bstat", os.path.join(REPO, "bin", "bstat"))
            spec = importlib.util.spec_from_loader("bstat", loader)
            bstat = importlib.util.module_from_spec(spec)
            loader.exec_module(bstat)
            out = io.StringIO()

            def run_bstat():
                with contextlib.redirect_stdout(out):
                    return bstat.main([f"127.0.0.1:{metrics.port}"])

            assert await asyncio.to_thread(run_bstat) == 0
            text = out.getvalue()
            assert "CONNECTED" in text            # ZK session state
            assert "last change" in text          # mirror staleness age
            assert q["trace"] in text             # in-flight trace ID
            assert "phase=store-lookup" in text   # current phase

            release.set()
            reply = await ask
            assert reply.rcode == Rcode.REFUSED
            await asyncio.sleep(0.05)
            assert not server.engine.inflight
            watchdog.stop()
            await server.stop()
            metrics.stop()
        asyncio.run(run())

    def test_slow_query_event(self, monkeypatch):
        async def run():
            import binder_tpu.server as server_mod
            monkeypatch.setattr(server_mod, "SLOW_QUERY_MS", 0.0)
            recorder = FlightRecorder()
            server, _store = await start_server(recorder=recorder)
            via_generic_path(server)
            await udp_ask(server.udp_port, f"web.{DOMAIN}", Type.A)
            slow = [e for e in recorder.events()
                    if e["type"] == "slow-query"]
            assert slow and slow[0]["name"] == f"web.{DOMAIN}"
            assert slow[0]["trace"]
            await server.stop()
        asyncio.run(run())

    def test_resolver_error_event(self):
        async def run():
            recorder = FlightRecorder()
            server, _store = await start_server(recorder=recorder)
            via_generic_path(server)

            def boom(query):
                async def fail():
                    raise RuntimeError("induced resolver failure")
                return fail()

            server.resolver.handle = boom
            reply = await udp_ask(server.udp_port, f"web.{DOMAIN}",
                                  Type.A)
            assert reply.rcode == Rcode.SERVFAIL
            errs = [e for e in recorder.events()
                    if e["type"] == "resolver-error"]
            assert errs and "induced resolver failure" in errs[0]["error"]
            assert not server.engine.inflight
            await server.stop()
        asyncio.run(run())


class TestBalancerFold:
    @staticmethod
    def serve_stats(path, payload_box):
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(path)
        srv.listen(4)

        def loop():
            while True:
                try:
                    conn, _ = srv.accept()
                except OSError:
                    return
                conn.sendall(json.dumps(payload_box[0]).encode())
                conn.close()

        threading.Thread(target=loop, daemon=True).start()
        return srv

    @staticmethod
    def stats(fp_cycles, fp_ops, rr_cycles, rr_ops):
        return {
            "cycles_per_us": 2900.0,
            "stage_cycles": {
                "frame-parse": {"cycles": fp_cycles, "ops": fp_ops},
                "reply-relay": {"cycles": rr_cycles, "ops": rr_ops},
            },
        }

    def test_fold_monotonic_across_restart(self, tmp_path):
        path = str(tmp_path / ".balancer.stats")
        box = [self.stats(1000, 10, 5000, 50)]
        srv = self.serve_stats(path, box)
        try:
            collector = MetricsCollector()
            fold = BalancerStatsFold(collector, path, timeout=2.0)
            text = collector.expose()
            assert validate_exposition(text) == []
            cyc = collector.get("binder_balancer_stage_cycles")
            assert cyc.value({"stage": "frame-parse"}) == 1000
            assert cyc.value({"stage": "reply-relay"}) == 5000
            assert collector.get("binder_balancer_up").value() == 1.0

            box[0] = self.stats(1500, 15, 9000, 90)   # balancer advances
            collector.expose()
            assert cyc.value({"stage": "frame-parse"}) == 1500
            assert cyc.value({"stage": "reply-relay"}) == 9000

            box[0] = self.stats(200, 2, 300, 3)       # balancer restarted
            collector.expose()
            # series stays monotonic: new totals fold in as fresh deltas
            assert cyc.value({"stage": "frame-parse"}) == 1700
            assert cyc.value({"stage": "reply-relay"}) == 9300
            ops = collector.get("binder_balancer_stage_ops")
            assert ops.value({"stage": "frame-parse"}) == 17
        finally:
            srv.close()
        # socket gone: up flips to 0, scrape keeps validating
        os.unlink(path)
        collector.expose()
        assert collector.get("binder_balancer_up").value() == 0.0
        assert validate_exposition(collector.expose()) == []
        assert fold is not None

    def test_no_balancer_is_clean(self, tmp_path):
        collector = MetricsCollector()
        BalancerStatsFold(collector,
                          str(tmp_path / "missing.stats"))
        text = collector.expose()
        assert validate_exposition(text) == []
        assert collector.get("binder_balancer_up").value() == 0.0


class TestSnapshotValidator:
    def test_rejects_missing_and_mistyped(self):
        good = {
            "service": {"name": "b", "pid": 1, "version": 1,
                        "uptime_seconds": 0.1, "generated_at": 1.0},
            "store": {"backend": "FakeStore", "state": "connected",
                      "connected": True, "disconnected_seconds": 0.0,
                      "session_establishments": 1, "transitions": []},
            "mirror": {"ready": True, "domain": "foo.com",
                       "generation": 1, "epoch": 1, "nodes": 2,
                       "names": 2, "reverse_entries": 1,
                       "interned_names": 3, "staleness_seconds": 0.5,
                       "last_rebuild_age_seconds": None,
                       "rebuild": {"pending": 0, "chunks": 1,
                                   "last_duration_seconds": 0.01}},
            "answer_cache": {"size": 10, "entries": 0, "hits": 0,
                             "misses": 0, "hit_ratio": 0.0,
                             "invalidations": 0, "expiry_ms": 1000.0,
                             "neg_hits": 0, "type_row_serves": 0,
                             "zone_put_skips": {"size": 0, "bytes": 0}},
            "inflight": {"count": 0, "queries": []},
            "tcp": {"open_conns": 0, "max_conns": 1024,
                    "idle_timeout_seconds": 30.0,
                    "max_write_buffer": 262144, "cap_refusals": 0,
                    "accepts": 0, "fast_serves": 0, "native_serves": 0,
                    "promotions": 0,
                    "oneshot_closes": 0, "idle_timeouts": 0,
                    "slow_reader_drops": 0, "coalesced_writes": 0,
                    "coalesced_frames": 0, "half_closes": 0,
                    "rst_drops": 0, "udp_truncated": 0},
            "recursion": None, "precompile": {"seed_remaining": 0},
            "loop": None,
            "flight_recorder": None, "policy": None, "verify": None,
            "io": None,
        }
        assert validate_status_snapshot(good) == []
        bad = json.loads(json.dumps(good))
        del bad["mirror"]["staleness_seconds"]
        bad["store"]["state"] = "confused"
        bad["inflight"]["count"] = 3
        del bad["loop"]
        errs = validate_status_snapshot(bad)
        assert any("staleness_seconds" in e for e in errs)
        assert any("unknown state" in e for e in errs)
        assert any("inflight.count" in e for e in errs)
        assert any(e.startswith("loop") for e in errs)
        assert validate_status_snapshot([]) != []


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
