"""Whole-system capstone: every subsystem at once, under faults.

Topology (all real protocols, in one process + the native balancer):

    dig-analog client ──UDP──▶ mbalancer ──unix──▶ 2 binder backends
                                                   │        │
                                         ZK wire (jute)  recursion (DNS)
                                                   │        │
                                     2-member ZK ensemble  remote-DC binder
                                     (shared ZKEnsembleState)

The individual paths each have their own suites; this test pins the
*interactions*: per-name invalidation propagating through the balancer
while recursion traffic flows, a ZK member dying without a SERVFAIL
window (session resumes on the survivor), and a backend dying with the
balancer failing over — queries answering correctly throughout.
"""
import asyncio
import io
import json
import os

import pytest

from binder_tpu.dns import Rcode, Type
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.recursion import DnsClient, Recursion, StaticResolverSource
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.store.zk_client import ZKClient
from binder_tpu.store.zk_testserver import ZKEnsembleState, ZKTestServer
from binder_tpu.utils.jsonlog import make_logger

from tests.test_balancer import (
    BALANCER,
    read_stats,
    start_balancer,
    udp_ask as _udp_ask,
)
from tests.test_full_stack import wait_for

DOMAIN = "foo.com"

pytestmark = pytest.mark.skipif(
    not os.path.exists(BALANCER),
    reason="mbalancer not built (make -C native)")


async def udp_ask(port, name, qtype, qid):
    # the shared helper (already decodes) with RD set — the clients in
    # this scenario are recursion-shaped
    return await _udp_ask(port, name, qtype, qid=qid, rd=True)


# all three serving postures: python-path (query_log=True, plain
# logger) keeps every query in Python; native-path (query_log=False)
# engages the full native stack — fastpath cache, zone
# precompilation, serve_wire on the balancer lane; native-logged
# (query_log=True + JSON logger) engages the native stack WITH the
# query-log ring — the reference-parity posture — and the test asserts
# real log records exist for natively served queries.  The SAME fault
# scenario (ZK member death, backend death, churn) runs in each.
@pytest.mark.parametrize("query_log,json_log",
                         [(True, False), (False, False), (True, True)],
                         ids=["python-path", "native-path",
                              "native-logged"])
def test_everything_at_once(tmp_path, query_log, json_log):
    sockdir = str(tmp_path)

    async def run():
        # -- 2-member ZK ensemble over one shared state --
        state = ZKEnsembleState()
        zk1 = ZKTestServer(state=state)
        zk2 = ZKTestServer(state=state)
        await zk1.start()
        await zk2.start()
        connect = f"127.0.0.1:{zk1.port},127.0.0.1:{zk2.port}"

        # registrar seeds the shared tree through member 2
        writer = ZKClient(address="127.0.0.1", port=zk2.port)
        writer.start()
        assert await wait_for(writer.is_connected)
        await writer.mkdirp("/com/foo/web", json.dumps(
            {"type": "host", "host": {"address": "10.1.0.1"}}).encode())
        await writer.mkdirp("/com/foo/api", json.dumps(
            {"type": "host", "host": {"address": "10.1.0.2"}}).encode())

        # -- remote-DC binder for recursion (fake store is fine there) --
        rstore = FakeStore()
        rcache = MirrorCache(rstore, DOMAIN)
        rstore.put_json("/com/foo/east", {"type": "service",
                                          "service": {"port": 53}})
        rstore.put_json("/com/foo/east/db",
                        {"type": "host",
                         "host": {"address": "10.99.0.7"}})
        rstore.start_session()
        log_streams = []

        def posture_log(tag):
            # native-logged posture: a real JSON stream logger (the
            # shape the log ring requires to arm)
            if not json_log:
                return None
            stream = io.StringIO()
            log_streams.append(stream)
            return make_logger(f"capstone-{tag}", stream=stream)

        remote = BinderServer(zk_cache=rcache, dns_domain=DOMAIN,
                              datacenter_name="east", host="127.0.0.1",
                              port=0, collector=MetricsCollector(),
                              query_log=query_log,
                              log=posture_log("remote"))
        await remote.start()

        # -- 2 ZK-backed backends with recursion, behind the balancer --
        backends = []
        for i in range(2):
            client = ZKClient(address=connect, port=2181,
                              session_timeout_ms=2000)
            cache = MirrorCache(client, DOMAIN)
            client.start()
            recursion = Recursion(
                zk_cache=cache, dns_domain=DOMAIN,
                datacenter_name="local",
                source=StaticResolverSource(
                    {"east": [f"127.0.0.1:{remote.udp_port}"]}),
                nic_provider=lambda: [],
                client=DnsClient(concurrency=2, timeout=2.0))
            await recursion.wait_ready()
            server = BinderServer(
                zk_cache=cache, dns_domain=DOMAIN,
                datacenter_name="local", recursion=recursion,
                host="127.0.0.1", port=0,
                balancer_socket=os.path.join(sockdir, str(i)),
                collector=MetricsCollector(), query_log=query_log,
                log=posture_log(f"backend{i}"))
            await server.start()
            backends.append((client, cache, recursion, server))
        assert await wait_for(lambda: all(
            c.lookup("api.foo.com") is not None
            and c.lookup("api.foo.com").data is not None
            for _cl, c, _r, _s in backends))

        # relay lane (-D): this scenario asserts the balancer's own
        # cache fill/invalidation counters, which direct return
        # bypasses by design (tools/balancer_smoke.py and
        # tests/test_balancer.py cover the direct lane)
        proc, port = await start_balancer(sockdir, direct=False)
        try:
            await asyncio.sleep(0.4)

            # 1. authoritative A through the balancer (fills its cache)
            for qid in (1, 2):
                m = await udp_ask(port, "web.foo.com", Type.A, qid)
                assert m.rcode == Rcode.NOERROR
                assert m.answers[0].address == "10.1.0.1"

            # 2. cross-DC recursion through the balancer (never cached)
            m = await udp_ask(port, "db.east.foo.com", Type.A, 5)
            assert m.rcode == Rcode.NOERROR
            assert m.answers[0].address == "10.99.0.7"

            # 3. churn web over ZK: per-name invalidation must ripple
            # through backend caches AND the balancer, while api stays
            # cached and recursion keeps working
            await udp_ask(port, "api.foo.com", Type.A, 6)
            await writer.set_data("/com/foo/web", json.dumps(
                {"type": "host",
                 "host": {"address": "10.1.0.99"}}).encode())
            assert await wait_for(lambda: all(
                c.lookup("web.foo.com").data["host"]["address"]
                == "10.1.0.99" for _cl, c, _r, _s in backends))
            assert await wait_for(
                lambda: read_stats(sockdir)["cache_invalidations"] >= 1)
            m = await udp_ask(port, "web.foo.com", Type.A, 7)
            assert m.answers[0].address == "10.1.0.99"
            m = await udp_ask(port, "api.foo.com", Type.A, 8)
            assert m.answers[0].address == "10.1.0.2"
            m = await udp_ask(port, "db.east.foo.com", Type.A, 9)
            assert m.answers[0].address == "10.99.0.7"

            # 4. ZK member 1 dies: sessions resume on member 2, mirrors
            # keep serving (no SERVFAIL window), watches re-arm
            sessions_before = [cl._session_id
                               for cl, _c, _r, _s in backends]
            await zk1.stop()
            for qid in range(20, 26):
                m = await udp_ask(port, "web.foo.com", Type.A, qid)
                assert m.rcode == Rcode.NOERROR, f"qid {qid}"
                assert m.answers[0].address == "10.1.0.99"
            assert await wait_for(lambda: all(
                cl.is_connected() for cl, _c, _r, _s in backends))
            assert [cl._session_id
                    for cl, _c, _r, _s in backends] == sessions_before
            # a post-failover mutation still propagates
            await writer.mkdirp("/com/foo/late", json.dumps(
                {"type": "host",
                 "host": {"address": "10.1.0.50"}}).encode())
            assert await wait_for(lambda: all(
                c.lookup("late.foo.com") is not None
                and c.lookup("late.foo.com").data is not None
                for _cl, c, _r, _s in backends))
            m = await udp_ask(port, "late.foo.com", Type.A, 30)
            assert m.answers[0].address == "10.1.0.50"

            # 5. backend 0 dies (SIGTERM unlinks its socket): the
            # balancer fails over and every path keeps answering
            await backends[0][3].stop()
            os_path = os.path.join(sockdir, "0")
            if os.path.exists(os_path):
                os.unlink(os_path)
            await asyncio.sleep(0.5)   # balancer sweep notices
            for qid in range(40, 44):
                m = await udp_ask(port, "web.foo.com", Type.A, qid)
                assert m.answers[0].address == "10.1.0.99"
            m = await udp_ask(port, "db.east.foo.com", Type.A, 50)
            assert m.answers[0].address == "10.99.0.7"

            if json_log:
                # reference-parity posture: the native stack must have
                # served under logging AND produced real log records
                native_lines = 0
                for _cl, _c, _r, s in backends[1:] + backends[:1]:
                    if s._fastpath is None:
                        continue
                    assert s._log_ring, "log ring failed to arm"
                    s._write_log()
                    import binder_tpu.server as _srv
                    stats = _srv._fastio.fastpath_stats(s._fastpath)
                    native_lines += stats["log_lines"]
                assert native_lines > 0, \
                    "no natively-logged serves in the logged posture"
                records = []
                for stream in log_streams:
                    for ln in stream.getvalue().splitlines():
                        rec = json.loads(ln)
                        if rec.get("msg") == "DNS query":
                            records.append(rec)
                # every answered query above must have left a record —
                # at minimum the early web.foo.com serves
                assert any(r.get("query", {}).get("name") ==
                           "web.foo.com" for r in records)
                assert len(records) >= native_lines
        finally:
            proc.kill()
            await proc.wait()
            for client, _c, recursion, server in backends:
                try:
                    await server.stop()
                except Exception:  # noqa: BLE001 — backend 0 already down
                    pass
                await recursion.close()
                client.close()
            writer.close()
            await remote.stop()
            await zk2.stop()

    asyncio.run(run())
