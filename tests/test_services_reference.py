"""The served path against the benchmark's plain reference, on a service
zone whose set sizes straddle every edge the program has (ISSUE 26).

``benchmark/reference.py`` resolves from the zone's description alone and
imports nothing of ``binder_tpu``; here its fixture is loaded into a fake
store under a server in the production posture's serving shape (zone
table, answer precompile, query log through the native ring), and every
service is asked the way the cell ``services_srv_open60`` asks: SRV
``_http._tcp.<service>`` over UDP without an OPT record, over UDP with OPT
1232, and over TCP.  A UDP answer with TC=1 is held to its header and its
retry over TCP to the whole set and glue, as the benchmark's ``correct``
holds them; and an answer is truncated exactly when the whole set does
not fit the limit.

The sizes: 6/7 is where 512 bytes run out, 8/9 the edge between the
deployment's ``small`` and ``medium`` classes, 16/17 where 1232 bytes run
out, 32/33 the precompiler's 64 *records* for an SRV set with glue
(``Precompiler.MAX_SET_RECORDS``), 64/65 the zone table's 64 *members*,
250 the deployment's largest set.
"""
import asyncio
import os
import socket
import sys
import threading

import pytest

from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from binder_tpu.utils.jsonlog import make_logger
from tests.test_ledger import tcp_oneshot
from tests.test_log_ring import byte_stream

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import dnswire  # noqa: E402
from reference import Zone, compare  # noqa: E402

DOMAIN = "foo.com"
SIZES = (2, 6, 7, 8, 9, 16, 17, 32, 33, 64, 65, 250)
EACH = 3                    # services of every size: 36 in all
TRANSPORTS = {"udp": None, "udp-opt1232": 1232, "tcp": None}

CONFIG = {
    "hosts": 0, "racks": 0, "subtree": "zs",
    "services": {
        "count": len(SIZES) * EACH, "srvce": "_http", "proto": "_tcp",
        "port": 80, "rank_period": len(SIZES),
        # one class a size, at one place of the period each; the last
        # takes whatever place is left, as reference.py wants one to
        "classes": [dict({"name": f"of{n}", "members": [n, n]},
                         **({"ranks_in_period": [k + 1]}
                            if k < len(SIZES) - 1 else {}))
                    for k, n in enumerate(SIZES)]},
    "chaos": {"writes": 0},
}


class Served:
    """A BinderServer on a loop of its own thread, for blocking asks."""

    def __init__(self, zone):
        self.zone = zone
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.server = self.call(self._start())

    def call(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(60)

    async def _start(self):
        store = FakeStore()
        cache = MirrorCache(store, DOMAIN)
        for path, record in self.zone.fixture().items():
            store.put_json(path, record)
        store.start_session()
        server = BinderServer(
            zk_cache=cache, dns_domain=DOMAIN, datacenter_name="coal",
            host="127.0.0.1", port=0, collector=MetricsCollector(),
            log=make_logger("binder-services-test",
                            stream=byte_stream()[0]),
            query_log=True, zone_precompile=True, answer_precompile=True)
        await server.start()
        return server

    def stop(self):
        self.call(self.server.stop())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)

    def ask_udp(self, wire):
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.settimeout(5.0)
            s.sendto(wire, ("127.0.0.1", self.server.udp_port))
            return s.recvfrom(65535)[0]

    def ask_tcp(self, wire):
        return tcp_oneshot(self.server.tcp_port, wire)


@pytest.fixture(scope="module")
def served():
    s = Served(Zone(CONFIG, DOMAIN, seed=2**31 + 26))
    yield s
    s.stop()


def test_the_zone_straddles_every_edge(served):
    sizes = sorted(len(s.members) for s in served.zone.services)
    assert sizes == sorted(SIZES * EACH)


@pytest.mark.parametrize("transport", TRANSPORTS)
@pytest.mark.parametrize("size", SIZES)
def test_srv_set_equals_the_reference(served, size, transport):
    zone, payload = served.zone, TRANSPORTS[transport]
    limit = payload or 512
    services = [s for s in zone.services if len(s.members) == size]
    assert len(services) == EACH
    # twice: the second ask of a name meets what the first one cached
    for qid, service in enumerate(services * 2):
        qname = f"_http._tcp.{service.label}.{DOMAIN}"
        want = zone.expected(qname, dnswire.SRV)
        assert len(want["answers"]) == len(want["glue"]) == size
        wire = dnswire.make_query(qname, dnswire.SRV, qid=qid + 1, rd=True,
                                  edns_payload=payload)
        whole = served.ask_tcp(wire)
        answer = dnswire.Answer(whole)
        assert compare(answer, qname, dnswire.SRV, want) == []
        if transport == "tcp":
            continue
        udp = dnswire.Answer(served.ask_udp(wire))
        # TC=1 exactly when the whole set does not fit the limit; held
        # to its header then, and its retry (above) to the whole set
        fits = len(whole) <= limit
        assert udp.tc == (not fits), (size, len(whole), limit)
        assert compare(udp, qname, dnswire.SRV, want,
                       whole=not udp.tc) == []
        # and a member's own A record, the glue's source
        label, address = service.members[qid % size]
        member = f"{label}.{service.label}.{DOMAIN}"
        got = dnswire.Answer(served.ask_udp(dnswire.make_query(
            member, dnswire.A, qid=99, rd=True, edns_payload=payload)))
        assert compare(got, member, dnswire.A,
                       zone.expected(member, dnswire.A)) == []
        assert got.answers[0][3] == address
