"""The served path against the benchmark's plain reference, on a service
zone whose set sizes straddle every edge the program has (ISSUE 26).

``benchmark/reference.py`` resolves from the zone's description alone and
imports nothing of ``binder_tpu``; here its fixture is loaded into a fake
store under a server in the production posture's serving shape (zone
table, query log through the native ring), and every
service is asked the way the cell ``services_srv_open60`` asks: SRV
``_http._tcp.<service>`` over UDP without an OPT record, over UDP with OPT
1232, and over TCP.  A UDP answer with TC=1 is held to its header and its
retry over TCP to the whole set and glue, as the benchmark's ``correct``
holds them; and an answer is truncated exactly when the whole set does
not fit the limit.

And what a zone answers without records (ISSUE 30's cases, owed since;
written with ISSUE 32): AAAA (NOTIMP by the type alone, every section
empty), SRV on a host (NODATA: one SOA, TTL and minimum 30) and an absent
name (REFUSED), each from the Python lanes' first sight, from the Python
answer cache, from a native lane (a promoted native cache entry; for AAAA
the zone table's type row) and over TCP, beside a member's A; every one
with and without an OPT record, held to
the reference's OPT row: exactly one OPT (version 0, in the additional
section) on an answer to a question that had one, none otherwise.

The sizes: 6/7 is where 512 bytes run out, 8/9 the edge between the
deployment's ``small`` and ``medium`` classes, 16/17 where 1232 bytes run
out, 32/33 the lazy render's 64 *records* for an SRV set with glue
(``engine.MAX_SET_RECORDS``), 64/65 what was the zone table's
64-*member* rule until ISSUE 39 (its SRV entries now hold every set a
TCP message carries), 250 the deployment's largest set.
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

fastio = pytest.importorskip(
    "binder_tpu._binderfastio",
    reason="fastio extension not built (make -C native)")

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import dnswire  # noqa: E402
from reference import Zone, compare, whole_size  # noqa: E402

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
            query_log=True, zone_precompile=True)
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

    def ask_python(self, wire):
        """One datagram as a sampled drain hands it over: to the Python
        lanes, whatever the native lanes hold."""
        async def ask():
            out = []
            self.server.engine._handle_raw(wire, ("127.0.0.9", 4242), "udp",
                                           out.append)
            return out[0]
        return self.call(ask())

    def served_by(self):
        """How many answers each lane has given so far."""
        async def read():
            hits = self.server.collector.get("binder_answer_cache_hits")
            stats = fastio.fastpath_stats(self.server._fastpath)
            return {"python-cache": int(hits.value({"tier": "python"})),
                    "native-cache": stats["hits"],
                    "zone-table": stats["zone_hits"]
                    - stats["zone_type_hits"],
                    "type-row": stats["zone_type_hits"]}
        return self.call(read())


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
        # TC=1 exactly when the whole set does not fit the limit, by the
        # reference's own plain wire (the zone table's stream frame
        # spells glue owners out and is longer); held to its header
        # then, and its retry (above) to the whole set
        want = zone.expected(qname, dnswire.SRV, payload=payload or 0)
        fits = whole_size(qname, want) <= limit
        assert udp.tc == (not fits), (size, len(whole), limit)
        assert compare(udp, qname, dnswire.SRV, want,
                       whole=not udp.tc, truncated=udp.tc) == []
        # and a member's own A record, the glue's source
        label, address = service.members[qid % size]
        member = f"{label}.{service.label}.{DOMAIN}"
        got = dnswire.Answer(served.ask_udp(dnswire.make_query(
            member, dnswire.A, qid=99, rd=True, edns_payload=payload)))
        assert compare(got, member, dnswire.A,
                       zone.expected(member, dnswire.A)) == []
        assert got.answers[0][3] == address


# -- answers without records, and the OPT echo, lane by lane --

#: the question a kind asks of a service and one of its members
KINDS = {
    "aaaa": lambda s, m: (f"{m}.{s}.{DOMAIN}", dnswire.AAAA),
    "srv-on-a-host": lambda s, m: (f"_http._tcp.{m}.{s}.{DOMAIN}",
                                   dnswire.SRV),
    "absent": lambda s, m: (f"no-{m}.{s}.{DOMAIN}", dnswire.A),
    "member-a": lambda s, m: (f"{m}.{s}.{DOMAIN}", dnswire.A),
}
WANT_RCODE = {"aaaa": dnswire.NOTIMP, "srv-on-a-host": dnswire.NOERROR,
              "absent": dnswire.REFUSED, "member-a": dnswire.NOERROR}
LANES = ("first-sight", "python-cache", "native", "tcp")
PAYLOADS = {"no-opt": 0, "opt1232": 1232}


def grew(before, after):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("opt", PAYLOADS)
@pytest.mark.parametrize("kind", KINDS)
def test_an_answer_and_its_opt_echo_equal_the_reference_in_every_lane(
        served, kind, opt, lane):
    zone, payload = served.zone, PAYLOADS[opt]
    # once the Python lanes have given an answer twice, the datagram
    # meets the entry promoted to the native answer cache; a declined
    # type is promoted nowhere: the zone table's type row has it
    native_lane = "type-row" if kind == "aaaa" else "native-cache"
    # a name of its own for every case, so that a first sight is one:
    # the largest services' members past those the test above asked
    case = (list(KINDS).index(kind) * len(PAYLOADS)
            + list(PAYLOADS).index(opt)) * len(LANES) + LANES.index(lane)
    service = [s for s in zone.services
               if len(s.members) == 250][case % EACH]
    qname, qtype = KINDS[kind](service.label,
                               service.members[10 + case // EACH][0])
    want = zone.expected(qname, qtype, payload=payload)
    assert want["rcode"] == WANT_RCODE[kind]
    assert (want["nodata"] is not None) == (kind == "srv-on-a-host")
    assert want["opt"] == (1 if payload else 0)
    wire = dnswire.make_query(qname, qtype, qid=case + 1, rd=True,
                              edns_payload=payload or None)

    def held(raw, udp=True):
        answer = dnswire.Answer(raw)
        assert compare(answer, qname, qtype, want,
                       truncated=False if udp else None) == []
        assert len(answer.opts) == (1 if payload else 0)
        return answer

    start = served.served_by()
    if lane == "tcp":
        held(served.ask_tcp(wire), udp=False)
        return
    first = held(served.ask_python(wire))
    assert grew(start, served.served_by()) == {}
    if lane == "first-sight":
        if not want["answers"] and not want["nodata"]:
            # the header, the question, the OPT echo, and nothing else
            assert first.size == 12 + len(dnswire.encode_name(qname)) + 4 \
                + (11 if payload else 0)
        return
    before = served.served_by()
    again = held(served.ask_python(wire))
    assert grew(before, served.served_by()) == {"python-cache": 1}
    assert again.rcode == first.rcode
    if lane == "python-cache":
        return
    before = served.served_by()
    held(served.ask_udp(wire))
    assert grew(before, served.served_by()) == {native_lane: 1}
