"""IPv6 reverse-map tests (ISSUE 11 satellite): AAAA/v6-addressed host
records populate ``ip6.arpa`` PTR entries alongside the v4 path.

Layers:
- wire helpers: canonical nibble render/parse round-trip;
- mirror: ``TreeNode.ip`` canonicalizes v6 text so reverse-map keys,
  dependency tags, and PTR lookups agree; upkeep on delete/re-address;
- engine: ``plan_ptr`` serves ip6.arpa alongside in-addr.arpa, REFUSED
  for malformed nibble names;
- the Python lanes through the engine: a v6 PTR hit from raw bytes;
- end to end: a live server answers the v6 PTR over UDP, including for
  hosts added after start (the mutation path).
"""
import asyncio

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.dns.query import QueryCtx
from binder_tpu.dns.wire import ip_from_reverse_name, reverse_name_for_ip
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.resolver import Resolver
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache

from tests.test_query_shapes import ask_raw, new_server

DOMAIN = "foo.com"

V6 = "fd00:1234::42"
V6_REV = reverse_name_for_ip(V6)            # canonical ip6.arpa name
V6_NONCANON = "FD00:1234:0:0:0:0:0:42"      # same address, other text


def make_stack(addr=V6):
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/web6",
                   {"type": "host", "host": {"address": addr}})
    store.put_json("/com/foo/web4",
                   {"type": "host", "host": {"address": "192.168.0.1"}})
    store.start_session()
    return store, cache


def ask(resolver, name, qtype):
    sent = []
    q = QueryCtx(make_query(name, qtype, qid=99), ("127.0.0.1", 5353),
                 "udp", sent.append)
    pending = resolver.handle(q)
    if pending is not None:
        asyncio.run(pending)
    assert len(sent) == 1
    return Message.decode(sent[0])


class TestWireHelpers:
    def test_round_trip(self):
        assert V6_REV.endswith(".ip6.arpa")
        assert len(V6_REV.split(".")) == 34  # 32 nibbles + ip6 + arpa
        assert ip_from_reverse_name(V6_REV) == "fd00:1234::42"

    def test_v4_round_trip_unchanged(self):
        assert reverse_name_for_ip("192.168.0.1") == \
            "1.0.168.192.in-addr.arpa"
        assert ip_from_reverse_name("1.0.168.192.in-addr.arpa") == \
            "192.168.0.1"

    def test_malformed_nibble_names_rejected(self):
        assert ip_from_reverse_name("1.2.3.4.ip6.arpa") is None
        assert ip_from_reverse_name(
            "g" + V6_REV[1:]) is None          # non-hex nibble
        assert ip_from_reverse_name(
            "ff." + V6_REV) is None            # 2-char label


class TestMirrorReverseMap:
    def test_v6_reverse_entry_keyed_canonically(self):
        store, cache = make_stack(addr=V6_NONCANON)
        node = cache.reverse_lookup("fd00:1234::42")
        assert node is not None
        assert node.ip == "fd00:1234::42"

    def test_v4_entries_unaffected(self):
        store, cache = make_stack()
        assert cache.reverse_lookup("192.168.0.1") is not None

    def test_delete_removes_v6_entry(self):
        store, cache = make_stack()
        assert cache.reverse_lookup("fd00:1234::42") is not None
        store.delete("/com/foo/web6")
        assert cache.reverse_lookup("fd00:1234::42") is None

    def test_readdress_repoints_entry(self):
        store, cache = make_stack()
        store.put_json("/com/foo/web6",
                       {"type": "host", "host": {"address": "fd00::9"}})
        assert cache.reverse_lookup("fd00:1234::42") is None
        assert cache.reverse_lookup("fd00::9") is not None

    def test_invalid_v6_text_yields_no_entry(self):
        store, cache = make_stack(addr="fd00::zz")
        assert cache.reverse_lookup("fd00::zz") is None


class TestEnginePtr:
    def test_v6_ptr_resolves(self):
        store, cache = make_stack()
        resolver = Resolver(cache, dns_domain=DOMAIN,
                            datacenter_name="coal")
        r = ask(resolver, V6_REV, Type.PTR)
        assert r.rcode == Rcode.NOERROR
        assert r.answers[0].target == "web6.foo.com"

    def test_v6_ptr_miss_refused(self):
        store, cache = make_stack()
        resolver = Resolver(cache, dns_domain=DOMAIN,
                            datacenter_name="coal")
        miss = reverse_name_for_ip("fd00::dead")
        assert ask(resolver, miss, Type.PTR).rcode == Rcode.REFUSED

    def test_malformed_v6_reverse_refused(self):
        store, cache = make_stack()
        resolver = Resolver(cache, dns_domain=DOMAIN,
                            datacenter_name="coal")
        r = ask(resolver, "1.2.3.4.ip6.arpa", Type.PTR)
        assert r.rcode == Rcode.REFUSED

    def test_v4_ptr_still_resolves(self):
        store, cache = make_stack()
        resolver = Resolver(cache, dns_domain=DOMAIN,
                            datacenter_name="coal")
        r = ask(resolver, "1.0.168.192.in-addr.arpa", Type.PTR)
        assert r.rcode == Rcode.NOERROR
        assert r.answers[0].target == "web4.foo.com"


class TestPythonLanes:
    def test_lane_serves_v6_hit(self):
        store, cache = make_stack()
        srv = new_server(cache)
        wire = make_query(V6_REV, Type.PTR, qid=7).encode()
        m = Message.decode(ask_raw(srv, wire))
        assert m.rcode == Rcode.NOERROR
        assert m.answers[0].target == "web6.foo.com"


class TestEndToEnd:
    def test_live_server_serves_v6_ptr_and_mutations(self):
        from tests.test_zone import udp_ask_raw

        async def run():
            store, cache = make_stack()
            server = BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                                  datacenter_name="coal",
                                  host="127.0.0.1", port=0,
                                  collector=MetricsCollector())
            await server.start()
            try:
                hit = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query(V6_REV, Type.PTR, qid=5).encode()))
                # a v6 host added AFTER start rides the mutation path
                store.put_json("/com/foo/late6",
                               {"type": "host",
                                "host": {"address": "fd00::77"}})
                late = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query(reverse_name_for_ip("fd00::77"),
                               Type.PTR, qid=6).encode()))
                v4 = Message.decode(await udp_ask_raw(
                    server.udp_port,
                    make_query("1.0.168.192.in-addr.arpa",
                               Type.PTR, qid=8).encode()))
                return hit, late, v4
            finally:
                await server.stop()

        hit, late, v4 = asyncio.run(run())
        assert hit.rcode == Rcode.NOERROR
        assert hit.answers[0].target == "web6.foo.com"
        assert late.rcode == Rcode.NOERROR
        assert late.answers[0].target == "late6.foo.com"
        assert v4.rcode == Rcode.NOERROR
        assert v4.answers[0].target == "web4.foo.com"
