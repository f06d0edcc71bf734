"""Hostile-traffic armor (ISSUE 12): malformed-frame corpus replay and
response rate limiting.

Pins the contracts the hostile plane must keep:

- **fuzz-clean parsing** — every frame in the committed malformed
  corpus (``tests/data/malformed_corpus.bin``, generated by
  ``tools/hostile.py --write-corpus``) produces FORMERR-or-drop on
  every serve lane — the UDP ingress, the TCP generic path, and the
  live TCP stream lane — never an exception and
  never a deposit into the decode cache or the answer cache;
- **RRL mechanics** — slip replies are TC=1 echoes no larger than the
  query, prefixes aggregate (/24 v4), the bucket table is LRU-capped,
  ``hot()`` trips on limiting and feeds the fastpath gate, and
  ``sample_cost`` charges sampled packets at the sampling factor (the
  duty-cycle detection under the native fastpath);
- **observability** — the ``binder_rrl_*`` exposition passes
  ``tools/lint.py validate_rrl_metrics`` (this is the family's tier-1
  wiring, same pattern as validate_tcp_metrics in test_tcp_stream.py).
"""
import asyncio
import os
import socket
import struct
import time

import pytest

from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.metrics.collector import MetricsCollector
from binder_tpu.policy.rrl import DROP, SEND, SLIP, ResponseRateLimiter
from binder_tpu.server import BinderServer
from binder_tpu.store import FakeStore, MirrorCache
from tools.hostile import malformed_frames, read_corpus
from tools.lint import validate_rrl_metrics

DOMAIN = "foo.com"
CORPUS = os.path.join(os.path.dirname(__file__), "data",
                      "malformed_corpus.bin")


def fixture_store():
    store = FakeStore()
    cache = MirrorCache(store, DOMAIN)
    store.put_json("/com/foo/web",
                   {"type": "host", "host": {"address": "192.168.0.1"}})
    store.put_json("/com/foo/svc", {
        "type": "service",
        "service": {"srvce": "_pg", "proto": "_tcp", "port": 5432},
    })
    store.put_json("/com/foo/svc/lb0",
                   {"type": "load_balancer",
                    "load_balancer": {"address": "10.0.1.1"}})
    store.start_session()
    return store, cache


def new_server(cache, **kw):
    return BinderServer(zk_cache=cache, dns_domain=DOMAIN,
                        datacenter_name="coal",
                        collector=MetricsCollector(), query_log=False, **kw)


def assert_formerr_or_drop(label, frame, responses):
    """The corpus contract: at most one reply, and a reply is a
    FORMERR (or REFUSED for frames that decode but ask garbage) with
    zero answer records — never an answered query."""
    assert len(responses) <= 1, f"{label}: {len(responses)} replies"
    for resp in responses:
        assert len(resp) >= 12, f"{label}: runt reply"
        assert resp[2] & 0x80, f"{label}: reply without QR"
        rcode = resp[3] & 0x0F
        assert rcode in (Rcode.FORMERR, Rcode.REFUSED, Rcode.NOTIMP, \
                         Rcode.SERVFAIL), \
            f"{label}: rcode {rcode}"
        (ancount,) = struct.unpack_from(">H", resp, 6)
        assert ancount == 0, f"{label}: answered with {ancount} records"
        if len(frame) >= 2:
            assert resp[:2] == frame[:2], f"{label}: reply id mismatch"


def assert_no_deposits(server):
    """Malformed traffic must never populate any cache lane: a flood
    of garbage would otherwise evict the legit working set."""
    assert not server.engine._decode_cache, "decode-cache deposit"
    stats = server.answer_cache.stats()
    assert stats["entries"] == 0, "answer-cache deposit"


class TestCorpus:
    def test_committed_corpus_matches_generator(self):
        """The checked-in corpus must be exactly what the harness
        generates (seed 1337) — a drifted corpus silently weakens
        every replay assertion below."""
        committed = read_corpus(CORPUS)
        generated = malformed_frames()
        assert [f for _, f in committed] == [f for _, f in generated]
        assert [l for l, _ in committed] == [l for l, _ in generated]
        assert len(committed) >= 300

    def test_udp_lane_formerr_or_drop_no_deposits(self):
        """Every corpus frame through the UDP ingress: FORMERR-or-drop,
        no exception, no cache deposit on any lane."""
        store, cache = fixture_store()
        server = new_server(cache)
        for label, frame in read_corpus(CORPUS):
            out = []
            server.engine._handle_raw(frame, ("192.0.2.7", 5353),
                                      "udp", out.append)
            assert_formerr_or_drop(f"udp:{label}", frame, out)
        assert_no_deposits(server)

    def test_tcp_lane_formerr_or_drop_no_deposits(self):
        """Same corpus through the TCP generic path (the lane a TC=1
        slip pushes real clients toward — it must be exactly as
        armored)."""
        store, cache = fixture_store()
        server = new_server(cache)
        for label, frame in read_corpus(CORPUS):
            out = []
            server.engine._handle_raw(frame, ("192.0.2.7", 40001),
                                      "tcp", out.append)
            assert_formerr_or_drop(f"tcp:{label}", frame, out)
        assert_no_deposits(server)

    def test_stream_lane_survives_corpus(self):
        """The live TCP stream lane fed every corpus frame as a
        complete length-prefixed message: the server never dies, never
        deposits, and still serves a legit query afterwards.  The lane
        may answer FORMERR or drop the connection — both are in
        contract; an unhandled exception or a wedged table is not."""
        async def run():
            store, cache = fixture_store()
            server = new_server(cache, host="127.0.0.1", port=0)
            await server.start()
            frames = read_corpus(CORPUS)
            replies = 0
            # batches per connection: a mid-corpus disconnect (server's
            # prerogative) must not skip the rest of the corpus
            for i in range(0, len(frames), 32):
                try:
                    reader, writer = await asyncio.open_connection(
                        "127.0.0.1", server.tcp_port)
                    for label, frame in frames[i:i + 32]:
                        writer.write(struct.pack(">H", len(frame))
                                     + frame)
                    await writer.drain()
                    writer.write_eof()
                    # drain whatever the lane answered (FORMERRs)
                    buf = b""
                    while True:
                        chunk = await asyncio.wait_for(
                            reader.read(65536), 2.0)
                        if not chunk:
                            break
                        buf += chunk
                    off = 0
                    while off + 2 <= len(buf):
                        (ln,) = struct.unpack_from(">H", buf, off)
                        resp = buf[off + 2:off + 2 + ln]
                        off += 2 + ln
                        if len(resp) >= 12:
                            assert (resp[3] & 0x0F) != Rcode.NOERROR
                            replies += 1
                    writer.close()
                    await writer.wait_closed()
                except (ConnectionError, asyncio.TimeoutError,
                        asyncio.IncompleteReadError, OSError):
                    pass   # disconnect-on-garbage is in contract
            # the lane is still alive for legitimate traffic
            wire = make_query("web.foo.com", Type.A, qid=9).encode()
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", server.tcp_port)
            writer.write(struct.pack(">H", len(wire)) + wire)
            await writer.drain()
            (ln,) = struct.unpack(">H", await asyncio.wait_for(
                reader.readexactly(2), 5.0))
            resp = await reader.readexactly(ln)
            writer.close()
            await writer.wait_closed()
            decode_cache = dict(server.engine._decode_cache)
            stats = server.answer_cache.stats()
            await server.stop()
            return resp, replies, decode_cache, stats

        resp, replies, decode_cache, stats = asyncio.run(run())
        assert Message.decode(resp).rcode == Rcode.NOERROR
        # the legit query may deposit; nothing from the corpus may have
        wire = make_query("web.foo.com", Type.A, qid=9).encode()
        for key in decode_cache:
            assert key == wire[2:], "corpus frame entered decode cache"
        assert stats["entries"] <= 1


class TestRRLMechanics:
    def test_slip_reply_is_tc_echo(self):
        wire = make_query("web.foo.com", Type.A, qid=0x5577).encode()
        slip = ResponseRateLimiter.slip_reply(wire)
        assert slip is not None and len(slip) == len(wire)
        msg = Message.decode(slip)
        assert msg.qr and msg.tc and msg.rcode == Rcode.NOERROR
        assert msg.id == 0x5577
        assert not msg.answers
        assert msg.questions[0].name == "web.foo.com"

    def test_slip_declines_runt_and_oversized(self):
        assert ResponseRateLimiter.slip_reply(b"\x00" * 11) is None
        assert ResponseRateLimiter.slip_reply(b"\x00" * 600) is None

    def test_overdraw_alternates_slip_and_drop(self):
        """slip_ratio=2: past the burst, every 2nd limited response is
        a SLIP, the rest DROP, and the drops reach note_shed with the
        prefix attached."""
        sheds = []
        rrl = ResponseRateLimiter(
            responses_per_second=0.0, burst=2.0, slip_ratio=2,
            note_shed=lambda reason, **kw: sheds.append((reason, kw)))
        verdicts = [rrl.decide("203.0.113.9") for _ in range(6)]
        assert verdicts == [SEND, SEND, DROP, SLIP, DROP, SLIP]
        assert rrl.responses == 2 and rrl.slipped == 2
        assert rrl.dropped == 2
        assert all(r == "response-ratelimit" for r, _ in sheds)
        assert sheds[0][1]["prefix"] == "203.0.113/24"

    def test_prefix_aggregation_and_isolation(self):
        """Hosts in one /24 share a bucket (spoofed low-octet sweeps
        can't mint fresh buckets); a different /24 is untouched."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=3.0,
                                  slip_ratio=0)
        for i in range(3):
            assert rrl.decide(f"198.51.100.{i + 1}") == SEND
        assert rrl.decide("198.51.100.250") == DROP
        assert rrl.decide("198.51.101.1") == SEND
        assert len(rrl._buckets) == 2

    def test_lru_cap_bounds_buckets(self):
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=1.0,
                                  max_buckets=4)
        for i in range(10):
            rrl.decide(f"10.{i}.0.1")
        assert len(rrl._buckets) <= 4
        assert rrl.evictions >= 6
        assert len(rrl._prefix_cache) <= 4

    def test_hot_trips_on_limiting(self):
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=1.0,
                                  slip_ratio=0)
        assert not rrl.hot()
        rrl.decide("192.0.2.1")
        assert not rrl.hot()       # within burst: not limiting
        rrl.decide("192.0.2.1")
        assert rrl.hot()           # overdrawn: fastpath gate signal

    def test_sample_cost_charges_sampling_factor(self):
        """Duty-cycle detection under the fastpath: one sampled packet
        at sample_cost=8 drains the bucket like 8 unsampled ones, so a
        C-served flood still trips the limiter."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=16.0,
                                  slip_ratio=0)
        rrl.sample_cost = 8.0
        assert rrl.decide("192.0.2.5") == SEND
        assert rrl.decide("192.0.2.5") == SEND
        assert rrl.decide("192.0.2.5") == DROP
        assert rrl.hot()

    def test_v6_prefix_aggregation(self):
        """A spoofed 64-bit-IID v6 flood maps to one /56 bucket."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=2.0,
                                  slip_ratio=0)
        assert rrl.decide("2001:db8:0:1::1") == SEND
        assert rrl.decide("2001:db8:0:1::2:3") == SEND
        assert rrl.decide("2001:db8:0:1:dead:beef::9") == DROP
        assert len(rrl._buckets) == 1

    def test_from_config_conventions(self):
        assert ResponseRateLimiter.from_config(None) is None
        assert ResponseRateLimiter.from_config(
            {"enabled": False}) is None
        rrl = ResponseRateLimiter.from_config({})
        assert rrl is not None and rrl.responses_per_second == 200.0
        rrl = ResponseRateLimiter.from_config(
            {"responsesPerSecond": 7, "burst": 9, "slipRatio": 1,
             "maxBuckets": 33})
        assert (rrl.responses_per_second, rrl.burst,
                rrl.slip_ratio, rrl.max_buckets) == (7.0, 9.0, 1, 33)

    def test_refill_restores_send(self):
        rrl = ResponseRateLimiter(responses_per_second=1000.0,
                                  burst=1.0, slip_ratio=0)
        assert rrl.decide("192.0.2.8") == SEND
        assert rrl.decide("192.0.2.8") == DROP
        time.sleep(0.01)
        assert rrl.decide("192.0.2.8") == SEND


class TestRRLv2:
    """Allowlists + adaptive bucket sizing (ISSUE 19)."""

    def test_allowlisted_prefix_never_limited_never_buckets(self):
        """An allowlisted source is passed pre-bucket: zero-rate RRL
        cannot touch it, and it never occupies an LRU slot (a spray
        cannot evict a monitoring host into limiting)."""
        rrl = ResponseRateLimiter(
            responses_per_second=0.0, burst=1.0, slip_ratio=0,
            allowlist=["192.0.2.0/24", "2001:db8::/32"])
        for i in range(50):
            assert rrl.decide(f"192.0.2.{i + 1}") == SEND
        assert rrl.decide("2001:db8::dead:beef") == SEND
        assert rrl.allowlisted == 51
        assert len(rrl._buckets) == 0
        # the /24 next door is still fully limited
        assert rrl.decide("192.0.3.1") == SEND
        assert rrl.decide("192.0.3.2") == DROP

    def test_allowlist_bad_entries_ignored(self):
        """Config typos must not crash the serve stack — garbage
        entries are dropped with a warning, good ones still match."""
        rrl = ResponseRateLimiter(
            allowlist=["banana", "10.0.0.0/99", "10.0.0.0/8"])
        assert len(rrl._allow_nets_v4) == 1
        assert rrl.decide("10.200.1.2") == SEND
        assert rrl.allowlisted == 1
        assert rrl.decide("11.0.0.1") == SEND
        assert rrl.allowlisted == 1     # neighbor did not match

    def test_from_config_v2_keys(self):
        rrl = ResponseRateLimiter.from_config(
            {"allowlist": ["203.0.113.0/24"], "adaptive": False,
             "adaptMaxMultiplier": 8, "adaptEvidence": 5})
        assert rrl.allowlist == ("203.0.113.0/24",)
        assert rrl.adaptive is False
        assert rrl.adapt_max_multiplier == 8.0
        assert rrl.adapt_evidence == 5
        # defaults: adaptive on, no allowlist
        rrl = ResponseRateLimiter.from_config({})
        assert rrl.adaptive is True and rrl.allowlist == ()

    def test_tcp_evidence_earns_doubling(self):
        """adapt_evidence completed TCP serves while limited buy one
        doubling, and the limited streak that pushed the client to TCP
        is attributed to the measured false-positive counter."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=2.0,
                                  slip_ratio=0, adapt_evidence=3)
        ip = "198.51.100.7"
        assert [rrl.decide(ip) for _ in range(3)] == [SEND, SEND, DROP]
        rrl.note_tcp(ip)
        rrl.note_tcp(ip)
        assert rrl.adaptations == 0     # evidence not yet sufficient
        rrl.note_tcp(ip)
        assert rrl.adaptations == 1
        assert rrl.adapted_count() == 1
        assert rrl._adapted["198.51.100/24"][0] == 2.0
        assert rrl.false_positives == 1   # the DROP that forced TCP

    def test_note_tcp_ignores_unlimited_prefixes(self):
        """Ordinary TCP traffic proves nothing: no bucket, or a bucket
        with headroom, must not mint adaptation records (a spoofed
        flood can't complete handshakes; a quiet client needs none)."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=10.0,
                                  slip_ratio=0)
        rrl.decide("192.0.2.1")          # within burst — not limited
        for _ in range(5):
            rrl.note_tcp("192.0.2.1")
        rrl.note_tcp("203.0.113.1")      # no bucket at all
        assert rrl._adapted == {}
        assert rrl.adaptations == 0 and rrl.adapted_count() == 0

    def test_adaptation_caps_at_max_multiplier(self):
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=2.0,
                                  slip_ratio=0, adapt_evidence=1,
                                  adapt_max_multiplier=4.0)
        ip = "198.51.100.9"
        assert [rrl.decide(ip) for _ in range(3)] == [SEND, SEND, DROP]
        for _ in range(5):
            rrl.note_tcp(ip)
        assert rrl._adapted["198.51.100/24"][0] == 4.0
        assert rrl.adaptations == 2      # 1x -> 2x -> 4x, then capped

    def test_adapted_multiplier_survives_bucket_eviction(self):
        """The earned multiplier lives outside the bucket LRU: a spray
        that evicts the farm's bucket must not erase the adaptation —
        the re-minted bucket starts with the doubled burst."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=1.0,
                                  slip_ratio=0, max_buckets=4,
                                  adapt_evidence=1)
        ip = "198.51.100.1"
        assert rrl.decide(ip) == SEND
        assert rrl.decide(ip) == DROP
        rrl.note_tcp(ip)
        assert rrl.adapted_count() == 1
        for i in range(10):              # spray evicts the farm bucket
            rrl.decide(f"10.{i}.0.1")
        assert "198.51.100/24" not in rrl._buckets
        assert rrl.adapted_count() == 1  # adaptation survived
        assert [rrl.decide(ip) for _ in range(3)] == [SEND, SEND, DROP]

    def test_max_buckets_boundary_spray(self):
        """maxBuckets=8192 under a 20k-prefix spray: the table stays
        at the cap, eviction count is bounded and honest, an *active*
        legit bucket survives (access-ordered LRU re-inserts it at the
        MRU end on every touch), and allowlisted sources never occupy
        a slot no matter how often they query."""
        rrl = ResponseRateLimiter(responses_per_second=0.0, burst=50.0,
                                  slip_ratio=0, max_buckets=8192,
                                  allowlist=["127.0.0.0/24"])
        legit = "198.51.100.1"
        rrl.decide(legit)
        for i in range(20000):
            rrl.decide(f"10.{(i >> 8) & 255}.{i & 255}.1")
            if i % 64 == 0:
                rrl.decide(legit)        # active client keeps its slot
            if i % 100 == 0:
                rrl.decide("127.0.0.5")  # allowlisted: pre-bucket pass
        assert len(rrl._buckets) <= 8192
        assert len(rrl._prefix_cache) <= 8192
        assert "198.51.100/24" in rrl._buckets
        assert "127.0.0/24" not in rrl._buckets
        assert rrl.evictions == 20001 - 8192
        assert rrl.allowlisted == 200

    def test_introspect_v2_keys(self):
        rrl = ResponseRateLimiter(allowlist=["192.0.2.0/24"])
        snap = rrl.introspect()
        assert snap["allowlist"] == ["192.0.2.0/24"]
        for key in ("allowlisted", "adaptive", "adapted_buckets",
                    "adaptations", "false_positives"):
            assert key in snap
        assert snap["adaptive"] is True and snap["adapted_buckets"] == 0


class TestEngineIntegration:
    def test_udp_ingress_slips_and_drops_before_decode(self):
        """The engine judges UDP packets at the ingress: a flooded
        prefix gets TC slips / silence while another prefix (and the
        TCP lane) is served normally."""
        store, cache = fixture_store()
        server = new_server(cache, admission={},
                            rrl={"responsesPerSecond": 0, "burst": 2,
                                 "slipRatio": 2})
        wire = make_query("web.foo.com", Type.A, qid=21).encode()
        flood, legit, tcp = [], [], []
        for _ in range(8):
            server.engine._handle_raw(wire, ("203.0.113.5", 9000),
                                      "udp", flood.append)
        # 2 full answers (burst), then drop/slip alternation
        full = [r for r in flood if not (r[2] & 0x02)]
        slips = [r for r in flood if r[2] & 0x02]
        assert len(full) == 2 and len(slips) == 3
        for s in slips:
            m = Message.decode(s)
            assert m.tc and not m.answers
        # another prefix unaffected; TCP exempt even for the flooded one
        server.engine._handle_raw(wire, ("198.51.100.1", 9000),
                                  "udp", legit.append)
        server.engine._handle_raw(wire, ("203.0.113.5", 9000),
                                  "tcp", tcp.append)
        assert len(legit) == 1 and not (legit[0][2] & 0x02)
        assert len(tcp) == 1 and not (tcp[0][2] & 0x02)
        assert server._rrl.hot()

    def test_malformed_flood_is_shed_at_raw_cost(self):
        """RRL judges BEFORE decode: a malformed flood from a limited
        prefix never reaches the parser (no FORMERR replies — slips
        echo the garbage with TC, drops stay silent)."""
        store, cache = fixture_store()
        server = new_server(cache,
                            rrl={"responsesPerSecond": 0, "burst": 1,
                                 "slipRatio": 0})
        junk = b"\x12\x34" + b"\xff" * 20
        out = []
        for _ in range(5):
            server.engine._handle_raw(junk, ("203.0.113.7", 9000),
                                      "udp", out.append)
        # first packet passes the bucket and earns a FORMERR; the
        # flood behind it is silence, not parser work
        assert len(out) == 1 and (out[0][3] & 0x0F) == Rcode.FORMERR
        assert server._rrl.dropped == 4


class TestSamplerRidesTheBatch:
    def test_duty_cycle_sampling_keeps_recvmmsg_batched(self):
        """The RRL duty-cycle sampler surfaces every Nth UDP readiness
        event to Python so the limiter sees a C-served flood — but the
        sampled event must still drain the socket with batched
        recvmmsg (recv_batch), never one recvfrom per packet.  A
        serialized sampled drain would turn 1/N of readiness events
        into per-packet syscall storms and collapse the batch-size
        histogram toward cell 0; this pins the histogram open under a
        backlogged socket with the fastpath gate open and RRL armed."""
        from binder_tpu.dns.server import _fastio
        if _fastio is None or not hasattr(_fastio, "io_stats"):
            pytest.skip("native fastio module not available")

        sample_every = ResponseRateLimiter.FASTPATH_SAMPLE_EVERY
        rounds = 4 * sample_every   # guarantees >= 4 sampled events
        per_round = 64              # one full recvmmsg batch queued

        async def run():
            store, cache = fixture_store()
            # generous limits: the limiter must be ACTIVE (sampler
            # armed) but never hot (hot() would shut the fastpath gate
            # and take the sampler out of play)
            server = new_server(cache, host="127.0.0.1", port=0,
                                rrl={"responsesPerSecond": 1000000,
                                     "burst": 1000000, "slipRatio": 2})
            await server.start()
            try:
                assert server.engine.fastpath is not None
                assert server.engine.fastpath_gate(), \
                    "fastpath gate must be open for the sampler to run"
                wire = make_query("web.foo.com", Type.A, qid=77).encode()
                addr = ("127.0.0.1", server.udp_port)
                csock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                try:
                    csock.setblocking(False)
                    _fastio.io_stats(True)   # reset process-wide counters
                    for _ in range(rounds):
                        # burst without yielding: the reader can't run
                        # until we await, so the socket accumulates a
                        # full batch before each readiness event
                        for _ in range(per_round):
                            try:
                                csock.sendto(wire, addr)
                            except OSError:
                                pass
                        await asyncio.sleep(0.002)
                    # one extra pass so the last round drains
                    await asyncio.sleep(0.02)
                finally:
                    csock.close()
                return _fastio.io_stats(True)
            finally:
                await server.stop()

        io = asyncio.run(run())
        cells = io["recv_cells"]
        # sanity: the flood actually moved through the batched reader
        assert io["recv_msgs"] >= rounds * per_round // 2, io
        assert io["recv_calls"] > 0, io
        # the regression: multi-packet batches must dominate.  With a
        # serialized sampled drain, 1/sample_every of the traffic lands
        # in cell 0 (batch == 1) and the average collapses toward 1.
        avg = io["recv_msgs"] / io["recv_calls"]
        assert avg >= 8, f"recvmmsg batching collapsed: avg={avg:.1f} {io}"
        assert sum(cells[3:]) > 0, \
            f"no batch >= 8 observed under backlog: {cells}"
        assert cells[0] <= io["recv_calls"] // 2, \
            f"batch-size histogram collapsed to 1: {cells}"


class TestExposition:
    def test_rrl_exposition_validates(self):
        """binder_rrl_* + the binder_shed_total response-ratelimit
        series pass the first-party validator from scrape 1 and after
        limiting (the family's tier-1 wiring)."""
        store, cache = fixture_store()
        server = new_server(cache, admission={},
                            rrl={"responsesPerSecond": 0, "burst": 1,
                                 "slipRatio": 2})
        errors = validate_rrl_metrics(server.collector.expose())
        assert errors == [], errors
        wire = make_query("web.foo.com", Type.A, qid=5).encode()
        for _ in range(6):
            server.engine._handle_raw(wire, ("203.0.113.9", 9000),
                                      "udp", lambda _b: None)
        text = server.collector.expose()
        assert validate_rrl_metrics(text) == []
        assert 'reason="response-ratelimit"' in text
