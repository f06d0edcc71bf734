"""Best-effort cross-datacenter recursive resolution.

Port of the reference's Recursion (``lib/recursion.js``): when a name (or
PTR address) misses the local cache and the client set RD, forward the
query to the binders of the datacenter named by the label in front of the
DNS domain — or, for PTR, to every binder we know of in parallel
(``lib/recursion.js:335-354``).

Structure preserved:
- **Resolver discovery** refreshes every 5 minutes (``:40,150-171``) from a
  pluggable source.  The reference hardcodes UFDS/LDAP (``listResolvers``);
  here that's the ``ResolverSource`` interface (SURVEY §7.1 step 6), with a
  config-driven ``StaticResolverSource`` and the real
  :class:`~binder_tpu.recursion.ufds.UfdsResolverSource` — a from-scratch
  LDAPv3 client selected when the config carries ``recursion.ufds.url``.
- **Best-effort init**: first discovery failure retries every 15 s forever
  and the service comes up anyway (``:183-196``); discovery errors after
  that are logged, never fatal (``:160-165``).
- **Self-filtering**: upstream addresses matching local NICs are dropped
  (30 s cached NIC list) so we don't recurse into ourselves (``:356-376``).
- **Answer rebuild**: upstream answers are re-added under the original
  query name, by record type, dropping unsupported types (``:299-323``);
  zero answers → REFUSED, same failover policy as the engine (``:292-296``).
"""
from __future__ import annotations

import asyncio
import logging
import time
from typing import Dict, List, Optional, Sequence

from binder_tpu.dns.query import QueryCtx
from binder_tpu.dns.wire import (
    AAAARecord,
    ARecord,
    CNAMERecord,
    Message,
    PTRRecord,
    Rcode,
    Record,
    SRVRecord,
    TXTRecord,
    Type,
    WireError,
    skip_name,
    skip_record,
)
from binder_tpu.dns.server import HANDLED_ASYNC
from binder_tpu.recursion.client import DnsClient, UpstreamError
from binder_tpu.utils import netif

REFRESH_INTERVAL = 300.0   # 5 min (lib/recursion.js:40)
INIT_RETRY = 15.0          # lib/recursion.js:190
NIC_CACHE_TTL = 30.0       # lib/recursion.js:363
PTR_CONCURRENCY = 100      # lib/recursion.js:76-78


def _host_of(resolver: str) -> str:
    """Host part of 'ip', 'ip:port', or '[v6]:port' — bare IPv6 addresses
    contain colons and must not be split."""
    if resolver.startswith("["):
        return resolver[1:resolver.index("]")]
    if resolver.count(":") == 1:
        return resolver.partition(":")[0]
    return resolver


class ResolverSource:
    """Discovery interface: where do other datacenters' binders live?

    The reference implements this against UFDS:
    ``sdc-ldap search -b 'region=<region>, o=smartdc' objectclass=resolver``
    (``lib/recursion.js:16-19,202-219``).
    """

    async def init(self, zk_cache) -> None:
        """One-time bootstrap; may use the local cache (the reference
        resolves UFDS's own address through binder's ZK mirror,
        ``lib/recursion.js:105-127``).  Raise to trigger the 15 s retry."""

    async def list_resolvers(self, region_name: str) -> List[Dict[str, str]]:
        """Return [{"datacenter": dc, "ip": addr}, ...]."""
        raise NotImplementedError


class StaticResolverSource(ResolverSource):
    """Config-driven source: {"dc-name": ["ip", ...], ...}."""

    def __init__(self, dcs: Dict[str, Sequence[str]]) -> None:
        self._dcs = dcs

    async def list_resolvers(self, region_name: str) -> List[Dict[str, str]]:
        return [{"datacenter": dc, "ip": ip}
                for dc, ips in self._dcs.items() for ip in ips]


class Recursion:
    def __init__(self, *, zk_cache, dns_domain: str, datacenter_name: str,
                 region_name: str = "",
                 source: Optional[ResolverSource] = None,
                 ufds: Optional[dict] = None,
                 log: Optional[logging.Logger] = None,
                 nic_provider=netif.local_addresses,
                 client: Optional[DnsClient] = None,
                 ptr_client: Optional[DnsClient] = None,
                 breakers=None, collector=None, recorder=None) -> None:
        self.zk_cache = zk_cache
        self.dns_domain = dns_domain.lower()
        self.datacenter_name = datacenter_name
        self.region_name = region_name
        self.log = log or logging.getLogger("binder.recursion")
        # Per-peer circuit breakers (binder_tpu/policy/breaker.py),
        # shared by BOTH clients so a peer's health is one fact.  On by
        # default: a dead remote binder must cost a hedge stagger, not
        # the full serial timeout, and once its breaker is open it
        # costs nothing at all (docs/degradation.md).
        if breakers is None:
            from binder_tpu.policy.breaker import PeerBreakers
            breakers = PeerBreakers(collector=collector,
                                    recorder=recorder, log=self.log)
        self.breakers = breakers
        if source is None:
            if ufds is not None and "dcs" in (ufds or {}):
                source = StaticResolverSource(ufds["dcs"])
            elif ufds is not None and ufds.get("url"):
                # the reference's real discovery path: UFDS over LDAP
                # (sapi template recursion.ufds, lib/recursion.js:129-148)
                from binder_tpu.recursion.ufds import UfdsResolverSource
                source = UfdsResolverSource(ufds, log=self.log)
            else:
                source = StaticResolverSource({})
        self.source = source
        self.nic_provider = nic_provider
        self.nsc = client or DnsClient(concurrency=2, breakers=breakers)
        # PTR fans out to every binder in parallel (lib/recursion.js:67-78)
        self.nsc_max = ptr_client or DnsClient(concurrency=PTR_CONCURRENCY,
                                               breakers=breakers)
        # injected clients (tests) still get the shared breaker registry
        # unless they brought their own
        for c in (self.nsc, self.nsc_max):
            if c.breakers is None:
                c.breakers = breakers
        if collector is not None:
            m = collector.counter(
                "binder_recursion_coalesced_total",
                "concurrent identical recursions collapsed onto one "
                "upstream exchange (single-flight)").labelled()
            m.inc(0)
            for c in (self.nsc, self.nsc_max):
                if c.m_coalesced is None:
                    c.m_coalesced = m

        # federation layer (binder_tpu/federation): set via
        # Federation.attach().  upstream_budget is the per-query
        # upstream-work ceiling (NXNSAttack, arXiv:2005.09107) applied
        # to the slow path's fan-out list; None = unbounded (classic).
        self.federation = None
        self.upstream_budget: Optional[int] = None

        self.dcs: Dict[str, List[str]] = {}
        # monotonic instant of the last successful resolver-discovery
        # pull — peer-health introspection (a stale map past several
        # REFRESH_INTERVALs means discovery is failing quietly)
        self.last_refresh_mono: Optional[float] = None
        # set by the owning server (engine._after): enables the
        # zero-coroutine fast path, whose future callback must run the
        # metrics/log after-hook itself
        self.engine_after = None
        self._ready = asyncio.Event()
        self._nics: Optional[List[str]] = None
        self._nics_at = 0.0
        self._bg: List[asyncio.Task] = []
        self._closed = False
        try:
            asyncio.get_running_loop()
            self._spawn(self._init())
        except RuntimeError:
            pass  # no loop yet; caller drives via wait_ready()

    # -- lifecycle --

    def _spawn(self, coro) -> None:
        task = asyncio.ensure_future(coro)
        self._bg.append(task)
        # completed tasks must not accumulate (the truncation-retry
        # path spawns per query)
        task.add_done_callback(self._bg_discard)

    def _bg_discard(self, task) -> None:
        try:
            self._bg.remove(task)
        except ValueError:
            pass

    async def wait_ready(self) -> None:
        if not self._bg and not self._ready.is_set():
            self._spawn(self._init())
        await self._ready.wait()

    async def close(self) -> None:
        self._closed = True
        for t in self._bg:
            t.cancel()
        await asyncio.gather(*self._bg, return_exceptions=True)
        self.nsc.close()
        self.nsc_max.close()
        closer = getattr(self.source, "close", None)
        if closer is not None:
            await closer()

    async def _init(self) -> None:
        """Best-effort client init with 15 s retry
        (lib/recursion.js:93-198)."""
        while not self._closed:
            try:
                await self.source.init(self.zk_cache)
                await self.refresh()
            except Exception as e:  # noqa: BLE001 — best effort by design
                self.log.warning(
                    "Recursion: configured for recursive dns but unable to "
                    "initialize (%s); will try again in %ss, continuing "
                    "since recursive resolves are best effort", e,
                    INIT_RETRY)
                self._ready.set()
                await asyncio.sleep(INIT_RETRY)
                continue
            self.log.info("Recursion: done initing clients")
            self._ready.set()
            self._spawn(self._refresh_loop())
            return

    async def _refresh_loop(self) -> None:
        while not self._closed:
            await asyncio.sleep(REFRESH_INTERVAL)
            try:
                await self.refresh()
            except Exception as e:  # noqa: BLE001
                self.log.error("Recursion: error on refresh: %s", e)

    async def refresh(self) -> None:
        """Re-pull the per-DC resolver map (lib/recursion.js:202-249)."""
        resolvers = await self.source.list_resolvers(self.region_name)
        dcs: Dict[str, List[str]] = {}
        for r in resolvers:
            ips = dcs.setdefault(r["datacenter"], [])
            if r["ip"] not in ips:
                ips.append(r["ip"])
        self.log.debug("Recursion: setting recursion resolvers: %r", dcs)
        self.dcs = dcs
        # drop pooled upstream sockets for resolvers that left the set
        # (long-lived processes see resolver churn)
        from binder_tpu.recursion.client import _parse_resolver
        keep = {_parse_resolver(ip)
                for ips in dcs.values() for ip in ips}
        self.nsc.prune(keep)
        self.nsc_max.prune(keep)
        self.last_refresh_mono = time.monotonic()

    def introspect(self) -> dict:
        """Peer-health section of the status snapshot
        (binder_tpu/introspect/status.py)."""
        dcs = {dc: list(ips) for dc, ips in self.dcs.items()}
        last = self.last_refresh_mono
        return {
            "ready": self._ready.is_set(),
            "region": self.region_name,
            "datacenters": dcs,
            "peer_count": sum(len(ips) for ips in dcs.values()),
            "last_refresh_age_seconds": (
                None if last is None else time.monotonic() - last),
            # dropped upstream responses whose dns0x20 question echo
            # mismatched — sustained growth means a spoofer or an
            # 0x20-incompatible peer
            "case_mismatch_drops": (self.nsc.case_mismatch_drops()
                                    + self.nsc_max.case_mismatch_drops()),
            # concurrent identical lookups collapsed by single-flight
            "coalesced": self.nsc.coalesced + self.nsc_max.coalesced,
            "upstream_budget": self.upstream_budget,
            # per-peer circuit breakers (docs/degradation.md): state,
            # failure runs, backoff, and the p95 behind the hedge delay
            "breakers": self.breakers.introspect(),
            "breakers_open": self.breakers.open_count(),
        }

    # -- the resolve path (lib/recursion.js:287-388) --

    def _my_addrs(self) -> List[str]:
        now = time.monotonic()
        if self._nics is None or now - self._nics_at > NIC_CACHE_TTL:
            self._nics = list(self.nic_provider())
            self._nics_at = now
        return self._nics

    def resolve(self, query: QueryCtx):
        """Entry point from the engine's recursion handoff.

        The dominant shape — forward query, one live upstream for the
        target DC, pooled port ready — is dispatched with ZERO coroutine
        machinery: the query goes out synchronously and a future
        callback completes it (splice-or-rebuild + respond + the
        engine's after hook), returning ``HANDLED_ASYNC``.  Everything
        else (PTR fan-out, multi-upstream DCs, cold ports, truncation
        retries) returns the coroutine the engine drives as a task."""
        # we ARE the recursive service for this shape: RA set on every
        # recursion-produced response, success or failure (the splice
        # path patches the same bit into forwarded wire)
        query.response.ra = True
        if self.engine_after is not None and query.qtype() != Type.PTR:
            domain = query.name().lower()
            if domain.endswith(self.dns_domain):
                prefix = domain[:len(domain) - len(self.dns_domain) - 1]
                dc = prefix[prefix.rfind(".") + 1:]
                ups = self.dcs.get(dc)
                if ups is not None and len(ups) == 1 \
                        and _host_of(ups[0]) not in self._my_addrs() \
                        and self.breakers.get(ups[0]).state == "closed":
                    # (non-closed breaker: the slow path owns the
                    # skip/probe/fail-fast policy via lookup_raw)
                    sent_at = time.monotonic()
                    fut = self.nsc.query_future(domain, query.qtype(),
                                                ups[0])
                    if fut is not None:
                        if self.federation is not None:
                            self.federation.note_forward(domain)
                        # attribution: "dispatch" = local work between
                        # the mirror miss and the upstream send
                        query.stamp("dispatch")
                        fut.add_done_callback(
                            lambda f: self._complete(query, domain, f,
                                                     sent_at, ups[0]))
                        return HANDLED_ASYNC
        return self._resolve_slow(query)

    def _complete(self, query: QueryCtx, domain: str,
                  fut: "asyncio.Future",
                  sent_at: Optional[float] = None,
                  upstream: Optional[str] = None) -> None:
        """Future callback finishing a fast-path forward: splice the
        validated upstream wire, or decode+rebuild for shapes the
        splice declines, or REFUSED on upstream failure — then run the
        engine's after hook (metrics/log)."""
        # Per-stage attribution for the 7.3ms p50 question (VERDICT r5
        # weak 6): how much of a recursive query is the wire round trip
        # vs sitting in the local event loop waiting for this callback?
        # The client stamps the datagram's arrival on the future
        # (binder_recv_t); the two spans are recorded separately so the
        # stage histograms can name the owner.
        now = time.monotonic()
        recv_t = getattr(fut, "binder_recv_t", None)
        if sent_at is not None and recv_t is not None:
            query.record_phase("upstream-rtt",
                               (recv_t - sent_at) * 1000.0)
            query.record_phase("loop-wait", (now - recv_t) * 1000.0)
        # consume the whole dispatch→callback wait into its own cursor
        # phase so the splice/rebuild stamps below time only local work
        query.stamp("await")
        try:
            exc = fut.exception()
            raw_up = None if exc is not None else fut.result()
            if upstream is not None:
                # breaker feedback for the zero-coroutine path (the
                # coroutine paths record inside _query_one): a response
                # of any rcode is a live peer; an exception (timeout,
                # socket death) is a transport failure
                self.breakers.record(
                    upstream, raw_up is not None,
                    None if recv_t is None or sent_at is None
                    else recv_t - sent_at)
            if raw_up is None and self.federation is not None:
                # transport-level failure (timeout / socket death), not
                # a negative answer: the owning DC may be dark — serve
                # the cached foreign answer per the degradation policy
                if self.federation.serve_dark(query, domain):
                    if self.engine_after is not None:
                        self.engine_after(query)
                    return
            if raw_up is not None:
                rcode = raw_up[3] & 0x0F
                if raw_up[2] & 0x02 and rcode == Rcode.NOERROR:
                    # truncated: the TCP retry needs real async — hand
                    # the rare path to a task
                    self._spawn(self._finish_tcp(query, domain))
                    return
                if rcode != Rcode.NOERROR:
                    if self.federation is not None:
                        # a negative answer is still a LIVE peer
                        self.federation.note_success(
                            domain, query.qtype(), raw_up)
                    raw_up = None       # REFUSED shape below
            self._finish_wire(query, domain, raw_up)
        except Exception:  # noqa: BLE001 — callback context: must not leak
            self.log.exception("recursion completion failed")
            if not query.responded:
                query.set_error(Rcode.SERVFAIL)
                try:
                    query.respond()
                except OSError:
                    pass
            if self.engine_after is not None:
                self.engine_after(query)

    async def _finish_tcp(self, query: QueryCtx, domain: str) -> None:
        raw_up = None
        try:
            raw_up = await self.nsc._query_one_tcp(
                domain, query.qtype(), self._dc_upstream(domain))
            if raw_up is not None and (raw_up[3] & 0x0F) != Rcode.NOERROR:
                raw_up = None
        except Exception as e:  # noqa: BLE001 — best-effort retry
            self.log.debug("recursion tcp retry failed: %s", e)
            raw_up = None
        self._finish_wire(query, domain, raw_up)

    def _dc_upstream(self, domain: str) -> str:
        prefix = domain[:len(domain) - len(self.dns_domain) - 1]
        dc = prefix[prefix.rfind(".") + 1:]
        return self.dcs[dc][0]

    def _finish_wire(self, query: QueryCtx, domain: str,
                     raw_up: Optional[bytes]) -> None:
        """Shared tail: splice / rebuild / REFUSED, then the after hook."""
        answers: List[Record] = []
        if raw_up is not None and self.federation is not None:
            # the DC answered: mark it alive and deposit the answer in
            # the foreign cache (the dark-serve fallback's inventory)
            self.federation.note_success(domain, query.qtype(), raw_up)
        if raw_up is not None:
            if self._try_splice(query, raw_up):
                if self.engine_after is not None:
                    self.engine_after(query)
                return
            try:
                answers = Message.decode(raw_up).answers
            except WireError as e:
                self.log.warning("recursion: undecodable upstream "
                                 "response (%s)", e)
        self._respond_rebuilt(query, domain, answers)
        if self.engine_after is not None:
            self.engine_after(query)

    def _respond_rebuilt(self, query: QueryCtx, domain: str,
                         answers: List[Record]) -> None:
        if not answers:
            # see the REFUSED comment in the engine
            query.set_error(Rcode.REFUSED)
        else:
            for rec in answers:
                rebuilt = self._rebuild(domain, rec)
                if rebuilt is not None:
                    query.add_answer(rebuilt)
            if not query.response.answers:
                query.set_error(Rcode.REFUSED)
        query.stamp("rebuild")   # decode+rebuild path (splice declined)
        query.respond()

    async def _resolve_slow(self, query: QueryCtx) -> None:
        # decode_name lowercases wire names already; normalize again in
        # case a caller hands us a hand-built query (0x20-style mixed case)
        domain = query.name().lower()
        answers: List[Record] = []

        is_ptr = query.qtype() == Type.PTR

        if not is_ptr and not domain.endswith(self.dns_domain):
            # never forward names outside our domain to public DNS
            self._respond_rebuilt(query, domain, answers)
            return

        if not is_ptr:
            prefix = domain[:len(domain) - len(self.dns_domain) - 1]
            dc = prefix[prefix.rfind(".") + 1:]
            if dc not in self.dcs:
                self._respond_rebuilt(query, domain, answers)
                return
            upstreams = list(self.dcs[dc])
        else:
            upstreams = [ip for ips in self.dcs.values() for ip in ips]

        my_addrs = self._my_addrs()
        upstreams = [u for u in upstreams
                     if _host_of(u) not in my_addrs]
        if not upstreams:
            self._respond_rebuilt(query, domain, answers)
            return

        # per-query upstream-work budget (NXNSAttack, arXiv:2005.09107):
        # one client query may touch at most this many upstreams — the
        # PTR fan-out across every DC is exactly the amplification shape
        # the budget exists to cap
        budget = self.upstream_budget
        if budget is not None and len(upstreams) > budget:
            upstreams = upstreams[:budget]
            query.log_ctx["budget_clamped"] = True
            if self.federation is not None:
                self.federation.m_budget.inc()

        nsc = self.nsc_max if is_ptr else self.nsc
        raw_up = None
        query.stamp("dispatch")
        if self.federation is not None and not is_ptr:
            self.federation.note_forward(domain)
        try:
            raw_up = await nsc.lookup_raw(
                domain, query.qtype(), upstreams,
                error_threshold=len(upstreams) if is_ptr else None)
            # whole awaited lookup (RTT + loop scheduling + any retries)
            # — the slow path can't split them like the future fast path
            query.stamp("upstream")
            if self.federation is not None and not is_ptr:
                self.federation.note_success(domain, query.qtype(), raw_up)
        except UpstreamError as e:
            self.log.debug("recursion upstream error: %s", e)
            if (self.federation is not None and not is_ptr
                    and not e.got_response
                    and self.federation.serve_dark(query, domain)):
                # transport-dark DC: stale-served (or withheld) from
                # the foreign cache — never a timeout
                return
        if raw_up is not None:
            # Raw splice (the hot path): the upstream answer — already
            # validated by id + dns0x20 question echo + NOERROR — is
            # forwarded as wire bytes with this client's id, RD bit, and
            # question case patched in, skipping decode and re-encode
            # entirely.  The reference rebuilds every record per type
            # per query (lib/recursion.js:299-323); splicing leaves the
            # semantics identical (differential-tested, byte-equal for
            # binder-shaped upstreams) at a fraction of the cost.
            # Shapes the splice can't prove safe fall back to the
            # decode+rebuild path below.
            if self._try_splice(query, raw_up):
                return
            try:
                answers = Message.decode(raw_up).answers
            except WireError as e:
                self.log.warning("recursion: undecodable upstream "
                                 "response (%s)", e)
                answers = []
        self._respond_rebuilt(query, domain, answers)

    def _try_splice(self, query: QueryCtx, up: bytes) -> bool:
        """Forward the upstream wire directly: patch id + RD + question
        case, keep (or strip) the EDNS OPT to match the client, send.

        Returns False — leaving the decode+rebuild path authoritative —
        for every shape it can't prove equivalent to the rebuild:
        multi-question, authority records, non-OPT additionals (the
        rebuild drops those), structural walk failures, a needed-but-
        absent OPT, an answer that would exceed the client's UDP
        ceiling, or a query whose log line needs decoded record detail
        (the logged posture keeps full answer summaries)."""
        raw = query.raw
        req = query.request
        if (raw is None or query.want_log_detail
                or len(req.questions) != 1):
            return False
        if query.latency_ms() > 1000.0:
            # the slow-query WARNING (SLOW_QUERY_MS) fires even with
            # query_log off and needs decoded answer summaries — a
            # forward that is ALREADY slow takes the rebuild path so
            # its log line carries them
            return False
        if len(up) < 12 or up[4:6] != b"\x00\x01" \
                or up[8:10] != b"\x00\x00":
            return False                # question/authority shape
        # walk the upstream question (uncompressed by construction —
        # our client sent it; the echo was verified byte-exact)
        q_end = skip_name(up, 12)
        if q_end is None or q_end + 4 > len(up):
            return False
        q_end += 4
        # client question section from the request wire: must be the
        # same name modulo 0x20 case, same type/class, same length
        cq_end = skip_name(raw, 12)
        if cq_end is None or cq_end + 4 > len(raw):
            return False
        cq_end += 4
        if cq_end != q_end \
                or raw[12:cq_end].lower() != up[12:q_end].lower():
            return False
        ancount = (up[6] << 8) | up[7]
        arcount = (up[10] << 8) | up[11]
        pos = q_end
        for _ in range(ancount):
            nxt = skip_record(up, pos)
            if nxt is None:
                return False
            pos = nxt[0]
        opt_start = None
        for i in range(arcount):
            start = pos
            nxt = skip_record(up, pos)
            if nxt is None:
                return False
            pos, rtype = nxt
            if rtype != Type.OPT:
                # the rebuild path drops non-OPT additionals; splicing
                # them through would diverge — decline
                return False
            if i != arcount - 1:
                return False            # OPT must be the final record
            opt_start = start
        if pos != len(up):
            return False                # trailing bytes
        if req.edns is not None:
            if opt_start is None:
                return False            # rebuild would add the echo OPT
            tail = up[q_end:]
            new_ar = arcount
        elif opt_start is not None:
            tail = up[q_end:opt_start]  # client spoke no EDNS: strip
            new_ar = arcount - 1
        else:
            tail = up[q_end:]
            new_ar = arcount
        # header: client id, upstream flags with the client's RD echoed
        # (we forward with RD=0), RA set — WE are the recursive service
        # here; the upstream answered authoritatively with its own RA
        # clear — and counts with the OPT adjustment
        flags2 = (up[2] & 0xFE) | (0x01 if req.rd else 0)
        wire = (req.id.to_bytes(2, "big")
                + bytes((flags2, up[3] | 0x80))
                + up[4:10] + new_ar.to_bytes(2, "big")
                + raw[12:q_end] + tail)
        if query.udp_semantics and len(wire) > req.max_udp_payload():
            return False                # truncation: rebuild path owns it
        query.response.rcode = up[3] & 0x0F   # for metrics
        query.log_ctx["spliced"] = True
        # attribution: local splice work only (the upstream wait was
        # consumed by the "await"/"upstream" stamps upstream of here)
        query.stamp("splice")
        query.respond_raw(wire)
        return True

    def _rebuild(self, domain: str, rec: Record) -> Optional[Record]:
        """Re-create the upstream answer under the original query name,
        by type (lib/recursion.js:299-323)."""
        ttl = rec.ttl
        if isinstance(rec, ARecord):
            return ARecord(name=domain, ttl=ttl, address=rec.address)
        if isinstance(rec, AAAARecord):
            return AAAARecord(name=domain, ttl=ttl, address=rec.address)
        if isinstance(rec, (PTRRecord, CNAMERecord)):
            return type(rec)(name=domain, ttl=ttl, target=rec.target)
        if isinstance(rec, TXTRecord):
            return TXTRecord(name=domain, ttl=ttl, texts=rec.texts)
        if isinstance(rec, SRVRecord):
            return SRVRecord(name=domain, ttl=ttl, priority=rec.priority,
                             weight=rec.weight, port=rec.port,
                             target=rec.target)
        self.log.warning("recursion: upstream returned unsupported record "
                         "type %s, dropping", type(rec).__name__)
        return None
