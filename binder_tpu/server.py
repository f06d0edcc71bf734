"""The binder server: transport engine + resolution + observability.

Port of the reference's ``createServer`` wiring (``lib/server.js:435-660``):
attaches the resolution engine to the transport engine's ``query`` hook,
and metrics + structured query logging to the ``after`` hook.  ``start()``
brings up UDP + TCP listeners and, when configured, the balancer UNIX
socket (``lib/server.js:609-653``).
"""
from __future__ import annotations

import asyncio
import json as _json
import logging
import os as _os
import re
import socket as _socket
import struct
import threading
import time
from urllib.parse import urlparse as _urlparse
from typing import Callable, Optional

try:  # native fast path (built by `make -C native`); optional
    from binder_tpu import _binderfastio as _fastio
except ImportError:
    _fastio = None

from binder_tpu.dns.query import QueryCtx
from binder_tpu.dns.server import DnsServer, bind_port_pair
from binder_tpu.dns.wire import (
    ARecord,
    OPTRecord,
    PTRRecord,
    Rcode,
    SRVRecord,
    Type,
    WireError,
    encode_name,
    ip_from_reverse_name,
    reverse_name_for_ip,
)
from binder_tpu.introspect.ledger import (
    EVENT_LANES,
    METRIC_STAGE_HISTOGRAM,
    STAGE_HISTOGRAM_HELP,
    TCP_STAGES,
    SpanFold,
    event,
)
from binder_tpu.metrics.collector import (
    DEFAULT_SIZE_BUCKETS,
    DEFAULT_STAGE_BUCKETS,
    MetricsCollector,
)
from binder_tpu.resolver.answer_cache import AnswerCache
from binder_tpu.store.names import rec_parts as _names_rec_parts
from binder_tpu.resolver.engine import (
    DEFAULT_TTL,
    MAX_SET_RECORDS,
    Resolver,
    SERVICE_CHILD_TYPES as _SERVICE_CHILD_TYPES,
    TYPE_RULE,
    _record_ttl as _engine_record_ttl,
)
from binder_tpu.utils.jsonlog import JsonFormatter, log_event
from binder_tpu.utils.probes import ProbeProvider
from binder_tpu.verify import Verifier

METRIC_REQUEST_COUNTER = "binder_requests_completed"
METRIC_LATENCY_HISTOGRAM = "binder_request_latency_seconds"
METRIC_SIZE_HISTOGRAM = "binder_response_size_bytes"
METRIC_TRUNCATED_COUNTER = "binder_truncated_responses"
METRIC_TRUNCATED_RENDERS = "binder_truncated_renders"
# per-stage attribution (METRIC_STAGE_HISTOGRAM): one histogram, labeled
# by stage, fed from the QueryCtx phase stamps at after-hook time — the
# scrapeable form of the query log's `timers` dict (same stage names) —
# and from the time ledger's leaf spans (introspect/ledger.py)

SLOW_QUERY_MS = 1000.0  # log at warn above this (lib/server.js:511-514)

# binder_udp_batch_size's upper bounds: the log2 cells of
# fastio_io.recv_cells (1, 2-3, 4-7, ..., >=128 as the +Inf cell)
UDP_BATCH_BUCKETS = (1, 3, 7, 15, 31, 63, 127)

# byte values a name label may contain for the native fast path; names
# outside this set are still served, just never through the C cache
# (keep in lockstep with fp_name_ok in native/fastio/fastpath.c)
_FP_NAME_OK = frozenset(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_")


def strip_suffix(suffix: str, s: str) -> str:
    """Log redaction of the (long, constant) DNS domain
    (lib/server.js:60-65)."""
    if s.endswith(suffix):
        return s[:len(s) - len(suffix)] + "..."
    return s


# one label of a registered srvce/proto pair, exactly what one group of
# the engine's SRV_RE can match — zone SRV entries are only pushed for
# qnames the engine would parse back to the same service
_SRV_LABEL_RE = re.compile(r"^_[^_.]*$")

# rotation-variant ceiling, in lockstep with FP_MAX_VARIANTS
# (native/fastio/fpcore.h) — a push with more variants than the C side
# accepts would be silently rejected and the name never precompiled
_FP_MAX_VARIANTS = 8

# Record types the zone table answers with one A record: exactly the
# host-likes the resolver maps to a single A record
# (resolver/engine.py:213-216).  'service' (rotation, SRV) and 'database'
# (URL parse) have their own zone pushes.
_LANE_HOST_TYPES = frozenset({
    "db_host", "host", "load_balancer", "moray_host", "redis_host",
    "ops_host", "rr_host",
})


def _rec_ttl(rec: tuple) -> int:
    """Deepest-object-wins TTL for a COMPACT record tuple
    (store/names.py) — sub-record TTL wins, else record TTL, else
    default; the compact invariant guarantees ints, so there is no
    garbage case to decline on."""
    parts = _names_rec_parts(rec)
    if parts[3] is not None:
        return parts[3]
    if parts[2] is not None:
        return parts[2]
    return DEFAULT_TTL


def _lane_ttl(record: dict, sub) -> Optional[int]:
    """Deepest-object-wins TTL (the one policy, engine._record_ttl:
    sub-record TTL wins, else record TTL, else default); None means the
    store value is garbage and the zone table must leave the name to
    the Python lanes.  Shared by the zone's A and PTR pushes so the
    precedence cannot drift between them."""
    ttl = record.get("ttl")
    sttl = sub.get("ttl") if type(sub) is dict else None
    if sttl is not None:
        ttl = sttl
    elif ttl is None:
        ttl = DEFAULT_TTL
    return ttl if type(ttl) is int else None


def _fastpath_key_parts(rd: bool, edns: bool, payload: int, qtype: int,
                        qclass: int, qname_wire: bytes) -> bytes:
    """The native answer-cache key, from its components.

    SINGLE SOURCE OF THE LAYOUT on the Python side — both
    ``BinderServer._fastpath_key`` and the native installs build
    through here.
    Must stay byte-for-byte with ``fp_build_key`` in
    native/fastio/fastpath.c and the balancer's copy (see
    docs/balancer-protocol.md):
    ``[flags rd|edns<<1][payload BE16][qtype BE16][qclass BE16][qname]``
    where qname is the wire-format name, lowercased.
    """
    return (bytes([(1 if rd else 0) | (2 if edns else 0)])
            + payload.to_bytes(2, "big") + qtype.to_bytes(2, "big")
            + qclass.to_bytes(2, "big") + qname_wire)


#: a log line's own fields, dumped as JsonFormatter dumps them; one
#: encoder, because json.dumps builds a new one a call when handed a
#: ``default``
_LOG_ENCODE = _json.JSONEncoder(default=str).encode


class BinderServer:
    def __init__(self, *, zk_cache, dns_domain: str,
                 datacenter_name: str = "",
                 recursion=None,
                 log: Optional[logging.Logger] = None,
                 collector: Optional[MetricsCollector] = None,
                 name: str = "binder",
                 host: str = "127.0.0.1", port: int = 53,
                 balancer_socket: Optional[str] = None,
                 query_log: bool = True,
                 cache_size: int = 10000,
                 cache_expiry_ms: int = 60000,
                 zone_precompile: bool = True,
                 tcp_idle_timeout: Optional[float] = None,
                 max_tcp_conns: Optional[int] = None,
                 max_tcp_write_buffer: Optional[int] = None,
                 probes: Optional[ProbeProvider] = None,
                 flight_recorder=None,
                 degradation: Optional[dict] = None,
                 admission: Optional[dict] = None,
                 rrl: Optional[dict] = None,
                 verify: Optional[dict] = None,
                 sockets: Optional[tuple] = None,
                 read_when_filled: bool = False,
                 announce: bool = True) -> None:
        self.log = log or logging.getLogger("binder.server")
        # introspection flight recorder (binder_tpu/introspect):
        # slow-query events from the after hook, resolver errors from
        # the engine's error path
        self.recorder = flight_recorder
        self.host = host
        self.port = port
        # shard mode (binder_tpu/shard): the supervisor binds one UDP
        # socket and one TCP listener a shard on ONE port
        # (SO_REUSEPORT) and every incarnation of the shard inherits
        # the pair (``sockets``: served here, never bound or unbound);
        # a roll's replacement reads them only once it is filled
        # (``read_when_filled``), its incumbent serving until then.  The
        # supervisor also owns the canonical "service started" announce
        # lines — workers keep quiet so harnesses never latch onto a
        # group still forming
        self.sockets = sockets
        self.read_when_filled = read_when_filled
        self.announce = announce
        # filled: the startup zone fill is done, so every name the
        # native lanes can serve is theirs
        self.filled = False
        self.on_filled: Optional[Callable[[], None]] = None
        self._filled_task = None
        self._fill_done = 0
        self.dns_domain = dns_domain
        self.balancer_socket = balancer_socket
        self.collector = collector or MetricsCollector()
        # per-query logging can be disabled for high-qps deployments;
        # slow queries (>1s) are logged regardless
        self.query_log = query_log
        # encoded-answer cache (the reference's -s/-a flags, main.js:34-38)
        self.zk_cache = zk_cache
        self.answer_cache = AnswerCache(
            size=cache_size, expiry_ms=cache_expiry_ms,
            # tag/qname strings dedup against the mirror's own domain
            # objects (the interned-name pool architecture, ISSUE 7)
            intern=getattr(zk_cache, "canon", None))
        self.cache_hit_counter = self.collector.counter(
            "binder_answer_cache_hits", "encoded-answer cache hits")
        # one child per tier: the Python lanes' hits are counted where
        # they happen, the C lanes' are folded in at scrape
        self._cache_hit_child = self.cache_hit_counter.labelled(
            {"tier": "python"})
        self._cache_hit_native_child = self.cache_hit_counter.labelled(
            {"tier": "native"})
        self._cache_hit_child.inc(0)        # both series from the start
        self._cache_hit_native_child.inc(0)
        self._fp_inval_total = 0   # C-side drops, updated at each fold
        self.collector.gauge(
            "binder_answer_cache_invalidations",
            "answer-cache entries dropped by per-name store invalidation"
        ).set_function(lambda: float(self.answer_cache.invalidations
                                     + self._fp_inval_total))

        self.request_counter = self.collector.counter(
            METRIC_REQUEST_COUNTER, "count of Binder requests completed")
        self.latency_histogram = self.collector.histogram(
            METRIC_LATENCY_HISTOGRAM,
            "total time to process Binder requests")
        self.size_histogram = self.collector.histogram(
            METRIC_SIZE_HISTOGRAM, "size in bytes of Binder responses",
            buckets=DEFAULT_SIZE_BUCKETS)
        self.stage_histogram = self.collector.histogram(
            METRIC_STAGE_HISTOGRAM, STAGE_HISTOGRAM_HELP,
            buckets=DEFAULT_STAGE_BUCKETS)
        # UDP answers that left with TC=1, for the client to fetch
        # again over TCP (RRL's slips are binder_rrl_slipped_total's);
        # A and SRV, the types whose sets grow, from scrape 1
        self.truncated_counter = self.collector.counter(
            METRIC_TRUNCATED_COUNTER,
            "UDP responses sent truncated (TC=1), by query type")
        for qtype in (Type.A, Type.SRV):
            self.truncated_counter.labelled(
                {"type": Type.name(qtype)}).inc(0)
        # of those, the ones a resolve of the Python lanes rendered the
        # whole set for, for the encode to drop it: what the answer
        # cache did not hold (it holds a truncated wire from its first
        # sight)
        self._tc_render_child = self.collector.counter(
            METRIC_TRUNCATED_RENDERS,
            "truncated UDP answers that cost a resolve: the set was "
            "rendered and the encode dropped it").labelled()
        self._tc_render_child.inc(0)   # series exists from scrape 1
        # per-qtype pre-resolved metric handles (label-sort once, not
        # per query); key is the numeric qtype
        self._metric_children: dict = {}
        # per-stage pre-resolved histogram handles, keyed by stage name
        self._stage_children: dict = {}

        # The time ledger (introspect/ledger.py): the query log's two
        # leaf spans are timed here, straight into their stage's
        # child; the socket calls and the native serve loop are timed
        # in C and fold into the stage histogram at scrape.
        self._log_write_child = self.stage_histogram.labelled(
            {"stage": "log-write"})
        self._log_line_child = self.stage_histogram.labelled(
            {"stage": "log-line"})
        self._log_bytes = self.collector.counter(
            "binder_query_log_bytes",
            "bytes of query-log lines written (native ring drains and "
            "Python-lane lines)")
        self._log_bytes_child = self._log_bytes.labelled()
        self._log_bytes_child.inc(0)
        # how often the direct render engages (_on_after); the native
        # lanes' lines are fastpath_stats' log_lines
        self._log_lines = log_lines = self.collector.counter(
            "binder_query_log_lines",
            "Python-lane query-log lines, by the path that rendered "
            "them: straight to bytes, or through logging")
        self._log_direct_child = log_lines.labelled({"path": "direct"})
        self._log_logging_child = log_lines.labelled({"path": "logging"})
        self._log_direct_child.inc(0)
        self._log_logging_child.inc(0)
        self._json_formatters = [h.formatter
                                 for h in self._find_json_handlers()]
        self._io_folds: dict = {}
        self._io_folded: dict = {}
        self._io_fold_lock = threading.Lock()
        if _fastio is not None and hasattr(_fastio, "io_span_grid"):
            _fastio.io_span_grid(
                [float(b) for b in self.stage_histogram.buckets])
            self._io_folds = {
                stage: SpanFold(self.collector, stage)
                for stage in ("udp-recv", "native-serve", "udp-send")}
            datagrams = self.collector.counter(
                "binder_udp_datagrams",
                "UDP datagrams moved by the batched socket calls")
            self._udp_in_child = datagrams.labelled({"dir": "in"})
            self._udp_out_child = datagrams.labelled({"dir": "out"})
            self._udp_in_child.inc(0)
            self._udp_out_child.inc(0)
            self._udp_batch_child = self.collector.histogram(
                "binder_udp_batch_size",
                "datagrams a recvmmsg call returned (calls that "
                "returned any)", buckets=UDP_BATCH_BUCKETS).labelled()
        self.collector.on_expose(self._fold_ledger)

        # USDT analog: provider 'binder', probes op-req-start/op-req-done
        # fired with the query context (lib/server.js:24-29,472-474,516-518)
        self.probes = probes or ProbeProvider("binder")
        self.p_req_start = self.probes.probe("op-req-start")
        self.p_req_done = self.probes.probe("op-req-done")

        self.resolver = Resolver(zk_cache, dns_domain=dns_domain,
                                 datacenter_name=datacenter_name,
                                 recursion=recursion, log=self.log)

        # Degradation policy engine (binder_tpu/policy, docs/
        # degradation.md).  Off by default at this layer — main.py
        # turns both on from config (`degradation` / `admission`
        # blocks, default enabled) like the other production knobs.
        self._policy = None
        self._policy_task = None
        store = getattr(zk_cache, "store", None)
        if (degradation is not None
                and degradation.get("enabled", True) and store is not None):
            from binder_tpu.policy import DegradationPolicy
            self._policy = DegradationPolicy(
                store=store, zk_cache=zk_cache,
                max_staleness_s=float(degradation.get(
                    "maxStalenessSeconds", 300.0)),
                stale_ttl_clamp_s=int(degradation.get(
                    "staleTtlClampSeconds", 30)),
                exhausted_action=str(degradation.get(
                    "exhaustedAction", "servfail")),
                collector=self.collector, recorder=flight_recorder,
                log=self.log)
            # answers rendered under one staleness mode must never be
            # served under another: every transition flushes all cached
            # lanes (Python, native, balancer) via the epoch
            self._policy.on_transition(self._on_degradation_transition)
            self.resolver.policy = self._policy
        self._admission = None
        if admission is not None and admission.get("enabled", True):
            from binder_tpu.policy import AdmissionControl
            self._admission = AdmissionControl(
                max_inflight=int(admission.get("maxInflight", 512)),
                recursion_rate=float(admission.get(
                    "recursionRate", 50.0)),
                recursion_burst=float(admission.get(
                    "recursionBurst", 100.0)),
                collector=self.collector, recorder=flight_recorder,
                log=self.log)
            self.resolver.admission = self._admission
        # Response rate limiting (binder_tpu/policy/rrl.py): per-client-
        # prefix slip/drop at the UDP ingress.  Same config convention as
        # admission — None disables (direct construction / tests), a
        # config block (even empty) enables with defaults.
        self._rrl = None
        if rrl is not None:
            from binder_tpu.policy import ResponseRateLimiter
            self._rrl = ResponseRateLimiter.from_config(
                rrl,
                note_shed=(self._admission._note_shed
                           if self._admission is not None else None),
                recorder=flight_recorder, log=self.log)
        self._rrl_children: dict = {}
        self._rrl_folded: dict = {}
        if self._rrl is not None:
            for field, help_text in (
                ("responses", "UDP responses admitted by response rate "
                 "limiting"),
                ("slipped", "rate-limited UDP queries answered with a "
                 "TC=1 slip (client retries over TCP)"),
                ("dropped", "rate-limited UDP queries dropped silently"),
                ("evictions", "RRL prefix buckets evicted at the LRU "
                 "cap"),
                ("allowlisted", "responses passed by an RRL allowlist "
                 "match (never limited, never bucketed)"),
                ("adaptations", "adaptive-bucket rate doublings earned "
                 "by TCP-proven prefixes"),
                ("false_positives", "rate-limited responses charged to "
                 "a prefix later proven real by completed TCP retries "
                 "(the measured RRL false-positive count)"),
            ):
                child = self.collector.counter(
                    "binder_rrl_" + field + "_total", help_text).labelled()
                child.inc(0)   # series exists from scrape 1
                self._rrl_children[field] = child
            self.collector.gauge(
                "binder_rrl_buckets",
                "client prefixes currently tracked by response rate "
                "limiting"
            ).set_function(lambda: float(len(self._rrl._buckets)))
            self.collector.gauge(
                "binder_rrl_active",
                "1 while response rate limiting shed traffic recently "
                "(the hostile-flood posture; also closes the native "
                "fastpath gate)"
            ).set_function(lambda: 1.0 if self._rrl.hot() else 0.0)
            self.collector.gauge(
                "binder_rrl_adapted_buckets",
                "client prefixes holding an earned adaptive rate "
                "multiplier (TCP-proven NAT'd farms)"
            ).set_function(lambda: float(self._rrl.adapted_count()))
        if recursion is not None and hasattr(recursion, "engine_after"):
            # arm the recursion fast path: its future callback completes
            # the query AND runs the engine's after hook itself
            recursion.engine_after = self._engine_after_hook
        # multi-DC federation handle (binder_tpu/federation) — set by
        # main.py (or tests) after construction; read by the
        # introspector for the /status federation section
        self.federation = None

        # Serving-plane verification (binder_tpu/verify, ISSUE 16):
        # incremental invariant checks off the same per-name
        # invalidation feed the zone drain takes, a sampled
        # budgeted full-zone audit, and mutation-to-glass propagation
        # tracing.  Same config convention as admission/rrl: None
        # disables (direct construction / tests), a config block
        # (even empty) enables with defaults.
        self._verify: Optional[Verifier] = None
        # trace contexts for names awaiting a zone re-push, popped by
        # _zone_refresh to mark the native-install stage; bounded so a
        # mutation storm on an unserved zone cannot grow it
        self._zone_trace: dict = {}
        if verify is not None and verify.get("enabled", True):
            self._verify = Verifier(
                zk_cache=zk_cache, config=verify,
                collector=self.collector, recorder=flight_recorder,
                log=self.log)
            # the mirror stamps each mutation's trace context at
            # bump_gen and marks mirror-apply at invalidation fan-out
            zk_cache.tracer = self._verify.tracer

        self.engine = DnsServer(log=self.log, name=name,
                                tcp_idle_timeout=tcp_idle_timeout,
                                max_tcp_conns=max_tcp_conns,
                                max_tcp_write_buffer=max_tcp_write_buffer)
        self.engine.on_query = self._on_query
        self.engine.on_after = self._on_after
        self.engine.recorder = flight_recorder
        self.engine.admission = self._admission
        self.engine.rrl = self._rrl
        # the ledger's stream-lane spans: timed in dns/stream.py and the
        # accept path, observed straight into their stage's child
        for stage, slot in zip(
                TCP_STAGES + ("tcp-register", "query-ingress"),
                ("span_accept", "span_recv", "span_send", "span_close",
                 "span_register", "span_ingress")):
            setattr(self.engine, slot, self.stage_histogram.labelled(
                {"stage": stage}).observe)
        # the ledger's event spans: every readiness callback of the
        # served path is registered behind its lane's, so the whole
        # callback is timed into binder_loop_event_seconds{lane}
        for lane in EVENT_LANES:
            setattr(self.engine, "event_" + lane,
                    event(self.collector, lane))
        # the engine's cap-refusal log line is rate-limited, so the
        # counter is the only complete record — surface it in the scrape
        self._cap_refusal_child = self.collector.counter(
            "binder_tcp_cap_refusals",
            "TCP connections refused at the connection cap").labelled()
        self._cap_refusal_child.inc(0)   # series exists from scrape 1
        self._cap_folded = 0
        # late (async-completed) UDP responses dropped at a full socket
        # buffer — previously a silent debug line (ISSUE 7 satellite)
        late_drops = self.collector.counter(
            "binder_udp_late_drops_total",
            "late (async-completed) UDP responses dropped because the "
            "socket send buffer stayed full through the retry").labelled()
        late_drops.inc(0)                # series exists from scrape 1
        self.engine.late_drop_counter = late_drops
        # answers of a UDP drain dropped the same way, by the lane that
        # sent them: the C lanes count their own (io_stats
        # "send_drops"), the engine the Python lanes'; folded at the
        # scrape like every such counter
        send_drops = self.collector.counter(
            "binder_udp_send_drops_total",
            "UDP answers dropped because the socket send buffer was "
            "still full at the one retry, by the lane that sent them")
        self._send_drop_children = {
            lane: send_drops.labelled({"lane": lane})
            for lane in ("native", "python", "balancer")}
        for child in self._send_drop_children.values():
            child.inc(0)                 # series exist from scrape 1
        # the C lanes' counts are the process's: what they held before
        # this server was made is not its own
        self._send_drops_folded = dict(self.udp_send_drops(), python=0)
        # drains the batched UDP reader chained behind another in one
        # readiness callback, with no select between them
        # (DnsServer._UDP_CHAIN_MIN)
        self._chained_child = self.collector.counter(
            "binder_udp_chained_drains_total",
            "UDP drains (the socket read empty, the answers sent) made "
            "in the callback of the drain before them, with no select "
            "and no log write between").labelled()
        self._chained_child.inc(0)       # series exists from scrape 1
        self._chained_folded = 0
        # stream-lane counters (dns/stream.py TcpStats), folded at
        # scrape time like the cap refusals; every series exists from
        # scrape 1 so absence is always an exporter bug
        # (tools/lint.py validate_tcp_metrics pins the family)
        self._tcp_stat_children: dict = {}
        for field, help_text in (
            ("accepts", "TCP connections accepted"),
            ("fast_serves", "frames served via the accept fast path "
             "(connections not yet promoted to the pipelined protocol)"),
            ("native_serves", "TCP frames the native bulk serve answered "
             "(answer cache or zone table; the rest went to the Python "
             "lanes)"),
            ("promotions", "TCP connections promoted to the full "
             "pipelined protocol (kept sending after the first served "
             "burst)"),
            ("oneshot_closes", "TCP connections closed after serving "
             "without ever promoting (one-shot clients)"),
            ("idle_timeouts", "TCP connections dropped by the idle "
             "deadline"),
            ("slow_reader_drops", "TCP connections disconnected at the "
             "write-buffer cap (client not reading responses)"),
            ("coalesced_writes", "vectored TCP writes that carried "
             "more than one response frame"),
            ("coalesced_frames", "TCP response frames sent through "
             "coalesced vectored writes"),
            ("half_closes", "half-closed TCP connections held to "
             "serve owed responses"),
            ("rst_drops", "TCP connections dropped on reset/error "
             "mid-read"),
        ):
            child = self.collector.counter("binder_tcp_" + field,
                                           help_text).labelled()
            child.inc(0)
            self._tcp_stat_children[field] = child
        self._tcp_stats_folded: dict = {}
        self.collector.gauge(
            "binder_tcp_open_conns",
            "TCP client connections currently open"
        ).set_function(lambda: float(len(self.engine._tcp_conns)))
        self.collector.on_expose(self._fold_engine_counters)

        # The zone table's dnsDomain suffix policy (_zone_suffix_ok):
        # the strings Resolver.resolve compares against, built once.
        dd = self.resolver.dns_domain
        self._lane_suffix = ("." + dd) if dd else None
        self._lane_dcsuff = dd + "." + self.resolver.datacenter_name

        # Native fast path: answer-cache hits served inside the C UDP
        # drain (native/fastio/fastpath.c).  Python remains the source of
        # truth — completed answer-cache entries are pushed down in
        # _on_query, and the C-side counters fold into the same
        # Prometheus collectors at scrape time (_fold_fastpath_metrics).
        # Balancer answer-cache support: the generation report carries
        # the mirror *epoch* (full-rebuild counter), so the balancer
        # only drops everything when a re-mirror really happened;
        # ordinary mutations ride the per-name invalidate frames
        # broadcast from _on_store_invalidate
        # (docs/balancer-protocol.md control frames)
        self.engine.gen_source = self._epoch_source
        if hasattr(zk_cache, "on_mutation"):
            zk_cache.on_mutation(self.engine.notify_mutation)
        # Per-name invalidation: a mirrored mutation drops exactly the
        # answer-cache/fast-path entries whose dependency tag it touched
        # (MirrorCache.invalidate); the epoch (bumped on full rebuilds)
        # covers everything else.  One churning record no longer evicts
        # every cached answer.
        if hasattr(zk_cache, "on_invalidate"):
            zk_cache.on_invalidate(self._on_store_invalidate)

        self._fastpath = None
        self._fp_folded: dict = {}
        self._fp_last_stats: dict = {}   # per-scrape snapshot (gauges)
        self._fp_fold_lock = threading.Lock()
        if (_fastio is not None and cache_size > 0
                and hasattr(_fastio, "fastpath_new")):
            self._fastpath = _fastio.fastpath_new(
                cache_size, cache_expiry_ms,
                [float(b) for b in self.latency_histogram.buckets],
                [float(b) for b in self.size_histogram.buckets])
            self.engine.fastpath = self._fastpath
            self.engine.fastpath_gen = self._epoch_source
            self.engine.fastpath_gate = self._fastpath_active
            self.collector.on_expose(self._fold_fastpath_metrics)
        # queries answered before this server was filled, every lane's
        # (after the native fold above): a fresh worker serves through
        # its fill, a roll's replacement must read 0
        self._unfilled_child = self.collector.counter(
            "binder_unfilled_serves_total",
            "queries answered before the startup zone fill was "
            "complete").labelled({})
        self._unfilled_child.inc(0)
        self._unfilled_folded = 0.0
        self.collector.on_expose(self._fold_unfilled)

        # The query log's one writer (_write_log).  With per-query
        # logging ON (the reference's always-on posture,
        # lib/server.js:537-591) and a logger that ends in a
        # JsonFormatter stream (the production logger from
        # make_logger), a line is rendered straight to bytes behind a
        # prefix rendered once: by C into a byte ring, one complete
        # bunyan-style line per native serve, and by _on_after into
        # _log_pending for a Python-lane query.  Ring and pending lines
        # go out in ONE stream write a readiness callback (a UDP one:
        # after the last drain of its chain), after the responses,
        # onto the same stream the JSON logger writes to.  Two
        # guarantees are kept: every served query's line is written
        # before the loop is given back to select, and no answer waits
        # behind a log write.  One was given up (ISSUE 46): a drain's
        # lines are written before the next recvmmsg of the same
        # callback.  Before the ring the fast path stood down
        # completely under logging, forfeiting ~9x throughput.  A
        # serve that cannot produce its line (ring full, no fragment)
        # DECLINES to the Python path, which logs: pressure degrades
        # throughput, never drops log records.  With any other logger
        # every line goes through `logging` and the old stand-down
        # gating applies unchanged.
        self._log_ring = False
        self._log_json_handlers: list = []
        self._log_prefix = b""
        #: rendered Python-lane lines awaiting the write; appended and
        #: taken under _log_lock (a record of another thread flushes
        #: them too, _before_record)
        self._log_pending: list = []
        self._log_lock = threading.RLock()
        self._log_soon = False       # a call_soon'd write is armed
        self._log_sec = -1           # the second _log_sec_head renders
        self._log_sec_head = b""
        self._log_flush_task: Optional[asyncio.Task] = None
        if self.query_log and self.log.isEnabledFor(logging.INFO):
            self._log_json_handlers = self._find_json_handlers()
        if self._log_json_handlers:
            self._log_prefix = self._native_log_prefix()
            self.engine.log_flush = self._write_log
            if (self._fastpath is not None
                    and hasattr(_fastio, "fastpath_log_enable")):
                # The ring holds what one UDP callback serves between
                # two writes: at most _UDP_BURST + 63 = 191 datagrams
                # (a drain starts its last recvmmsg of 64 with 127
                # taken), whether the callback is one drain or a chain.
                # 191 lines of the hosts zone's 388 bytes are 74 KB; of
                # the longest a native serve can write (512 of prefix,
                # 256 of overhead, a fragment of FP_MAX_FRAG 4,096)
                # 929,024 bytes, under the 1,048,576 here.  A serve that
                # finds no room declines to Python (70 us in place of
                # 3), which logs: no line is lost either way.
                try:
                    _fastio.fastpath_log_enable(
                        self._fastpath, self._log_prefix, 1 << 20)
                    self._log_ring = True
                    self.engine.fastpath_logged = True
                except ValueError:
                    pass    # no ring: the fast path stands down

        # Zone precompilation (fpcore.h zone table): finished answer
        # bodies for the dominant record shapes (host A, PTR) are pushed
        # into the C drain from the STORE MIRROR — at startup and on
        # every mirrored mutation — so even the first query for a name
        # never surfaces to Python.  The reference resolves every cold
        # name per query (lib/server.js:136); this is the rebuild's
        # NSD/Knot-style answer to that.  `zonePrecompile: false`
        # disables it (the tests' reference servers use that to make
        # every answer a resolve).
        self._zone_enabled = (
            zone_precompile and self._fastpath is not None
            and hasattr(_fastio, "fastpath_zone_put"))
        # The zone table's type row: the engine's own rule (TYPE_RULE)
        # of which types it resolves and what rcode the rest get by the
        # type alone.  Such an answer is a header and the question, so
        # C gives it with no name in its key and no first sight in
        # Python per name.  In the logged posture the row carries the
        # one fragment a Python-lane first sight of a declined question
        # logs: no `cached` — the native answer IS the engine's
        # decision, not a replay of it.  `_type_row` is
        # the served types while the row is installed, else None.
        self._type_row: Optional[frozenset] = None
        if self._zone_enabled and hasattr(_fastio, "fastpath_type_row"):
            served, declined = TYPE_RULE
            frag = (self._log_frag({}, declined, [], [])
                    if self._log_ring else None)
            if _fastio.fastpath_type_row(self._fastpath, sorted(served),
                                         declined, frag):
                self._type_row = served
        # churn-path coalescing: batched C invalidation + deferred zone
        # refills (see _on_store_invalidate)
        self._fp_inval_many = getattr(_fastio, "fastpath_invalidate_many",
                                      None)
        self._zone_dirty: set = set()
        self._zone_drain_pending = False
        self._zone_fill_task = None
        self.zone_serve_counter = self.collector.counter(
            "binder_zone_serves",
            "queries answered from precompiled zone entries")
        self._zone_serve_child = self.zone_serve_counter.labelled({})
        self._zone_type_serve_child = self.collector.counter(
            "binder_zone_type_serves",
            "answers the zone table gave by the question's type alone"
        ).labelled({})
        # fp_zone_put's refusals of a well-formed entry: the names they
        # leave to the Python lanes are slower, never wrong, and this
        # is the one place that says so (runbook "Large zones")
        zone_put_skips = self.collector.counter(
            "binder_zone_put_skips",
            "zone entries the native table refused, by reason: size (a "
            "body or log fragment above what a message over TCP "
            "carries), bytes (the table's byte cap)")
        self._zone_put_skip_children = {
            reason: zone_put_skips.labelled({"reason": reason})
            for reason in ("size", "bytes")}
        for child in self._zone_put_skip_children.values():
            child.inc(0)                    # both series from the start
        if self._fastpath is not None:
            # Residency gauges: operators watching a mirror fill (or an
            # epoch rebuild) can see the native tables converge.  All
            # four read the single snapshot _fold_fastpath_metrics takes
            # per scrape (it runs as a pre-expose hook) — one stats
            # build per scrape, not one per gauge.
            def _fp_stat(key):
                return lambda: float(self._fp_last_stats.get(key, 0))
            self.collector.gauge(
                "binder_zone_entries",
                "precompiled answers resident in the native zone tables"
            ).set_function(_fp_stat("zone_entries"))
            self.collector.gauge(
                "binder_zone_bytes",
                "bytes held by precompiled zone answer bodies"
            ).set_function(_fp_stat("zone_bytes"))
            self.collector.gauge(
                "binder_fastpath_entries",
                "entries resident in the native answer cache"
            ).set_function(_fp_stat("entries"))
            self.collector.gauge(
                "binder_fastpath_bytes",
                "bytes held by native answer-cache wires"
            ).set_function(_fp_stat("bytes"))

        # actual bound ports (for tests / ephemeral binds)
        self.udp_port: Optional[int] = None
        self.tcp_port: Optional[int] = None

    def _engine_after_hook(self, query: QueryCtx) -> None:
        """After-hook entry for self-completing paths (the recursion
        fast path) — identical semantics to the engine's post-task
        _after call."""
        self.engine._after(query)

    def _epoch_source(self) -> int:
        """The epoch every cached lane validates against — evaluated
        THROUGH the degradation policy, so a lazy state transition
        (and its epoch-bumping cache flush) lands before the epoch is
        read.  Without this ordering, the first post-session-loss
        query could serve an unclamped cached wire from the native
        drain before any Python path noticed the transition."""
        if self._policy is not None:
            self._policy.mode()
        return self.zk_cache.epoch

    def _on_degradation_transition(self, old: str, new: str) -> None:
        """Degradation state edge: flush every cached answer lane.  The
        epoch bump invalidates the Python answer cache, the native C
        caches, and (via the generation frame) the
        balancer — so a wire rendered fresh is never served into
        exhaustion and clamped-TTL stale wires never survive recovery."""
        self.zk_cache.invalidate_all(
            reason=f"degradation {old} -> {new}")

    async def _policy_tick_loop(self) -> None:
        """1 s degradation-policy evaluator: transitions (and their
        metrics / flight-recorder events) must fire on an idle binder
        too, not only when a query happens to ask."""
        while True:
            await asyncio.sleep(1.0)
            try:
                self._policy.tick()
            except Exception:
                self.log.exception("degradation policy tick failed")

    # -- query hook (lib/server.js:471-507); sync, may return an awaitable
    # for the recursion path (see DnsServer._dispatch) --

    def _on_query(self, query: QueryCtx):
        if self.query_log:
            # log lines need decoded answer summaries: response paths
            # that would shortcut decoding (recursion splice) must not
            query.want_log_detail = True
        if self.p_req_start.enabled:   # skip closure alloc when off
            self.p_req_start.fire(lambda: {
                "trace": query.trace_id,
                "id": query.request.id, "name": query.name(),
                "type": query.qtype_name(), "client": query.src[0],
                "protocol": query.protocol,
            })
        # Answer-cache fast path.  The key is built from the decoded
        # fields the response actually depends on — transport semantics
        # (truncation), RD (drives the recursion-vs-REFUSED split on
        # misses), question, EDNS presence and payload ceiling — NOT the
        # raw wire: wire bytes vary with per-packet EDNS options (DNS
        # cookies, padding) and ignored padding sections, which would
        # mint one key per packet and evict the real entries.
        key = None
        req = query.request
        if len(req.questions) == 1 and req.opcode == 0:
            q0 = req.questions[0]
            key = (query.udp_semantics, req.rd, q0.qtype, q0.qclass,
                   q0.name, req.edns is not None, req.max_udp_payload())
            # policy-aware epoch: a pending degradation transition must
            # flush the caches BEFORE this probe can hit
            cached = self.answer_cache.get(key, self._epoch_source())
            if cached is not None:
                wire, ans, add = cached
                self._cache_hit_child.inc()
                query.response.rcode = wire[3] & 0x0F  # for metrics/logs
                query.log_ctx["cached"] = True
                query.cached_summary = (ans, add)
                query.stamp("cache-hit")   # context→probe; the respond
                # and the native promotion below land in `log-after`
                query.respond_raw(wire)
                # promote-on-first-hit: a repeat proves the name is hot,
                # so hand the entry to the C fast path NOW (resolve-time
                # pushes made one-shot cold names pay the native-push
                # cost for entries never served again).  Not a key whose
                # type the type row answers: C serves the row ahead of
                # its cache probe, so such an entry could never be hit
                if (query.udp_semantics and self._fastpath is not None
                        and (self._type_row is None
                             or q0.qtype in self._type_row)
                        and self._fastpath_active()):
                    self._fastpath_push(key, self.zk_cache.epoch, query)
                return None

        pending = self.resolver.handle(query)

        answered = (pending is None and query.responded
                    and query.wire is not None)
        # a wire that left truncated (only a UDP answer does) is its
        # header, whatever set the resolve rendered for the encode to
        # drop
        truncated = answered and bool(query.wire[2] & 0x02)
        if truncated:
            self._tc_render_child.inc()

        if (answered and key is not None and not query.no_store
                and query.rcode() != Rcode.SERVFAIL):
            ans = [self._summarize(r) for r in query.response.answers]
            add = [self._summarize(r) for r in query.response.additionals
                   if not isinstance(r, OPTRecord)]
            # reused by _on_after for this query's own log line too —
            # summaries are built exactly once per resolve
            query.cached_summary = (ans, add)
            epoch = self.zk_cache.epoch
            # dependency tag: the store name this answer derives from
            # (set by the resolver at its lookup points); immutable
            # shapes (out-of-suffix REFUSED, NOTIMP) never consulted the
            # store, but tagging them with their own qname is harmless —
            # no mutation will ever emit it.  The native push happens at
            # the entry's first HIT (promote-on-first-hit above), never
            # here on the cold path.
            tag = query.dep_domain or q0.name
            rcode = query.rcode()
            self.answer_cache.put(
                key, epoch, (query.wire, ans, add),
                # a truncated wire has one variant: no rotation of the
                # set shows in a header, so the entry is complete from
                # its first sight
                rotatable=(len(query.response.answers) > 1
                           and not truncated), tag=tag,
                # negative answers (NXDOMAIN / NODATA) cache like
                # positives but are accounted separately; SERVFAIL is
                # excluded above — the never-cache rule
                negative=(rcode == Rcode.NXDOMAIN
                          or (rcode == Rcode.NOERROR
                              and not query.response.answers)))
        return pending

    @staticmethod
    def _qname_wire(name: str) -> Optional[bytes]:
        """Lowercased wire label form of a dotted name — the dependency
        tag format shared with the C caches (fpcore.h fp_invalidate_tag).
        Delegates to the one real name encoder (wire.encode_name, which
        normalizes case and enforces label/name bounds); None for names
        that cannot appear as a C-side tag."""
        buf = bytearray()
        try:
            encode_name(name, buf, None)
        except (WireError, UnicodeEncodeError):
            return None
        return bytes(buf)

    def _on_store_invalidate(self, tags) -> None:
        """MirrorCache invalidation subscriber: drop the cached answers
        whose dependency tag a store mutation touched — in the Python
        answer cache, the native fast path (one batched table pass for
        the whole event, not one scan per tag), and (via opcode-1
        control frames) the balancer's cache.  The DROPS are synchronous
        (coherence: a stale answer must never survive its mutation);
        the zone RE-PUSHES are refill work and are deferred to a
        bounded dirty-set drain between serving batches, so a mutation
        burst can't stall the hot loop (VERDICT r4 weak 5).  Until a
        name's refresh runs, its queries resolve through the Python
        lanes (_on_query) — slower, never stale."""
        wires = []
        for tag in tags:
            self.answer_cache.invalidate_tag(tag)
            wire = self._qname_wire(tag)
            if wire is not None:
                wires.append(wire)
        if wires and self._fastpath is not None:
            try:
                if self._fp_inval_many is not None:
                    self._fp_inval_many(self._fastpath, wires)
                else:   # older extension: per-tag fallback
                    for wire in wires:
                        _fastio.fastpath_invalidate(self._fastpath, wire)
            except (TypeError, ValueError):
                pass
        if wires:
            self.engine.notify_invalidate(wires)
        if self._verify is not None:
            # incremental verification rides the same feed (after the
            # drops: the checker sees the post-mutation tables, never
            # the stale ones)
            self._verify.enqueue_tags(tags)
            ctx = self._verify.tracer.current
            if ctx is not None and self._zone_enabled:
                zt = self._zone_trace
                for tag in tags:
                    zt[tag] = ctx
                while len(zt) > self._ZONE_TRACE_CAP:
                    del zt[next(iter(zt))]
        if self._zone_enabled:
            self._zone_dirty.update(tags)
            self._schedule_zone_drain()

    #: zone re-pushes drained per event-loop pass; bounds the refill
    #: work a mutation burst can inject between serving batches
    _ZONE_DRAIN_BATCH = 64

    #: pending native-install trace contexts retained (oldest dropped
    #: first — an evicted trace loses one stage sample, nothing else)
    _ZONE_TRACE_CAP = 4096

    def _schedule_zone_drain(self) -> None:
        if self._zone_drain_pending or not self._zone_dirty:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            # no loop (synchronous setup paths): refresh inline
            dirty, self._zone_dirty = self._zone_dirty, set()
            for tag in dirty:
                self._zone_refresh(tag)
            return
        self._zone_drain_pending = True
        loop.call_soon(self._drain_zone_dirty)

    def _drain_zone_dirty(self) -> None:
        self._zone_drain_pending = False
        n = 0
        while self._zone_dirty and n < self._ZONE_DRAIN_BATCH:
            self._zone_refresh(self._zone_dirty.pop())
            n += 1
        if self._zone_dirty:
            # more pending: yield to I/O first (call_soon callbacks
            # added during a loop pass run on the NEXT pass)
            self._schedule_zone_drain()

    # -- zone precompilation (fpcore.h zone table) --

    def _zone_refresh(self, name: str) -> None:
        """(Re-)push the precompiled answer for one store name, if the
        mirror currently resolves it to a shape the zone table serves.
        Stale entries were already dropped by tag invalidation; absent
        or ineligible names simply stay un-pushed and resolve through
        the Python lanes (_on_query)."""
        ctx = self._zone_trace.pop(name, None)
        try:
            if name.endswith(".in-addr.arpa") or name.endswith(".ip6.arpa"):
                if name.endswith(".ip6.arpa"):
                    # v6 reverse: canonical nibble parse; the PTR body
                    # is address-family-agnostic once the owner is found
                    ip = ip_from_reverse_name(name)
                    if ip is None:
                        return
                else:
                    parts = name.split(".")
                    if len(parts) < 3:
                        return
                    ip = ".".join(reversed(parts[:-2]))
                owner = self.zk_cache.reverse_lookup(ip)
                if owner is not None:
                    self._zone_push_ptr(name, owner)
            else:
                node = self.zk_cache.lookup(name)
                if node is None:
                    pass
                elif (type(node.rec) is dict
                        and node.rec.get("type") == "service"):
                    self._zone_push_service_a(name, node)
                    self._zone_push_service_srv(name, node)
                else:
                    self._zone_push_a(name, node)
        except Exception:
            # zone fill is an optimization: a push failure must never
            # break the mutation path that feeds it
            self.log.exception("zone push failed for %s", name)
        if ctx is not None and self._verify is not None:
            # the zone lane finished with this name — for a mutation's
            # trace that is "the glass shows it" (even a now-ineligible
            # name: its stale native entry is gone, which is the state
            # the zone table should serve)
            self._verify.tracer.observe("native-install", ctx)

    # -- chaos injection hook (chaos/plan.py drop-reverse; the driver
    # dispatches on the method name) --

    def drop_reverse(self, ip: Optional[str] = None):
        """Delete one reverse-map entry without touching the forward
        node — the forward/reverse coherence break the ptr-coherence
        audit must catch (no invalidation fires here).
        Returns the dropped address or None."""
        rl = self.zk_cache.rev_lookup
        if ip is None:
            ip = next(iter(rl), None)
        if ip is None or ip not in rl:
            return None
        node = rl.pop(ip)
        self.log.warning("chaos: dropped reverse entry %s -> %s",
                         ip, getattr(node, "domain", "?"))
        return ip

    def _zone_host_shape(self, node):
        """(record, sub, packed_addr, ttl) when ``Resolver.resolve``
        answers `node` with exactly one A record, else None.  The rules:
        a type in _LANE_HOST_TYPES, a dict sub-record, a canonical
        dotted-quad address, int TTLs; anything else stays un-pushed,
        so the zone table never answers what the resolver would answer
        otherwise (invalid records, store garbage it SERVFAILs on)."""
        rec = node.rec
        if type(rec) is tuple:
            # compact host-like: the only decline left is the address
            # canonicality check (TTLs are ints by invariant)
            if rec[0] not in _LANE_HOST_TYPES:
                return None
            packed = BinderServer._zone_packed_addr(rec[1])
            if packed is None:
                return None
            return rec, None, packed, _rec_ttl(rec)
        rt = rec.get("type") if type(rec) is dict else None
        if rt not in _LANE_HOST_TYPES:
            return None
        sub = rec.get(rt)
        if type(sub) is not dict:
            return None
        return BinderServer._zone_a_tail(rec, sub, sub.get("address"))

    @staticmethod
    def _zone_packed_addr(addr):
        """Canonical-dotted-quad check shared by every zone push —
        returns the packed address, or None to decline to Python.  ONE
        copy, so the rule cannot drift between the host, database, and
        service member paths."""
        if type(addr) is not str:
            return None
        try:
            packed = _socket.inet_aton(addr)
        except (OSError, TypeError):
            return None
        if _socket.inet_ntoa(packed) != addr:
            return None
        return packed

    @staticmethod
    def _zone_a_tail(record, sub, addr):
        """Validation tail for the single-A shapes (host-likes,
        database): canonical address + int TTL, or decline.  Returns
        the full (record, sub, packed, ttl) shape so callers are a
        single return."""
        packed = BinderServer._zone_packed_addr(addr)
        if packed is None:
            return None
        ttl = _lane_ttl(record, sub)
        if ttl is None:
            return None
        return record, sub, packed, ttl

    @staticmethod
    def _zone_database_shape(record):
        """The database branch of engine.resolve — one A record whose
        address is the hostname of the ``primary`` URL
        (lib/server.js:295-305) — when it would encode cleanly, else
        None (non-IP hostnames and malformed URLs stay in Python)."""
        sub = record.get("database")
        if type(sub) is not dict:
            return None
        primary = sub.get("primary", "")
        if type(primary) is not str:
            return None                 # urlparse(non-str) raises
        try:
            addr = _urlparse(primary).hostname
        except ValueError:
            return None
        return BinderServer._zone_a_tail(record, sub, addr)

    def _zone_push_a(self, name: str, node) -> None:
        """Precompile the A answer for a host-like or database record
        (``Resolver.resolve``'s host-like and database branches, done
        once at mutation time instead of per query)."""
        if not self._zone_suffix_ok(name):
            return
        rec = node.rec
        if type(rec) is dict and rec.get("type") == "database":
            shape = self._zone_database_shape(rec)
        else:
            shape = self._zone_host_shape(node)
        if shape is None:
            return
        _record, _sub, packed, ttl = shape
        qn = self._qname_wire(name)
        if qn is None:
            return
        body = (b"\xc0\x0c\x00\x01\x00\x01"
                + struct.pack(">IH", ttl & 0xFFFFFFFF, 4) + packed)
        frags = None
        if self._log_ring:
            # zone serves replace what Python would resolve fresh —
            # the fragment mirrors the resolve-path log line
            addr = _socket.inet_ntoa(packed)
            frags = [self._log_frag(
                {"query": {"srv": None, "name": name, "type": "A"}},
                Rcode.NOERROR,
                [self._summarize(ARecord(name=name, ttl=ttl,
                                         address=addr))], [])]
            if frags[0] is None:
                return
        try:
            self._zone_put(b"\x00\x01\x00\x01" + qn, 1, [body], qn,
                           0, frags)
        except (TypeError, ValueError, MemoryError) as e:
            self.log.debug("zone A push skipped for %s: %s", name, e)

    def _zone_suffix_ok(self, name: str) -> bool:
        """``Resolver.resolve``'s dnsDomain suffix policy (a name
        outside the suffix, or with it doubled up, is REFUSED, never
        answered) — shared by every forward zone push."""
        dd_suffix = self._lane_suffix
        if dd_suffix is None or not name.endswith(dd_suffix):
            return False
        stripped = name[:-len(dd_suffix)]
        dd = self.resolver.dns_domain
        return not (stripped == dd or stripped.endswith(dd_suffix)
                    or stripped == self._lane_dcsuff
                    or stripped.endswith("." + self._lane_dcsuff))

    @staticmethod
    def _zone_service_ttl(record):
        """``(s, ttl)`` from a service record — the sub-record after the
        nested-historical-format unwrap plus the engine's TTL precedence
        (engine.resolve + _resolve_service head) — or None when the
        shape would not resolve as a service."""
        if not (type(record) is dict
                and type(record.get("service")) is dict):
            return None                 # engine SERVFAILs: decline
        s = record["service"]
        ttl = _engine_record_ttl(record, s)
        if type(s.get("service")) is dict:
            s = s["service"]            # nested historical format
        if s.get("ttl") is not None:
            ttl = s["ttl"]
        if type(ttl) is not int:
            return None
        return s, ttl

    def _zone_service_members(self, node, ttl):
        """Validated member list ``[(knode, ksub, packed_addr, rttl)]``
        for a service node — the one place the member eligibility rules
        live, consumed by both the plain-A and the SRV push so the two
        zone paths cannot drift.  None when the generic path would
        SERVFAIL mid-set or a value would fail to encode (decline to
        Python); addressless or foreign-typed kids are skipped exactly
        like engine._resolve_service does."""
        members = []
        for knode in node.children:
            kr = knode.rec
            if type(kr) is tuple:
                # compact member (store/names.py): address present and
                # TTLs int by invariant; no ports key — the SRV push
                # falls back to the service-level default port
                if kr[0] not in _SERVICE_CHILD_TYPES:
                    continue
                packed = self._zone_packed_addr(kr[1])
                if packed is None:
                    return None         # encode would fail: decline
                parts = _names_rec_parts(kr)
                rttl = parts[3] if parts[3] is not None else (
                    parts[2] if parts[2] is not None else ttl)
                members.append((knode, None, packed, rttl))
                continue
            if not (type(kr) is dict
                    and kr.get("type") in _SERVICE_CHILD_TYPES):
                continue                # engine filters these out too
            ksub = kr.get(kr["type"])
            if type(ksub) is not dict:
                return None             # engine SERVFAILs mid-set
            addr = ksub.get("address")
            if addr is None:
                continue                # engine skips addressless kids
            packed = self._zone_packed_addr(addr)
            if packed is None:
                return None             # encode would fail: decline
            rttl = _engine_record_ttl(kr, ksub, ttl)
            if type(rttl) is not int:
                return None
            members.append((knode, ksub, packed, rttl))
        return members

    def _zone_push_service_a(self, name: str, node) -> None:
        """Precompile the plain-A rotation for a service record
        (engine._resolve_service's A branch, done once at mutation time):
        one variant per cyclic rotation of the member set, so serves
        round-robin like the shuffled generic path.  Declines (leaving
        the Python path authoritative) on anything _resolve_service
        would not answer as a plain multi-A set: invalid child records
        (SERVFAIL), empty member sets (NODATA), non-int TTLs,
        non-canonical addresses."""
        if not self._zone_suffix_ok(name):
            return
        head = self._zone_service_ttl(node.data)
        if head is None:
            return
        _s, ttl = head
        members = self._zone_service_members(node, ttl)
        if not members:
            return                      # NODATA shape: Python answers
        if len(members) > MAX_SET_RECORDS:
            return      # oversize rotation set: the engine's lazy render
        answers = [
            (b"\xc0\x0c\x00\x01\x00\x01"
             + struct.pack(">IH", min(ttl, rttl) & 0xFFFFFFFF, 4)
             + packed)
            for _knode, _ksub, packed, rttl in members]
        qn = self._qname_wire(name)
        if qn is None:
            return
        nv = min(len(answers), _FP_MAX_VARIANTS)
        bodies = [b"".join(answers[i:] + answers[:i]) for i in range(nv)]
        frags = None
        if self._log_ring:
            # per-variant summaries rotate in lockstep with the bodies
            sums = [self._summarize(ARecord(
                        name=name, ttl=min(ttl, rttl),
                        address=_socket.inet_ntoa(packed)))
                    for _knode, _ksub, packed, rttl in members]
            ctx = {"query": {"srv": None, "name": name, "type": "A"}}
            frags = [self._log_frag(ctx, Rcode.NOERROR,
                                    sums[i:] + sums[:i], [])
                     for i in range(nv)]
            if any(f is None for f in frags):
                return
        try:
            self._zone_put(b"\x00\x01\x00\x01" + qn, len(answers),
                           bodies, qn, 0, frags)
        except (TypeError, ValueError, MemoryError) as e:
            self.log.debug("zone service push skipped for %s: %s",
                           name, e)

    def _zone_push_service_srv(self, name: str, node) -> None:
        """Precompile the SRV answer set for a service record under its
        registered ``srvce.proto.name`` qname (engine._resolve_service's
        SRV branch): per member per port an SRV answer at the
        service-level TTL, plus one A additional per member at the
        member TTL, rotating together.  The dependency tag is the
        service NODE name — not the SRV qname — so these entries live in
        the C side's alien table and are invalidated by its bounded
        scan.  Negative SRV shapes (wrong srvce/proto → NXDOMAIN, SRV on
        a non-service → NODATA+SOA, malformed qnames → REFUSED) are
        never pushed and keep resolving through Python.

        A set is held whole up to what a message over TCP carries; C
        holds a datagram to its key's payload at serve time, and counts
        the set that passes the stream's bound or the table's byte cap
        (``binder_zone_put_skips``).  Each member's pieces, wire and
        log summary alike, are rendered once: a rotation variant is a
        join of them."""
        if not self._zone_suffix_ok(name):
            return
        head = self._zone_service_ttl(node.data)
        if head is None:
            return
        s, ttl = head
        srvce, proto = s.get("srvce"), s.get("proto")
        # Only qnames the engine's SRV_RE would parse back to exactly
        # this service can ever match this entry — and only LOWERCASE
        # registrations: decoded query labels arrive lowercased
        # (wire.py:185) and the engine compares them against the stored
        # strings exactly, so an uppercase-registered srvce/proto is
        # unmatchable (NXDOMAIN for every query) and must never be
        # precompiled under its lowercased qname.
        if not (type(srvce) is str and _SRV_LABEL_RE.match(srvce)
                and srvce == srvce.lower()
                and type(proto) is str and _SRV_LABEL_RE.match(proto)
                and proto == proto.lower()):
            return
        default_port = s.get("port")
        raw_members = self._zone_service_members(node, ttl)
        if not raw_members:
            return                      # empty set: NOERROR via Python
        dumps = _json.dumps
        members = []
        for knode, ksub, packed, rttl in raw_members:
            # compact members (ksub None) carry no ports key by
            # invariant: the service-level default port applies
            ports = ksub.get("ports") if type(ksub) is dict else None
            if not ports:
                ports = [default_port]
            if type(ports) is not list:
                return
            target = f"{knode.name}.{name}"
            tw = self._qname_wire(target)
            if tw is None:
                return
            ans = b""
            srv_sums = []
            for p in ports:
                if type(p) is not int or not 0 <= p <= 0xFFFF:
                    return              # encode would fail: decline
                # SRV rdata: priority 0, weight 10 (engine constants),
                # port, uncompressed target (RFC 2782 forbids pointers
                # in SRV rdata)
                ans += (b"\xc0\x0c\x00\x21\x00\x01"
                        + struct.pack(">IH", ttl & 0xFFFFFFFF,
                                      6 + len(tw))
                        + struct.pack(">HHH", 0, 10, p) + tw)
                if self._log_ring:
                    srv_sums.append(dumps(self._summarize(SRVRecord(
                        name=name, ttl=ttl, priority=0, weight=10,
                        port=p, target=target))))
            # summaries rendered only in the logged posture — churn-path
            # zone refreshes in the log-off posture must not pay for them
            add_sum = (dumps(self._summarize(ARecord(
                name=target, ttl=rttl,
                address=_socket.inet_ntoa(packed))))
                if self._log_ring else None)
            add = (tw + b"\x00\x01\x00\x01"
                   + struct.pack(">IH", rttl & 0xFFFFFFFF, 4) + packed)
            members.append((ans, add, len(ports), srv_sums, add_sum))
        qn = self._qname_wire(f"{srvce}.{proto}.{name}")
        tag = self._qname_wire(name)
        if qn is None or tag is None:
            return
        ancount = sum(m[2] for m in members)
        arcount = len(members)
        if ancount > 0xFFFF:
            return
        rotations = [members[i:] + members[:i]
                     for i in range(min(len(members), _FP_MAX_VARIANTS))]
        bodies = [b"".join(m[0] for m in rot) + b"".join(m[1] for m in rot)
                  for rot in rotations]
        frags = None
        if self._log_ring:
            # _log_frag's bytes for each variant, from the members'
            # summaries as rendered above (C holds a fragment to the
            # stream's bound, as it does the body)
            head = dumps({"query": {"srv": f"{srvce}.{proto}",
                                    "name": name, "type": "SRV"},
                          "rcode": Rcode.name(Rcode.NOERROR)})[1:-1]
            frags = []
            for rot in rotations:
                ans = ", ".join(s for m in rot for s in m[3])
                add = ", ".join(m[4] for m in rot)
                frags.append(f'{head}, "answers": [{ans}], '
                             f'"additional": [{add}]'.encode())
        try:
            self._zone_put(b"\x00\x21\x00\x01" + qn, ancount, bodies,
                           tag, arcount, frags)
        except (TypeError, ValueError, MemoryError) as e:
            self.log.debug("zone SRV push skipped for %s: %s", name, e)

    def _zone_push_ptr(self, rev_name: str, owner) -> None:
        """Precompile the PTR answer for a reverse name
        (``Resolver.resolve_ptr``'s answer; NO dnsDomain suffix policy
        on the reverse tree, lib/server.js:67-134)."""
        shape = self._zone_host_shape(owner)
        if shape is None:
            return
        _record, _sub, _packed, ttl = shape
        target = owner.domain
        if target.endswith(".arpa"):
            # the resolver's encoder could compress such a target
            # against the reverse qname; the push's fixed body cannot
            return
        tw = self._qname_wire(target)
        if tw is None:
            return
        qn = self._qname_wire(rev_name)
        if qn is None:
            return
        body = (b"\xc0\x0c\x00\x0c\x00\x01"
                + struct.pack(">IH", ttl & 0xFFFFFFFF, len(tw)) + tw)
        frags = None
        if self._log_ring:
            ip = ".".join(reversed(rev_name.split(".")[:-2]))
            frags = [self._log_frag(
                {"query": {"ip": ip, "type": "PTR"}}, Rcode.NOERROR,
                [self._summarize(PTRRecord(name=rev_name, ttl=ttl,
                                           target=target))], [])]
            if frags[0] is None:
                return
        try:
            self._zone_put(b"\x00\x0c\x00\x01" + qn, 1, [body], qn,
                           0, frags)
        except (TypeError, ValueError, MemoryError) as e:
            self.log.debug("zone PTR push skipped for %s: %s", rev_name, e)

    def _zone_put(self, zkey: bytes, ancount: int, bodies, tag: bytes,
                  arcount: int, frags) -> None:
        """The one zone_put call site: appends the per-variant log
        fragments only when present, so an older compiled extension
        (pre-log-ring arity) keeps accepting log-off pushes."""
        if frags is not None:
            _fastio.fastpath_zone_put(self._fastpath, zkey,
                                      self.zk_cache.epoch, ancount,
                                      bodies, tag, arcount, frags)
        else:
            _fastio.fastpath_zone_put(self._fastpath, zkey,
                                      self.zk_cache.epoch, ancount,
                                      bodies, tag, arcount)

    #: per-pass wall budget for the chunked zone fill
    _FILL_BUDGET_S = 0.002
    #: a mirror of this many names or fewer fills inline at start (filled
    #: before query one, what every small-zone test relies on); a larger
    #: one fills from a chunked background task, so a million-name zone
    #: serves at once and fills in behind the traffic
    _FILL_INLINE_MAX = 20000

    def _zone_fill(self) -> None:
        """Walk the mirror and push every eligible precompiled answer —
        run at server start for mirrors built before this server
        subscribed to invalidation events (later arrivals ride
        _on_store_invalidate).  Small zones fill inline (the historical
        semantics); at zone scale the walk moves to a time-budgeted
        background task so serving starts immediately and the fill
        streams in behind it (un-filled names resolve through the
        Python lanes, _on_query — slower, never wrong)."""
        if not self._zone_enabled:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        nodes = self.zk_cache.nodes
        reserve = getattr(_fastio, "fastpath_zone_reserve", None)
        if reserve is not None and len(nodes) > 1024:
            # presize the native zone table for the fill (one A + one
            # PTR entry per host): growth rehashes are O(table) and the
            # largest one at zone scale measured ~370 ms — an
            # event-loop stall mid-serving, not a hiccup
            try:
                reserve(self._fastpath, 2 * len(nodes))
            except (TypeError, ValueError, MemoryError) as e:
                self.log.debug("zone-table reserve skipped: %s", e)
        if loop is not None and len(nodes) > self._FILL_INLINE_MAX:
            self._zone_fill_task = loop.create_task(
                self._zone_fill_chunked())
            return
        for domain in list(nodes):
            self._zone_fill_one(domain)

    def _zone_fill_one(self, domain: str) -> None:
        node = self.zk_cache.nodes.get(domain)
        if node is None:
            return                      # left the mirror mid-walk
        self._zone_refresh(domain)
        ip = node.ip
        if ip and type(ip) is str:
            if ":" in ip:
                # v6 (already canonical via TreeNode.ip): precompile
                # the ip6.arpa PTR alongside the forward name
                try:
                    rev = reverse_name_for_ip(ip)
                except ValueError:
                    return
                self._zone_refresh(rev)
                return
            parts = ip.split(".")
            if len(parts) == 4 and all(p.isdigit() for p in parts):
                self._zone_refresh(
                    ".".join(reversed(parts)) + ".in-addr.arpa")

    async def _zone_fill_chunked(self) -> None:
        domains = list(self.zk_cache.nodes)
        self.log.info("zone fill: %d names, chunked", len(domains))
        started = time.perf_counter()
        i = 0
        while i < len(domains):
            t0 = time.perf_counter()
            while i < len(domains) \
                    and time.perf_counter() - t0 < self._FILL_BUDGET_S:
                self._zone_fill_one(domains[i])
                i += 1
            self._fill_done = i
            await asyncio.sleep(0)
        self.log.info("zone fill done: %d names in %.1fs", len(domains),
                      time.perf_counter() - started)

    def _fastpath_push(self, key, epoch: int, query: QueryCtx) -> None:
        """Promote an answer-cache entry to the native fast path (on
        its first hit — see _on_query).  The C key is built from the
        request's raw qname bytes so both key builders see identical
        input; names outside the hostname charset (which Python decodes
        with replacement) are skipped — they keep being served by the
        Python path."""
        claimed = self.answer_cache.take_push(key, epoch)
        if claimed is None:
            return
        variants, tag = claimed
        ckey = self._fastpath_key(query)
        if ckey is None:
            return
        tag_wire = self._qname_wire(tag)
        if tag_wire is None:
            return                      # not invalidatable: keep in Python
        wires = [v[0] for v in variants]
        frags = None
        if self._log_ring:
            # native serves of this entry are cache hits; the Python
            # hit path logs exactly {cached: true} + rcode + summaries
            # (_on_query cache-hit branch + _on_after), so the fragment
            # mirrors that shape per variant
            frags = [self._log_frag({"cached": True}, w[3] & 0x0F, a, d)
                     for (w, a, d) in variants]
            if any(f is None for f in frags):
                return                  # unloggable: stays in Python
        ttl_ms = self.answer_cache.remaining_ttl_ms(key, epoch)
        ttl_arg = -1 if ttl_ms is None else int(ttl_ms)
        try:
            # frags appended only when present so an older compiled
            # extension keeps accepting log-off pushes
            if frags is not None:
                _fastio.fastpath_put(self._fastpath, ckey, query.qtype(),
                                     epoch, wires, ttl_arg, tag_wire,
                                     frags)
            else:
                _fastio.fastpath_put(self._fastpath, ckey, query.qtype(),
                                     epoch, wires, ttl_arg, tag_wire)
        except (TypeError, ValueError, MemoryError) as e:
            self.log.debug("fastpath push skipped: %s", e)

    @staticmethod
    def _fastpath_key(query: QueryCtx) -> Optional[bytes]:
        # layout must match fp_build_key in native/fastio/fastpath.c:
        # [flags rd|edns<<1][payload BE16][qtype BE16][qclass BE16][qname]
        raw = query.raw
        req = query.request
        if raw is None or len(raw) < 17:
            return None
        off = 12
        try:
            while True:
                label_len = raw[off]
                if label_len == 0:
                    off += 1
                    break
                if label_len & 0xC0:
                    return None   # compressed question name: C punts too
                label = raw[off + 1:off + 1 + label_len]
                if (len(label) != label_len
                        or not _FP_NAME_OK.issuperset(label)):
                    return None
                off += 1 + label_len
                if off - 12 > 255:
                    return None
        except IndexError:
            return None
        q0 = req.questions[0]
        return _fastpath_key_parts(req.rd, req.edns is not None,
                                   req.max_udp_payload(), q0.qtype,
                                   q0.qclass, raw[12:off].lower())

    def type_row_serves(self) -> int:
        """Answers the zone table's type row has given since start (0
        without the row): ``/status`` ``answer_cache.type_row_serves``,
        read from C as ``binder_zone_type_serves`` is at a scrape."""
        if self._type_row is None:
            return 0
        return int(_fastio.fastpath_stats(
            self._fastpath)["zone_type_hits"])

    def zone_put_skips(self) -> dict:
        """Zone entries the native table has refused since start, by
        reason: ``/status`` ``answer_cache.zone_put_skips``, read from
        C as ``binder_zone_put_skips`` is at a scrape."""
        stats = (_fastio.fastpath_stats(self._fastpath)
                 if self._zone_enabled else {})
        return {reason: int(stats.get(f"zone_put_skips_{reason}", 0))
                for reason in self._zone_put_skip_children}

    def _fold_engine_counters(self) -> None:
        # scrapes run on ThreadingHTTPServer threads: fold under the
        # shared lock or two concurrent scrapes double-count the delta
        with self._fp_fold_lock:
            delta = self.engine.tcp_cap_refusals - self._cap_folded
            if delta > 0:
                self._cap_refusal_child.inc(delta)
                self._cap_folded += delta
            delta = self.engine.udp_chained_drains - self._chained_folded
            if delta > 0:
                self._chained_child.inc(delta)
                self._chained_folded += delta
            # a C count that stepped back (a test's io_stats(True))
            # restarts its baseline, like the ledger's
            drops = self.udp_send_drops()
            for lane, child in self._send_drop_children.items():
                delta = drops[lane] - self._send_drops_folded[lane]
                if delta > 0:
                    child.inc(delta)
            self._send_drops_folded = drops
            snap = self.engine.tcp_stats.snapshot()
            folded = self._tcp_stats_folded
            for field, child in self._tcp_stat_children.items():
                d = snap[field] - folded.get(field, 0)
                if d > 0:
                    child.inc(d)
                    folded[field] = snap[field]
            if self._rrl is not None:
                rfolded = self._rrl_folded
                for field, child in self._rrl_children.items():
                    val = getattr(self._rrl, field)
                    d = val - rfolded.get(field, 0)
                    if d > 0:
                        child.inc(d)
                        rfolded[field] = val

    def _fold_unfilled(self) -> None:
        if self.filled:
            return
        with self._fp_fold_lock:
            delta = self.request_counter.total() - self._unfilled_folded
            if delta > 0:
                self._unfilled_child.inc(delta)
                self._unfilled_folded += delta

    def udp_send_drops(self) -> dict:
        """UDP answers dropped at a send buffer still full at the retry,
        by lane: the C lanes' from ``io_stats`` (process-wide, like the
        rest of it), the Python lanes' from the engine."""
        drops = {"native": 0, "python": self.engine.udp_send_drops,
                 "balancer": 0}
        if self._io_folds:
            drops.update(_fastio.io_stats().get("send_drops", {}))
        return drops

    def _fold_fastpath_metrics(self) -> None:
        """Fold the C fast path's monotonic counters into the Prometheus
        collectors (registered as a pre-scrape hook).  Deltas are taken
        against the last fold under a lock — concurrent scrapes must not
        double-count."""
        with self._fp_fold_lock:
            # Snapshot inside the lock: with it outside, two concurrent
            # scrapes could fold in order new-then-old, regressing the
            # delta baseline and double-counting on the next fold.
            stats = _fastio.fastpath_stats(self._fastpath)
            self._fp_last_stats = stats   # shared with residency gauges
            last = self._fp_folded
            # an extension built before a counter has none of it
            for key, child in (
                    ("hits", self._cache_hit_native_child),
                    ("zone_hits", self._zone_serve_child),
                    ("zone_type_hits", self._zone_type_serve_child),
                    *((f"zone_put_skips_{reason}", child) for reason, child
                      in self._zone_put_skip_children.items())):
                now = stats.get(key, 0)
                if now > last.get(key, 0):
                    child.inc(now - last.get(key, 0))
                last[key] = now
            self._fp_inval_total = stats.get("invalidations", 0)
            for qtype, s in stats["per_qtype"].items():
                children = self._children_for(qtype)
                prev = last.get(qtype)
                count_delta = s["count"] - (prev["count"] if prev else 0)
                if count_delta > 0:
                    children[0].inc(count_delta)
                    children[1].merge(
                        [c - (prev["lat_cells"][i] if prev else 0)
                         for i, c in enumerate(s["lat_cells"])],
                        s["lat_sum"] - (prev["lat_sum"] if prev else 0.0))
                    children[2].merge(
                        [c - (prev["size_cells"][i] if prev else 0)
                         for i, c in enumerate(s["size_cells"])],
                        s["size_sum"] - (prev["size_sum"] if prev else 0.0))
                    # an answer-cache entry promoted from a truncated
                    # answer leaves C truncated (an extension built
                    # before the count has none)
                    cut = s.get("truncated", 0) - (
                        prev.get("truncated", 0) if prev else 0)
                    if cut > 0:
                        children[3].inc(cut)
                last[qtype] = s

    def _fold_ledger(self) -> None:
        """Fold the time ledger's C spans and the socket counters
        (``_fastio.io_stats``, process-wide) into the collectors, by
        deltas against the last fold.  A counter that stepped back (a
        test's ``io_stats(True)``) restarts the baseline and is skipped,
        never folded as negative."""
        if not self._io_folds:
            return
        with self._io_fold_lock:
            io = _fastio.io_stats()
            for stage, fold in self._io_folds.items():
                span = io["spans"][stage]
                fold.fold(span["cells"], span["sum"])
            last = self._io_folded
            self._io_folded = io
            cells = [c - p for c, p in zip(
                io["recv_cells"],
                last.get("recv_cells") or [0] * len(io["recv_cells"]))]
            msgs_in = io["recv_msgs"] - last.get("recv_msgs", 0)
            msgs_out = io["send_msgs"] - last.get("send_msgs", 0)
            if min(cells) < 0 or msgs_in < 0 or msgs_out < 0:
                return
            self._udp_in_child.inc(msgs_in)
            self._udp_out_child.inc(msgs_out)
            self._udp_batch_child.merge(cells, msgs_in)

    def io_introspect(self) -> dict:
        """The ``io`` section of ``/status``: what the socket calls and
        the query log moved since the process started, beside the
        ledger's spans on ``/metrics`` (docs/observability.md)."""
        out = {"log_writes": self.stage_histogram.count(
                   {"stage": "log-write"}),
               "log_lines": self.stage_histogram.count(
                   {"stage": "log-line"}),
               "log_lines_direct": int(self._log_lines.value(
                   {"path": "direct"})),
               "log_bytes": int(self._log_bytes.value()),
               "recv_calls": 0, "recv_empty": 0, "recv_datagrams": 0,
               "recv_chained": self.engine.udp_chained_drains,
               "recv_batch_cells": [0] * (len(UDP_BATCH_BUCKETS) + 1),
               "send_calls": 0, "send_datagrams": 0,
               "send_drops": self.udp_send_drops()}
        if self._io_folds:
            io = _fastio.io_stats()
            out.update(
                recv_calls=io["spans"]["udp-recv"]["count"],
                recv_empty=(io["spans"]["udp-recv"]["count"]
                            - io["recv_calls"]),
                recv_datagrams=io["recv_msgs"],
                recv_batch_cells=io["recv_cells"],
                send_calls=io["spans"]["udp-send"]["count"],
                send_datagrams=io["send_msgs"])
        return out

    def _children_for(self, qtype: int):
        """Pre-resolved (counter, latency, size) metric handles for a
        qtype — label-sort once, not per query; shared by the after-hook
        and the fast-path fold."""
        children = self._metric_children.get(qtype)
        if children is None:
            # 0xFFFF is the C stats catch-all past its per-qtype slots
            labels = {"type": "other" if qtype == 0xFFFF
                      else Type.name(qtype)}
            children = (self.request_counter.labelled(labels),
                        self.latency_histogram.labelled(labels),
                        self.size_histogram.labelled(labels),
                        self.truncated_counter.labelled(labels))
            self._metric_children[qtype] = children
        return children

    def _fastpath_active(self) -> bool:
        """The C path bypasses Python entirely, so it must stand down
        whenever every query has to surface: a probe consumer attached,
        per-query logging on WITHOUT the native log ring (with the
        ring armed, the C path produces the log lines itself), or
        response rate limiting actively shedding a flood (the limiter
        judges per-prefix in Python; serving cache hits in C would
        answer the flood before RRL could see it)."""
        return (not self.p_req_start.enabled
                and not self.p_req_done.enabled
                and (not self.query_log or self._log_ring)
                and (self._rrl is None or not self._rrl.hot()))

    # -- query-log plumbing: the ring, the pending lines, one writer --

    def _find_json_handlers(self) -> list:
        """StreamHandlers with a JsonFormatter reachable from this
        server's logger (walking propagation like logging does) — the
        sinks the pre-rendered lines are written to."""
        handlers = []
        lg: Optional[logging.Logger] = self.log
        while lg is not None:
            for h in lg.handlers:
                if (isinstance(h, logging.StreamHandler)
                        and isinstance(h.formatter, JsonFormatter)
                        and h.level <= logging.INFO):
                    handlers.append(h)
            if not lg.propagate:
                break
            lg = lg.parent
        return handlers

    def _native_log_prefix(self) -> bytes:
        """Constant head of every pre-rendered log line, up to and
        including ``"time": "`` — rendered once from the logger's
        identity, so these lines carry the same envelope as
        JsonFormatter's."""
        fmt = self._log_json_handlers[0].formatter
        head = {"name": fmt.name, "hostname": fmt.hostname,
                "pid": _os.getpid(), "level": 30,
                "component": self.log.name, "msg": "DNS query"}
        return (_json.dumps(head)[:-1] + ', "time": "').encode()

    @staticmethod
    def _log_frag(ctx: dict, rcode: int, ans, add) -> Optional[bytes]:
        """Pre-rendered middle of a log line (the answer-dependent
        fields) for one entry variant; None when it cannot be rendered
        or would exceed the native bound (the entry then declines to
        Python under logging, which is always correct)."""
        d = dict(ctx)
        d["rcode"] = Rcode.name(rcode)
        d["answers"] = ans
        d["additional"] = add
        try:
            frag = _json.dumps(d, default=str)[1:-1].encode()
        except (TypeError, ValueError):
            return None
        return frag if 0 < len(frag) <= 4096 else None

    @staticmethod
    def _byte_sink(h: logging.StreamHandler):
        """The binary layer under a handler's stream, where bytes
        written to it are what the text layer would have produced
        (UTF-8-family encoding, no newline translation); else None:
        pre-rendered and formatter lines would mix encodings or line
        endings in one file."""
        stream = h.stream
        enc = (getattr(stream, "encoding", "") or "") \
            .lower().replace("-", "")
        if (enc in ("utf8", "ascii", "usascii")
                and getattr(stream, "newlines", None) in (None, "\n")):
            return getattr(stream, "buffer", None)
        return None

    def _log_direct(self) -> bool:
        """Whether a Python-lane INFO line may be rendered straight to
        bytes: read from the logger, exactly where the ring's byte
        path is sound."""
        handlers = self._log_json_handlers
        if not handlers or not self.log.isEnabledFor(logging.INFO):
            return False
        for h in handlers:
            if self._byte_sink(h) is None:
                return False
        return True

    def _render_log_line(self, fields: dict) -> bytes:
        """One complete ``DNS query`` line: the prefix, the time at
        microsecond resolution (the seconds part rendered once a
        second, as C does), ``"v": 0`` and the line's own fields, as
        JsonFormatter would have dumped them."""
        sec, ns = divmod(time.time_ns(), 1_000_000_000)
        if sec != self._log_sec:
            self._log_sec = sec
            self._log_sec_head = time.strftime(
                "%Y-%m-%dT%H:%M:%S.", time.gmtime(sec)).encode()
        return b'%b%b%06dZ", "v": 0, %b\n' % (
            self._log_prefix, self._log_sec_head, ns // 1000,
            _LOG_ENCODE(fields)[1:].encode())

    def _log_owed(self) -> None:
        """A line went into ``_log_pending``: see that it is written
        before the loop next blocks.  A UDP callback writes in its own
        ``finally``, after its last drain's ``send_batch``; every other
        lane's line arms one ``call_soon``."""
        if self._log_soon or self.engine.log_flush_owed:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            self._write_log()
            return
        self._log_soon = True
        loop.call_soon(self.engine.event_deferred, self._write_log_soon)

    def _write_log_soon(self) -> None:
        self._log_soon = False
        self._write_log()

    def _before_record(self, record: logging.LogRecord) -> bool:
        """Filter on the JSON handlers while the server runs: a record
        that goes out through ``logging`` does not overtake the lines
        rendered before it."""
        if self._log_pending:
            self._write_log()
        return True

    def _write_log(self) -> None:
        """The query log's one writer: the native ring's complete lines
        and the pending Python-lane lines, in one write per handler.
        Called once a readiness event (the UDP lane too: once a
        callback, after the last drain of its chain) by the lane that
        served it, in its ``finally`` after the responses are sent and
        before the loop is given back to ``select``
        (``DnsServer._flush_log``), by ``_log_owed``'s ``call_soon``,
        by a record on its way through ``logging``, and by the
        periodic flusher that nets idle tails."""
        with self._log_lock:
            block = b""
            if self._log_ring:
                try:
                    block = _fastio.fastpath_log_drain(
                        self._fastpath) or b""
                except (TypeError, ValueError):
                    pass
            if self._log_pending:
                block += b"".join(self._log_pending)
                self._log_pending = []
            if not block:
                return
            text = None
            t0 = time.monotonic()
            for h in self._log_json_handlers:
                try:
                    h.acquire()
                    try:
                        buf = self._byte_sink(h)
                        if buf is not None:
                            # (flush the text layer first so lines the
                            # Python formatter wrote stay ordered)
                            h.stream.flush()
                            buf.write(block)
                            buf.flush()
                        else:
                            if text is None:
                                text = block.decode("utf-8", "replace")
                            h.stream.write(text)
                            h.flush()
                        self._log_bytes_child.inc(len(block))
                    finally:
                        h.release()
                except Exception:
                    pass   # a dead log sink must never take down serving
            self._log_write_child.observe(time.monotonic() - t0)

    async def _log_flush_loop(self) -> None:
        try:
            while True:
                await asyncio.sleep(0.1)
                self.engine.event_deferred(self._write_log)
        except asyncio.CancelledError:
            self._write_log()
            raise

    # -- after hook: metrics + query log (lib/server.js:509-591) --

    def _on_after(self, query: QueryCtx) -> None:
        query.stamp("log-after")
        lat_ms = query.latency_ms()
        if self.p_req_done.enabled:
            self.p_req_done.fire(lambda: {
                "trace": query.trace_id,
                "id": query.request.id, "name": query.name(),
                "type": query.qtype_name(),
                "rcode": Rcode.name(query.rcode()),
                "latency_ms": round(lat_ms, 3), "bytes": query.bytes_sent,
                "stages": {k: round(v, 3)
                           for k, v in query.times.items()},
            })
        slow = lat_ms > SLOW_QUERY_MS
        if slow and self.recorder is not None:
            self.recorder.record(
                "slow-query", trace=query.trace_id, name=query.name(),
                qtype=query.qtype_name(), rcode=Rcode.name(query.rcode()),
                latency_ms=round(lat_ms, 3),
                stages={k: round(v, 3) for k, v in query.times.items()})

        children = self._children_for(query.qtype())
        children[0].inc()
        children[1].observe(lat_ms / 1000.0)
        children[2].observe(query.bytes_sent)
        if query.udp_semantics and query.wire[2] & 0x02:
            children[3].inc()
        for stage, ms in query.times.items():
            child = self._stage_children.get(stage)
            if child is None:
                child = self._stage_children[stage] = \
                    self.stage_histogram.labelled({"stage": stage})
            child.observe(ms / 1000.0)

        if not self.query_log and not slow:
            return
        if query.cached_summary is not None:
            ans, add = query.cached_summary
        else:
            ans = [self._summarize(r) for r in query.response.answers]
            add = [self._summarize(r) for r in query.response.additionals
                   if not isinstance(r, OPTRecord)]
        # request envelope built here, not per-query in _on_query: most
        # queries never log (queryLog off / fast), so the dict work
        # happens only on the slow/logged path
        fields = {
            "trace": query.trace_id,
            "req_id": query.request.id,
            "client": query.src[0],
            "port": f"{query.src[1]}/{query.protocol}",
            "edns": query.request.edns is not None,
            **query.log_ctx,
            "rcode": Rcode.name(query.rcode()),
            "answers": ans,
            "additional": add,
            "latency": lat_ms,
            "timers": query.times,
        }
        # log-line: the line's render (direct) or its trip through
        # logging (every other logger, and the slow-query warning),
        # timed around it; it goes to the histogram only, not into
        # `timers`, which the line has already rendered
        t0 = time.monotonic()
        if not slow and self._log_direct():
            line = self._render_log_line(fields)
            with self._log_lock:
                self._log_pending.append(line)
            self._log_owed()
            self._log_line_child.observe(time.monotonic() - t0)
            self._log_direct_child.inc()
            return
        fmts = self._json_formatters
        wrote = sum(f.bytes_out for f in fmts)
        log_event(self.log,
                  logging.WARNING if slow else logging.INFO,
                  "DNS query", **fields)
        self._log_line_child.observe(time.monotonic() - t0)
        self._log_bytes_child.inc(sum(f.bytes_out for f in fmts) - wrote)
        self._log_logging_child.inc()

    def _summarize(self, rec) -> object:
        if isinstance(rec, SRVRecord):
            return (f"SRV {strip_suffix('.' + self.dns_domain, rec.target)}"
                    f":{rec.port}")
        if isinstance(rec, ARecord):
            return (f"{strip_suffix('.' + self.dns_domain, rec.name)} "
                    f"A {rec.address}")
        d = {"type": Type.name(rec.rtype), "name": rec.name, "ttl": rec.ttl}
        if hasattr(rec, "target"):
            d["target"] = rec.target
        return d

    # -- lifecycle (lib/server.js:609-657) --

    async def start(self) -> None:
        self._zone_fill()
        if self.balancer_socket:
            await self.engine.listen_balancer(self.balancer_socket)
        # UDP and TCP share one port number (the reference serves both
        # on the same port, lib/server.js:643-653); a kernel-chosen
        # draw that is taken on TCP is made again (the observed CI
        # flake: EADDRINUSE on the UDP-chosen port).  Announce only
        # once the PAIR is secured: harnesses watch the "service
        # started" lines for the port, and a line printed for a draw
        # that is then released advertises a dead port (observed as a
        # CI dnsblast connection-refused failure)
        if self._zone_fill_task is None:
            self._set_filled()      # inline fill: filled before query one
        elif self.read_when_filled:
            self.engine.hold_reads()
        if self.sockets is not None:
            udp, tcp = self.sockets
            self.udp_port = await self.engine.listen_udp(
                self.host, self.port, announce=False, sock=udp)
            self.tcp_port = await self.engine.listen_tcp(
                self.host, self.port, announce=False, sock=tcp)
        else:
            try:
                self.udp_port, self.tcp_port = await bind_port_pair(
                    self.port,
                    lambda: self.engine.listen_udp(
                        self.host, self.port, announce=False),
                    lambda port: self.engine.listen_tcp(
                        self.host, port, announce=False),
                    self.engine.close_udp_listener)
            except OSError:
                # failed for good (a fixed port taken on UDP or on TCP):
                # release the balancer listener opened above so the
                # raise leaves no socket behind
                await self.engine.close()
                raise
        if self._zone_fill_task is not None:
            self._filled_task = asyncio.get_running_loop().create_task(
                self._await_filled(self._zone_fill_task))
        if self.announce:
            self.engine.announce_udp(self.host, self.udp_port)
            self.engine.announce_tcp(self.host, self.tcp_port)
        if self._log_json_handlers and self._log_flush_task is None:
            for h in self._log_json_handlers:
                h.addFilter(self._before_record)
            # a net for tails (a native serve on a lane that did not
            # write, a line whose write failed); every lane writes its
            # own lines within the turn that served them
            self._log_flush_task = asyncio.get_running_loop().create_task(
                self._log_flush_loop())
        if self._policy is not None and self._policy_task is None:
            self._policy_task = asyncio.get_running_loop().create_task(
                self._policy_tick_loop())
        if self._verify is not None:
            self._verify.start(asyncio.get_running_loop())

    async def _await_filled(self, fill) -> None:
        await asyncio.wait([fill])
        self._set_filled()

    def _set_filled(self) -> None:
        """The startup zone fill is done: what was answered until now is
        ``binder_unfilled_serves_total`` for good, held reads start, and
        whoever waits for it (a shard worker's supervisor) is told."""
        if self._fastpath is not None:
            self._fold_fastpath_metrics()   # the C lanes' serves so far
        self._fold_unfilled()
        self.filled = True
        self.engine.start_reading()
        if self.on_filled is not None:
            self.on_filled()

    def fill_progress(self) -> int:
        """Names the zone fill has passed; grows until ``filled``."""
        return self._fill_done

    async def stop(self) -> None:
        if self._filled_task is not None:
            self._filled_task.cancel()
            self._filled_task = None
        if self._verify is not None:
            await self._verify.stop()
        if self._policy_task is not None:
            self._policy_task.cancel()
            try:
                await self._policy_task
            except asyncio.CancelledError:
                pass
            self._policy_task = None
        if self._log_flush_task is not None:
            self._log_flush_task.cancel()
            try:
                await self._log_flush_task
            except asyncio.CancelledError:
                pass
            self._log_flush_task = None
        await self.engine.close()
        # queries the close served out have rendered their lines
        self._write_log()
        for h in self._log_json_handlers:
            h.removeFilter(self._before_record)


def create_server(**kwargs) -> BinderServer:
    return BinderServer(**kwargs)
