/*
 * fpcore.h — pure-C core of the fastpath answer cache.
 *
 * Everything below is Python-free: the cache table, key lookup, the
 * insert/replace/evict policy, and the per-packet serve path (variant
 * rotation + id/0x20 question patching).  fastio/fastpath.c wraps this
 * in CPython glue (capsule lifecycle, argument validation, recvmmsg/
 * sendmmsg batching); native/fuzz/fuzz_fastpath.cpp drives the same
 * code under ASan+UBSan with mutated inputs.
 *
 * The split exists so the sanitized fuzz target exercises the real
 * fill/serve/rotation code, not a re-implementation (VERDICT r2 weak 2).
 */
#ifndef BINDER_FPCORE_H
#define BINDER_FPCORE_H

#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

#include "../common/dnskey.h"

#ifdef __cplusplus
#define FP_STATIC_ASSERT(cond, why) static_assert(cond, why)
#else
#define FP_STATIC_ASSERT(cond, why) _Static_assert(cond, why)
#endif

#define FP_MAX_VARIANTS 8
#define FP_PROBE 8
#define FP_MAX_WIRE 4096          /* a datagram's ceiling, and an answer-
                                   * cache wire's: larger stay in Python */
/* What only a stream can carry: a DNS message over TCP has a 16-bit
 * length in front of it (RFC 1035 4.2.2) and no other ceiling.  The
 * zone table holds every set such a message can carry; a serve above
 * FP_MAX_WIRE is reachable through FP_VIA_STREAM alone, so no datagram
 * buffer is larger than FP_MAX_WIRE. */
#define FP_MAX_STREAM_WIRE 65535
#define FP_MAX_KEY DNSKEY_MAX
#define FP_MAX_QTYPES 16
#define FP_MAX_BUCKETS 24
#define FP_MAX_TOTAL_BYTES (64u << 20)
#define FP_QTYPE_OTHER 0xFFFF     /* stats catch-all past FP_MAX_QTYPES */

#define FP_MAX_TAG 256            /* a qname in wire label format */

/*
 * Query-log ring: lets the fast path serve while per-query logging is
 * on (the reference's always-on posture, lib/server.js:537-591) instead
 * of standing down.  Entries carry a pre-rendered JSON *fragment* (the
 * answer-dependent middle of the log line: query/cached/rcode/answers/
 * additional — rendered ONCE at push time by Python, not per query);
 * at serve time the C side appends one complete bunyan-style line to a
 * byte ring: constant prefix (name/hostname/pid/level/component/msg,
 * supplied by Python at enable time) + timestamp + per-query fields
 * (req id, client, port/proto, edns) + the fragment + latency.  Python
 * drains the ring in batches and writes it to the log stream — one
 * stream write per batch, not one formatting pass per query.
 *
 * Parity rule: a serve that CANNOT produce its log line (no fragment
 * pushed, ring full because Python is draining too slowly, no client
 * address available) must DECLINE to Python — which logs normally —
 * never serve-and-drop the line.  Logged-posture serving degrades to
 * the slow path under pressure; it never loses log records.
 */
#define FP_MAX_FRAG 4096          /* per-variant pre-rendered fragment of
                                   * an answer-cache entry or the type
                                   * row; a zone entry's is held to
                                   * FP_MAX_STREAM_WIRE like its body */
#define FP_LOG_PREFIX_MAX 512    /* constant line head from Python */
#define FP_LOG_OVERHEAD 256      /* time+id+client+port+latency+glue */

typedef struct {                  /* per-serve source context */
    const char *client;           /* numeric address string, JSON-safe */
    unsigned port;
    const char *proto;            /* "udp" / "tcp" / "balancer" */
} fp_logsrc_t;

typedef struct {
    uint8_t *buf;
    size_t cap;
    size_t len;
    uint64_t lines;               /* lines appended since enable */
    uint64_t declines;            /* serves declined for log reasons */
    uint8_t prefix[FP_LOG_PREFIX_MAX];
    size_t prefix_len;
    int enabled;
    time_t cached_sec;            /* strftime result reused per second */
    char secbuf[24];
    int secbuf_len;
} fp_logring_t;

typedef struct {
    uint8_t key[FP_MAX_KEY];
    uint16_t keylen;
    /* dependency tag (hashed): the wire-format qname of the store name
     * this answer derives from (SRV answers are keyed by the full
     * _svc._proto.name qname but depend on the service node's domain) —
     * matched by fp_invalidate_tag when that name mutates.  Only the
     * 64-bit hash is kept: equality is the only operation, and a hash
     * collision merely drops an extra entry that then re-resolves, so
     * the always-resident slot table stays small */
    uint64_t taghash;
    uint8_t has_tag;
    uint64_t gen;
    double expire_at;
    double inserted_at;
    uint8_t n_variants;
    uint8_t next_variant;
    uint16_t qtype;
    uint8_t *wires[FP_MAX_VARIANTS];
    uint16_t wire_lens[FP_MAX_VARIANTS];
    /* pre-rendered per-variant log fragments (NULL when pushed in the
     * log-off posture; such entries decline when logging is on) */
    uint8_t *frags[FP_MAX_VARIANTS];
    uint16_t frag_lens[FP_MAX_VARIANTS];
    int used;
} fp_entry_t;

typedef struct {
    uint16_t qtype;
    uint64_t count;
    uint64_t truncated;     /* served to a UDP client with TC=1 */
    double lat_sum;
    double size_sum;
    uint64_t lat_cells[FP_MAX_BUCKETS + 1];
    uint64_t size_cells[FP_MAX_BUCKETS + 1];
} fp_qstat_t;

/*
 * Zone table: precompiled authoritative answers (NSD/Knot-style zone
 * compilation, re-designed for a live mirror).  Where the answer cache
 * above remembers what Python resolved, the zone table is filled from
 * the STORE MIRROR itself — on every node-data arrival the server
 * pushes the finished answer body for that name — so even the first
 * query for a name is served inside the C drain.  The reference
 * resolves every cold name per query (lib/server.js:136); precompiling
 * the dominant record shapes is the rebuild's cold-path answer to that.
 *
 * Keyed by qtype+qclass+lowercased-wire-qname only (the last keylen-3
 * bytes of the dnskey) — unlike cache entries, a zone answer does not
 * depend on RD/EDNS/payload: those are patched/echoed at serve time and
 * the payload ceiling is re-checked per packet (truncation declines to
 * Python).  Entries carry the mirror epoch (stale generations are
 * lazily dropped) and the same dependency-tag invalidation as the
 * cache, so the one store-mutation path keeps every layer coherent.
 */
typedef struct {
    uint8_t key[FP_MAX_KEY];  /* qtype BE16 + qclass BE16 + qname */
    uint16_t keylen;
    uint64_t taghash;
    uint8_t has_tag;
    uint64_t gen;
    uint16_t qtype;
    uint16_t ancount;
    uint16_t arcount;         /* additionals baked into the body (SRV) */
    uint8_t n_variants;
    uint8_t next_variant;
    /* answer(+additional) sections; compression ptrs target offset 12 */
    uint8_t *bodies[FP_MAX_VARIANTS];
    uint16_t body_lens[FP_MAX_VARIANTS];
    uint8_t *frags[FP_MAX_VARIANTS];
    uint16_t frag_lens[FP_MAX_VARIANTS];
    int used;
} fp_zentry_t;

/* One zone hash table (open-addressed, FP_PROBE window, grown by
 * rehash).  There are two instances: `zmain` for entries whose
 * dependency tag is their own qname (host A, PTR, service plain-A) —
 * invalidated by O(1) key drops — and `zalien` for entries whose tag
 * differs (SRV: qname _svc._proto.name, tag = the service name), which
 * are invalidated by scanning.  Keeping the alien entries in their own
 * small table (sized by service count, not host count) bounds that
 * scan, which matters during mirror-build storms of tens of thousands
 * of invalidation events. */
typedef struct {
    fp_zentry_t *slots;
    uint32_t mask;            /* slot count - 1; 0 when unallocated */
    uint32_t n;
} fp_ztab_t;

/*
 * Type row: the zone's answer to any question whose TYPE the engine
 * declines before any lookup (lib/server.js:491-506).  Such an answer
 * is its header and the question echoed: it depends on no name and no
 * store generation, so the row has neither in its key, and one row
 * answers every name.  Which types are resolved and which rcode the
 * rest get is the engine's statement (resolver/engine.py TYPE_RULE),
 * handed down by Python when it arms the zone table; C holds no list
 * of its own.
 */
#define FP_ROW_MAX_TYPES 16

typedef struct {
    uint16_t served[FP_ROW_MAX_TYPES];  /* types the engine resolves */
    uint8_t n_served;                   /* 0: no row installed */
    uint8_t rcode;                      /* what every other type gets */
    uint8_t *frag;            /* the one pre-rendered log fragment (NULL
                               * when installed in the log-off posture) */
    uint16_t frag_len;
} fp_typerow_t;

typedef struct {
    fp_entry_t *slots;
    uint32_t mask;            /* slot count - 1 (power of two) */
    uint32_t n_entries;
    uint64_t total_bytes;     /* wire bytes held */
    double expiry_s;
    double lat_buckets[FP_MAX_BUCKETS];
    int n_lat_buckets;
    double size_buckets[FP_MAX_BUCKETS];
    int n_size_buckets;
    fp_qstat_t qstats[FP_MAX_QTYPES];
    int n_qstats;
    uint64_t hits;
    uint64_t lookups;
    uint64_t invalidations;   /* entries dropped by fp_invalidate_tag */
    fp_ztab_t zmain;          /* tag == qname: O(1) invalidation */
    fp_ztab_t zalien;         /* tag != qname: scan invalidation */
    uint64_t ztotal_bytes;
    uint64_t zput_skips[2];   /* fp_zone_put's refusals, by FP_ZSKIP_* */
    uint64_t zone_hits;
    fp_typerow_t trow;
    uint64_t zone_type_hits;  /* the subset of zone_hits the row gave */
    fp_logring_t lr;
} fp_cache_t;

/* EDNS OPT echoed on zone serves: root name, type 41, payload 1232,
 * no flags/options — byte-for-byte dns/query.py _ECHO_OPT's encoding */
static const uint8_t fp_opt_echo[11] = {
    0x00, 0x00, 0x29, 0x04, 0xD0, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00
};

static inline double
fp_now(void)
{
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

static inline uint64_t
fp_hash(const uint8_t *key, size_t len)
{
    uint64_t h = 1469598103934665603ull;        /* FNV-1a 64 */
    for (size_t i = 0; i < len; i++) {
        h ^= key[i];
        h *= 1099511628211ull;
    }
    return h;
}

static inline void
fp_entry_free(fp_cache_t *c, fp_entry_t *e)
{
    for (int i = 0; i < e->n_variants; i++) {
        c->total_bytes -= e->wire_lens[i];
        free(e->wires[i]);
        e->wires[i] = NULL;
        if (e->frags[i] != NULL) {
            c->total_bytes -= e->frag_lens[i];
            free(e->frags[i]);
            e->frags[i] = NULL;
        }
    }
    e->n_variants = 0;
    if (e->used) {
        e->used = 0;
        c->n_entries--;
    }
}

/* allocate the slot table; returns 0 ok, -1 OOM */
static inline int
fp_core_init(fp_cache_t *c, long size, long expiry_ms)
{
    /* 2x capacity so the probe window rarely fills before `size`
     * distinct keys are live */
    uint64_t want = 64;
    while (want < (uint64_t)size * 2 && want < (1u << 24))
        want <<= 1;
    c->slots = (fp_entry_t *)calloc(want, sizeof(fp_entry_t));
    if (c->slots == NULL)
        return -1;
    c->mask = (uint32_t)(want - 1);
    c->expiry_s = (double)expiry_ms / 1000.0;
    return 0;
}

static inline void
fp_zentry_free(fp_cache_t *c, fp_ztab_t *t, fp_zentry_t *e)
{
    for (int i = 0; i < e->n_variants; i++) {
        c->ztotal_bytes -= e->body_lens[i];
        free(e->bodies[i]);
        e->bodies[i] = NULL;
        if (e->frags[i] != NULL) {
            c->ztotal_bytes -= e->frag_lens[i];
            free(e->frags[i]);
            e->frags[i] = NULL;
        }
    }
    e->n_variants = 0;
    if (e->used) {
        e->used = 0;
        t->n--;
    }
}

static inline void
fp_ztab_clear(fp_cache_t *c, fp_ztab_t *t)
{
    if (t->slots == NULL)
        return;
    for (uint32_t i = 0; i <= t->mask; i++) {
        if (t->slots[i].used)
            fp_zentry_free(c, t, &t->slots[i]);
    }
}

static inline void
fp_core_clear(fp_cache_t *c)
{
    for (uint32_t i = 0; i <= c->mask; i++) {
        if (c->slots[i].used)
            fp_entry_free(c, &c->slots[i]);
    }
    fp_ztab_clear(c, &c->zmain);
    fp_ztab_clear(c, &c->zalien);
}

static inline void
fp_core_free(fp_cache_t *c)
{
    if (c->slots != NULL) {
        fp_core_clear(c);
        free(c->slots);
        c->slots = NULL;
    }
    free(c->zmain.slots);
    c->zmain.slots = NULL;
    free(c->zalien.slots);
    c->zalien.slots = NULL;
    free(c->trow.frag);
    memset(&c->trow, 0, sizeof(c->trow));
    free(c->lr.buf);
    c->lr.buf = NULL;
    c->lr.enabled = 0;
}

/* ---------------- query-log ring ---------------- */

/* Arm the log ring: `prefix` is the constant head of every line, up to
 * and including `"time":"` (Python renders it once from its logger
 * identity).  Returns 0 ok, -1 on OOM/bad args. */
static inline int
fp_log_enable(fp_cache_t *c, const uint8_t *prefix, size_t plen,
              size_t cap)
{
    if (plen == 0 || plen > FP_LOG_PREFIX_MAX)
        return -1;
    if (cap < 4096)
        cap = 4096;
    uint8_t *buf = (uint8_t *)malloc(cap);
    if (buf == NULL)
        return -1;
    free(c->lr.buf);
    memset(&c->lr, 0, sizeof(c->lr));
    c->lr.buf = buf;
    c->lr.cap = cap;
    memcpy(c->lr.prefix, prefix, plen);
    c->lr.prefix_len = plen;
    c->lr.cached_sec = (time_t)-1;
    c->lr.enabled = 1;
    return 0;
}

static inline void
fp_log_disable(fp_cache_t *c)
{
    free(c->lr.buf);
    memset(&c->lr, 0, sizeof(c->lr));
}

/* room for one line with an `fraglen`-byte fragment?  (the decline
 * check run BEFORE a serve commits to answering natively) */
static inline int
fp_log_room(const fp_cache_t *c, size_t fraglen)
{
    return c->lr.len + c->lr.prefix_len + fraglen + FP_LOG_OVERHEAD
        <= c->lr.cap;
}

/* append the RFC3339 UTC timestamp; seconds part cached per second */
static inline int
fp_log_time(fp_logring_t *lr, char *p)
{
    struct timespec ts;
    clock_gettime(CLOCK_REALTIME, &ts);
    if (ts.tv_sec != lr->cached_sec) {
        struct tm tm;
        gmtime_r(&ts.tv_sec, &tm);
        lr->secbuf_len = (int)strftime(lr->secbuf, sizeof(lr->secbuf),
                                       "%Y-%m-%dT%H:%M:%S", &tm);
        lr->cached_sec = ts.tv_sec;
    }
    memcpy(p, lr->secbuf, (size_t)lr->secbuf_len);
    return lr->secbuf_len + sprintf(p + lr->secbuf_len, ".%03ldZ",
                                    ts.tv_nsec / 1000000L);
}

/* Append one complete log line.  The caller has already verified
 * fp_log_room for this fragment; src/frag are non-NULL. */
static inline void
fp_log_append(fp_cache_t *c, const uint8_t *pkt, int edns,
              const uint8_t *frag, size_t fraglen,
              const fp_logsrc_t *src, double lat_ms)
{
    fp_logring_t *lr = &c->lr;
    char *base = (char *)lr->buf;
    char *p = base + lr->len;
    memcpy(p, lr->prefix, lr->prefix_len);
    p += lr->prefix_len;
    p += fp_log_time(lr, p);
    p += sprintf(p,
                 "\",\"v\":0,\"req_id\":%u,\"client\":\"%s\","
                 "\"port\":\"%u/%s\",\"edns\":%s,",
                 (unsigned)((pkt[0] << 8) | pkt[1]), src->client,
                 src->port, src->proto, edns ? "true" : "false");
    memcpy(p, frag, fraglen);
    p += fraglen;
    p += sprintf(p, ",\"latency\":%.3f,\"timers\":{}}\n", lat_ms);
    lr->len = (size_t)(p - base);
    lr->lines++;
}

static inline int
fp_bucket_index(const double *buckets, int n, double v)
{
    /* first bucket with bound >= v; n == +Inf cell (matches Python's
     * bisect_left non-cumulative cells in metrics/collector.py) */
    int i = 0;
    while (i < n && buckets[i] < v)
        i++;
    return i;
}

static inline fp_qstat_t *
fp_qstat(fp_cache_t *c, uint16_t qtype)
{
    for (int i = 0; i < c->n_qstats; i++) {
        if (c->qstats[i].qtype == qtype)
            return &c->qstats[i];
    }
    if (c->n_qstats < FP_MAX_QTYPES - 1) {
        fp_qstat_t *s = &c->qstats[c->n_qstats++];
        memset(s, 0, sizeof(*s));
        s->qtype = qtype;
        return s;
    }
    /* overflow: the final slot is a dedicated catch-all labeled with the
     * sentinel qtype (folded as "other" by the server) — a client
     * cycling many qtypes must not misattribute counts to a real type */
    fp_qstat_t *s = &c->qstats[FP_MAX_QTYPES - 1];
    if (c->n_qstats < FP_MAX_QTYPES) {
        memset(s, 0, sizeof(*s));
        s->qtype = FP_QTYPE_OTHER;
        c->n_qstats = FP_MAX_QTYPES;
    }
    return s;
}

static inline fp_entry_t *
fp_find(fp_cache_t *c, const uint8_t *key, size_t keylen, uint64_t gen,
        double now)
{
    uint64_t h = fp_hash(key, keylen);
    for (int p = 0; p < FP_PROBE; p++) {
        fp_entry_t *e = &c->slots[(h + (uint64_t)p) & c->mask];
        if (!e->used)
            continue;
        if (e->keylen != keylen || memcmp(e->key, key, keylen) != 0)
            continue;
        if (e->gen != gen || now > e->expire_at) {
            fp_entry_free(c, e);        /* lazy invalidation */
            return NULL;
        }
        return e;
    }
    return NULL;
}

/*
 * Insert or replace an entry.  `expiry_s` is the effective lifetime for
 * THIS entry (the pusher may hand down a remaining lifetime shorter than
 * the cache-wide default).  `frags`/`frag_lens` (may be NULL) are the
 * per-variant pre-rendered log fragments for the logged posture; an
 * entry without them declines to Python whenever the log ring is on.
 * Returns 1 stored, 0 skipped (bounds/caps), -1 OOM (entry freed,
 * cache consistent).
 */
static inline int
fp_put_raw(fp_cache_t *c, const uint8_t *key, size_t keylen,
           uint16_t qtype, uint64_t gen, const uint8_t *const *wires,
           const uint16_t *wire_lens, int nw, double now, double expiry_s,
           const uint8_t *tag, size_t taglen,
           const uint8_t *const *frags, const uint16_t *frag_lens)
{
    if (keylen < 8 || keylen > FP_MAX_KEY)
        return 0;                       /* not representable: skip */
    if (taglen > FP_MAX_TAG)
        return 0;                       /* not invalidatable: skip */
    if (nw < 1 || nw > FP_MAX_VARIANTS)
        return 0;
    uint64_t add_bytes = 0;
    for (int i = 0; i < nw; i++) {
        if (wire_lens[i] < 12 || wire_lens[i] > FP_MAX_WIRE)
            return 0;                   /* oversize answers stay in Python */
        if (frags != NULL) {
            if (frags[i] == NULL || frag_lens[i] == 0
                    || frag_lens[i] > FP_MAX_FRAG)
                return 0;               /* unloggable: stays in Python */
            add_bytes += (uint64_t)frag_lens[i];
        }
        add_bytes += (uint64_t)wire_lens[i];
    }
    if (c->total_bytes + add_bytes > FP_MAX_TOTAL_BYTES)
        return 0;

    uint64_t h = fp_hash(key, keylen);
    fp_entry_t *target = NULL, *oldest = NULL;
    for (int p = 0; p < FP_PROBE; p++) {
        fp_entry_t *e = &c->slots[(h + (uint64_t)p) & c->mask];
        if (e->used && e->keylen == keylen &&
            memcmp(e->key, key, keylen) == 0) {
            target = e;                 /* replace in place */
            break;
        }
        if (!e->used) {
            if (target == NULL)
                target = e;
            continue;
        }
        if (oldest == NULL || e->inserted_at < oldest->inserted_at)
            oldest = e;
    }
    if (target == NULL)
        target = oldest;                /* probe window full: evict oldest */
    if (target->used)
        fp_entry_free(c, target);

    memcpy(target->key, key, keylen);
    target->keylen = (uint16_t)keylen;
    target->taghash = taglen > 0 ? fp_hash(tag, taglen) : 0;
    target->has_tag = taglen > 0;
    target->gen = gen;
    target->inserted_at = now;
    target->expire_at = now + expiry_s;
    target->next_variant = 0;
    target->qtype = qtype;
    target->n_variants = 0;
    for (int i = 0; i < nw; i++) {
        uint8_t *copy = (uint8_t *)malloc((size_t)wire_lens[i]);
        if (copy == NULL) {
            fp_entry_free(c, target);
            return -1;
        }
        memcpy(copy, wires[i], (size_t)wire_lens[i]);
        target->wires[i] = copy;
        target->wire_lens[i] = wire_lens[i];
        target->frags[i] = NULL;
        target->frag_lens[i] = 0;
        c->total_bytes += (uint64_t)wire_lens[i];
        if (frags != NULL) {
            uint8_t *fc = (uint8_t *)malloc((size_t)frag_lens[i]);
            if (fc == NULL) {
                target->n_variants = (uint8_t)(i + 1);
                fp_entry_free(c, target);
                return -1;
            }
            memcpy(fc, frags[i], (size_t)frag_lens[i]);
            target->frags[i] = fc;
            target->frag_lens[i] = frag_lens[i];
            c->total_bytes += (uint64_t)frag_lens[i];
        }
        target->n_variants = (uint8_t)(i + 1);
    }
    target->used = 1;
    c->n_entries++;
    return 1;
}

/* ---------------- zone table ---------------- */

#define FP_ZONE_MIN_SLOTS 1024
#define FP_ZONE_MAX_SLOTS (1u << 24)
#define FP_ZONE_MAX_BYTES (512u << 20)

/* Why fp_zone_put refused an entry that was well formed: the names it
 * leaves to the Python lanes are slower, never wrong, and nothing else
 * says that they are. */
#define FP_ZSKIP_SIZE 0     /* a body or fragment above its bound */
#define FP_ZSKIP_BYTES 1    /* the table's byte cap */

FP_STATIC_ASSERT(FP_MAX_WIRE <= FP_MAX_STREAM_WIRE
                 && FP_MAX_STREAM_WIRE <= UINT16_MAX,
                 "a zone entry's lengths are uint16_t");

/* Grow (or create) a zone slot table so a put can always find a free
 * probe slot at <=50% load.  Every live entry MUST stay findable
 * within the FP_PROBE lookup window — an entry displaced past it would
 * evade fp_ztab_find and therefore per-name invalidation, and could
 * later serve pre-mutation answers: a silent coherence violation.  So
 * the rehash reinserts under the same bound, retries at double size
 * when a probe cluster exceeds it, and as a last resort FREES the
 * unplaceable entry (those names fall back to the Python path until
 * their next push — slower, never stale).
 * Returns 0 ok, -1 OOM (table unchanged). */
static inline int
fp_zone_grow(fp_cache_t *c, fp_ztab_t *t, uint32_t want)
{
retry:
    if (want > FP_ZONE_MAX_SLOTS)
        return -1;
    fp_zentry_t *ns = (fp_zentry_t *)calloc(want, sizeof(fp_zentry_t));
    if (ns == NULL)
        return -1;
    fp_zentry_t *old = t->slots;
    uint32_t old_mask = t->mask;
    if (old != NULL) {
        for (uint32_t i = 0; i <= old_mask; i++) {
            fp_zentry_t *e = &old[i];
            if (!e->used)
                continue;
            uint64_t h = fp_hash(e->key, e->keylen);
            int placed = 0;
            for (uint32_t p = 0; p < FP_PROBE; p++) {
                fp_zentry_t *dst = &ns[(h + p) & (want - 1)];
                if (!dst->used) {
                    *dst = *e;
                    placed = 1;
                    break;
                }
            }
            if (!placed) {
                if (want * 2 <= FP_ZONE_MAX_SLOTS) {
                    free(ns);           /* cluster > window: go bigger */
                    want *= 2;
                    goto retry;
                }
                /* at the size cap: drop rather than displace */
                fp_zentry_free(c, t, e);
            }
        }
    }
    t->slots = ns;
    t->mask = want - 1;
    free(old);
    return 0;
}

static inline int
fp_zone_ensure(fp_cache_t *c, fp_ztab_t *t)
{
    if (t->slots != NULL && t->n * 2 <= t->mask)
        return 0;
    uint32_t want = t->slots == NULL ? FP_ZONE_MIN_SLOTS
                                     : (t->mask + 1) * 2;
    return fp_zone_grow(c, t, want);
}

/* Presize for an expected entry count so a bulk zone fill never
 * rehashes mid-serving: growth rehashes are O(table), and at
 * production zone scale the largest one measured ~370 ms on the dev
 * VM — an event-loop stall, not a hiccup.  The Python fill walk calls
 * this once with the mirror's name count before pushing. */
static inline int
fp_zone_reserve(fp_cache_t *c, fp_ztab_t *t, uint32_t entries)
{
    uint64_t want = FP_ZONE_MIN_SLOTS;
    while (want < (uint64_t)entries * 2)
        want <<= 1;
    if (want > FP_ZONE_MAX_SLOTS)
        want = FP_ZONE_MAX_SLOTS;
    if (t->slots != NULL && (uint64_t)t->mask + 1 >= want)
        return 0;
    return fp_zone_grow(c, t, (uint32_t)want);
}

static inline fp_zentry_t *
fp_ztab_find(fp_ztab_t *t, const uint8_t *zkey, size_t zklen)
{
    if (t->slots == NULL)
        return NULL;
    uint64_t h = fp_hash(zkey, zklen);
    for (int p = 0; p < FP_PROBE; p++) {
        fp_zentry_t *e = &t->slots[(h + (uint64_t)p) & t->mask];
        if (e->used && e->keylen == zklen &&
            memcmp(e->key, zkey, zklen) == 0)
            return e;
    }
    return NULL;
}

/*
 * Insert or replace a precompiled answer.  `zkey` is qtype+qclass+
 * lowercased wire qname (the dnskey minus its 3 request-dependent
 * lead bytes); bodies are finished answer(+additional) sections whose
 * compression pointers target offset 12; `arcount` additionals (SRV
 * target A records) are included at the tail of each body.  Routes to
 * zmain when the tag is the entry's own qname with a directly-probed
 * qtype/class (O(1) invalidation), zalien otherwise (scan).
 * A body is admitted up to what a stream can carry (FP_MAX_STREAM_WIRE
 * less the header, the question and the OPT echo, so that a stored
 * entry serves every posture of a frame); its fragment's uint16_t
 * length is the same bound.  What a datagram may carry of it is
 * fp_zone_serve's to say.
 * Returns 1 stored, 0 skipped (counted in zput_skips where the entry
 * was well formed and a bound refused it), -1 OOM.
 */
static inline int
fp_zone_put(fp_cache_t *c, const uint8_t *zkey, size_t zklen,
            uint64_t gen, uint16_t ancount, uint16_t arcount,
            const uint8_t *const *bodies, const uint16_t *body_lens,
            int nv, const uint8_t *tag, size_t taglen,
            const uint8_t *const *frags, const uint16_t *frag_lens)
{
    if (zklen < 5 || zklen > FP_MAX_KEY)
        return 0;
    if (taglen == 0 || taglen > FP_MAX_TAG)
        return 0;                   /* uninvalidatable: never stale-safe */
    if (nv < 1 || nv > FP_MAX_VARIANTS || ancount == 0)
        return 0;
    /* header + question (the key less type and class is the qname)
     * + type and class + OPT echo */
    size_t max_body = FP_MAX_STREAM_WIRE
        - (12 + (zklen - 4) + 4 + sizeof(fp_opt_echo));
    uint64_t add = 0;
    for (int i = 0; i < nv; i++) {
        if (body_lens[i] == 0)
            return 0;
        if (body_lens[i] > max_body) {
            c->zput_skips[FP_ZSKIP_SIZE]++;
            return 0;
        }
        if (frags != NULL) {
            if (frags[i] == NULL || frag_lens[i] == 0)
                return 0;           /* unloggable: stays in Python */
            add += frag_lens[i];
        }
        add += body_lens[i];
    }
    if (c->ztotal_bytes + add > FP_ZONE_MAX_BYTES) {
        c->zput_skips[FP_ZSKIP_BYTES]++;
        return 0;
    }

    /* Table routing must be a function of the KEY alone (the serve
     * path has only the key): (A|PTR, IN) keys live in zmain — where
     * fp_invalidate_tag's O(1) drop rebuilds them as (qtype, IN, tag),
     * which is only correct when the tag IS the qname, so any other
     * tag on such a key is rejected outright — and every other key
     * lives in the scanned (small) alien table. */
    uint16_t zqtype = (uint16_t)((zkey[0] << 8) | zkey[1]);
    uint16_t zqclass = (uint16_t)((zkey[2] << 8) | zkey[3]);
    int main_table = (zqtype == 1 || zqtype == 12) && zqclass == 1;
    if (main_table && !(taglen == zklen - 4 &&
                        memcmp(tag, zkey + 4, taglen) == 0))
        return 0;
    fp_ztab_t *t = main_table ? &c->zmain : &c->zalien;
    if (fp_zone_ensure(c, t) < 0)
        return -1;

    uint64_t h = fp_hash(zkey, zklen);
    fp_zentry_t *target = NULL, *stale = NULL, *oldest = NULL;
    for (int p = 0; p < FP_PROBE; p++) {
        fp_zentry_t *e = &t->slots[(h + (uint64_t)p) & t->mask];
        if (e->used && e->keylen == zklen &&
            memcmp(e->key, zkey, zklen) == 0) {
            target = e;             /* replace in place */
            break;
        }
        if (!e->used) {
            if (target == NULL)
                target = e;
            continue;
        }
        if (e->gen != gen && stale == NULL)
            stale = e;              /* pre-rebuild leftover: evictable */
        if (oldest == NULL)
            oldest = e;
    }
    if (target == NULL)
        target = stale != NULL ? stale : oldest;
    if (target->used)
        fp_zentry_free(c, t, target);

    memcpy(target->key, zkey, zklen);
    target->keylen = (uint16_t)zklen;
    target->taghash = fp_hash(tag, taglen);
    target->has_tag = 1;
    target->gen = gen;
    target->qtype = zqtype;
    target->ancount = ancount;
    target->arcount = arcount;
    target->next_variant = 0;
    target->n_variants = 0;
    for (int i = 0; i < nv; i++) {
        uint8_t *copy = (uint8_t *)malloc((size_t)body_lens[i]);
        if (copy == NULL) {
            fp_zentry_free(c, t, target);
            return -1;
        }
        memcpy(copy, bodies[i], (size_t)body_lens[i]);
        target->bodies[i] = copy;
        target->body_lens[i] = body_lens[i];
        target->frags[i] = NULL;
        target->frag_lens[i] = 0;
        c->ztotal_bytes += (uint64_t)body_lens[i];
        if (frags != NULL) {
            uint8_t *fc = (uint8_t *)malloc((size_t)frag_lens[i]);
            if (fc == NULL) {
                target->n_variants = (uint8_t)(i + 1);
                fp_zentry_free(c, t, target);
                return -1;
            }
            memcpy(fc, frags[i], (size_t)frag_lens[i]);
            target->frags[i] = fc;
            target->frag_lens[i] = frag_lens[i];
            c->ztotal_bytes += (uint64_t)frag_lens[i];
        }
        target->n_variants = (uint8_t)(i + 1);
    }
    target->used = 1;
    t->n++;
    return 1;
}

/*
 * Drop every entry whose dependency tag equals `tag` (a mirrored store
 * mutation changed that name's answers) — in the answer cache AND the
 * zone table, so one store-mutation path keeps every layer coherent.
 * Cache: full-table scan (mutation rates ~hundreds/s times thousands of
 * slots is microseconds, and needs no auxiliary index).  Zone: entries
 * are tagged with their own qname by construction (A, PTR), so two
 * O(1) key drops replace the scan; a scan runs only while alien-tagged
 * entries exist.  The distinction matters at mirror-build time, when
 * tens of thousands of invalidation events arrive while the zone table
 * is large.  Returns the number of entries dropped.
 */
#define FP_INVAL_BATCH 32   /* tags per batched invalidation pass */

/* Batched spelling: ONE pass over each scanned table for up to
 * FP_INVAL_BATCH tags.  A single store mutation emits several tags
 * (name, parent service, old/new PTR qnames); per-tag scans would cost
 * one full cache-table walk each, and mutation storms multiply that —
 * the batch form keeps the churn path at one walk per event. */
static inline uint32_t
fp_invalidate_tags(fp_cache_t *c, const uint8_t *const *tags,
                   const size_t *taglens, int ntags)
{
    if (ntags > FP_INVAL_BATCH) {
        /* oversize batches recurse in chunks — truncating instead
         * would silently leave tags 33+ serving pre-mutation answers,
         * the exact coherence violation this path exists to prevent */
        uint32_t n = 0;
        for (int off = 0; off < ntags; off += FP_INVAL_BATCH) {
            int chunk = ntags - off;
            if (chunk > FP_INVAL_BATCH)
                chunk = FP_INVAL_BATCH;
            n += fp_invalidate_tags(c, tags + off, taglens + off, chunk);
        }
        return n;
    }
    uint64_t hashes[FP_INVAL_BATCH];
    int nh = 0;
    for (int t = 0; t < ntags; t++) {
        if (taglens[t] == 0 || taglens[t] > FP_MAX_TAG)
            continue;
        hashes[nh++] = fp_hash(tags[t], taglens[t]);
    }
    if (nh == 0)
        return 0;
    uint32_t n = 0;
    if (c->n_entries > 0) {
        for (uint32_t i = 0; i <= c->mask; i++) {
            fp_entry_t *e = &c->slots[i];
            if (!e->used || !e->has_tag)
                continue;
            for (int t = 0; t < nh; t++) {
                if (e->taghash == hashes[t]) {
                    fp_entry_free(c, e);
                    n++;
                    break;
                }
            }
        }
    }
    if (c->zmain.n > 0) {
        static const uint16_t qtypes[2] = {1, 12};   /* A, PTR */
        uint8_t zkey[FP_MAX_KEY];
        int hi = 0;
        for (int t = 0; t < ntags; t++) {
            size_t taglen = taglens[t];
            if (taglen == 0 || taglen > FP_MAX_TAG)
                continue;
            uint64_t h = hashes[hi++];
            if (taglen + 4 > FP_MAX_KEY)
                continue;
            zkey[2] = 0;
            zkey[3] = 1;                             /* class IN */
            memcpy(zkey + 4, tags[t], taglen);
            for (int q = 0; q < 2; q++) {
                zkey[0] = (uint8_t)(qtypes[q] >> 8);
                zkey[1] = (uint8_t)(qtypes[q] & 0xFF);
                fp_zentry_t *e = fp_ztab_find(&c->zmain, zkey,
                                              taglen + 4);
                if (e != NULL && e->has_tag && e->taghash == h) {
                    fp_zentry_free(c, &c->zmain, e);
                    n++;
                }
            }
        }
    }
    if (c->zalien.n > 0) {
        /* the scan is bounded by the alien table's size (services, not
         * hosts) — cheap even under mirror-build invalidation storms */
        for (uint32_t i = 0; i <= c->zalien.mask; i++) {
            fp_zentry_t *e = &c->zalien.slots[i];
            if (!e->used || !e->has_tag)
                continue;
            for (int t = 0; t < nh; t++) {
                if (e->taghash == hashes[t]) {
                    fp_zentry_free(c, &c->zalien, e);
                    n++;
                    break;
                }
            }
        }
    }
    c->invalidations += n;
    return n;
}

static inline uint32_t
fp_invalidate_tag(fp_cache_t *c, const uint8_t *tag, size_t taglen)
{
    return fp_invalidate_tags(c, &tag, &taglen, 1);
}

/* The transport a query arrived on, as far as the serving entry knows
 * it.  A dnskey carries none: a query without an OPT record has the
 * payload 512 in its key whatever socket it came from, so the ceiling
 * of a native serve is the caller's to say. */
#define FP_VIA_DATAGRAM 0   /* UDP: the key's payload is the ceiling and
                             * a cached TC=1 wire is the right answer */
#define FP_VIA_UNKNOWN 1    /* either (fastpath_serve_wire): the key's
                             * payload is the ceiling AND a cached TC=1
                             * wire declines, so the serve is right on
                             * both transports */
#define FP_VIA_STREAM 2     /* TCP (fastpath_serve_frames): a frame has
                             * the stream's ceiling, FP_MAX_STREAM_WIRE,
                             * and is never truncated */

/*
 * Serve one packet from the zone table: assemble header + question echo
 * (original case) + precompiled body + optional OPT echo.  `key` is the
 * full dnskey (RD/EDNS/payload in its lead bytes), `out` holds `room`
 * bytes.  The ceiling is the transport's: the key's payload and at most
 * FP_MAX_WIRE for a datagram and for a caller that does not know, the
 * stream's own for a frame; and never more than `room`.  Returns
 * response length, or 0 to decline to Python (miss, stale generation,
 * or over the ceiling: would-truncate, or an entry only a stream
 * carries).
 */
static inline size_t
fp_zone_serve(fp_cache_t *c, const uint8_t *pkt, const uint8_t *key,
              size_t keylen, size_t qn_len, uint64_t gen, uint8_t *out,
              size_t room, uint16_t *qtype_out, double now, int via,
              const fp_logsrc_t *src)
{
    /* table routing mirrors fp_zone_put exactly: (A|PTR, IN) keys can
     * only live in zmain, everything else only in zalien — probing the
     * other table would be a guaranteed miss on every lookup */
    uint16_t zqtype = (uint16_t)((key[3] << 8) | key[4]);
    uint16_t zqclass = (uint16_t)((key[5] << 8) | key[6]);
    fp_ztab_t *t = ((zqtype == 1 || zqtype == 12) && zqclass == 1)
        ? &c->zmain : &c->zalien;
    fp_zentry_t *e = fp_ztab_find(t, key + 3, keylen - 3);
    if (e == NULL)
        return 0;
    if (e->gen != gen) {
        fp_zentry_free(c, t, e);        /* lazy epoch invalidation */
        return 0;
    }
    int rd = key[0] & 1;
    int edns = key[0] & 2;
    size_t ceiling = FP_MAX_STREAM_WIRE;
    if (via != FP_VIA_STREAM) {
        ceiling = ((size_t)key[1] << 8) | key[2];
        if (ceiling > FP_MAX_WIRE)
            ceiling = FP_MAX_WIRE;
    }
    if (ceiling > room)
        ceiling = room;

    uint8_t v = e->next_variant;
    size_t blen = e->body_lens[v];
    size_t total = 12 + qn_len + 4 + blen + (edns ? sizeof(fp_opt_echo) : 0);
    if (total > ceiling)
        /* truncation semantics, or a set no datagram carries: Python
         * (BEFORE rotation and log accounting: the entry's next stream
         * serve takes the variant this one left) */
        return 0;
    if (c->lr.enabled) {
        /* logged posture: a serve whose log line cannot be produced
         * declines (BEFORE rotation/accounting) — Python logs it */
        if (src == NULL || e->frags[v] == NULL
                || !fp_log_room(c, e->frag_lens[v])) {
            c->lr.declines++;
            return 0;
        }
    }
    e->next_variant = (uint8_t)((v + 1) % e->n_variants);

    out[0] = pkt[0];                    /* request id */
    out[1] = pkt[1];
    out[2] = (uint8_t)(0x84 | (rd ? 0x01 : 0));   /* QR|AA, RD echo */
    out[3] = 0;                         /* RA=0, rcode NOERROR */
    out[4] = 0; out[5] = 1;             /* QD=1 */
    out[6] = (uint8_t)(e->ancount >> 8);
    out[7] = (uint8_t)(e->ancount & 0xFF);
    out[8] = 0; out[9] = 0;             /* NS=0 */
    /* additionals baked into the body, plus the OPT echo when the
     * query carried EDNS (the OPT is appended after the body, i.e.
     * last in the additionals section, where the generic encoder also
     * places it) */
    uint16_t ar = (uint16_t)(e->arcount + (edns ? 1 : 0));
    out[10] = (uint8_t)(ar >> 8);
    out[11] = (uint8_t)(ar & 0xFF);
    memcpy(out + 12, pkt + 12, qn_len + 4);       /* 0x20 case echo */
    memcpy(out + 12 + qn_len + 4, e->bodies[v], blen);
    if (edns)
        memcpy(out + 12 + qn_len + 4 + blen, fp_opt_echo,
               sizeof(fp_opt_echo));
    if (qtype_out != NULL)
        *qtype_out = e->qtype;
    c->zone_hits++;
    if (c->lr.enabled)
        fp_log_append(c, pkt, edns, e->frags[v], e->frag_lens[v], src,
                      (fp_now() - now) * 1e3);
    return total;
}

/*
 * Install (or replace) the type row.  `frag` (may be NULL: log-off
 * posture) is the middle of the log line a Python-lane first sight of
 * a declined question renders.  The row is no table entry: it is not
 * counted in the zone's entries or bytes, no generation or tag drops
 * it, and fp_core_clear leaves it (it holds nothing of the store).
 * Returns 1 stored, 0 skipped (bounds), -1 OOM (row unchanged).
 */
static inline int
fp_type_row_put(fp_cache_t *c, const uint16_t *served, int n_served,
                int rcode, const uint8_t *frag, size_t fraglen)
{
    if (n_served < 1 || n_served > FP_ROW_MAX_TYPES
            || rcode < 0 || rcode > 15)
        return 0;
    uint8_t *fc = NULL;
    if (frag != NULL) {
        if (fraglen == 0 || fraglen > FP_MAX_FRAG)
            return 0;                   /* unloggable: stays in Python */
        fc = (uint8_t *)malloc(fraglen);
        if (fc == NULL)
            return -1;
        memcpy(fc, frag, fraglen);
    }
    free(c->trow.frag);
    memset(&c->trow, 0, sizeof(c->trow));
    memcpy(c->trow.served, served, (size_t)n_served * sizeof(*served));
    c->trow.n_served = (uint8_t)n_served;
    c->trow.rcode = (uint8_t)rcode;
    c->trow.frag = fc;
    c->trow.frag_len = (uint16_t)(fc != NULL ? fraglen : 0);
    return 1;
}

/* does the row decline this type?  (0 without a row) */
static inline int
fp_type_row_covers(const fp_typerow_t *r, uint16_t qtype)
{
    if (r->n_served == 0)
        return 0;
    for (int i = 0; i < r->n_served; i++) {
        if (r->served[i] == qtype)
            return 0;
    }
    return 1;
}

/*
 * Serve a question of a declined type from the row: header (the row's
 * rcode, no records) + question echo (original case) + OPT echo when
 * the key says EDNS, byte for byte QueryCtx.respond's encoding of the
 * engine's decision.  At most 12 + 255 + 4 + 11 bytes: under every
 * payload ceiling, so right on every transport.  Returns the length,
 * or 0 to decline to Python (logged posture and the line cannot be
 * produced).
 */
static inline size_t
fp_type_serve(fp_cache_t *c, const uint8_t *pkt, const uint8_t *key,
              size_t qn_len, uint8_t *out, double now,
              const fp_logsrc_t *src)
{
    const fp_typerow_t *r = &c->trow;
    if (c->lr.enabled) {
        if (src == NULL || r->frag == NULL
                || !fp_log_room(c, r->frag_len)) {
            c->lr.declines++;
            return 0;
        }
    }
    int edns = key[0] & 2;
    out[0] = pkt[0];                    /* request id */
    out[1] = pkt[1];
    out[2] = (uint8_t)(0x84 | (key[0] & 1));      /* QR|AA, RD echo */
    out[3] = r->rcode;                  /* RA=0 */
    out[4] = 0; out[5] = 1;             /* QD=1 */
    out[6] = 0; out[7] = 0;             /* AN=0 */
    out[8] = 0; out[9] = 0;             /* NS=0 */
    out[10] = 0; out[11] = (uint8_t)(edns ? 1 : 0);
    memcpy(out + 12, pkt + 12, qn_len + 4);       /* 0x20 case echo */
    size_t total = 12 + qn_len + 4;
    if (edns) {
        memcpy(out + total, fp_opt_echo, sizeof(fp_opt_echo));
        total += sizeof(fp_opt_echo);
    }
    c->zone_hits++;
    c->zone_type_hits++;
    if (c->lr.enabled)
        fp_log_append(c, pkt, edns, r->frag, r->frag_len, src,
                      (fp_now() - now) * 1e3);
    return total;
}

/*
 * Serve one packet from the cache: key build, lookup (with lazy gen/TTL
 * invalidation), variant rotation, id + 0x20 question patching.  `out`
 * holds `room` bytes, FP_MAX_WIRE at least (what an answer-cache wire
 * and the type row's answer may take; only a zone serve over a stream
 * uses more).  Returns the response length on hit, 0 on miss (the
 * caller surfaces the packet to the slow path).
 *
 * A question whose type the installed type row declines is answered
 * from the row ahead of both probes, for every caller alike.
 *
 * `via` (FP_VIA_*) is the transport the packet arrived on.  A cached
 * wire whose next variant carries TC=1 was promoted off the UDP path
 * and is right for a datagram only.  The socket-free entry that cannot
 * know its caller's transport (fastpath_serve_wire) declines it; a
 * stream frame (fastpath_serve_frames) passes it over and asks the zone
 * table, which holds the whole set and serves it up to the stream's
 * ceiling.
 * Either happens BEFORE hit accounting and rotation, so a passed-over
 * entry neither inflates the folded cache-hit counter nor burns a
 * rotation step.
 */
static inline size_t
fp_serve_one_lx(fp_cache_t *c, const uint8_t *pkt, size_t plen,
                uint64_t gen, double now, uint8_t *out, size_t room,
                uint16_t *qtype_out, int via,
                const fp_logsrc_t *src)
{
    uint8_t key[FP_MAX_KEY];
    size_t qn_len = 0;
    uint16_t qtype = 0;

    c->lookups++;
    size_t keylen = dnskey_build(pkt, plen, key, &qn_len, &qtype);
    if (keylen == 0)
        return 0;
    if (fp_type_row_covers(&c->trow, qtype)) {
        /* a declined type is its header: no name to probe for */
        if (qtype_out != NULL)
            *qtype_out = qtype;
        return fp_type_serve(c, pkt, key, qn_len, out, now, src);
    }
    fp_entry_t *e = fp_find(c, key, keylen, gen, now);
    if (e != NULL && via != FP_VIA_DATAGRAM
            && e->wire_lens[e->next_variant] >= 3
            && (e->wires[e->next_variant][2] & 0x02)) {
        if (via != FP_VIA_STREAM)
            return 0;
        e = NULL;
    }
    if (e == NULL)
        /* not in the answer cache (or held there truncated, for a
         * stream): a precompiled zone answer still serves it natively,
         * the first query for a name included */
        return fp_zone_serve(c, pkt, key, keylen, qn_len, gen, out,
                             room, qtype_out, now, via, src);

    /* hit: copy the variant, patch id + the client's question bytes
     * (same length by construction — key match implies identical
     * lowercased label structure) */
    uint8_t v = e->next_variant;
    if (c->lr.enabled) {
        /* logged posture: decline (before rotation/accounting) when the
         * line can't be produced — Python serves AND logs instead */
        if (src == NULL || e->frags[v] == NULL
                || !fp_log_room(c, e->frag_lens[v])) {
            c->lr.declines++;
            return 0;
        }
    }
    e->next_variant = (uint8_t)((v + 1) % e->n_variants);
    const uint8_t *wire = e->wires[v];
    size_t wlen = e->wire_lens[v];
    if (wlen < 12 + qn_len + 4) {
        /* defensive: a cached response must embed the question */
        fp_entry_free(c, e);
        return 0;
    }
    memcpy(out, wire, wlen);
    out[0] = pkt[0];
    out[1] = pkt[1];
    memcpy(out + 12, pkt + 12, qn_len + 4);
    if (qtype_out != NULL)
        *qtype_out = e->qtype;
    c->hits++;
    if (c->lr.enabled)
        fp_log_append(c, pkt, key[0] & 2, e->frags[v], e->frag_lens[v],
                      src, (fp_now() - now) * 1e3);
    return wlen;
}

/* drain-path spelling, log off: TC wires serve (a UDP requester asked
 * for them); `out` holds FP_MAX_WIRE */
static inline size_t
fp_serve_one(fp_cache_t *c, const uint8_t *pkt, size_t plen, uint64_t gen,
             double now, uint8_t *out, uint16_t *qtype_out)
{
    return fp_serve_one_lx(c, pkt, plen, gen, now, out, FP_MAX_WIRE,
                           qtype_out, FP_VIA_DATAGRAM, NULL);
}

#endif /* BINDER_FPCORE_H */
