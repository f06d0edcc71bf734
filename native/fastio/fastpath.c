/*
 * _binderfastio fast path — native encoded-answer cache for the UDP drain.
 *
 * The Python answer cache (binder_tpu/resolver/answer_cache.py) already
 * makes repeat queries cheap; this moves the *hit* path out of Python
 * entirely.  `fastpath_drain(fd)` replaces `recv_batch(fd)` on the UDP
 * reader: it recvmmsg()s a batch, parses each question directly from the
 * wire, looks it up in a native cache, and answers hits with one
 * sendmmsg() — the Python event loop only ever sees the misses.  Python
 * stays the source of truth: it resolves misses through the normal
 * engine (binder_tpu/resolver/engine.py) and pushes the completed,
 * fully-encoded response variants down with `fastpath_put`.
 *
 * The cache/serve core itself is Python-free and lives in fpcore.h (also
 * driven by the sanitized fuzz target native/fuzz/fuzz_fastpath.cpp);
 * this file is the CPython glue: capsule lifecycle, argument validation,
 * recvmmsg/sendmmsg batching, stats marshaling.
 *
 * Semantics preserved relative to the Python hit path
 * (BinderServer._on_query):
 *  - the key covers exactly the decoded fields the response depends on:
 *    RD bit, EDNS presence, effective payload ceiling, qtype, qclass,
 *    lowercased qname (wire label format).  EDNS option bytes (cookies,
 *    padding) vary per packet and are deliberately NOT keyed;
 *  - store-generation check: every entry records the mirror-cache
 *    generation it was resolved under; drain() is handed the current
 *    generation and treats stale entries as misses (lazy invalidation);
 *  - time expiry (the reference's -a expiry flag, main.js:34-38);
 *  - round-robin: multi-answer entries carry the shuffle variants the
 *    Python cache collected and hits cycle through them;
 *  - 0x20 case echo: the response's question section is patched with the
 *    client's original bytes, so mixed-case (RFC draft-vixie-dnsext-dns0x20)
 *    queries verify.
 *
 * Only plain hostname-charset names ([a-zA-Z0-9_-] labels) take the fast
 * path; anything else — multi-question, non-zero opcode, compression in
 * the question, unknown additionals, trailing bytes — falls through to
 * Python, which is always correct.
 *
 * Queries answered here never reach the Python after-hook, so the cache
 * keeps its own per-qtype counters and latency/size histogram cells
 * (bucket bounds supplied by Python at construction, matching the
 * Prometheus collectors); the server folds them in at scrape time.
 * The fast path is only engaged when per-query logging and probes are
 * off — with those on, every query must surface to Python.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <arpa/inet.h>
#include <errno.h>
#include <netinet/in.h>
#include <stdint.h>
#include <string.h>
#include <sys/socket.h>
#include <time.h>

#include "../common/dnskey.h"
#include "fastpath.h"
#include "fpcore.h"

#define FP_BATCH FASTIO_BATCH

static const char *FP_CAPSULE_NAME = "binder_tpu._binderfastio.fastpath";

static void
fp_cache_free(fp_cache_t *c)
{
    fp_core_free(c);
    free(c);
}

static void
fp_capsule_destructor(PyObject *capsule)
{
    fp_cache_t *c = PyCapsule_GetPointer(capsule, FP_CAPSULE_NAME);
    if (c != NULL)
        fp_cache_free(c);
}

static fp_cache_t *
fp_from_capsule(PyObject *capsule)
{
    return PyCapsule_GetPointer(capsule, FP_CAPSULE_NAME);
}

static int
fp_load_buckets(PyObject *seq, double *out, int *n_out, const char *what)
{
    PyObject *fast = PySequence_Fast(seq, "buckets must be a sequence");
    if (fast == NULL)
        return -1;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    if (n > FP_MAX_BUCKETS) {
        Py_DECREF(fast);
        PyErr_Format(PyExc_ValueError, "too many %s buckets (max %d)",
                     what, FP_MAX_BUCKETS);
        return -1;
    }
    double prev = -1.0;
    for (Py_ssize_t i = 0; i < n; i++) {
        double v = PyFloat_AsDouble(PySequence_Fast_GET_ITEM(fast, i));
        if (v == -1.0 && PyErr_Occurred()) {
            Py_DECREF(fast);
            return -1;
        }
        if (v <= prev) {
            Py_DECREF(fast);
            PyErr_Format(PyExc_ValueError,
                         "%s buckets must be strictly increasing", what);
            return -1;
        }
        out[i] = v;
        prev = v;
    }
    *n_out = (int)n;
    Py_DECREF(fast);
    return 0;
}

/* Append (payload, addr) to the miss list in recv_batch's item format.
 * Returns 0 on success; -1 with a Python exception set. */
static int
surface_miss(PyObject *misses, const uint8_t *pkt, size_t plen,
             const struct sockaddr_storage *addr)
{
    PyObject *payload = PyBytes_FromStringAndSize((const char *)pkt,
                                                  (Py_ssize_t)plen);
    PyObject *addr_t = payload ? fastio_addr_to_tuple(addr) : NULL;
    PyObject *item = (payload && addr_t)
        ? PyTuple_Pack(2, payload, addr_t) : NULL;
    Py_XDECREF(payload);
    Py_XDECREF(addr_t);
    if (item == NULL)
        return -1;
    int rc = PyList_Append(misses, item);
    Py_DECREF(item);
    return rc;
}

/* ---------------- module functions ---------------- */

PyObject *
fastpath_new(PyObject *self, PyObject *args)
{
    (void)self;
    long size;
    long expiry_ms;
    PyObject *lat_buckets, *size_buckets;

    if (!PyArg_ParseTuple(args, "llOO", &size, &expiry_ms,
                          &lat_buckets, &size_buckets))
        return NULL;
    if (size < 1) {
        PyErr_SetString(PyExc_ValueError, "size must be >= 1");
        return NULL;
    }
    fp_cache_t *c = calloc(1, sizeof(*c));
    if (c == NULL)
        return PyErr_NoMemory();
    if (fp_core_init(c, size, expiry_ms) < 0) {
        free(c);
        return PyErr_NoMemory();
    }
    if (fp_load_buckets(lat_buckets, c->lat_buckets,
                        &c->n_lat_buckets, "latency") < 0 ||
        fp_load_buckets(size_buckets, c->size_buckets,
                        &c->n_size_buckets, "size") < 0) {
        fp_cache_free(c);
        return NULL;
    }
    PyObject *capsule = PyCapsule_New(c, FP_CAPSULE_NAME,
                                      fp_capsule_destructor);
    if (capsule == NULL) {
        fp_cache_free(c);
        return NULL;
    }
    return capsule;
}

/* Borrow (ptr, len) arrays for a per-variant fragment sequence.  On
 * success *fast_out holds the sequence keeping the pointers alive and
 * frag_ptrs/frag_lens are filled for exactly `expect` items, none
 * longer than `max_len` (the bound of the table the entry is for).
 * Returns 1 usable, 0 skip-the-put (wrong count / empty), -2 the same
 * for a fragment above `max_len`, -1 with a Python exception set. */
static int
fp_load_frags(PyObject *frags, Py_ssize_t expect, Py_ssize_t max_len,
              PyObject **fast_out, const uint8_t **frag_ptrs,
              uint16_t *frag_lens)
{
    *fast_out = NULL;
    if (frags == NULL || frags == Py_None)
        return 1;                   /* no fragments: log-off posture */
    PyObject *fast = PySequence_Fast(frags, "frags must be a sequence");
    if (fast == NULL)
        return -1;
    if (PySequence_Fast_GET_SIZE(fast) != expect) {
        Py_DECREF(fast);
        return 0;                   /* per-variant mismatch: skip */
    }
    for (Py_ssize_t i = 0; i < expect; i++) {
        char *data;
        Py_ssize_t dlen;
        if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fast, i),
                                    &data, &dlen) < 0) {
            Py_DECREF(fast);
            return -1;
        }
        if (dlen < 1 || dlen > max_len) {
            Py_DECREF(fast);
            return dlen < 1 ? 0 : -2;   /* unloggable: stays in Python */
        }
        frag_ptrs[i] = (const uint8_t *)data;
        frag_lens[i] = (uint16_t)dlen;
    }
    *fast_out = fast;
    return 1;
}

PyObject *
fastpath_put(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule, *wires;
    PyObject *frags = NULL;
    Py_buffer keybuf, tagbuf;
    unsigned long long gen;
    int qtype;
    long expiry_ms = -1;   /* default: the cache-wide expiry */

    tagbuf.buf = NULL;
    tagbuf.len = 0;
    tagbuf.obj = NULL;
    if (!PyArg_ParseTuple(args, "Oy*iKO|ly*O", &capsule, &keybuf, &qtype,
                          &gen, &wires, &expiry_ms, &tagbuf, &frags))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&keybuf);
        if (tagbuf.obj != NULL)
            PyBuffer_Release(&tagbuf);
        return NULL;
    }
    PyObject *fast = PySequence_Fast(wires, "wires must be a sequence");
    if (fast == NULL) {
        PyBuffer_Release(&keybuf);
        if (tagbuf.obj != NULL)
            PyBuffer_Release(&tagbuf);
        return NULL;
    }
    Py_ssize_t nw = PySequence_Fast_GET_SIZE(fast);
    int rc = 0;
    if (nw >= 1 && nw <= FP_MAX_VARIANTS) {
        /* borrow the wire pointers (valid while `fast` is held) */
        const uint8_t *wire_ptrs[FP_MAX_VARIANTS];
        uint16_t wire_lens[FP_MAX_VARIANTS];
        const uint8_t *frag_ptrs[FP_MAX_VARIANTS];
        uint16_t frag_lens[FP_MAX_VARIANTS];
        PyObject *frag_fast = NULL;
        int sizes_ok = 1;
        for (Py_ssize_t i = 0; i < nw; i++) {
            char *data;
            Py_ssize_t dlen;
            if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fast, i),
                                        &data, &dlen) < 0) {
                Py_DECREF(fast);
                PyBuffer_Release(&keybuf);
                if (tagbuf.obj != NULL)
                    PyBuffer_Release(&tagbuf);
                return NULL;
            }
            if (dlen < 12 || dlen > FP_MAX_WIRE) {
                sizes_ok = 0;       /* oversize answers stay in Python */
                break;
            }
            wire_ptrs[i] = (const uint8_t *)data;
            wire_lens[i] = (uint16_t)dlen;
        }
        int frc = sizes_ok
            ? fp_load_frags(frags, nw, FP_MAX_FRAG, &frag_fast, frag_ptrs,
                            frag_lens)
            : 1;
        if (frc == -1) {
            Py_DECREF(fast);
            PyBuffer_Release(&keybuf);
            if (tagbuf.obj != NULL)
                PyBuffer_Release(&tagbuf);
            return NULL;
        }
        if (sizes_ok && frc > 0) {
            double expiry_s = expiry_ms >= 0 ? (double)expiry_ms / 1000.0
                                             : c->expiry_s;
            rc = fp_put_raw(c, keybuf.buf, (size_t)keybuf.len,
                            (uint16_t)qtype, (uint64_t)gen, wire_ptrs,
                            wire_lens, (int)nw, fp_now(), expiry_s,
                            (const uint8_t *)tagbuf.buf,
                            (size_t)tagbuf.len,
                            frag_fast != NULL ? frag_ptrs : NULL,
                            frag_fast != NULL ? frag_lens : NULL);
        }
        Py_XDECREF(frag_fast);
    }
    Py_DECREF(fast);
    PyBuffer_Release(&keybuf);
    if (tagbuf.obj != NULL)
        PyBuffer_Release(&tagbuf);
    if (rc < 0)
        return PyErr_NoMemory();
    if (rc == 0)
        Py_RETURN_FALSE;
    Py_RETURN_TRUE;
}

PyObject *
fastpath_zone_put(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule, *bodies;
    PyObject *frags = NULL;
    Py_buffer zkeybuf, tagbuf;
    unsigned long long gen;
    int ancount;
    int arcount = 0;

    if (!PyArg_ParseTuple(args, "Oy*KiOy*|iO", &capsule, &zkeybuf, &gen,
                          &ancount, &bodies, &tagbuf, &arcount, &frags))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    PyObject *fast = c != NULL
        ? PySequence_Fast(bodies, "bodies must be a sequence") : NULL;
    if (fast == NULL) {
        PyBuffer_Release(&zkeybuf);
        PyBuffer_Release(&tagbuf);
        return NULL;
    }
    Py_ssize_t nv = PySequence_Fast_GET_SIZE(fast);
    int rc = 0;
    if (ancount > 0 && ancount <= 0xFFFF
            && arcount >= 0 && arcount <= 0xFFFF
            && nv >= 1 && nv <= FP_MAX_VARIANTS) {
        const uint8_t *body_ptrs[FP_MAX_VARIANTS];
        uint16_t body_lens[FP_MAX_VARIANTS];
        const uint8_t *frag_ptrs[FP_MAX_VARIANTS];
        uint16_t frag_lens[FP_MAX_VARIANTS];
        PyObject *frag_fast = NULL;
        int sizes_ok = 1;
        for (Py_ssize_t i = 0; i < nv; i++) {
            char *data;
            Py_ssize_t dlen;
            if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fast, i),
                                        &data, &dlen) < 0) {
                Py_DECREF(fast);
                PyBuffer_Release(&zkeybuf);
                PyBuffer_Release(&tagbuf);
                return NULL;
            }
            /* what a uint16_t cannot hold is above every bound of
             * fp_zone_put's, which judges the rest */
            if (dlen < 1 || dlen > FP_MAX_STREAM_WIRE) {
                sizes_ok = 0;
                if (dlen >= 1)
                    c->zput_skips[FP_ZSKIP_SIZE]++;
                break;
            }
            body_ptrs[i] = (const uint8_t *)data;
            body_lens[i] = (uint16_t)dlen;
        }
        int frc = sizes_ok
            ? fp_load_frags(frags, nv, FP_MAX_STREAM_WIRE, &frag_fast,
                            frag_ptrs, frag_lens)
            : 1;
        if (frc == -2)
            c->zput_skips[FP_ZSKIP_SIZE]++;
        if (frc == -1) {
            Py_DECREF(fast);
            PyBuffer_Release(&zkeybuf);
            PyBuffer_Release(&tagbuf);
            return NULL;
        }
        if (sizes_ok && frc > 0)
            rc = fp_zone_put(c, zkeybuf.buf, (size_t)zkeybuf.len,
                             (uint64_t)gen, (uint16_t)ancount,
                             (uint16_t)arcount, body_ptrs,
                             body_lens, (int)nv,
                             (const uint8_t *)tagbuf.buf,
                             (size_t)tagbuf.len,
                             frag_fast != NULL ? frag_ptrs : NULL,
                             frag_fast != NULL ? frag_lens : NULL);
        Py_XDECREF(frag_fast);
    }
    Py_DECREF(fast);
    PyBuffer_Release(&zkeybuf);
    PyBuffer_Release(&tagbuf);
    if (rc < 0)
        return PyErr_NoMemory();
    if (rc == 0)
        Py_RETURN_FALSE;
    Py_RETURN_TRUE;
}

PyObject *
fastpath_type_row(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule, *served;
    int rcode;
    Py_buffer fragbuf;

    fragbuf.buf = NULL;
    fragbuf.len = 0;
    fragbuf.obj = NULL;
    if (!PyArg_ParseTuple(args, "OOi|z*", &capsule, &served, &rcode,
                          &fragbuf))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    PyObject *fast = c != NULL
        ? PySequence_Fast(served, "served types must be a sequence") : NULL;
    if (fast == NULL) {
        PyBuffer_Release(&fragbuf);     /* a no-op without a fragment */
        return NULL;
    }
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    uint16_t types[FP_ROW_MAX_TYPES];
    int rc = 0;
    if (n >= 1 && n <= FP_ROW_MAX_TYPES) {
        for (Py_ssize_t i = 0; i < n; i++) {
            long t = PyLong_AsLong(PySequence_Fast_GET_ITEM(fast, i));
            if (t == -1 && PyErr_Occurred()) {
                Py_DECREF(fast);
                PyBuffer_Release(&fragbuf);
                return NULL;
            }
            if (t < 0 || t > 0xFFFF) {
                n = 0;                  /* no such type: skip the put */
                break;
            }
            types[i] = (uint16_t)t;
        }
        if (n > 0)
            rc = fp_type_row_put(c, types, (int)n, rcode,
                                 (const uint8_t *)fragbuf.buf,
                                 (size_t)fragbuf.len);
    }
    Py_DECREF(fast);
    PyBuffer_Release(&fragbuf);
    if (rc < 0)
        return PyErr_NoMemory();
    if (rc == 0)
        Py_RETURN_FALSE;
    Py_RETURN_TRUE;
}

PyObject *
fastpath_serve_wire(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    Py_buffer pkt;
    unsigned long long gen;
    const char *client = NULL;
    const char *proto = "tcp";
    unsigned port = 0;

    if (!PyArg_ParseTuple(args, "Oy*K|sIs", &capsule, &pkt, &gen,
                          &client, &port, &proto))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&pkt);
        return NULL;
    }
    /* FP_MAX_WIRE holds whatever this entry serves: a zone serve above
     * it is FP_VIA_STREAM's alone */
    static uint8_t out[FP_MAX_WIRE];
    uint16_t qtype = 0;
    double t0 = fp_now();
    /* logged posture: the caller must supply the client context or the
     * serve declines inside the core (parity: Python then logs) */
    fp_logsrc_t src = { client, port, proto };
    /* FP_VIA_UNKNOWN: TC responses cached off the UDP path are correct
     * for UDP requesters but must never replay over TCP (Python answers
     * those in full — its cache keys carry transport semantics; this
     * entry point cannot know the transport, so the core declines every
     * truncated wire before any hit accounting, and holds a zone serve
     * to the key's payload).  Its callers are the balancer socket's
     * Python lane and the stream frames the bulk serve never saw; the
     * stream's own ceiling is fastpath_serve_frames' alone, and comes
     * here with the cell that reaches this entry (balancer_fronted,
     * PERF.md section 7). */
    size_t wlen = fp_serve_one_lx(c, pkt.buf, (size_t)pkt.len,
                                  (uint64_t)gen, t0, out, sizeof(out),
                                  &qtype, FP_VIA_UNKNOWN,
                                  client != NULL ? &src : NULL);
    PyBuffer_Release(&pkt);
    if (wlen == 0)
        Py_RETURN_NONE;
    /* same per-qtype accounting as the drain path, so TCP/balancer
     * serves land in the identical Prometheus series at fold time */
    fp_qstat_t *qs = fp_qstat(c, qtype);
    double elapsed = fp_now() - t0;
    qs->count++;
    qs->lat_sum += elapsed;
    qs->lat_cells[fp_bucket_index(c->lat_buckets, c->n_lat_buckets,
                                  elapsed)]++;
    qs->size_sum += (double)wlen;
    qs->size_cells[fp_bucket_index(c->size_buckets, c->n_size_buckets,
                                   (double)wlen)]++;
    return PyBytes_FromStringAndSize((const char *)out,
                                     (Py_ssize_t)wlen);
}

PyObject *
fastpath_serve_frames(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    Py_buffer data;
    unsigned long long gen;
    const char *client = NULL;
    const char *proto = "tcp";
    unsigned port = 0;

    if (!PyArg_ParseTuple(args, "Oy*K|sIs", &capsule, &data, &gen,
                          &client, &port, &proto))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&data);
        return NULL;
    }
    fp_logsrc_t src = { client, port, proto };
    fp_logsrc_t *srcp = client != NULL ? &src : NULL;

    /* responses for every hit in the chunk, RFC 1035 framed, written
     * back with ONE writer call; misses surface as payload bytes for
     * the Python path.  Static arena is safe: the GIL is held for the
     * whole call (like serve_wire's). */
    static uint8_t out[262144];
    size_t out_used = 0;
    size_t consumed = 0;
    /* native-serve: the whole call, misses' surfacing included, as a
     * drained batch's span holds its misses' */
    double t_call = fp_now();
    const uint8_t *p = (const uint8_t *)data.buf;
    size_t n = (size_t)data.len;
    PyObject *misses = PyList_New(0);
    if (misses == NULL) {
        PyBuffer_Release(&data);
        return NULL;
    }

    while (consumed + 2 <= n) {
        size_t flen = ((size_t)p[consumed] << 8) | p[consumed + 1];
        if (flen == 0)
            break;          /* protocol garbage: Python closes the conn */
        if (consumed + 2 + flen > n)
            break;          /* partial frame: caller keeps the tail */
        if (out_used + 2 + FP_MAX_WIRE > sizeof(out))
            break;          /* arena full: caller re-feeds the rest */
        const uint8_t *pkt = p + consumed + 2;
        uint16_t qtype = 0;
        double t0 = fp_now();
        /* FP_VIA_STREAM: a cached TC wire never replays over TCP, and
         * the zone table serves the whole set up to the stream's
         * ceiling, whatever UDP payload the frame's key holds.  The
         * slot is what is left of the arena: an empty arena holds three
         * answers of the stream's bound, and a set longer than what is
         * left behind the answers before it is a miss (Python's) */
        size_t wlen = fp_serve_one_lx(c, pkt, flen, (uint64_t)gen, t0,
                                      out + out_used + 2,
                                      sizeof(out) - out_used - 2, &qtype,
                                      FP_VIA_STREAM, srcp);
        if (wlen == 0) {
            PyObject *payload = PyBytes_FromStringAndSize(
                (const char *)pkt, (Py_ssize_t)flen);
            int rc = payload == NULL ? -1
                : PyList_Append(misses, payload);
            Py_XDECREF(payload);
            if (rc < 0) {
                Py_DECREF(misses);
                PyBuffer_Release(&data);
                return NULL;
            }
        } else {
            out[out_used] = (uint8_t)(wlen >> 8);
            out[out_used + 1] = (uint8_t)(wlen & 0xFF);
            out_used += 2 + wlen;
            /* same per-qtype accounting as serve_wire */
            fp_qstat_t *qs = fp_qstat(c, qtype);
            double elapsed = fp_now() - t0;
            qs->count++;
            qs->lat_sum += elapsed;
            qs->lat_cells[fp_bucket_index(c->lat_buckets,
                                          c->n_lat_buckets, elapsed)]++;
            qs->size_sum += (double)wlen;
            qs->size_cells[fp_bucket_index(c->size_buckets,
                                           c->n_size_buckets,
                                           (double)wlen)]++;
        }
        consumed += 2 + flen;
    }
    PyBuffer_Release(&data);
    PyObject *resp = PyBytes_FromStringAndSize((const char *)out,
                                               (Py_ssize_t)out_used);
    if (resp == NULL) {
        Py_DECREF(misses);
        return NULL;
    }
    if (consumed > 0)
        fastio_span_note(FASTIO_SPAN_SERVE, fp_now() - t_call);
    return Py_BuildValue("(NnN)", resp, (Py_ssize_t)consumed, misses);
}

/* Balancer wire constants (docs/balancer-protocol.md); the Python
 * definitions in binder_tpu/dns/server.py are authoritative. */
#define BAL_HDR 21
#define BAL_VERSION 1
#define BAL_MAX_FRAME 65556
#define BAL_TRANSPORT_UDP 0

/* Flush a direct-return batch on the balancer-owned fd.  Same
 * per-destination tolerance as the drain flush.  Returns 0, or the
 * socket-fatal errno (positive) for the caller to surface. */
static int
bal_flush(int fd, struct mmsghdr *omsgs, int n_hits)
{
    int off = 0;
    while (off < n_hits) {
        double t0 = fp_now();
        int sent = sendmmsg(fd, omsgs + off, (unsigned)(n_hits - off),
                            MSG_DONTWAIT);
        int send_errno = errno;
        fastio_span_note(FASTIO_SPAN_SEND, fp_now() - t0);
        errno = send_errno;
        if (sent >= 0) {
            fastio_io_note_send(sent);
            off += sent > 0 ? sent : 1;
            continue;
        }
        if (errno == EINTR)
            continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
            /* buffer full: drop the rest (UDP), and say how many */
            fastio_io.send_drops[FASTIO_LANE_BALANCER] +=
                (unsigned long long)(n_hits - off);
            return 0;
        }
        if (errno == EBADF || errno == ENOTSOCK || errno == EFAULT ||
            errno == ENOMEM)
            return errno;        /* fatal: caller drops direct mode */
        off += 1;                /* per-destination failure: skip one */
    }
    return 0;
}

PyObject *
fastpath_serve_balancer(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    Py_buffer data;
    unsigned long long gen;
    int fd;

    if (!PyArg_ParseTuple(args, "Oy*Ki", &capsule, &data, &gen, &fd))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&data);
        return NULL;
    }

    /* direct server return: every UDP-transport hit in the chunk is
     * answered straight onto the balancer's client-facing socket (the
     * passed fd) with the client sockaddr from the frame as msg_name —
     * the reply never re-enters the balancer process.  Everything else
     * (misses, control frames, TCP transport, unknown versions)
     * surfaces as raw frames for the Python lane. */
    static uint8_t outs[FP_BATCH][FP_MAX_WIRE];
    struct mmsghdr omsgs[FP_BATCH];
    struct iovec oiovs[FP_BATCH];
    struct sockaddr_storage oaddrs[FP_BATCH];
    int n_hits = 0;
    long served = 0;
    int fatal_errno = 0;
    memset(omsgs, 0, sizeof(omsgs));

    PyObject *misses = PyList_New(0);
    if (misses == NULL) {
        PyBuffer_Release(&data);
        return NULL;
    }

    const uint8_t *p = (const uint8_t *)data.buf;
    size_t n = (size_t)data.len;
    size_t consumed = 0;

    while (fatal_errno == 0 && consumed + 4 <= n) {
        size_t flen = ((size_t)p[consumed] << 24)
                    | ((size_t)p[consumed + 1] << 16)
                    | ((size_t)p[consumed + 2] << 8)
                    | (size_t)p[consumed + 3];
        if (flen < BAL_HDR || flen > BAL_MAX_FRAME)
            break;          /* protocol garbage: Python closes the link */
        if (consumed + 4 + flen > n)
            break;          /* partial frame: caller keeps the tail */
        const uint8_t *fr = p + consumed + 4;
        uint8_t version = fr[0], family = fr[1], transport = fr[2];
        const uint8_t *addr = fr + 3;
        uint16_t port = (uint16_t)(((uint16_t)fr[19] << 8) | fr[20]);
        const uint8_t *pkt = fr + BAL_HDR;
        size_t plen = flen - BAL_HDR;

        size_t wlen = 0;
        uint16_t qtype = 0;
        double t0 = fp_now();
        if (version == BAL_VERSION && (family == 4 || family == 6)
                && transport == BAL_TRANSPORT_UDP && plen >= 12) {
            /* logged posture: stringify the frame's client so the core
             * can emit its line (only when the ring is armed) */
            char client[INET6_ADDRSTRLEN];
            fp_logsrc_t src = { NULL, port, "udp" };
            if (c->lr.enabled
                    && inet_ntop(family == 4 ? AF_INET : AF_INET6, addr,
                                 client, sizeof(client)) != NULL)
                src.client = client;
            /* the transport is known UDP (TCP-transport frames never
             * get here: they surface to Python above), so truncated
             * wires replay exactly as on the direct UDP drain */
            wlen = fp_serve_one_lx(c, pkt, plen, (uint64_t)gen, t0,
                                   outs[n_hits], sizeof(outs[n_hits]),
                                   &qtype, FP_VIA_DATAGRAM,
                                   src.client != NULL ? &src : NULL);
        }
        if (wlen == 0) {
            PyObject *raw = PyBytes_FromStringAndSize(
                (const char *)fr, (Py_ssize_t)flen);
            int rc = raw == NULL ? -1 : PyList_Append(misses, raw);
            Py_XDECREF(raw);
            if (rc < 0) {
                Py_DECREF(misses);
                PyBuffer_Release(&data);
                return NULL;
            }
        } else {
            struct sockaddr_storage *ss = &oaddrs[n_hits];
            socklen_t alen;
            memset(ss, 0, sizeof(*ss));
            if (family == 4) {
                struct sockaddr_in *sa = (struct sockaddr_in *)ss;
                sa->sin_family = AF_INET;
                memcpy(&sa->sin_addr, addr, 4);
                sa->sin_port = htons(port);
                alen = sizeof(*sa);
            } else {
                struct sockaddr_in6 *sa6 = (struct sockaddr_in6 *)ss;
                sa6->sin6_family = AF_INET6;
                memcpy(&sa6->sin6_addr, addr, 16);
                sa6->sin6_port = htons(port);
                alen = sizeof(*sa6);
            }
            oiovs[n_hits].iov_base = outs[n_hits];
            oiovs[n_hits].iov_len = wlen;
            omsgs[n_hits].msg_hdr.msg_iov = &oiovs[n_hits];
            omsgs[n_hits].msg_hdr.msg_iovlen = 1;
            omsgs[n_hits].msg_hdr.msg_name = ss;
            omsgs[n_hits].msg_hdr.msg_namelen = alen;
            n_hits++;
            served++;
            /* same per-qtype accounting as serve_wire */
            fp_qstat_t *qs = fp_qstat(c, qtype);
            double elapsed = fp_now() - t0;
            qs->count++;
            /* a cached wire that was truncated for this posture */
            qs->truncated += (outs[n_hits - 1][2] & 0x02) != 0;
            qs->lat_sum += elapsed;
            qs->lat_cells[fp_bucket_index(c->lat_buckets,
                                          c->n_lat_buckets, elapsed)]++;
            qs->size_sum += (double)wlen;
            qs->size_cells[fp_bucket_index(c->size_buckets,
                                           c->n_size_buckets,
                                           (double)wlen)]++;
            if (n_hits == FP_BATCH) {
                fatal_errno = bal_flush(fd, omsgs, n_hits);
                n_hits = 0;
                memset(omsgs, 0, sizeof(omsgs));
            }
        }
        consumed += 4 + flen;
    }
    if (fatal_errno == 0 && n_hits > 0)
        fatal_errno = bal_flush(fd, omsgs, n_hits);
    PyBuffer_Release(&data);
    if (fatal_errno != 0) {
        Py_DECREF(misses);
        errno = fatal_errno;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    return Py_BuildValue("(nlN)", (Py_ssize_t)consumed, served, misses);
}

PyObject *
fastpath_zone_reserve(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    unsigned long entries;

    if (!PyArg_ParseTuple(args, "Ok", &capsule, &entries))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL)
        return NULL;
    if (entries > FP_ZONE_MAX_SLOTS)
        entries = FP_ZONE_MAX_SLOTS;
    if (fp_zone_reserve(c, &c->zmain, (uint32_t)entries) != 0)
        return PyErr_NoMemory();
    Py_RETURN_NONE;
}

PyObject *
fastpath_invalidate(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    Py_buffer tagbuf;

    if (!PyArg_ParseTuple(args, "Oy*", &capsule, &tagbuf))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&tagbuf);
        return NULL;
    }
    uint32_t n = fp_invalidate_tag(c, tagbuf.buf, (size_t)tagbuf.len);
    PyBuffer_Release(&tagbuf);
    return PyLong_FromUnsignedLong((unsigned long)n);
}

PyObject *
fastpath_drain(PyObject *self, PyObject *args)
{
    (void)self;
    int fd, max_n = FP_BATCH;
    PyObject *capsule;
    unsigned long long gen;

    if (!PyArg_ParseTuple(args, "OiK|i", &capsule, &fd, &gen, &max_n))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL)
        return NULL;
    if (max_n < 1) max_n = 1;
    if (max_n > FP_BATCH) max_n = FP_BATCH;

    /* receive arena shared with recv_batch (GIL-serialized); the
     * response arena is fast-path-only */
    unsigned char (*bufs)[FASTIO_DGRAM_MAX] = fastio_shared_bufs;
    static unsigned char outs[FP_BATCH][FP_MAX_WIRE];
    struct mmsghdr msgs[FP_BATCH];
    struct iovec iovs[FP_BATCH];
    struct sockaddr_storage addrs[FP_BATCH];

    memset(msgs, 0, sizeof(struct mmsghdr) * (size_t)max_n);
    for (int i = 0; i < max_n; i++) {
        iovs[i].iov_base = bufs[i];
        iovs[i].iov_len = FASTIO_DGRAM_MAX;
        msgs[i].msg_hdr.msg_iov = &iovs[i];
        msgs[i].msg_hdr.msg_iovlen = 1;
        msgs[i].msg_hdr.msg_name = &addrs[i];
        msgs[i].msg_hdr.msg_namelen = sizeof(addrs[i]);
    }

    /* the time ledger's three leaf spans of a batch: udp-recv is the
     * recvmmsg (an EAGAIN costs the same crossing and counts),
     * native-serve runs from there to the flush, udp-send is each
     * sendmmsg; the clock is read where one ends and the next begins */
    double t0 = fp_now();
    int n = recvmmsg(fd, msgs, (unsigned)max_n, MSG_DONTWAIT, NULL);
    int recv_errno = errno;
    double t_recv = fp_now();
    fastio_span_note(FASTIO_SPAN_RECV, t_recv - t0);
    errno = recv_errno;
    if (n < 0) {
        if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR) {
            PyObject *empty = PyList_New(0);
            if (empty == NULL)
                return NULL;
            return Py_BuildValue("(Niii)", empty, 0, 0, 0);
        }
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    fastio_io_note_recv(n);

    PyObject *misses = PyList_New(0);
    if (misses == NULL)
        return NULL;

    struct mmsghdr omsgs[FP_BATCH];
    struct iovec oiovs[FP_BATCH];
    int n_hits = 0;
    int batch_qtype_counts[FP_MAX_QTYPES];
    memset(batch_qtype_counts, 0, sizeof(batch_qtype_counts));
    memset(omsgs, 0, sizeof(omsgs[0]) * (size_t)(n > 0 ? n : 1));

    for (int i = 0; i < n; i++) {
        const uint8_t *pkt = bufs[i];
        size_t plen = msgs[i].msg_len;
        uint16_t entry_qtype = 0;
        uint8_t *out = outs[n_hits];

        /* logged posture: stringify this packet's source so the core
         * can emit its log line (only when the ring is armed) */
        char client[INET6_ADDRSTRLEN];
        fp_logsrc_t src = { NULL, 0, "udp" };
        if (c->lr.enabled) {
            const struct sockaddr_storage *ss = &addrs[i];
            if (ss->ss_family == AF_INET) {
                const struct sockaddr_in *sa =
                    (const struct sockaddr_in *)ss;
                if (inet_ntop(AF_INET, &sa->sin_addr, client,
                              sizeof(client)) != NULL) {
                    src.client = client;
                    src.port = ntohs(sa->sin_port);
                }
            } else if (ss->ss_family == AF_INET6) {
                const struct sockaddr_in6 *sa6 =
                    (const struct sockaddr_in6 *)ss;
                if (inet_ntop(AF_INET6, &sa6->sin6_addr, client,
                              sizeof(client)) != NULL) {
                    src.client = client;
                    src.port = ntohs(sa6->sin6_port);
                }
            }
        }
        size_t wlen = fp_serve_one_lx(c, pkt, plen, (uint64_t)gen, t0,
                                      out, sizeof(outs[0]), &entry_qtype,
                                      FP_VIA_DATAGRAM,
                                      src.client != NULL ? &src : NULL);
        if (wlen == 0) {
            /* miss: surface to Python exactly like recv_batch */
            if (surface_miss(misses, pkt, plen, &addrs[i]) < 0) {
                Py_DECREF(misses);
                return NULL;
            }
            continue;
        }

        oiovs[n_hits].iov_base = out;
        oiovs[n_hits].iov_len = wlen;
        omsgs[n_hits].msg_hdr.msg_iov = &oiovs[n_hits];
        omsgs[n_hits].msg_hdr.msg_iovlen = 1;
        omsgs[n_hits].msg_hdr.msg_name = &addrs[i];
        omsgs[n_hits].msg_hdr.msg_namelen = msgs[i].msg_hdr.msg_namelen;
        n_hits++;

        fp_qstat_t *qs = fp_qstat(c, entry_qtype);
        /* the answer cache's entries are keyed by posture: one promoted
         * from a truncated answer is served truncated */
        qs->truncated += (out[2] & 0x02) != 0;
        qs->size_sum += (double)wlen;
        qs->size_cells[fp_bucket_index(c->size_buckets,
                                       c->n_size_buckets,
                                       (double)wlen)]++;
        batch_qtype_counts[(int)(qs - c->qstats)]++;
    }

    double t_sent = t_recv;
    if (n > 0) {
        t_sent = fp_now();
        fastio_span_note(FASTIO_SPAN_SERVE, t_sent - t_recv);
    }

    /* flush hits; per-destination errors skip one datagram and continue
     * (same policy as send_batch — one unreachable client must not drop
     * other clients' responses).  A full send buffer gets the rest one
     * more try, as the Python lanes give theirs; what is still left is
     * dropped (UDP clients retransmit, and blocking here would stall
     * every other client), counted, and reported with the retry: the
     * caller sends no more on this socket before the loop has turned */
    int off = 0, retried = 0, dropped = 0;
    while (off < n_hits) {
        double t_send = t_sent;     /* where the last span ended */
        int sent = sendmmsg(fd, omsgs + off, (unsigned)(n_hits - off),
                            MSG_DONTWAIT);
        int send_errno = errno;
        t_sent = fp_now();
        fastio_span_note(FASTIO_SPAN_SEND, t_sent - t_send);
        errno = send_errno;
        if (sent >= 0) {
            fastio_io_note_send(sent);
            off += sent > 0 ? sent : 1;
            continue;
        }
        if (errno == EINTR)
            continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
            if (retried == 0) {
                retried = n_hits - off;
                continue;
            }
            dropped = n_hits - off;
            fastio_io.send_drops[FASTIO_LANE_NATIVE] +=
                (unsigned long long)dropped;
            break;
        }
        if (errno == EBADF || errno == ENOTSOCK || errno == EFAULT ||
            errno == ENOMEM) {
            Py_DECREF(misses);
            return PyErr_SetFromErrno(PyExc_OSError);
        }
        off += 1;                       /* per-destination failure */
    }

    /* latency: the whole batch window, attributed to each hit — an
     * upper bound (a hit waited at most recv..send of its batch) */
    if (n_hits > 0) {
        double elapsed = t_sent - t0;
        int li = fp_bucket_index(c->lat_buckets, c->n_lat_buckets,
                                 elapsed);
        for (int s = 0; s < FP_MAX_QTYPES; s++) {
            int cnt = batch_qtype_counts[s];
            if (cnt > 0) {
                c->qstats[s].count += (uint64_t)cnt;
                c->qstats[s].lat_sum += elapsed * (double)cnt;
                c->qstats[s].lat_cells[li] += (uint64_t)cnt;
            }
        }
    }

    return Py_BuildValue("(Niii)", misses, n_hits, retried, dropped);
}

PyObject *
fastpath_invalidate_many(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule, *tags;

    if (!PyArg_ParseTuple(args, "OO", &capsule, &tags))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    PyObject *fast = c != NULL
        ? PySequence_Fast(tags, "tags must be a sequence") : NULL;
    if (fast == NULL)
        return NULL;
    Py_ssize_t total = PySequence_Fast_GET_SIZE(fast);
    if (total > INT_MAX)
        total = INT_MAX;
    /* borrow all tag pointers once; fp_invalidate_tags chunks oversize
     * batches internally (stack arrays cover the common event sizes) */
    const uint8_t *stack_ptrs[FP_INVAL_BATCH];
    size_t stack_lens[FP_INVAL_BATCH];
    const uint8_t **tag_ptrs = stack_ptrs;
    size_t *tag_lens = stack_lens;
    if (total > FP_INVAL_BATCH) {
        tag_ptrs = (const uint8_t **)malloc(
            (size_t)total * sizeof(*tag_ptrs));
        tag_lens = (size_t *)malloc((size_t)total * sizeof(*tag_lens));
        if (tag_ptrs == NULL || tag_lens == NULL) {
            free((void *)tag_ptrs == (void *)stack_ptrs ? NULL
                 : (void *)tag_ptrs);
            free(tag_lens == stack_lens ? NULL : (void *)tag_lens);
            Py_DECREF(fast);
            return PyErr_NoMemory();
        }
    }
    for (Py_ssize_t i = 0; i < total; i++) {
        char *data;
        Py_ssize_t dlen;
        if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(fast, i),
                                    &data, &dlen) < 0) {
            if (tag_ptrs != stack_ptrs) {
                free((void *)tag_ptrs);
                free(tag_lens);
            }
            Py_DECREF(fast);
            return NULL;
        }
        tag_ptrs[i] = (const uint8_t *)data;
        tag_lens[i] = (size_t)dlen;
    }
    unsigned long dropped = fp_invalidate_tags(c, tag_ptrs, tag_lens,
                                               (int)total);
    if (tag_ptrs != stack_ptrs) {
        free((void *)tag_ptrs);
        free(tag_lens);
    }
    Py_DECREF(fast);
    return PyLong_FromUnsignedLong(dropped);
}

PyObject *
fastpath_log_enable(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;
    Py_buffer prefix;
    unsigned long cap = 1u << 20;

    if (!PyArg_ParseTuple(args, "Oy*|k", &capsule, &prefix, &cap))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL) {
        PyBuffer_Release(&prefix);
        return NULL;
    }
    int rc = fp_log_enable(c, (const uint8_t *)prefix.buf,
                           (size_t)prefix.len, (size_t)cap);
    PyBuffer_Release(&prefix);
    if (rc < 0) {
        PyErr_SetString(PyExc_ValueError,
                        "log ring enable failed (prefix/capacity)");
        return NULL;
    }
    Py_RETURN_NONE;
}

PyObject *
fastpath_log_drain(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;

    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL)
        return NULL;
    if (!c->lr.enabled || c->lr.len == 0)
        return PyBytes_FromStringAndSize(NULL, 0);
    PyObject *out = PyBytes_FromStringAndSize((const char *)c->lr.buf,
                                              (Py_ssize_t)c->lr.len);
    if (out == NULL)
        return NULL;
    c->lr.len = 0;
    return out;
}

PyObject *
fastpath_stats(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;

    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL)
        return NULL;

    PyObject *per = PyDict_New();
    if (per == NULL)
        return NULL;
    for (int i = 0; i < c->n_qstats; i++) {
        fp_qstat_t *s = &c->qstats[i];
        PyObject *lat = PyTuple_New(c->n_lat_buckets + 1);
        PyObject *sz = PyTuple_New(c->n_size_buckets + 1);
        if (lat == NULL || sz == NULL) {
            Py_XDECREF(lat);
            Py_XDECREF(sz);
            Py_DECREF(per);
            return NULL;
        }
        for (int b = 0; b <= c->n_lat_buckets; b++)
            PyTuple_SET_ITEM(lat, b,
                             PyLong_FromUnsignedLongLong(s->lat_cells[b]));
        for (int b = 0; b <= c->n_size_buckets; b++)
            PyTuple_SET_ITEM(sz, b,
                             PyLong_FromUnsignedLongLong(s->size_cells[b]));
        PyObject *d = Py_BuildValue(
            "{s:K,s:K,s:d,s:N,s:d,s:N}",
            "count", (unsigned long long)s->count,
            "truncated", (unsigned long long)s->truncated,
            "lat_sum", s->lat_sum, "lat_cells", lat,
            "size_sum", s->size_sum, "size_cells", sz);
        if (d == NULL) {
            Py_DECREF(per);
            return NULL;
        }
        PyObject *k = PyLong_FromLong((long)s->qtype);
        int rc = k == NULL ? -1 : PyDict_SetItem(per, k, d);
        Py_XDECREF(k);
        Py_DECREF(d);
        if (rc < 0) {
            Py_DECREF(per);
            return NULL;
        }
    }
    return Py_BuildValue(
        "{s:K,s:K,s:I,s:K,s:K,s:K,s:K,s:I,s:K,s:K,s:K,s:K,s:K,s:K,s:N}",
        "hits", (unsigned long long)c->hits,
        "lookups", (unsigned long long)c->lookups,
        "entries", (unsigned)c->n_entries,
        "bytes", (unsigned long long)c->total_bytes,
        "invalidations", (unsigned long long)c->invalidations,
        "zone_hits", (unsigned long long)c->zone_hits,
        "zone_type_hits", (unsigned long long)c->zone_type_hits,
        "zone_entries", (unsigned)(c->zmain.n + c->zalien.n),
        "zone_bytes", (unsigned long long)c->ztotal_bytes,
        "zone_put_skips_size",
        (unsigned long long)c->zput_skips[FP_ZSKIP_SIZE],
        "zone_put_skips_bytes",
        (unsigned long long)c->zput_skips[FP_ZSKIP_BYTES],
        "log_lines", (unsigned long long)c->lr.lines,
        "log_declines", (unsigned long long)c->lr.declines,
        "log_pending", (unsigned long long)c->lr.len,
        "per_qtype", per);
}

PyObject *
fastpath_clear(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *capsule;

    if (!PyArg_ParseTuple(args, "O", &capsule))
        return NULL;
    fp_cache_t *c = fp_from_capsule(capsule);
    if (c == NULL)
        return NULL;
    fp_core_clear(c);
    Py_RETURN_NONE;
}
