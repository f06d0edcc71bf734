/*
 * _binderfastio — batched UDP syscalls for the DNS hot path.
 *
 * The reference's hot path is one recvfrom + one sendto per query inside
 * the Node event loop (via the mname engine); per-packet syscall and
 * event-loop costs are the floor of its throughput.  This extension
 * lowers that floor for the rebuild: recvmmsg(2)/sendmmsg(2) move up to
 * BATCH datagrams per kernel crossing, which matters on the single-core
 * deployment unit (reference scales by adding processes, not threads —
 * boot/setup.sh:145-149 — so per-process efficiency is the multiplier).
 *
 * API (IPv4 + IPv6 UDP sockets, non-blocking):
 *   recv_batch(fd, max_n)  -> list[(bytes payload, (str host, int port))]
 *                             empty list when the socket would block
 *   send_batch(fd, msgs)   -> int processed count; msgs is a sequence of
 *                             (bytes payload, addr) where addr is
 *                             (host, port), for IPv6 optionally
 *                             (host, port, flowinfo, scope_id), or
 *                             None on a connected socket.
 *                             Per-destination errors (EHOSTUNREACH,
 *                             EPERM, ...) skip that one datagram and
 *                             continue — one unreachable client must not
 *                             drop other clients' responses (same
 *                             tolerance as the per-packet sendto path,
 *                             reference lib/server.js:593-607).  Only
 *                             EAGAIN stops early; caller retries or
 *                             drops the remainder (UDP best effort).
 *
 * Pure CPython C API (no pybind11 in this image; see repo NOTES.md).
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <arpa/inet.h>
#include <errno.h>
#include <netinet/in.h>
#include <string.h>
#include <sys/socket.h>
#include <time.h>

#include "fastpath.h"

unsigned char fastio_shared_bufs[FASTIO_BATCH][FASTIO_DGRAM_MAX];

fastio_io_t fastio_io;
double fastio_span_grid[FASTIO_SPAN_MAX_BUCKETS];
int fastio_span_grid_n;

/* CLOCK_MONOTONIC in seconds: fp_now() of fpcore.h, which this file
 * does not include */
static inline double
fastio_now(void)
{
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

/* stage label of each span, in the enum's order */
static const char *const fastio_span_names[FASTIO_N_SPANS] = {
    "udp-recv", "native-serve", "udp-send",
};

PyObject *
fastio_addr_to_tuple(const struct sockaddr_storage *ss)
{
    char host[INET6_ADDRSTRLEN];

    if (ss->ss_family == AF_INET) {
        const struct sockaddr_in *sa = (const struct sockaddr_in *)ss;
        if (inet_ntop(AF_INET, &sa->sin_addr, host, sizeof(host)) == NULL)
            return NULL;
        return Py_BuildValue("(sI)", host, (unsigned)ntohs(sa->sin_port));
    }
    if (ss->ss_family == AF_INET6) {
        /* Python's 4-tuple form, keeping flowinfo and the scope id —
         * without the scope id, replies to link-local (fe80::) clients
         * cannot be routed */
        const struct sockaddr_in6 *sa6 = (const struct sockaddr_in6 *)ss;
        if (inet_ntop(AF_INET6, &sa6->sin6_addr, host, sizeof(host)) == NULL)
            return NULL;
        return Py_BuildValue("(sIII)", host,
                             (unsigned)ntohs(sa6->sin6_port),
                             (unsigned)ntohl(sa6->sin6_flowinfo),
                             (unsigned)sa6->sin6_scope_id);
    }
    PyErr_Format(PyExc_OSError, "unsupported address family %d",
                 (int)ss->ss_family);
    return NULL;
}

static int
tuple_to_addr(PyObject *addr, struct sockaddr_storage *ss, socklen_t *len)
{
    const char *host;
    unsigned port;
    unsigned flowinfo = 0, scope_id = 0;

    if (addr == Py_None) {      /* a connected socket's own peer */
        *len = 0;
        return 0;
    }
    if (!PyTuple_Check(addr)) {
        PyErr_SetString(PyExc_TypeError,
                        "address must be (host, port[, flowinfo, scope_id])");
        return -1;
    }
    if (!PyArg_ParseTuple(addr, "sI|II;address must be (host, port"
                          "[, flowinfo, scope_id])",
                          &host, &port, &flowinfo, &scope_id))
        return -1;
    memset(ss, 0, sizeof(*ss));
    if (strchr(host, ':') != NULL) {
        struct sockaddr_in6 *sa6 = (struct sockaddr_in6 *)ss;
        sa6->sin6_family = AF_INET6;
        sa6->sin6_port = htons((uint16_t)port);
        sa6->sin6_flowinfo = htonl(flowinfo);
        sa6->sin6_scope_id = scope_id;
        if (inet_pton(AF_INET6, host, &sa6->sin6_addr) != 1) {
            PyErr_Format(PyExc_ValueError, "bad IPv6 address %s", host);
            return -1;
        }
        *len = sizeof(*sa6);
    } else {
        struct sockaddr_in *sa = (struct sockaddr_in *)ss;
        sa->sin_family = AF_INET;
        sa->sin_port = htons((uint16_t)port);
        if (inet_pton(AF_INET, host, &sa->sin_addr) != 1) {
            PyErr_Format(PyExc_ValueError, "bad IPv4 address %s", host);
            return -1;
        }
        *len = sizeof(*sa);
    }
    return 0;
}

static PyObject *
fastio_recv_batch(PyObject *self, PyObject *args)
{
    int fd;
    int max_n = FASTIO_BATCH;
    (void)self;

    if (!PyArg_ParseTuple(args, "i|i", &fd, &max_n))
        return NULL;
    if (max_n < 1) max_n = 1;
    if (max_n > FASTIO_BATCH) max_n = FASTIO_BATCH;

    /* shared payload arena reused across calls; safe because the GIL is
     * held for the whole call (MSG_DONTWAIT never blocks, so there is
     * nothing to gain from releasing it) */
    unsigned char (*bufs)[FASTIO_DGRAM_MAX] = fastio_shared_bufs;
    struct mmsghdr msgs[FASTIO_BATCH];
    struct iovec iovs[FASTIO_BATCH];
    struct sockaddr_storage addrs[FASTIO_BATCH];

    memset(msgs, 0, sizeof(struct mmsghdr) * (size_t)max_n);
    for (int i = 0; i < max_n; i++) {
        iovs[i].iov_base = bufs[i];
        iovs[i].iov_len = FASTIO_DGRAM_MAX;
        msgs[i].msg_hdr.msg_iov = &iovs[i];
        msgs[i].msg_hdr.msg_iovlen = 1;
        msgs[i].msg_hdr.msg_name = &addrs[i];
        msgs[i].msg_hdr.msg_namelen = sizeof(addrs[i]);
    }

    double t0 = fastio_now();
    int n = recvmmsg(fd, msgs, (unsigned)max_n, MSG_DONTWAIT, NULL);
    int recv_errno = errno;
    /* an empty-handed call costs the same kernel crossing: it counts */
    fastio_span_note(FASTIO_SPAN_RECV, fastio_now() - t0);
    errno = recv_errno;

    if (n < 0) {
        if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR)
            return PyList_New(0);
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    fastio_io_note_recv(n);

    PyObject *out = PyList_New(n);
    if (out == NULL)
        return NULL;
    for (int i = 0; i < n; i++) {
        PyObject *payload = PyBytes_FromStringAndSize(
            (const char *)bufs[i], (Py_ssize_t)msgs[i].msg_len);
        PyObject *addr = payload ? fastio_addr_to_tuple(&addrs[i]) : NULL;
        if (payload == NULL || addr == NULL) {
            Py_XDECREF(payload);
            Py_XDECREF(addr);
            Py_DECREF(out);
            return NULL;
        }
        PyObject *item = PyTuple_Pack(2, payload, addr);
        Py_DECREF(payload);
        Py_DECREF(addr);
        if (item == NULL) {
            Py_DECREF(out);
            return NULL;
        }
        PyList_SET_ITEM(out, i, item);
    }
    return out;
}

static PyObject *
fastio_send_batch(PyObject *self, PyObject *args)
{
    int fd;
    PyObject *seq;
    (void)self;

    if (!PyArg_ParseTuple(args, "iO", &fd, &seq))
        return NULL;
    PyObject *fast = PySequence_Fast(seq, "msgs must be a sequence");
    if (fast == NULL)
        return NULL;
    Py_ssize_t total = PySequence_Fast_GET_SIZE(fast);
    Py_ssize_t done = 0;

    while (done < total) {
        struct mmsghdr msgs[FASTIO_BATCH];
        struct iovec iovs[FASTIO_BATCH];
        struct sockaddr_storage addrs[FASTIO_BATCH];
        int n = 0;

        memset(msgs, 0, sizeof(msgs[0]) * FASTIO_BATCH);
        for (; n < FASTIO_BATCH && done + n < total; n++) {
            PyObject *item = PySequence_Fast_GET_ITEM(fast, done + n);
            PyObject *payload, *addr;
            char *data;
            Py_ssize_t dlen;

            if (!PyTuple_Check(item) || PyTuple_GET_SIZE(item) != 2) {
                PyErr_SetString(PyExc_TypeError,
                                "each msg must be (bytes, (host, port))");
                goto fail;
            }
            payload = PyTuple_GET_ITEM(item, 0);
            addr = PyTuple_GET_ITEM(item, 1);
            if (PyBytes_AsStringAndSize(payload, &data, &dlen) < 0)
                goto fail;
            socklen_t alen;
            if (tuple_to_addr(addr, &addrs[n], &alen) < 0)
                goto fail;
            iovs[n].iov_base = data;
            iovs[n].iov_len = (size_t)dlen;
            msgs[n].msg_hdr.msg_iov = &iovs[n];
            msgs[n].msg_hdr.msg_iovlen = 1;
            msgs[n].msg_hdr.msg_name = alen ? &addrs[n] : NULL;
            msgs[n].msg_hdr.msg_namelen = alen;
        }

        /* drain this parsed chunk without rebuilding it: `off` advances
         * past sent and skipped datagrams so a run of failing
         * destinations costs one syscall each, not a chunk re-parse */
        int off = 0;
        int blocked = 0;
        while (off < n) {
            int sent, send_errno;
            double t0 = fastio_now(), t1;
            Py_BEGIN_ALLOW_THREADS
            sent = sendmmsg(fd, msgs + off, (unsigned)(n - off),
                            MSG_DONTWAIT);
            send_errno = errno;
            t1 = fastio_now();
            Py_END_ALLOW_THREADS
            fastio_span_note(FASTIO_SPAN_SEND, t1 - t0);
            errno = send_errno;
            if (sent >= 0) {
                /* a short count means msgs[off+sent] hit an error; the
                 * next pass re-sends from there and classifies it */
                fastio_io_note_send(sent);
                off += sent > 0 ? sent : 1;
                continue;
            }
            if (errno == EINTR)
                continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                blocked = 1;  /* buffer full: caller retries/drops rest */
                break;
            }
            if (errno == EBADF || errno == ENOTSOCK || errno == EFAULT ||
                errno == ENOMEM) {
                /* socket-fatal, not per-destination: surface it rather
                 * than mislabel the batch as delivered */
                Py_DECREF(fast);
                return PyErr_SetFromErrno(PyExc_OSError);
            }
            /* per-destination failure on the first datagram of the
             * remainder (EHOSTUNREACH/EPERM/EINVAL-bad-port/...): skip
             * that one datagram and carry on — one unreachable client
             * must not discard every other client's response */
            off += 1;
        }
        done += off;
        if (blocked)
            break;
    }
    Py_DECREF(fast);
    return PyLong_FromSsize_t(done);

fail:
    Py_DECREF(fast);
    return NULL;
}

static PyObject *
fastio_cells_list(const unsigned long long *cells, int n)
{
    PyObject *out = PyList_New(n);
    if (out == NULL)
        return NULL;
    for (int i = 0; i < n; i++) {
        PyObject *v = PyLong_FromUnsignedLongLong(cells[i]);
        if (v == NULL) {
            Py_DECREF(out);
            return NULL;
        }
        PyList_SET_ITEM(out, i, v);
    }
    return out;
}

static PyObject *
fastio_io_stats(PyObject *self, PyObject *args)
{
    int reset = 0;
    (void)self;

    if (!PyArg_ParseTuple(args, "|p", &reset))
        return NULL;
    PyObject *spans = PyDict_New();
    if (spans == NULL)
        return NULL;
    for (int i = 0; i < FASTIO_N_SPANS; i++) {
        const fastio_span_t *sp = &fastio_io.spans[i];
        PyObject *cells = fastio_cells_list(sp->cells,
                                            fastio_span_grid_n + 1);
        PyObject *d = cells == NULL ? NULL : Py_BuildValue(
            "{s:d,s:K,s:N}", "sum", sp->sum, "count", sp->count,
            "cells", cells);
        int rc = d == NULL ? -1
            : PyDict_SetItemString(spans, fastio_span_names[i], d);
        Py_XDECREF(d);
        if (rc < 0) {
            Py_DECREF(spans);
            return NULL;
        }
    }
    PyObject *cells = fastio_cells_list(fastio_io.recv_cells,
                                        FASTIO_IO_CELLS);
    if (cells == NULL) {
        Py_DECREF(spans);
        return NULL;
    }
    PyObject *d = Py_BuildValue(
        "{s:K,s:K,s:K,s:K,s:{s:K,s:K},s:N,s:N}",
        "recv_calls", fastio_io.recv_calls,
        "recv_msgs", fastio_io.recv_msgs,
        "send_calls", fastio_io.send_calls,
        "send_msgs", fastio_io.send_msgs,
        "send_drops",
        "native", fastio_io.send_drops[FASTIO_LANE_NATIVE],
        "balancer", fastio_io.send_drops[FASTIO_LANE_BALANCER],
        "recv_cells", cells,
        "spans", spans);
    if (d == NULL)
        return NULL;
    if (reset)
        memset(&fastio_io, 0, sizeof(fastio_io));
    return d;
}

static PyObject *
fastio_io_span_grid(PyObject *self, PyObject *args)
{
    PyObject *seq;
    (void)self;

    if (!PyArg_ParseTuple(args, "O", &seq))
        return NULL;
    PyObject *fast = PySequence_Fast(seq, "grid must be a sequence");
    if (fast == NULL)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    double grid[FASTIO_SPAN_MAX_BUCKETS];
    if (n > FASTIO_SPAN_MAX_BUCKETS) {
        Py_DECREF(fast);
        PyErr_Format(PyExc_ValueError, "too many span buckets (max %d)",
                     FASTIO_SPAN_MAX_BUCKETS);
        return NULL;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        grid[i] = PyFloat_AsDouble(PySequence_Fast_GET_ITEM(fast, i));
        if ((grid[i] == -1.0 && PyErr_Occurred())
                || (i > 0 && grid[i] <= grid[i - 1])) {
            Py_DECREF(fast);
            if (!PyErr_Occurred())
                PyErr_SetString(PyExc_ValueError,
                                "span buckets must be strictly "
                                "increasing");
            return NULL;
        }
    }
    Py_DECREF(fast);
    /* every server of a process hands over the same grid; only a
     * different one restarts the cells (a fold skips the step back) */
    if ((int)n != fastio_span_grid_n
            || memcmp(grid, fastio_span_grid,
                      (size_t)n * sizeof(double)) != 0) {
        memcpy(fastio_span_grid, grid, (size_t)n * sizeof(double));
        fastio_span_grid_n = (int)n;
        memset(fastio_io.spans, 0, sizeof(fastio_io.spans));
    }
    Py_RETURN_NONE;
}

static PyMethodDef fastio_methods[] = {
    {"recv_batch", fastio_recv_batch, METH_VARARGS,
     "recv_batch(fd, max_n=64) -> list[(bytes, (host, port))]"},
    {"send_batch", fastio_send_batch, METH_VARARGS,
     "send_batch(fd, msgs) -> int sent"},
    {"io_stats", fastio_io_stats, METH_VARARGS,
     "io_stats(reset=False) -> dict of process-wide batched-I/O "
     "counters (recvmmsg/sendmmsg calls, messages, the answers the C "
     "lanes dropped at a full send buffer, the recvmmsg batch-size "
     "log2 histogram) and the time ledger's spans "
     "(udp-recv, native-serve, udp-send: sum, count, cells)"},
    {"io_span_grid", fastio_io_span_grid, METH_VARARGS,
     "io_span_grid(buckets) -> None; the stage histogram's upper "
     "bounds, handed over once at start"},
    {"fastpath_new", fastpath_new, METH_VARARGS,
     "fastpath_new(size, expiry_ms, lat_buckets, size_buckets) -> capsule"},
    {"fastpath_put", fastpath_put, METH_VARARGS,
     "fastpath_put(cache, key, qtype, gen, wires) -> bool accepted"},
    {"fastpath_zone_put", fastpath_zone_put, METH_VARARGS,
     "fastpath_zone_put(cache, zkey, gen, ancount, bodies, tag"
     "[, arcount]) -> bool"},
    {"fastpath_type_row", fastpath_type_row, METH_VARARGS,
     "fastpath_type_row(cache, served_qtypes, rcode[, log_frag]) -> bool "
     "(the zone table's answer to every question of a type the engine "
     "declines by the type alone: rcode, no records, no name in its key)"},
    {"fastpath_serve_wire", fastpath_serve_wire, METH_VARARGS,
     "fastpath_serve_wire(cache, packet, gen) -> bytes | None"},
    {"fastpath_serve_frames", fastpath_serve_frames, METH_VARARGS,
     "fastpath_serve_frames(cache, framed, gen[, client, port, proto])"
     " -> (framed_responses, consumed, [miss_payload, ...])"},
    {"fastpath_serve_balancer", fastpath_serve_balancer, METH_VARARGS,
     "fastpath_serve_balancer(cache, chunk, gen, fd) -> "
     "(consumed, served, [raw_frame, ...]) — walk balancer frames in "
     "the chunk, answer UDP-transport hits directly on the passed "
     "(balancer-owned) fd via sendmmsg with explicit msg_name, and "
     "surface everything else as raw frames for the Python lane"},
    {"fastpath_drain", fastpath_drain, METH_VARARGS,
     "fastpath_drain(cache, fd, gen, max_n=64) -> (misses, served, "
     "retried, dropped): the answers a full send buffer made wait for "
     "the one retry, and those it still refused"},
    {"fastpath_stats", fastpath_stats, METH_VARARGS,
     "fastpath_stats(cache) -> dict"},
    {"fastpath_clear", fastpath_clear, METH_VARARGS,
     "fastpath_clear(cache) -> None"},
    {"fastpath_zone_reserve", fastpath_zone_reserve, METH_VARARGS,
     "fastpath_zone_reserve(cache, expected_entries) -> None "
     "(presize the zone table so a bulk fill never rehashes "
     "mid-serving)"},
    {"fastpath_invalidate", fastpath_invalidate, METH_VARARGS,
     "fastpath_invalidate(cache, tag_qname_wire) -> dropped count"},
    {"fastpath_invalidate_many", fastpath_invalidate_many, METH_VARARGS,
     "fastpath_invalidate_many(cache, [tag_qname_wire, ...]) -> dropped"},
    {"fastpath_log_enable", fastpath_log_enable, METH_VARARGS,
     "fastpath_log_enable(cache, line_prefix, capacity=1MiB) -> None"},
    {"fastpath_log_drain", fastpath_log_drain, METH_VARARGS,
     "fastpath_log_drain(cache) -> bytes of complete log lines"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef fastio_module = {
    PyModuleDef_HEAD_INIT,
    "_binderfastio",
    "Batched UDP recvmmsg/sendmmsg for the DNS hot path",
    -1,
    fastio_methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC
PyInit__binderfastio(void)
{
    return PyModule_Create(&fastio_module);
}
