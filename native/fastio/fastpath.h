/*
 * Shared declarations between fastio.c (module definition, batched
 * recv/send) and fastpath.c (native answer cache).
 */
#ifndef BINDER_FASTPATH_H
#define BINDER_FASTPATH_H

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <sys/socket.h>

#define FASTIO_BATCH 64
#define FASTIO_DGRAM_MAX 65535

/* fastio.c */
PyObject *fastio_addr_to_tuple(const struct sockaddr_storage *ss);

/* Process-wide I/O accounting shared by every batched entry point
 * (recv_batch, send_batch, fastpath_drain, fastpath_serve_balancer),
 * read by BinderServer's scrape fold (binder_udp_datagrams,
 * binder_udp_batch_size) and /status `io`.
 * The batch-size histogram is the observable for "sampling must not
 * defeat batching": if the duty-cycle sampler serialized the drain,
 * every cell above recv_cells[0] would empty out. */
#define FASTIO_IO_CELLS 8   /* log2 cells: 1, 2-3, 4-7, ..., >=128 */

/* The worker's time ledger, C half: leaf spans of
 * binder_query_stage_seconds timed where the work happens (stage names
 * in fastio_span_names, fastio.c).  Each keeps sum, count and
 * non-cumulative cells on the stage grid Python hands over once at
 * start (io_span_grid); the scrape folds them in by deltas like the
 * per-qtype latency.  All on CLOCK_MONOTONIC. */
#define FASTIO_SPAN_MAX_BUCKETS 24
enum {
    FASTIO_SPAN_RECV = 0,   /* udp-recv: one recvmmsg, EAGAIN included */
    FASTIO_SPAN_SERVE,      /* native-serve: after recvmmsg to before
                             * sendmmsg, per batch with a datagram; a
                             * fastpath_serve_frames call with a frame */
    FASTIO_SPAN_SEND,       /* udp-send: one sendmmsg */
    FASTIO_N_SPANS
};
typedef struct {
    double sum;
    unsigned long long count;
    unsigned long long cells[FASTIO_SPAN_MAX_BUCKETS + 1];
} fastio_span_t;

/* The C lanes that send UDP answers themselves and so can lose one to
 * a full send buffer (binder_udp_send_drops_total{lane}; the Python
 * lanes' send_batch reports its count and its caller keeps the tally) */
enum {
    FASTIO_LANE_NATIVE = 0,     /* fastpath_drain */
    FASTIO_LANE_BALANCER,       /* fastpath_serve_balancer */
    FASTIO_N_LANES
};

typedef struct {
    unsigned long long recv_calls;   /* recvmmsg calls that returned >0 */
    unsigned long long recv_msgs;
    unsigned long long recv_cells[FASTIO_IO_CELLS];
    unsigned long long send_calls;   /* sendmmsg calls that sent >0 */
    unsigned long long send_msgs;
    /* answers a lane gave up on: the send buffer was still full */
    unsigned long long send_drops[FASTIO_N_LANES];
    fastio_span_t spans[FASTIO_N_SPANS];
} fastio_io_t;
extern fastio_io_t fastio_io;
/* the stage grid outlives io_stats(reset=True) */
extern double fastio_span_grid[FASTIO_SPAN_MAX_BUCKETS];
extern int fastio_span_grid_n;

static inline void
fastio_span_note(int which, double seconds)
{
    fastio_span_t *s = &fastio_io.spans[which];
    /* first bound >= v, the +Inf cell last: collector.py's bisect_left */
    int i = 0;
    while (i < fastio_span_grid_n && fastio_span_grid[i] < seconds)
        i++;
    s->sum += seconds;
    s->count++;
    s->cells[i]++;
}

static inline void
fastio_io_note_recv(int n)
{
    if (n <= 0)
        return;
    fastio_io.recv_calls++;
    fastio_io.recv_msgs += (unsigned long long)n;
    int cell = 0;
    while (cell < FASTIO_IO_CELLS - 1 && (1 << (cell + 1)) <= n)
        cell++;
    fastio_io.recv_cells[cell]++;
}

static inline void
fastio_io_note_send(int n)
{
    if (n <= 0)
        return;
    fastio_io.send_calls++;
    fastio_io.send_msgs += (unsigned long long)n;
}

/* receive arena shared by recv_batch and fastpath_drain — only one of
 * them runs at a time (both hold the GIL for the whole call), and a
 * process uses one or the other per readiness event; sharing saves ~4MB
 * RSS over two static copies */
extern unsigned char fastio_shared_bufs[FASTIO_BATCH][FASTIO_DGRAM_MAX];

/* fastpath.c */
PyObject *fastpath_new(PyObject *self, PyObject *args);
PyObject *fastpath_put(PyObject *self, PyObject *args);
PyObject *fastpath_zone_put(PyObject *self, PyObject *args);
PyObject *fastpath_type_row(PyObject *self, PyObject *args);
PyObject *fastpath_serve_wire(PyObject *self, PyObject *args);
PyObject *fastpath_serve_frames(PyObject *self, PyObject *args);
PyObject *fastpath_serve_balancer(PyObject *self, PyObject *args);
PyObject *fastpath_drain(PyObject *self, PyObject *args);
PyObject *fastpath_stats(PyObject *self, PyObject *args);
PyObject *fastpath_clear(PyObject *self, PyObject *args);
PyObject *fastpath_zone_reserve(PyObject *self, PyObject *args);
PyObject *fastpath_invalidate(PyObject *self, PyObject *args);
PyObject *fastpath_invalidate_many(PyObject *self, PyObject *args);
PyObject *fastpath_log_enable(PyObject *self, PyObject *args);
PyObject *fastpath_log_drain(PyObject *self, PyObject *args);

#endif /* BINDER_FASTPATH_H */
