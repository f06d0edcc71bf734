/*
 * mbalancer — DNS load balancer fronting N binder backend processes.
 *
 * C++ rebuild of the reference's mname-balancer (SURVEY §2.2 L1; the
 * reference submodule is not vendored, so the wire protocol is our own
 * spec, docs/balancer-protocol.md).  Behavior match:
 *
 *  - owns the public UDP + TCP DNS port and fans queries out to backend
 *    processes over per-backend UNIX stream sockets found in a socket
 *    directory (reference: /var/run/binder/sockets, boot/setup.sh);
 *  - frames carry the ORIGINAL client address + transport so backends
 *    log/answer as if they received the packet directly;
 *  - remote-IP -> backend affinity (reference g_remotes AVL,
 *    bin/balstat:19-31), round-robin assignment of new remotes across
 *    healthy backends (reference g_backends);
 *  - backends leave by unlinking their socket (reference main.js:181-193):
 *    periodic directory rescans pick up joins/leaves; send errors mark a
 *    backend unhealthy immediately;
 *  - introspection: JSON state dump served on <sockdir>/.balancer.stats
 *    (replaces the reference's mdb-based bin/balstat).
 *
 * Single-threaded epoll event loop; no allocations on the per-packet path
 * beyond buffer reuse.  Usage:
 *     mbalancer -d <sockdir> [-p port] [-b bindaddr] [-s scan_ms]
 */
#include <arpa/inet.h>
#include <dirent.h>
#include <errno.h>
#include <fcntl.h>
#include <getopt.h>
#include <netinet/in.h>
#include <signal.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/timerfd.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <sys/un.h>
#include <unistd.h>

#include <cstdarg>
#include <deque>
#include <map>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "../common/dnskey.h"

namespace {

constexpr uint8_t kProtoVersion = 1;
constexpr size_t kFrameHdr = 21;      /* ver+family+transport+addr16+port */
constexpr size_t kMaxFrame = 65556;
constexpr uint8_t kTransportUdp = 0;
constexpr uint8_t kTransportTcp = 1;
/* response-only marker from backends: route like UDP, never cache
 * (recursion answers belong to another DC's store) */
constexpr uint8_t kTransportUdpNoStore = 2;
/* control-frame opcodes (family 0; opcode rides the transport byte).
 * 0/1 are backend->balancer; 2 is the direct-return negotiation: the
 * backend announces the capability, and the balancer answers with the
 * same opcode carrying its client-facing UDP fd via SCM_RIGHTS
 * (docs/balancer-protocol.md "Direct-return negotiation"). */
constexpr uint8_t kCtlGen = 0;
constexpr uint8_t kCtlInvalidate = 1;
constexpr uint8_t kCtlDirect = 2;
constexpr size_t kMaxUdpPacket = 65535;
/* Affinity-table cap: the map is keyed by remote host, and mbalancer owns
 * a public UDP port — without a bound, spoofed source addresses would grow
 * it until OOM.  On overflow the whole table resets (stickiness is a
 * best-effort optimization, not a correctness requirement). */
constexpr size_t kMaxRemotes = 65536;

int g_verbose = 0;
/* -D: keep every reply on the relay lane even for capable backends
 * (the tests' A/B arm, and an operator escape hatch) */
int g_no_direct = 0;
/* packet-path syscall count (epoll_wait, recvmmsg, sendmmsg, read,
 * writev, accept4, the fd-pass sendmsg): divided by queries it shows
 * whether direct return dropped the per-query kernel-crossing floor,
 * not just the cycle shares */
uint64_t g_syscalls = 0;

void logmsg(const char *fmt, ...) {
    va_list ap;
    va_start(ap, fmt);
    fprintf(stderr, "mbalancer: ");
    vfprintf(stderr, fmt, ap);
    fprintf(stderr, "\n");
    va_end(ap);
}

void tracemsg(const char *fmt, ...) {
    if (!g_verbose) return;
    va_list ap;
    va_start(ap, fmt);
    fprintf(stderr, "mbalancer: ");
    vfprintf(stderr, fmt, ap);
    fprintf(stderr, "\n");
    va_end(ap);
}

uint64_t now_ms() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (uint64_t)ts.tv_sec * 1000 + ts.tv_nsec / 1000000;
}

/* ---- per-stage cycle counters ----
 *
 * Decompose the balancer's own packet path: where inside a query's
 * transit through this process do the cycles go?  Four stages cover
 * every hop a frame takes:
 *
 *   frame-parse    frame/datagram walk: backend frame validation and
 *                  control handling, TCP client reframing — excluding
 *                  the nested stages below
 *   cache-probe    answer-cache work: key build, lookup, hit serve,
 *                  response harvest into the cache
 *   backend-write  query frame build + queue + the writev flush
 *                  toward backends
 *   reply-relay    response routing to clients (UDP sendmmsg batch
 *                  add/flush, TCP framed write)
 *
 * Counters are raw TSC cycles on x86 (CLOCK_MONOTONIC ns elsewhere);
 * one pair of reads per region ~10ns, cheap enough to stay always-on.
 * `cycles_per_us` is calibrated over process lifetime at stats-read
 * time, so consumers (balstat) convert without knowing the TSC
 * rate.  Nested regions subtract out: a stage's cycles are exclusive,
 * so the four cells sum to the balancer's total attributable work and
 * a share-of-total per stage is meaningful. */
static inline uint64_t cycles_now() {
#if defined(__x86_64__) || defined(__i386__)
    unsigned lo, hi;
    __asm__ __volatile__("rdtsc" : "=a"(lo), "=d"(hi));
    return ((uint64_t)hi << 32) | lo;
#else
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
#endif
}

struct StageCell {
    uint64_t cycles = 0;
    uint64_t ops = 0;
};
struct StageCounters {
    StageCell frame_parse, cache_probe, backend_write, reply_relay;
};
StageCounters g_stages;
/* gross cycles of completed nested scopes, reported upward so an
 * enclosing stage times only its own work (single-threaded loop: a
 * plain global is the whole mechanism) */
uint64_t g_nested_cycles = 0;
uint64_t g_cal_cycles0 = 0;     /* lifetime calibration anchors (main) */
double g_cal_mono0 = 0.0;

struct ScopedStage {
    StageCell &cell;
    uint64_t t0, nested0;
    explicit ScopedStage(StageCell &c)
        : cell(c), t0(cycles_now()), nested0(g_nested_cycles) {}
    ~ScopedStage() {
        uint64_t gross = cycles_now() - t0;
        uint64_t nested = g_nested_cycles - nested0;
        cell.cycles += gross > nested ? gross - nested : 0;
        cell.ops++;
        /* replace (not add to) the nested tally: our gross span already
         * contains any grandchildren, so the parent must subtract this
         * span exactly once */
        g_nested_cycles = nested0 + gross;
    }
};

/* ---- client address key: family + 16 bytes + port ---- */
struct ClientKey {
    uint8_t family;
    uint8_t addr[16];
    uint16_t port;
    bool operator==(const ClientKey &o) const {
        return family == o.family && port == o.port &&
               memcmp(addr, o.addr, 16) == 0;
    }
};
struct ClientKeyHash {
    size_t operator()(const ClientKey &k) const {
        size_t h = 1469598103934665603ULL;
        auto mix = [&h](uint8_t b) { h ^= b; h *= 1099511628211ULL; };
        mix(k.family);
        for (int i = 0; i < 16; i++) mix(k.addr[i]);
        mix(k.port & 0xff);
        mix(k.port >> 8);
        return h;
    }
};

ClientKey key_from_sockaddr(const struct sockaddr_storage &ss) {
    ClientKey k{};
    if (ss.ss_family == AF_INET) {
        auto *sin = (const struct sockaddr_in *)&ss;
        k.family = 4;
        memcpy(k.addr, &sin->sin_addr, 4);
        k.port = ntohs(sin->sin_port);
    } else {
        auto *sin6 = (const struct sockaddr_in6 *)&ss;
        k.family = 6;
        memcpy(k.addr, &sin6->sin6_addr, 16);
        k.port = ntohs(sin6->sin6_port);
    }
    return k;
}

void sockaddr_from_key(const ClientKey &k, struct sockaddr_storage *ss,
                       socklen_t *len) {
    memset(ss, 0, sizeof(*ss));
    if (k.family == 4) {
        auto *sin = (struct sockaddr_in *)ss;
        sin->sin_family = AF_INET;
        memcpy(&sin->sin_addr, k.addr, 4);
        sin->sin_port = htons(k.port);
        *len = sizeof(*sin);
    } else {
        auto *sin6 = (struct sockaddr_in6 *)ss;
        sin6->sin6_family = AF_INET6;
        memcpy(&sin6->sin6_addr, k.addr, 16);
        sin6->sin6_port = htons(k.port);
        *len = sizeof(*sin6);
    }
}

/* ---- buffered stream connection (backend or TCP client) ---- */
struct Stream {
    int fd = -1;
    std::vector<uint8_t> rbuf;
    std::deque<std::vector<uint8_t>> wq;   /* pending writes */
    size_t wq_off = 0;                     /* offset into wq.front() */
    size_t wq_bytes = 0;                   /* sum of queued buffers */
    uint64_t flushed_total = 0;            /* lifetime bytes written */

    void queue_write(std::vector<uint8_t> &&data) {
        wq_bytes += data.size();
        wq.push_back(std::move(data));
    }

    /* Drain the queue with writev — under load many query frames are
     * queued per event-loop pass (see flush_pending_backends), and one
     * gathered write moves them all in a single syscall instead of one
     * write per frame.  Returns false on fatal error. */
    bool flush() {
        while (!wq.empty()) {
            struct iovec iov[64];
            int cnt = 0;
            for (auto it = wq.begin(); it != wq.end() && cnt < 64;
                 ++it, ++cnt) {
                size_t skip = (cnt == 0) ? wq_off : 0;
                iov[cnt].iov_base = (void *)(it->data() + skip);
                iov[cnt].iov_len = it->size() - skip;
            }
            ssize_t n = writev(fd, iov, cnt);
            g_syscalls++;
            if (n < 0) {
                if (errno == EAGAIN || errno == EWOULDBLOCK) return true;
                if (errno == EINTR) continue;
                return false;
            }
            flushed_total += (uint64_t)n;
            size_t left = (size_t)n;
            while (left > 0) {
                size_t avail = wq.front().size() - wq_off;
                if (left >= avail) {
                    left -= avail;
                    wq_bytes -= wq.front().size();
                    wq.pop_front();
                    wq_off = 0;
                } else {
                    wq_off += left;
                    left = 0;
                }
            }
        }
        return true;
    }
    bool want_write() const { return !wq.empty(); }
};

struct CacheEntry {
    double expire_at = 0;
    /* Round-robin preservation, mirroring the backend answer cache
     * (binder_tpu/resolver/answer_cache.py): multi-answer responses
     * are collected until kCacheVariants independent shuffles exist,
     * and only then served, cycling through them.  Single-answer
     * entries are complete at one variant. */
    std::vector<std::vector<uint8_t>> wires;
    /* dependency-tag hash: the store name this answer derives from,
     * derived from the key at fill time (cache_tag_hash); matched by
     * the backend's per-name invalidate control frames (opcode 1) */
    uint64_t taghash = 0;
    uint8_t next_variant = 0;
    bool complete = false;
    size_t bytes = 0;
};
constexpr size_t kCacheVariants = 8;
uint64_t g_cache_bytes = 0;           /* across all backends */

uint64_t fnv64(const uint8_t *p, size_t n) {
    uint64_t h = 1469598103934665603ull;        /* FNV-1a 64 */
    for (size_t i = 0; i < n; i++) {
        h ^= p[i];
        h *= 1099511628211ull;
    }
    return h;
}

/*
 * Dependency-tag hash for a cache key (dnskey layout: qtype at [3:5],
 * lowercased qname wire from [7]).  The tag is the store name the
 * answer derives from: for SRV qnames the resolver strips the leading
 * _service._proto labels and looks up the remainder
 * (binder_tpu/resolver/engine.py SRV_RE), so the tag is that suffix of
 * the label chain; for everything else (A-likes, PTR reverse names)
 * the qname itself.  Must stay in lockstep with the tag wires the
 * backend emits (BinderServer._on_store_invalidate -> opcode 1).
 */
uint64_t cache_tag_hash(const std::string &mkey) {
    const uint8_t *k = (const uint8_t *)mkey.data();
    size_t n = mkey.size();
    if (n < 8)
        return 0;
    uint16_t qtype = (uint16_t)((k[3] << 8) | k[4]);
    const uint8_t *qn = k + 7;
    size_t qlen = n - 7;
    if (qtype == 33) {                  /* SRV */
        const uint8_t *p = qn;
        size_t rem = qlen;
        int stripped = 0;
        for (int i = 0; i < 2; i++) {
            if (rem < 2 || p[0] == 0 || p[1] != '_')
                break;
            size_t l = p[0];
            if (1 + l >= rem)
                break;
            p += 1 + l;
            rem -= 1 + l;
            stripped++;
        }
        if (stripped == 2 && rem > 1) {
            qn = p;
            qlen = rem;
        }
    }
    return fnv64(qn, qlen);
}
constexpr size_t kMaxCacheEntriesPerBackend = 65536;
constexpr uint64_t kMaxCacheBytes = 64ull << 20;
constexpr size_t kMaxCacheWire = 4096;

/* ---- backend (one binder process behind a UNIX socket) ---- */
struct Backend {
    int id = -1;
    std::string path;          /* socket path */
    Stream conn;
    bool healthy = false;
    bool present = true;       /* socket file still exists */
    uint64_t forwarded = 0;
    uint64_t responded = 0;
    uint64_t connect_failures = 0;
    /* deferred-flush state (see flush_pending_backends) */
    bool flush_pending = false;
    size_t pending_queued = 0;
    int stall_ticks = 0;       /* consecutive no-drain ticks at depth */
    uint64_t last_flushed_total = 0;   /* drain progress marker */
    /* answer-cache invalidation state: the backend reports its mirror
     * generation over the socket (control frames); entries resolved
     * under an older generation are stale.  epoch distinguishes
     * reconnects — a restarted backend's generation counter restarts,
     * so entries from the previous process must never match. */
    uint64_t gen = 0;
    bool gen_known = false;
    uint32_t epoch = 0;
    /* direct-return negotiation state (docs/balancer-protocol.md):
     * capability announced by the backend (control opcode 2), fd
     * passed by us via SCM_RIGHTS; pending marks a deferred pass
     * (write queue busy at announce time) retried by the timer sweep */
    bool direct_capable = false;
    bool fd_passed = false;
    bool fd_pass_pending = false;
    /* per-backend answer cache (see backend_cache_clear for the
     * invalidation invariant) */
    std::unordered_map<std::string, CacheEntry> cache;
    uint64_t cache_bytes = 0;
};

/* ---- write-queue / connection bounds ----
 * Everything facing a peer that can stop reading must be bounded:
 * a stalled backend or slowloris TCP client must cost O(cap) memory
 * and eventually lose its connection, never OOM the balancer.
 * Defaults are production values; the env overrides exist so tests can
 * trip the caps without shoving megabytes through loopback. */
size_t g_max_backend_wq = 8u << 20;    /* per backend stream */
size_t g_max_client_wq = 1u << 20;     /* per TCP client */
constexpr int kBackendStallTicks = 3;  /* timer ticks at cap => down */
constexpr double kEvictIdleFloorS = 1.0;  /* min idle before cap-evict */

void load_bound_overrides() {
    const char *s = getenv("MBALANCER_MAX_BACKEND_WQ");
    if (s != nullptr && atol(s) > 0) g_max_backend_wq = (size_t)atol(s);
    s = getenv("MBALANCER_MAX_CLIENT_WQ");
    if (s != nullptr && atol(s) > 0) g_max_client_wq = (size_t)atol(s);
}

/* ---- TCP client connection state ---- */
struct TcpClient {
    Stream conn;
    ClientKey key;
    double last_active = 0;   /* mono_s() of last read/write progress */
};

struct Balancer {
    std::string sockdir;
    std::string bind_addr = "0.0.0.0";
    int port = 53;
    int scan_ms = 2000;
    int cache_ms = 60000;      /* answer-cache expiry; 0 disables */
    int tcp_idle_ms = 30000;   /* idle TCP clients are evicted */
    int max_tcp_clients = 1024;

    int epfd = -1;
    int udp_fd = -1;
    int tcp_fd = -1;
    int stats_fd = -1;
    int timer_fd = -1;

    std::vector<Backend> backends;
    std::unordered_map<std::string, int> backend_by_path;
    std::unordered_map<int, int> backend_by_fd;       /* fd -> index */
    std::unordered_map<ClientKey, int, ClientKeyHash> remotes; /* affinity */
    std::unordered_map<int, TcpClient> tcp_clients;   /* fd -> client */
    std::unordered_map<ClientKey, int, ClientKeyHash> tcp_by_key;
    int rr_next = 0;

    uint64_t udp_queries = 0, tcp_queries = 0, drops = 0;
    uint64_t cache_hits = 0;
    uint64_t cache_misses = 0;    /* key built, no fresh entry: forwarded */
    uint64_t uncacheable = 0;     /* key declined: forwarded, never filled */
    uint64_t cache_invalidations = 0;  /* entries dropped by opcode 1 */
    /* forward round-trip (query forwarded on a cache miss -> matching
     * response from the backend), so a topology-axis delta can be
     * attributed: balancer packet path (hits) vs backend round trip
     * (misses).  Log2 cells in µs: [<1, <2, <4, ..., <16384, rest]. */
    static constexpr int kRttCells = 16;
    uint64_t fwd_rtt_count = 0;
    double fwd_rtt_sum_s = 0.0;
    uint64_t fwd_rtt_cells[kRttCells] = {0};
    size_t backend_wq_peak = 0;   /* high-water backend stream queue */
    uint64_t wq_overflows = 0;    /* frames refused: stream at byte cap */
    uint64_t idle_closes = 0;     /* TCP clients evicted for idleness */
    uint64_t client_evictions = 0; /* evicted to admit a new client */
    uint64_t backend_stalls = 0;  /* backends downed for a stuck queue */
    /* direct-return accounting: fds passed (one per negotiated backend
     * connection) and queries forwarded with the reply hop eliminated */
    uint64_t fd_passes = 0;
    uint64_t direct_forwards = 0;
    /* recvmmsg batch-size histogram on the UDP front (log2 cells:
     * 1, 2-3, 4-7, ..., >=128): proves the batching survived whatever
     * the datapath change was — a collapse to cell 0 is per-packet
     * dispatch again */
    static constexpr int kBatchCells = 8;
    uint64_t udp_batch_cells[kBatchCells] = {0};
    uint64_t started_at = 0;
};

Balancer g_bal;

/* fds whose close() is deferred to the end of the current epoll batch:
 * closing mid-batch lets accept4/connect reuse the fd number while stale
 * queued events for the old owner are still pending, which would dispatch
 * against (and tear down) the new connection */
std::vector<int> g_deferred_close;

void defer_close(int fd) {
    epoll_ctl(g_bal.epfd, EPOLL_CTL_DEL, fd, nullptr);
    g_deferred_close.push_back(fd);
}

void epoll_add(int fd, uint32_t events, uint64_t tag) {
    struct epoll_event ev{};
    ev.events = events;
    ev.data.u64 = tag;
    if (epoll_ctl(g_bal.epfd, EPOLL_CTL_ADD, fd, &ev) != 0) {
        logmsg("epoll_ctl ADD failed: %s", strerror(errno));
        exit(1);
    }
}

void epoll_mod(int fd, uint32_t events, uint64_t tag) {
    struct epoll_event ev{};
    ev.events = events;
    ev.data.u64 = tag;
    (void)epoll_ctl(g_bal.epfd, EPOLL_CTL_MOD, fd, &ev);
}

/* epoll tags: low 32 bits fd, high 32 bits kind */
enum Kind : uint64_t {
    KIND_UDP = 1, KIND_TCP_LISTEN, KIND_TCP_CLIENT, KIND_BACKEND,
    KIND_STATS, KIND_TIMER,
};
uint64_t tag(Kind kind, int fd) { return ((uint64_t)kind << 32) | (uint32_t)fd; }

/* ---------------- backend management ---------------- */

void backend_cache_clear(Backend &be);   /* defined with the cache below */
void maybe_pass_fd(Backend &be);         /* defined with the framing below */

void backend_mark_down(Backend &be) {
    if (be.conn.fd >= 0) {
        g_bal.backend_by_fd.erase(be.conn.fd);
        defer_close(be.conn.fd);
        be.conn = Stream();
    }
    be.healthy = false;
    be.gen_known = false;
    be.stall_ticks = 0;
    be.last_flushed_total = 0;
    /* negotiation is per connection: a reconnected backend re-announces
     * capability and receives a fresh fd */
    be.direct_capable = false;
    be.fd_passed = false;
    be.fd_pass_pending = false;
    backend_cache_clear(be);   /* a restarted process restarts its gen */
}

bool backend_connect(Backend &be) {
    int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) return false;
    struct sockaddr_un sun{};
    sun.sun_family = AF_UNIX;
    snprintf(sun.sun_path, sizeof(sun.sun_path), "%s", be.path.c_str());
    if (connect(fd, (struct sockaddr *)&sun, sizeof(sun)) != 0 &&
        errno != EINPROGRESS) {
        close(fd);
        be.connect_failures++;
        return false;
    }
    be.conn = Stream();
    be.conn.fd = fd;
    be.stall_ticks = 0;
    be.last_flushed_total = 0;
    be.direct_capable = false;
    be.fd_passed = false;
    be.fd_pass_pending = false;
    be.healthy = true;   /* optimistic; demoted on first error */
    /* new process behind the same socket path: its generation counter
     * restarts, so retire every cache entry from the previous epoch */
    be.epoch++;
    be.gen_known = false;
    g_bal.backend_by_fd[fd] = be.id;
    epoll_add(fd, EPOLLIN, tag(KIND_BACKEND, fd));
    tracemsg("backend %d connected at %s", be.id, be.path.c_str());
    return true;
}

void scan_sockdir() {
    DIR *d = opendir(g_bal.sockdir.c_str());
    if (d == nullptr) {
        logmsg("cannot open socket dir %s: %s", g_bal.sockdir.c_str(),
               strerror(errno));
        return;
    }
    for (auto &be : g_bal.backends) be.present = false;

    struct dirent *de;
    while ((de = readdir(d)) != nullptr) {
        if (de->d_name[0] == '.') continue;  /* incl. .balancer.stats */
        std::string path = g_bal.sockdir + "/" + de->d_name;
        struct stat st;
        if (stat(path.c_str(), &st) != 0 || !S_ISSOCK(st.st_mode)) continue;
        auto it = g_bal.backend_by_path.find(path);
        if (it == g_bal.backend_by_path.end()) {
            Backend be;
            be.id = (int)g_bal.backends.size();
            be.path = path;
            be.present = true;
            g_bal.backends.push_back(std::move(be));
            g_bal.backend_by_path[path] = g_bal.backends.back().id;
            backend_connect(g_bal.backends.back());
            logmsg("backend %d added: %s",
                   g_bal.backends.back().id, path.c_str());
        } else {
            Backend &be = g_bal.backends[it->second];
            be.present = true;
            if (!be.healthy) backend_connect(be);
        }
    }
    closedir(d);

    /* sockets that vanished: the backend told us it's going away */
    for (auto &be : g_bal.backends) {
        if (!be.present && be.healthy) {
            logmsg("backend %d socket removed, draining", be.id);
            backend_mark_down(be);
        }
    }
}

void tcp_client_close(int fd);   /* defined with the TCP front below */
double mono_s();                 /* defined with the cache below */

/* Periodic resource sweep (rides the sockdir-scan timer): evict TCP
 * clients idle past the deadline, and mark down backends whose write
 * queue has sat at the byte cap for kBackendStallTicks consecutive
 * ticks — a backend that stopped reading is as dead as one that
 * closed, it just fails slower. */
void sweep_connections() {
    double now = mono_s();
    if (g_bal.tcp_idle_ms > 0) {   /* -T 0 disables, like -c 0 */
        double idle_cutoff = now - (double)g_bal.tcp_idle_ms / 1000.0;
        std::vector<int> idle;
        for (const auto &p : g_bal.tcp_clients)
            if (p.second.last_active < idle_cutoff)
                idle.push_back(p.first);
        for (int fd : idle) {
            g_bal.idle_closes++;
            tracemsg("closing idle TCP client fd %d", fd);
            tcp_client_close(fd);
        }
    }
    for (auto &be : g_bal.backends) {
        if (be.conn.fd < 0) continue;
        /* "stalled" = deep queue AND zero drain progress since the
         * last tick — a saturated-but-draining backend (flushed_total
         * advancing) is busy, not dead, and must stay in rotation */
        if (be.conn.wq_bytes >= g_max_backend_wq / 2 &&
            be.conn.flushed_total == be.last_flushed_total) {
            if (++be.stall_ticks >= kBackendStallTicks) {
                logmsg("backend %d stalled (%zu bytes queued, no drain), "
                       "marking down", be.id, be.conn.wq_bytes);
                g_bal.backend_stalls++;
                backend_mark_down(be);
            }
        } else {
            be.stall_ticks = 0;
        }
        be.last_flushed_total = be.conn.flushed_total;
        if (be.fd_pass_pending)
            maybe_pass_fd(be);   /* deferred pass: queue was busy */
    }
}

int pick_backend(const ClientKey &client) {
    size_t n = g_bal.backends.size();
    if (n == 0) return -1;

    /* affinity is per remote host (reference remote_t keeps rem_addr
     * only), so ignore the source port */
    ClientKey host = client;
    host.port = 0;

    auto it = g_bal.remotes.find(host);
    if (it != g_bal.remotes.end()) {
        Backend &be = g_bal.backends[it->second];
        if (be.healthy) return it->second;
        g_bal.remotes.erase(it);   /* affinity to a dead backend */
    }
    /* round-robin over healthy backends */
    for (size_t i = 0; i < n; i++) {
        int idx = (g_bal.rr_next + (int)i) % (int)n;
        if (g_bal.backends[idx].healthy) {
            g_bal.rr_next = (idx + 1) % (int)n;
            if (g_bal.remotes.size() >= kMaxRemotes) g_bal.remotes.clear();
            g_bal.remotes[host] = idx;
            return idx;
        }
    }
    return -1;
}

/* ---------------- framing ---------------- */

std::vector<uint8_t> make_frame(const ClientKey &k, uint8_t transport,
                                const uint8_t *payload, size_t len) {
    std::vector<uint8_t> out(4 + kFrameHdr + len);
    uint32_t L = htonl((uint32_t)(kFrameHdr + len));
    memcpy(out.data(), &L, 4);
    out[4] = kProtoVersion;
    out[5] = k.family;
    out[6] = transport;
    memcpy(out.data() + 7, k.addr, 16);
    out[23] = (uint8_t)(k.port >> 8);
    out[24] = (uint8_t)(k.port & 0xff);
    memcpy(out.data() + 25, payload, len);
    return out;
}

/* ---------------- direct-return fd passing ----------------
 *
 * A capable backend (control opcode 2) receives our client-facing UDP
 * socket over the UNIX channel via SCM_RIGHTS and answers UDP clients
 * on it directly (sendmmsg with the frame's sockaddr as msg_name) —
 * the reply never re-enters this process.  The ancillary payload must
 * ride a specific sendmsg, so the pass happens only while the backend
 * stream's write queue is empty (otherwise mid-frame bytes would be
 * interleaved); a busy queue defers the pass to the timer sweep. */
void maybe_pass_fd(Backend &be) {
    if (g_no_direct || !be.direct_capable || be.fd_passed ||
        be.conn.fd < 0 || g_bal.udp_fd < 0)
        return;
    if (be.conn.want_write()) {
        be.fd_pass_pending = true;
        return;
    }
    uint8_t frame[4 + kFrameHdr];
    uint32_t L = htonl((uint32_t)kFrameHdr);
    memcpy(frame, &L, 4);
    frame[4] = kProtoVersion;
    frame[5] = 0;            /* control */
    frame[6] = kCtlDirect;   /* fd-pass */
    memset(frame + 7, 0, kFrameHdr - 3);
    struct iovec iov;
    iov.iov_base = frame;
    iov.iov_len = sizeof(frame);
    char cbuf[CMSG_SPACE(sizeof(int))];
    memset(cbuf, 0, sizeof(cbuf));
    struct msghdr msg{};
    msg.msg_iov = &iov;
    msg.msg_iovlen = 1;
    msg.msg_control = cbuf;
    msg.msg_controllen = sizeof(cbuf);
    struct cmsghdr *cm = CMSG_FIRSTHDR(&msg);
    cm->cmsg_level = SOL_SOCKET;
    cm->cmsg_type = SCM_RIGHTS;
    cm->cmsg_len = CMSG_LEN(sizeof(int));
    memcpy(CMSG_DATA(cm), &g_bal.udp_fd, sizeof(int));
    ssize_t n;
    do {
        n = sendmsg(be.conn.fd, &msg, MSG_NOSIGNAL);
    } while (n < 0 && errno == EINTR);
    g_syscalls++;
    if (n == (ssize_t)sizeof(frame)) {
        be.fd_passed = true;
        be.fd_pass_pending = false;
        g_bal.fd_passes++;
        tracemsg("backend %d: direct-return fd passed", be.id);
        return;
    }
    if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        be.fd_pass_pending = true;   /* socket buffer full: retry later */
        return;
    }
    /* Hard failure (or a partial write, impossible for 25 bytes into an
     * empty non-blocking stream buffer but handled): direct return is
     * an optimization, so give up on the pass and keep the relay lane;
     * a genuinely broken link fails on the next regular write/read. */
    be.fd_pass_pending = false;
    logmsg("backend %d: fd pass failed (%s), staying on relay lane",
           be.id, n < 0 ? strerror(errno) : "partial write");
}

/* ---------------- answer cache ----------------
 *
 * The balancer caches single-answer UDP responses it forwards, keyed by
 * (backend id, backend epoch, question key) — the question key is the
 * same dnskey_build the backend fast path uses.  Correctness mirrors
 * the backend's own answer cache:
 *  - entries record the backend's mirror generation at fill time;
 *    backends report it over the socket (control frames, sent on
 *    connect and on every store mutation), and stale-generation
 *    entries are lazily dropped;
 *  - a reconnect bumps the epoch, retiring all prior entries (a
 *    restarted backend's generation counter restarts);
 *  - time expiry (-c <ms>, default 60 s, 0 disables);
 *  - round-robin rotation is preserved like the backend cache
 *    preserves it: multi-answer entries collect kCacheVariants
 *    independent shuffles before serving, then cycle through them;
 *  - SERVFAIL is never cached (matches BinderServer._on_query).
 * Fill state rides a fixed pending table keyed by (client, qid): the
 * forward records the question key, the matching response harvests it.
 */
struct PendingFill {
    ClientKey client{};
    uint16_t qid = 0;
    uint16_t keylen = 0;
    int backend_id = -1;
    uint32_t epoch = 0;
    bool used = false;
    double sent_at = 0.0;         /* forward time, for the RTT cells */
    uint8_t key[DNSKEY_MAX];
};
constexpr size_t kPendingSlots = 8192;   /* power of two */
PendingFill g_pending_fill[kPendingSlots];

size_t pending_slot(const ClientKey &k, uint16_t qid) {
    size_t h = ClientKeyHash{}(k);
    h ^= (size_t)qid * 1099511628211ULL;
    return h & (kPendingSlots - 1);
}

double mono_s() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

/* Every entry in a backend's cache was filled under its *current*
 * generation and connection epoch — a generation report that advances
 * the generation, and every reconnect, clears the whole per-backend
 * map.  That keeps invalidation O(changed backend), reclaims dead
 * entries immediately (no unreachable-key garbage), and removes any
 * need for per-entry generation checks on the hit path. */
void backend_cache_clear(Backend &be) {
    g_cache_bytes -= be.cache_bytes;
    be.cache_bytes = 0;
    be.cache.clear();
}

void backend_cache_insert(Backend &be, const uint8_t *key, size_t keylen,
                          const uint8_t *wire, size_t len, bool rotatable) {
    std::string mkey((const char *)key, keylen);
    {
        /* discard-before-evict: a late fill that will be thrown away
         * must not trigger the budget eviction below (which could wipe
         * another backend's entire hot cache for a 0-byte insert) */
        auto it = be.cache.find(mkey);
        if (it != be.cache.end() &&
            (it->second.complete ||
             it->second.wires.size() >= kCacheVariants))
            return;   /* late fill from a pre-completion forward */
    }
    if (be.cache.size() >= kMaxCacheEntriesPerBackend) {
        /* bounded reset, like the affinity table: the cache is an
         * optimization, and a flood of distinct questions must not OOM */
        backend_cache_clear(be);
    }
    while (g_cache_bytes + len > kMaxCacheBytes) {
        /* The byte budget is global, so shed from whichever backend
         * holds the most — clearing the *inserting* backend would let
         * one dominant backend starve the others' (small) caches
         * without ever bringing the total under the cap. */
        Backend *fat = &be;
        for (auto &other : g_bal.backends)
            if (other.cache_bytes > fat->cache_bytes)
                fat = &other;
        if (fat->cache_bytes == 0)
            break;                     /* len alone exceeds the budget */
        backend_cache_clear(*fat);
    }
    CacheEntry &e = be.cache[mkey];
    if (e.wires.empty()) {
        e.expire_at = mono_s() + (double)g_bal.cache_ms / 1000.0;
        e.taghash = cache_tag_hash(mkey);
    }
    e.wires.emplace_back(wire, wire + len);
    e.bytes += len;
    g_cache_bytes += len;
    be.cache_bytes += len;
    /* single-answer responses have nothing to rotate; rotatable ones
     * serve only once enough independent shuffles are collected */
    e.complete = !rotatable || e.wires.size() >= kCacheVariants;
}

/* Backends with frames queued this event-loop pass; flushed once per
 * pass (flush_pending_backends) so a burst of N queries to a backend
 * costs one writev, not N writes. */
std::vector<int> g_flush_pending;

void forward_query_to(int idx, const ClientKey &client, uint8_t transport,
                      const uint8_t *payload, size_t len) {
    Backend &be = g_bal.backends[idx];
    if (be.conn.wq_bytes >= g_max_backend_wq) {
        /* backend not draining: shed this query (clients retry) rather
         * than grow the queue without bound; a persistently stuck queue
         * gets the backend marked down by the timer sweep */
        g_bal.drops++;
        g_bal.wq_overflows++;
        return;
    }
    ScopedStage _ss(g_stages.backend_write);
    be.conn.queue_write(make_frame(client, transport, payload, len));
    if (be.conn.wq_bytes > g_bal.backend_wq_peak)
        g_bal.backend_wq_peak = be.conn.wq_bytes;
    be.forwarded++;
    be.pending_queued++;
    if (!be.flush_pending) {
        be.flush_pending = true;
        g_flush_pending.push_back(idx);
    }
}

void forward_query(const ClientKey &client, uint8_t transport,
                   const uint8_t *payload, size_t len) {
    int idx = pick_backend(client);
    if (idx < 0) {
        g_bal.drops++;
        tracemsg("no healthy backend, dropping query");
        return;
    }
    forward_query_to(idx, client, transport, payload, len);
}

void flush_pending_backends() {
    if (g_flush_pending.empty()) return;
    ScopedStage _ss(g_stages.backend_write);
    for (int idx : g_flush_pending) {
        Backend &be = g_bal.backends[idx];
        be.flush_pending = false;
        size_t queued = be.pending_queued;
        be.pending_queued = 0;
        if (be.conn.fd < 0) {
            /* went down earlier in this pass; its write queue (and the
             * frames just queued) died with the connection */
            g_bal.drops += queued;
            continue;
        }
        if (!be.conn.flush()) {
            logmsg("backend %d write error: %s", be.id, strerror(errno));
            backend_mark_down(be);
            g_bal.drops += queued;
            continue;
        }
        if (be.conn.want_write())
            epoll_mod(be.conn.fd, EPOLLIN | EPOLLOUT,
                      tag(KIND_BACKEND, be.conn.fd));
    }
    g_flush_pending.clear();
}

/* UDP egress batch: responses decoded from one backend-read pass are
 * flushed with a single sendmmsg.  Payload pointers reference the
 * backend's read buffer, so the batch MUST be flushed before that
 * buffer is mutated (handle_backend flushes after each framing pass).
 * Per-destination errors skip one datagram and continue — one
 * unreachable client must not drop other clients' responses. */
struct UdpOut {
    struct mmsghdr msgs[64];
    struct iovec iovs[64];
    struct sockaddr_storage addrs[64];
    /* copy arena for cache-hit responses (they need id/question patching
     * and must outlive the cache entry until the flush) */
    uint8_t copybuf[64][kMaxCacheWire];
    int n = 0;
} g_udp_out;

void udp_out_flush() {
    if (g_udp_out.n == 0) return;
    ScopedStage _ss(g_stages.reply_relay);
    int off = 0;
    while (off < g_udp_out.n) {
        int sent = sendmmsg(g_bal.udp_fd, g_udp_out.msgs + off,
                            (unsigned)(g_udp_out.n - off), MSG_DONTWAIT);
        g_syscalls++;
        if (sent >= 0) {
            off += sent > 0 ? sent : 1;
            continue;
        }
        if (errno == EINTR) continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
            /* socket buffer full: drop rest (UDP) */
            g_bal.drops += (uint64_t)(g_udp_out.n - off);
            break;
        }
        if (errno == EBADF || errno == ENOTSOCK || errno == EFAULT ||
            errno == ENOMEM) {
            /* batch-fatal, not per-destination (same policy as
             * fastpath.c's hit flush): retrying datagram-by-datagram
             * on a dead fd or OOM just spins 64 times */
            g_bal.drops += (uint64_t)(g_udp_out.n - off);
            logmsg("udp_out_flush: fatal sendmmsg errno %d", errno);
            break;
        }
        g_bal.drops += 1;
        off += 1;              /* per-destination failure: skip one */
    }
    g_udp_out.n = 0;
}

void udp_out_add(const struct sockaddr_storage &ss, socklen_t slen,
                 const uint8_t *payload, size_t len) {
    if (g_udp_out.n == 64)
        udp_out_flush();
    int i = g_udp_out.n++;
    g_udp_out.addrs[i] = ss;
    g_udp_out.iovs[i].iov_base = (void *)payload;
    g_udp_out.iovs[i].iov_len = len;
    memset(&g_udp_out.msgs[i], 0, sizeof(g_udp_out.msgs[i]));
    g_udp_out.msgs[i].msg_hdr.msg_iov = &g_udp_out.iovs[i];
    g_udp_out.msgs[i].msg_hdr.msg_iovlen = 1;
    g_udp_out.msgs[i].msg_hdr.msg_name = &g_udp_out.addrs[i];
    g_udp_out.msgs[i].msg_hdr.msg_namelen = slen;
}

/* Like udp_out_add, but copies the payload into the batch's own arena
 * and returns the copy for in-place patching (cache-hit responses). */
uint8_t *udp_out_add_copy(const struct sockaddr_storage &ss,
                          socklen_t slen, const uint8_t *payload,
                          size_t len) {
    if (g_udp_out.n == 64)
        udp_out_flush();
    uint8_t *dst = g_udp_out.copybuf[g_udp_out.n];
    memcpy(dst, payload, len);
    udp_out_add(ss, slen, dst, len);
    return dst;
}

/* ---------------- fronts ---------------- */

void handle_udp() {
    /* recvmmsg drain: up to 64 datagrams per kernel crossing (the same
     * batching the backend datapath uses, native/fastio/fastio.c) */
    static uint8_t bufs[64][kMaxUdpPacket];
    struct mmsghdr msgs[64];
    struct iovec iovs[64];
    struct sockaddr_storage addrs[64];

    for (;;) {
        memset(msgs, 0, sizeof(msgs));
        for (int i = 0; i < 64; i++) {
            iovs[i].iov_base = bufs[i];
            iovs[i].iov_len = kMaxUdpPacket;
            msgs[i].msg_hdr.msg_iov = &iovs[i];
            msgs[i].msg_hdr.msg_iovlen = 1;
            msgs[i].msg_hdr.msg_name = &addrs[i];
            msgs[i].msg_hdr.msg_namelen = sizeof(addrs[i]);
        }
        int n = recvmmsg(g_bal.udp_fd, msgs, 64, MSG_DONTWAIT, nullptr);
        g_syscalls++;
        if (n < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR)
                break;
            logmsg("udp recv error: %s", strerror(errno));
            break;
        }
        if (n > 0) {
            int cell = 0;
            while (cell < Balancer::kBatchCells - 1 &&
                   (1 << (cell + 1)) <= n)
                cell++;
            g_bal.udp_batch_cells[cell]++;
        }
        for (int i = 0; i < n; i++) {
            size_t plen = msgs[i].msg_len;
            const uint8_t *pkt = bufs[i];
            if (plen < 12) continue;             /* short of a header */
            g_bal.udp_queries++;
            ClientKey ck = key_from_sockaddr(addrs[i]);

            /* direct-return backends answer UDP clients on our socket
             * themselves: no reply ever transits this process, so the
             * answer cache can never fill for them — skip the probe
             * and pending bookkeeping, just forward */
            {
                int didx = pick_backend(ck);
                if (didx < 0) {
                    g_bal.drops++;
                    continue;
                }
                if (g_bal.backends[didx].fd_passed) {
                    g_bal.direct_forwards++;
                    forward_query_to(didx, ck, kTransportUdp, pkt, plen);
                    continue;
                }
            }
            if (g_bal.cache_ms > 0) {
                /* attribution: key build + affinity pick + cache
                 * lookup + hit serve / miss record (the nested
                 * backend-write on a miss subtracts itself out) */
                ScopedStage _probe(g_stages.cache_probe);
                uint8_t key[DNSKEY_MAX];
                size_t qn_len = 0;
                uint16_t qtype = 0;
                size_t keylen = dnskey_build(pkt, plen, key, &qn_len,
                                             &qtype);
                if (keylen != 0) {
                    int idx = pick_backend(ck);
                    if (idx < 0) {
                        g_bal.drops++;
                        continue;
                    }
                    Backend &be = g_bal.backends[idx];
                    /* reused buffer: no per-packet allocation on the
                     * lookup path once its capacity has grown */
                    static std::string lookup_key;
                    lookup_key.assign((const char *)key, keylen);
                    auto it = be.cache.find(lookup_key);
                    if (it != be.cache.end()) {
                        CacheEntry &e = it->second;
                        if (mono_s() > e.expire_at) {
                            g_cache_bytes -= e.bytes;
                            be.cache_bytes -= e.bytes;
                            be.cache.erase(it);   /* expired */
                        } else if (e.complete) {
                            const auto &w = e.wires[
                                e.next_variant % e.wires.size()];
                            e.next_variant = (uint8_t)(
                                (e.next_variant + 1) % e.wires.size());
                            if (w.size() >= 12 + qn_len + 4) {
                                uint8_t *out = udp_out_add_copy(
                                    addrs[i], msgs[i].msg_hdr.msg_namelen,
                                    w.data(), w.size());
                                out[0] = pkt[0];    /* request id */
                                out[1] = pkt[1];
                                /* 0x20 case echo */
                                memcpy(out + 12, pkt + 12, qn_len + 4);
                                g_bal.cache_hits++;
                                continue;
                            }
                        }
                        /* incomplete: keep forwarding so responses
                         * collect more shuffle variants */
                    }
                    /* miss: remember the key so the response can fill */
                    g_bal.cache_misses++;
                    PendingFill &pf = g_pending_fill[
                        pending_slot(ck, dnskey_rd16(pkt))];
                    pf.client = ck;
                    pf.qid = dnskey_rd16(pkt);
                    pf.keylen = (uint16_t)keylen;
                    pf.backend_id = be.id;
                    pf.epoch = be.epoch;
                    pf.used = true;
                    pf.sent_at = mono_s();
                    memcpy(pf.key, key, keylen);
                    forward_query_to(idx, ck, kTransportUdp, pkt, plen);
                    continue;
                }
                g_bal.uncacheable++;
            }
            forward_query(ck, kTransportUdp, pkt, plen);
        }
        if (n < 64) break;
    }
    flush_pending_backends();
    udp_out_flush();
}

void tcp_client_close(int fd) {
    auto it = g_bal.tcp_clients.find(fd);
    if (it != g_bal.tcp_clients.end()) {
        g_bal.tcp_by_key.erase(it->second.key);
        g_bal.tcp_clients.erase(it);
    }
    defer_close(fd);
}

void handle_tcp_accept() {
    for (;;) {
        struct sockaddr_storage ss{};
        socklen_t slen = sizeof(ss);
        int fd = accept4(g_bal.tcp_fd, (struct sockaddr *)&ss, &slen,
                         SOCK_NONBLOCK);
        g_syscalls++;
        if (fd < 0) return;
        if ((int)g_bal.tcp_clients.size() >= g_bal.max_tcp_clients) {
            /* At the connection cap: evict the idlest client to admit
             * the newcomer — but only one genuinely idle (past the
             * floor).  Unconditional evict-idlest would let a cheap
             * connect() flood displace every established client, since
             * fresh attacker connections always carry newer activity
             * stamps than the legitimate ones they evict. */
            int idlest = -1;
            double oldest = 1e300;
            for (const auto &p : g_bal.tcp_clients) {
                if (p.second.last_active < oldest) {
                    oldest = p.second.last_active;
                    idlest = p.first;
                }
            }
            if (idlest >= 0 && mono_s() - oldest >= kEvictIdleFloorS) {
                g_bal.client_evictions++;
                tcp_client_close(idlest);
            } else {
                /* everyone is recently active (or cap is 0): refuse
                 * the newcomer; idle-timeout sweeps recycle slots */
                close(fd);
                continue;
            }
        }
        TcpClient tc;
        tc.conn.fd = fd;
        tc.key = key_from_sockaddr(ss);
        tc.last_active = mono_s();
        g_bal.tcp_clients[fd] = std::move(tc);
        g_bal.tcp_by_key[g_bal.tcp_clients[fd].key] = fd;
        epoll_add(fd, EPOLLIN, tag(KIND_TCP_CLIENT, fd));
    }
}

void handle_tcp_client(int fd, uint32_t events) {
    auto it = g_bal.tcp_clients.find(fd);
    if (it == g_bal.tcp_clients.end()) return;
    TcpClient &tc = it->second;

    if (events & (EPOLLHUP | EPOLLERR)) {
        tcp_client_close(fd);
        return;
    }
    if (events & EPOLLOUT) {
        if (!tc.conn.flush()) {
            tcp_client_close(fd);
            return;
        }
        tc.last_active = mono_s();
        if (!tc.conn.want_write())
            epoll_mod(fd, EPOLLIN, tag(KIND_TCP_CLIENT, fd));
    }
    if (!(events & EPOLLIN)) return;

    uint8_t buf[16384];
    for (;;) {
        ssize_t n = read(fd, buf, sizeof(buf));
        g_syscalls++;
        if (n < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) break;
            flush_pending_backends();
            tcp_client_close(fd);
            return;
        }
        if (n == 0) {
            flush_pending_backends();
            tcp_client_close(fd);
            return;
        }
        tc.last_active = mono_s();
        auto &rb = tc.conn.rbuf;
        rb.insert(rb.end(), buf, buf + n);
        /* RFC 1035 4.2.2 framing: u16 length + message */
        size_t off = 0;
        {
            ScopedStage _parse(g_stages.frame_parse);
            while (rb.size() - off >= 2) {
                uint16_t mlen = (uint16_t)((rb[off] << 8) | rb[off + 1]);
                if (rb.size() - off - 2 < mlen) break;
                g_bal.tcp_queries++;
                forward_query(tc.key, kTransportTcp,
                              rb.data() + off + 2, mlen);
                off += 2 + mlen;
            }
        }
        if (off > 0) rb.erase(rb.begin(), rb.begin() + off);
        if (rb.size() > kMaxFrame) {  /* garbage flood */
            flush_pending_backends();
            tcp_client_close(fd);
            return;
        }
    }
    flush_pending_backends();
}

/* ---------------- backend responses ---------------- */

/* Harvest a forwarded response into the answer cache when its pending
 * record matches (see the miss path in handle_udp).  Non-SERVFAIL UDP
 * responses under a known backend generation are cacheable;
 * multi-answer responses enter as rotation variants (CacheEntry). */
/* The pending record alone is NOT proof the response answers the
 * recorded question: (client, qid) collide whenever a client has two
 * queries in flight under one qid (routine for stub resolvers), and a
 * slot overwrite would otherwise cache answer A under question B's key.
 * So the response's own echoed question must byte-match the key. */
bool response_matches_key(const PendingFill &pf, const uint8_t *payload,
                          size_t len) {
    uint16_t flags = dnskey_rd16(payload + 2);
    if (!(flags & 0x8000))                       /* not a response */
        return false;
    if (flags & 0x0200)                          /* truncated: payload- */
        return false;                            /* ceiling dependent */
    if (((flags >> 8) & 1) != (pf.key[0] & 1))   /* RD echo */
        return false;
    if (dnskey_rd16(payload + 4) != 1)           /* qdcount */
        return false;
    unsigned ceiling = ((unsigned)pf.key[1] << 8) | pf.key[2];
    if (len > ceiling)
        return false;
    /* question name: uncompressed labels, lowercased compare against
     * the key's qname (key layout: 7 fixed bytes + qname) */
    size_t off = 12;
    size_t klen = (size_t)pf.keylen - 7;
    const uint8_t *kn = pf.key + 7;
    for (size_t i = 0; i < klen; i++) {
        if (off + i >= len)
            return false;
        uint8_t ch = payload[off + i];
        if (ch >= 'A' && ch <= 'Z')
            ch = (uint8_t)(ch + 32);
        if (ch != kn[i])
            return false;
    }
    off += klen;
    if (off + 4 > len)
        return false;
    return payload[off] == pf.key[3] && payload[off + 1] == pf.key[4]
        && payload[off + 2] == pf.key[5] && payload[off + 3] == pf.key[6];
}

void maybe_cache_fill(Backend &be, uint8_t family, const uint8_t *addr16,
                      uint16_t port, const uint8_t *payload, size_t len) {
    if (!be.gen_known || len < 12 + 5 || len > kMaxCacheWire)
        return;
    ScopedStage _ss(g_stages.cache_probe);
    ClientKey ck{};
    ck.family = family;
    memcpy(ck.addr, addr16, 16);
    ck.port = port;
    uint16_t qid = dnskey_rd16(payload);
    PendingFill &pf = g_pending_fill[pending_slot(ck, qid)];
    if (!pf.used || pf.qid != qid || !(pf.client == ck)
            || pf.backend_id != be.id || pf.epoch != be.epoch)
        return;
    if (!response_matches_key(pf, payload, len))
        return;                                  /* qid reuse / mismatch */
    pf.used = false;
    /* matched forward->response pair: record the backend round trip */
    double rtt = mono_s() - pf.sent_at;
    if (rtt >= 0.0) {
        g_bal.fwd_rtt_count++;
        g_bal.fwd_rtt_sum_s += rtt;
        double us = rtt * 1e6;
        int cell = 0;
        while (cell < Balancer::kRttCells - 1 && us >= 1.0) {
            us /= 2.0;
            cell++;
        }
        g_bal.fwd_rtt_cells[cell]++;
    }
    if ((payload[3] & 0x0F) == 2)                /* SERVFAIL */
        return;
    backend_cache_insert(be, pf.key, pf.keylen, payload, len,
                         /* rotatable= */ dnskey_rd16(payload + 6) > 1);
}

void route_response(uint8_t family, uint8_t transport,
                    const uint8_t *addr16, uint16_t port,
                    const uint8_t *payload, size_t len) {
    ScopedStage _ss(g_stages.reply_relay);
    ClientKey k{};
    k.family = family;
    memcpy(k.addr, addr16, 16);
    k.port = port;

    if (transport == kTransportUdp) {
        struct sockaddr_storage ss;
        socklen_t slen;
        sockaddr_from_key(k, &ss, &slen);
        udp_out_add(ss, slen, payload, len);
    } else {
        auto it = g_bal.tcp_by_key.find(k);
        if (it == g_bal.tcp_by_key.end()) {
            g_bal.drops++;   /* client went away */
            return;
        }
        TcpClient &tc = g_bal.tcp_clients[it->second];
        if (tc.conn.wq_bytes >= g_max_client_wq) {
            /* client asked but stopped reading answers: disconnect
             * rather than buffer unboundedly */
            g_bal.wq_overflows++;
            tcp_client_close(it->second);
            return;
        }
        std::vector<uint8_t> out(2 + len);
        out[0] = (uint8_t)(len >> 8);
        out[1] = (uint8_t)(len & 0xff);
        memcpy(out.data() + 2, payload, len);
        tc.conn.queue_write(std::move(out));
        if (!tc.conn.flush()) {
            tcp_client_close(it->second);
            return;
        }
        /* delivering a response is activity: a client whose query takes
         * longer than tcp_idle_ms, or that receives a steady stream of
         * answers without writing again, must not be swept as idle */
        tc.last_active = mono_s();
        if (tc.conn.want_write())
            epoll_mod(tc.conn.fd, EPOLLIN | EPOLLOUT,
                      tag(KIND_TCP_CLIENT, tc.conn.fd));
    }
}

/* Append `n` bytes from a backend connection to its stream buffer and
 * walk the complete frames in it.  Returns false on a protocol error
 * (caller marks the backend down).  Split out of handle_backend so the
 * frame parser can be driven directly with hostile bytes (fuzz target
 * native/fuzz/fuzz_frames.cpp). */
bool backend_consume(Backend &be, const uint8_t *buf, size_t n) {
    /* attribution: the frame walk itself; the nested cache-probe
     * (maybe_cache_fill) and reply-relay (route_response, the batched
     * udp_out_flush) scopes subtract themselves out */
    ScopedStage _ss(g_stages.frame_parse);
    auto &rb = be.conn.rbuf;
    rb.insert(rb.end(), buf, buf + n);
    size_t off = 0;
    bool ok = true;
    std::unordered_set<uint64_t> pending_inval;
    while (rb.size() - off >= 4) {
        uint32_t L;
        memcpy(&L, rb.data() + off, 4);
        L = ntohl(L);
        if (L < kFrameHdr || L > kMaxFrame) {
            logmsg("backend %d protocol error (frame len %u)", be.id, L);
            ok = false;
            break;
        }
        if (rb.size() - off - 4 < L) break;
        const uint8_t *f = rb.data() + off + 4;
        if (f[0] != kProtoVersion) {
            logmsg("backend %d protocol version %u", be.id, f[0]);
            ok = false;
            break;
        }
        if (f[1] == 0) {
            /* control frame; opcode in the transport byte (unknown
             * opcodes are skipped so the channel can grow).
             * 0 = generation (epoch) report: 8 bytes BE in the address
             * field; an advance means a full re-mirror — every cached
             * entry from this backend is stale.
             * 1 = per-name invalidate: the payload after the frame
             * header is the tag qname wire; drop exactly the entries
             * whose answers derive from it (ordinary store churn). */
            if (f[2] == kCtlGen && L >= kFrameHdr) {
                uint64_t g = 0;
                for (int b = 0; b < 8; b++)
                    g = (g << 8) | f[3 + b];
                if (!be.gen_known || be.gen != g)
                    backend_cache_clear(be);   /* all entries stale */
                be.gen = g;
                be.gen_known = true;
            } else if (f[2] == kCtlDirect) {
                /* direct-return capability announce: answer with our
                 * UDP fd over SCM_RIGHTS (unless -D keeps the relay) */
                be.direct_capable = true;
                maybe_pass_fd(be);
            } else if (f[2] == kCtlInvalidate && L > kFrameHdr) {
                size_t tlen = L - kFrameHdr;
                if (tlen >= 2 && tlen <= 256)
                    /* batched: applied in one cache scan after the
                     * frame loop — the backend coalesces one flush of
                     * tags per mutation turn, which arrives as one
                     * read, so churn costs one scan per turn, not one
                     * per tag */
                    pending_inval.insert(fnv64(f + kFrameHdr, tlen));
            }
            off += 4 + L;
            continue;
        }
        uint16_t port = (uint16_t)((f[19] << 8) | f[20]);
        be.responded++;
        if (g_bal.cache_ms > 0 && f[2] == kTransportUdp)
            maybe_cache_fill(be, f[1], f + 3, port, f + kFrameHdr,
                             L - kFrameHdr);
        uint8_t transport = f[2] == kTransportUdpNoStore
            ? kTransportUdp : f[2];
        route_response(f[1], transport, f + 3, port, f + kFrameHdr,
                       L - kFrameHdr);
        off += 4 + L;
    }
    /* batched UDP responses reference rb — flush before it mutates */
    udp_out_flush();
    if (off > 0) rb.erase(rb.begin(), rb.begin() + off);
    if (!pending_inval.empty()) {
        for (auto it = be.cache.begin(); it != be.cache.end(); ) {
            if (pending_inval.count(it->second.taghash) != 0) {
                g_cache_bytes -= it->second.bytes;
                be.cache_bytes -= it->second.bytes;
                g_bal.cache_invalidations++;
                it = be.cache.erase(it);
            } else {
                ++it;
            }
        }
    }
    return ok;
}

void handle_backend(int fd, uint32_t events) {
    auto it = g_bal.backend_by_fd.find(fd);
    if (it == g_bal.backend_by_fd.end()) return;
    Backend &be = g_bal.backends[it->second];

    if (events & (EPOLLHUP | EPOLLERR)) {
        logmsg("backend %d connection lost", be.id);
        backend_mark_down(be);
        return;
    }
    if (events & EPOLLOUT) {
        if (!be.conn.flush()) {
            backend_mark_down(be);
            return;
        }
        if (!be.conn.want_write()) {
            epoll_mod(fd, EPOLLIN, tag(KIND_BACKEND, fd));
            if (be.fd_pass_pending)
                maybe_pass_fd(be);   /* queue just drained */
        }
    }
    if (!(events & EPOLLIN)) return;

    uint8_t buf[16384];
    for (;;) {
        ssize_t n = read(fd, buf, sizeof(buf));
        g_syscalls++;
        if (n < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) break;
            logmsg("backend %d read error: %s", be.id, strerror(errno));
            backend_mark_down(be);
            return;
        }
        if (n == 0) {
            logmsg("backend %d closed connection", be.id);
            backend_mark_down(be);
            return;
        }
        if (!backend_consume(be, buf, (size_t)n)) {
            backend_mark_down(be);
            return;
        }
    }
}

/* ---------------- stats socket ---------------- */

void handle_stats() {
    for (;;) {
        int fd = accept4(g_bal.stats_fd, nullptr, nullptr, SOCK_NONBLOCK);
        if (fd < 0) return;
        std::string out = "{\n";
        /* ~20 u64 fields at up to 20 digits each on top of ~600 bytes
         * of literal text: smaller buffers would truncate near-max
         * counters and emit unparseable stats JSON */
        char line[2048];
        snprintf(line, sizeof(line),
                 "  \"uptime_ms\": %llu,\n  \"udp_queries\": %llu,\n"
                 "  \"tcp_queries\": %llu,\n  \"drops\": %llu,\n"
                 "  \"cache_hits\": %llu,\n  \"cache_misses\": %llu,\n"
                 "  \"uncacheable\": %llu,\n  \"cache_entries\": %zu,\n"
                 "  \"cache_invalidations\": %llu,\n"
                 "  \"fwd_rtt_count\": %llu,\n"
                 "  \"fwd_rtt_sum_s\": %.6f,\n"
                 "  \"backend_wq_peak\": %zu,\n"
                 "  \"tcp_clients\": %zu,\n  \"wq_overflows\": %llu,\n"
                 "  \"idle_closes\": %llu,\n"
                 "  \"client_evictions\": %llu,\n"
                 "  \"backend_stalls\": %llu,\n"
                 "  \"direct_return\": %s,\n"
                 "  \"fd_passes\": %llu,\n"
                 "  \"direct_forwards\": %llu,\n"
                 "  \"syscalls\": %llu,\n"
                 "  \"remotes\": %zu,\n",
                 (unsigned long long)(now_ms() - g_bal.started_at),
                 (unsigned long long)g_bal.udp_queries,
                 (unsigned long long)g_bal.tcp_queries,
                 (unsigned long long)g_bal.drops,
                 (unsigned long long)g_bal.cache_hits,
                 (unsigned long long)g_bal.cache_misses,
                 (unsigned long long)g_bal.uncacheable,
                 [] { size_t n = 0;
                      for (const auto &b : g_bal.backends)
                          n += b.cache.size();
                      return n; }(),
                 (unsigned long long)g_bal.cache_invalidations,
                 (unsigned long long)g_bal.fwd_rtt_count,
                 g_bal.fwd_rtt_sum_s,
                 g_bal.backend_wq_peak,
                 g_bal.tcp_clients.size(),
                 (unsigned long long)g_bal.wq_overflows,
                 (unsigned long long)g_bal.idle_closes,
                 (unsigned long long)g_bal.client_evictions,
                 (unsigned long long)g_bal.backend_stalls,
                 g_no_direct ? "false" : "true",
                 (unsigned long long)g_bal.fd_passes,
                 (unsigned long long)g_bal.direct_forwards,
                 (unsigned long long)g_syscalls,
                 g_bal.remotes.size());
        out += line;
        /* UDP-front recvmmsg batch sizes (log2 cells: 1, 2-3, 4-7,
         * ..., >=128): mass above the first cell proves batching held */
        out += "  \"udp_batch_cells\": [";
        for (int c = 0; c < Balancer::kBatchCells; c++) {
            snprintf(line, sizeof(line), "%s%llu",
                     c == 0 ? "" : ", ",
                     (unsigned long long)g_bal.udp_batch_cells[c]);
            out += line;
        }
        out += "],\n";
        /* forward-RTT histogram: log2 µs upper bounds, open-ended last
         * cell — enough to localize a topology regression to the
         * backend round trip vs the balancer's own packet path */
        out += "  \"fwd_rtt_us_cells\": [";
        for (int c = 0; c < Balancer::kRttCells; c++) {
            snprintf(line, sizeof(line), "%s%llu",
                     c == 0 ? "" : ", ",
                     (unsigned long long)g_bal.fwd_rtt_cells[c]);
            out += line;
        }
        out += "],\n";
        /* per-stage cycle attribution (see the StageCounters comment):
         * exclusive cycles + timed-region count per stage, plus the
         * lifetime-calibrated TSC rate so consumers convert to µs */
        {
            double cal_us = (mono_s() - g_cal_mono0) * 1e6;
            double cpu = cal_us > 0.0
                ? (double)(cycles_now() - g_cal_cycles0) / cal_us : 0.0;
            snprintf(line, sizeof(line),
                     "  \"cycles_per_us\": %.1f,\n"
                     "  \"stage_cycles\": {\n"
                     "    \"frame-parse\": {\"cycles\": %llu, \"ops\": %llu},\n"
                     "    \"cache-probe\": {\"cycles\": %llu, \"ops\": %llu},\n"
                     "    \"backend-write\": {\"cycles\": %llu, \"ops\": %llu},\n"
                     "    \"reply-relay\": {\"cycles\": %llu, \"ops\": %llu}\n"
                     "  },\n",
                     cpu,
                     (unsigned long long)g_stages.frame_parse.cycles,
                     (unsigned long long)g_stages.frame_parse.ops,
                     (unsigned long long)g_stages.cache_probe.cycles,
                     (unsigned long long)g_stages.cache_probe.ops,
                     (unsigned long long)g_stages.backend_write.cycles,
                     (unsigned long long)g_stages.backend_write.ops,
                     (unsigned long long)g_stages.reply_relay.cycles,
                     (unsigned long long)g_stages.reply_relay.ops);
            out += line;
        }
        out += "  \"backends\": [\n";
        /* one pass over the affinity map (reference be_remotes), not
         * one scan per backend */
        std::vector<size_t> remote_counts(g_bal.backends.size(), 0);
        for (const auto &r : g_bal.remotes) {
            if (r.second >= 0 &&
                (size_t)r.second < remote_counts.size())
                remote_counts[r.second]++;
        }
        for (size_t i = 0; i < g_bal.backends.size(); i++) {
            const Backend &be = g_bal.backends[i];
            snprintf(line, sizeof(line),
                     "    {\"id\": %d, \"path\": \"%s\", \"healthy\": %s, "
                     "\"forwarded\": %llu, \"responded\": %llu, "
                     "\"gen_known\": %s, \"gen\": %llu, "
                     "\"wq_bytes\": %zu, \"direct\": %s, "
                     "\"remotes\": %zu}%s\n",
                     be.id, be.path.c_str(), be.healthy ? "true" : "false",
                     (unsigned long long)be.forwarded,
                     (unsigned long long)be.responded,
                     be.gen_known ? "true" : "false",
                     (unsigned long long)be.gen,
                     be.conn.wq_bytes,
                     be.fd_passed ? "true" : "false",
                     remote_counts[i],
                     i + 1 < g_bal.backends.size() ? "," : "");
            out += line;
        }
        out += "  ]\n}\n";
        (void)write(fd, out.data(), out.size());
        close(fd);
    }
}

/* ---------------- setup ---------------- */

/* Bind-address family follows -b: a ':' means IPv6 (with V6ONLY off,
 * so "::" serves both stacks — v4 clients appear as v4-mapped v6
 * addresses, which the frame protocol and backends already carry as
 * family-6). Default stays "0.0.0.0". */
/* `fatal=false` returns -1 on EADDRINUSE instead of dying — used by
 * the ephemeral pair-bind retry, where a collision on the UDP-chosen
 * port just means redraw. */
int listen_front(int socktype, const char *what, bool fatal = true) {
    bool v6 = g_bal.bind_addr.find(':') != std::string::npos;
    int fd = socket(v6 ? AF_INET6 : AF_INET, socktype | SOCK_NONBLOCK, 0);
    if (fd < 0) { perror(what); exit(1); }
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    int rc;
    if (v6) {
        int zero = 0;
        setsockopt(fd, IPPROTO_IPV6, IPV6_V6ONLY, &zero, sizeof(zero));
        struct sockaddr_in6 sin6{};
        sin6.sin6_family = AF_INET6;
        sin6.sin6_port = htons((uint16_t)g_bal.port);
        if (inet_pton(AF_INET6, g_bal.bind_addr.c_str(),
                      &sin6.sin6_addr) != 1) {
            fprintf(stderr, "mbalancer: bad bind address '%s'\n",
                    g_bal.bind_addr.c_str());
            exit(1);
        }
        rc = bind(fd, (struct sockaddr *)&sin6, sizeof(sin6));
    } else {
        struct sockaddr_in sin{};
        sin.sin_family = AF_INET;
        sin.sin_port = htons((uint16_t)g_bal.port);
        if (inet_pton(AF_INET, g_bal.bind_addr.c_str(),
                      &sin.sin_addr) != 1) {
            fprintf(stderr, "mbalancer: bad bind address '%s'\n",
                    g_bal.bind_addr.c_str());
            exit(1);
        }
        rc = bind(fd, (struct sockaddr *)&sin, sizeof(sin));
    }
    if (rc != 0) {
        if (!fatal && errno == EADDRINUSE) {
            close(fd);
            return -1;
        }
        perror(what);
        exit(1);
    }
    return fd;
}

int listen_udp() {
    return listen_front(SOCK_DGRAM, "bind udp");
}

int listen_tcp(bool fatal = true) {
    int fd = listen_front(SOCK_STREAM, "bind tcp", fatal);
    if (fd < 0)
        return -1;
    if (listen(fd, 128) != 0) {
        /* with SO_REUSEADDR a colliding port can pass bind() and fail
         * only here (peer still in its own bind->listen window): the
         * non-fatal caller's redraw loop must handle that shape too */
        if (!fatal && errno == EADDRINUSE) {
            close(fd);
            return -1;
        }
        perror("listen tcp");
        exit(1);
    }
    return fd;
}

int listen_stats() {
    std::string path = g_bal.sockdir + "/.balancer.stats";
    unlink(path.c_str());
    int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) { perror("socket stats"); exit(1); }
    struct sockaddr_un sun{};
    sun.sun_family = AF_UNIX;
    snprintf(sun.sun_path, sizeof(sun.sun_path), "%s", path.c_str());
    if (bind(fd, (struct sockaddr *)&sun, sizeof(sun)) != 0 ||
        listen(fd, 16) != 0) {
        perror("bind stats");
        exit(1);
    }
    return fd;
}

uint16_t local_port(int fd) {
    struct sockaddr_storage ss{};
    socklen_t slen = sizeof(ss);
    getsockname(fd, (struct sockaddr *)&ss, &slen);
    if (ss.ss_family == AF_INET6)
        return ntohs(((struct sockaddr_in6 *)&ss)->sin6_port);
    return ntohs(((struct sockaddr_in *)&ss)->sin_port);
}

void report_port() {
    /* with -p 0 (tests), report the kernel-chosen port on stdout */
    printf("PORT %d\n", local_port(g_bal.udp_fd));
    fflush(stdout);
}

}  // namespace

int main(int argc, char **argv) {
    int c;
    while ((c = getopt(argc, argv, "d:p:b:s:c:T:m:Dv")) != -1) {
        switch (c) {
        case 'd': g_bal.sockdir = optarg; break;
        case 'p': g_bal.port = atoi(optarg); break;
        case 'b': g_bal.bind_addr = optarg; break;
        case 's': g_bal.scan_ms = atoi(optarg); break;
        case 'c': g_bal.cache_ms = atoi(optarg); break;
        case 'T': g_bal.tcp_idle_ms = atoi(optarg); break;
        case 'm': g_bal.max_tcp_clients = atoi(optarg); break;
        case 'D': g_no_direct = 1; break;
        case 'v': g_verbose = 1; break;
        default:
            fprintf(stderr, "usage: mbalancer -d sockdir [-p port] "
                            "[-b bindaddr] [-s scan_ms] [-c cache_ms] "
                            "[-T tcp_idle_ms] [-m max_tcp_clients] "
                            "[-D (disable direct-return fd passing)] "
                            "[-v]\n");
            return 1;
        }
    }
    if (g_bal.sockdir.empty()) {
        fprintf(stderr, "mbalancer: -d sockdir is required\n");
        return 1;
    }
    signal(SIGPIPE, SIG_IGN);
    load_bound_overrides();
    g_bal.started_at = now_ms();
    g_cal_cycles0 = cycles_now();   /* TSC-rate calibration anchors */
    g_cal_mono0 = mono_s();

    g_bal.epfd = epoll_create1(0);
    g_bal.udp_fd = listen_udp();
    g_bal.tcp_fd = listen_tcp();
    g_bal.stats_fd = listen_stats();

    /* Both fronts bind the same port number (production :53/:53).
     * With -p 0 the kernel picks the UDP port — a number any unrelated
     * socket may already hold on TCP — so the rebind is a retry loop:
     * release the draw and redraw instead of dying (observed as a
     * transient startup death under load, "bind tcp: Address already in
     * use"; the backend's ephemeral pair bind handles the same race
     * the same way). */
    if (g_bal.port == 0) {
        close(g_bal.tcp_fd);
        for (int attempt = 0; ; attempt++) {
            g_bal.port = local_port(g_bal.udp_fd);
            g_bal.tcp_fd = listen_tcp(/*fatal=*/false);
            if (g_bal.tcp_fd >= 0)
                break;
            if (attempt >= 15) {
                fprintf(stderr,
                        "mbalancer: no bindable udp/tcp port pair\n");
                exit(1);
            }
            close(g_bal.udp_fd);
            g_bal.port = 0;
            g_bal.udp_fd = listen_udp();
        }
    }

    g_bal.timer_fd = timerfd_create(CLOCK_MONOTONIC, TFD_NONBLOCK);
    struct itimerspec its{};
    its.it_interval.tv_sec = g_bal.scan_ms / 1000;
    its.it_interval.tv_nsec = (g_bal.scan_ms % 1000) * 1000000L;
    its.it_value = its.it_interval;
    timerfd_settime(g_bal.timer_fd, 0, &its, nullptr);

    epoll_add(g_bal.udp_fd, EPOLLIN, tag(KIND_UDP, g_bal.udp_fd));
    epoll_add(g_bal.tcp_fd, EPOLLIN, tag(KIND_TCP_LISTEN, g_bal.tcp_fd));
    epoll_add(g_bal.stats_fd, EPOLLIN, tag(KIND_STATS, g_bal.stats_fd));
    epoll_add(g_bal.timer_fd, EPOLLIN, tag(KIND_TIMER, g_bal.timer_fd));

    scan_sockdir();
    report_port();
    logmsg("listening on %s:%d (udp+tcp), sockdir %s",
           g_bal.bind_addr.c_str(), g_bal.port, g_bal.sockdir.c_str());

    struct epoll_event events[64];
    for (;;) {
        int n = epoll_wait(g_bal.epfd, events, 64, -1);
        g_syscalls++;
        if (n < 0) {
            if (errno == EINTR) continue;
            perror("epoll_wait");
            return 1;
        }
        for (int i = 0; i < n; i++) {
            uint64_t t = events[i].data.u64;
            int evfd = (int)(t & 0xffffffff);
            bool closed = false;
            for (int dfd : g_deferred_close)
                if (dfd == evfd) { closed = true; break; }
            if (closed) continue;   /* stale event for a dying fd */
            Kind kind = (Kind)(t >> 32);
            int fd = (int)(t & 0xffffffff);
            switch (kind) {
            case KIND_UDP: handle_udp(); break;
            case KIND_TCP_LISTEN: handle_tcp_accept(); break;
            case KIND_TCP_CLIENT: handle_tcp_client(fd, events[i].events); break;
            case KIND_BACKEND: handle_backend(fd, events[i].events); break;
            case KIND_STATS: handle_stats(); break;
            case KIND_TIMER: {
                uint64_t expirations;
                while (read(g_bal.timer_fd, &expirations, 8) == 8) {}
                scan_sockdir();
                sweep_connections();
                break;
            }
            }
        }
        for (int dfd : g_deferred_close) close(dfd);
        g_deferred_close.clear();
    }
    return 0;
}
