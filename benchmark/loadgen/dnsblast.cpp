/*
 * dnsblast (benchmark copy) — DNS load generator for a window of seconds.
 *
 * A copy of native/loadgen/dnsblast.cpp, kept under benchmark/ so that the
 * yardstick does not move when the program does.  What the copy changes:
 *
 *   - it runs for a duration (-W warm seconds, then -d measured seconds),
 *     not for a count; ids are unique among one socket's in-flight
 *     queries (slot + generation), not across the run;
 *   - it reads a seeded sequence of template indexes (-q) instead of
 *     cycling the templates; bit 31 of an entry asks for the answer's
 *     bytes to be kept (-c) so the harness can compare them with its
 *     reference once the window has closed;
 *   - closed loop (default): -C callers, each of which sends its next
 *     query when its last one was answered or timed out; a query goes out
 *     on the next of the -S source sockets in turn, not on its caller's
 *     own, as a resolver draws a fresh source port per query: a caller
 *     pinned to one 4-tuple would be pinned to one reuseport worker, and
 *     a few hundred such pins land unevenly (+-20% a worker) and anew in
 *     every run;
 *     open loop (-a): one query per entry of a file of due times, sent
 *     when due whatever is still outstanding; latency is then counted
 *     from the due time, and how late each send left is histogrammed;
 *   - a TC=1 answer is retried once over a one-shot TCP connection from
 *     the same source address (-R), timed across both legs;
 *   - every template carries the rcode and answer count it must get; an
 *     answer with another one, a timeout (-T seconds, no retransmit), a
 *     failed send or TCP leg counts as failed, by kind;
 *   - -j sender threads, each with its own sockets and epoll set, and its
 *     own getrusage(RUSAGE_THREAD) over the window, so that a saturated
 *     or starved generator shows;
 *   - the full latency histogram goes out (log-linear, 512 buckets to
 *     the octave, nanoseconds); percentiles are the harness's arithmetic;
 *   - every template carries the index of the traffic mix's entry it was
 *     drawn from; where the mix has more than one entry the latency
 *     histogram is kept once more for each (one more array index an
 *     answer; a mix of one entry does per answer what it always did, and
 *     its one histogram is the whole), so that a reader can give the
 *     median of one kind of question and its share of the answers;
 *   - where the window is cut into segments (-g, seconds from the window's
 *     first due time) the latency histogram is kept once more for each, by
 *     the query's *due* time (its send time in a closed loop), with the
 *     count of the queries of that segment that failed: the same mechanism,
 *     and a window without segments does per answer what it did plus one
 *     branch, its one histogram the whole; -s names a file that gets the
 *     window's first due time (CLOCK_MONOTONIC, nanoseconds) as soon as it
 *     is fixed, so that the harness can place an event inside the window;
 *   - every sender thread compares each of its loop's own clock reads (the
 *     top of a pass, after each open-loop send, after a pass's events) with
 *     the one before and keeps the intervals of 50 ms or more (`gaps_ns` in
 *     the JSON, a list a thread, offsets from the window's first due time;
 *     warm-up and the wait after the window included): a sender never
 *     sleeps that long, so all threads at once stood still only if the
 *     machine did (stats.py machine_stops); -f gets every measured query
 *     that failed or was unanswered at the end, with its due time and kind,
 *     and -n the due time of every measured send, written only where some
 *     thread saw a gap (8 bytes a send), so that the harness can leave the
 *     queries a stop of the machine covers out of its counts.
 *
 * Files:
 *   -t templates: repeated [u16 BE wire length][u8 expected rcode]
 *                 [u16 BE expected answer count][u8 mix entry][query wire]
 *   -q sequence:  u32 LE template indexes, bit 31 = keep the answer
 *   -a arrivals:  u64 LE nanoseconds after the start (warm-up included),
 *                 non-decreasing; must outlast warm-up + window
 *   -g cuts:      seconds into the window, ascending, comma-separated: the
 *                 boundaries of its segments (n cuts, n + 1 segments)
 *   -s start:     out; the window's first due time, decimal nanoseconds on
 *                 CLOCK_MONOTONIC, written once the start is fixed
 *   -f failures:  out; repeated [i64 LE due time, ns from the window's first
 *                 due time][i64 LE kind: index into "fail_kinds"]
 *   -n sends:     out, only where "gaps_ns" names a gap (the caller removes
 *                 an earlier run's); i64 LE due times as above, a thread's
 *                 after another's
 *   -c captures:  out; repeated [u32 LE sequence position][u32 LE
 *                 template][u8 came over TCP][u16 LE length][answer wire]
 * Output (-o file, else stdout): one JSON object.
 */

#include <arpa/inet.h>
#include <cerrno>
#include <cinttypes>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/resource.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <atomic>
#include <string>
#include <thread>
#include <utility>
#include <vector>

namespace {

constexpr int kHistBits = 9;                    /* 512 buckets / octave */
constexpr size_t kHistSize = 40u << kHistBits;
constexpr uint32_t kCaptureFlag = 0x80000000u;
constexpr int kClosedSlots = 4;       /* in flight per socket, closed loop */
constexpr int kOpenSlots = 64;        /* in flight per socket, open loop */
constexpr size_t kCaptureCap = 8192;  /* answers kept, over all threads */
constexpr int64_t kGapNs = 50000000;  /* between two clock reads: a gap */

int64_t now_ns() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

void die(const char *msg) {
    perror(msg);
    exit(1);
}

void bail(const char *msg) {
    fprintf(stderr, "dnsblast: %s\n", msg);
    exit(1);
}

/* v < 1024: bucket v; above, 512 buckets to each power of two */
size_t hist_bucket(int64_t v) {
    if (v < 0) v = 0;
    if (v < (1LL << (kHistBits + 1))) return (size_t)v;
    int e = 63 - __builtin_clzll((unsigned long long)v);
    int shift = e - kHistBits;
    size_t idx = ((size_t)shift << kHistBits) + (size_t)(v >> shift);
    return idx < kHistSize ? idx : kHistSize - 1;
}

struct Template {
    std::string wire;
    uint8_t rcode = 0;
    uint16_t ancount = 0;
    uint8_t entry = 0;              /* index into the traffic mix */
};

std::string read_file(const char *path) {
    FILE *f = fopen(path, "rb");
    if (f == nullptr) die(path);
    std::string out;
    char buf[1 << 16];
    size_t got;
    while ((got = fread(buf, 1, sizeof(buf), f)) > 0) out.append(buf, got);
    fclose(f);
    return out;
}

std::vector<Template> load_templates(const char *path) {
    std::string raw = read_file(path);
    std::vector<Template> out;
    size_t off = 0;
    while (off < raw.size()) {
        if (raw.size() - off < 6) bail("truncated template file");
        const unsigned char *p = (const unsigned char *)raw.data() + off;
        size_t len = ((size_t)p[0] << 8) | p[1];
        Template t;
        t.rcode = p[2];
        t.ancount = (uint16_t)(((unsigned)p[3] << 8) | p[4]);
        t.entry = p[5];
        if (len < 12 || raw.size() - off - 6 < len)
            bail("bad template length");
        t.wire.assign(raw.data() + off + 6, len);
        out.push_back(std::move(t));
        off += 6 + len;
    }
    if (out.empty()) bail("no templates");
    return out;
}

template <typename T>
std::vector<T> load_array(const char *path) {
    std::string raw = read_file(path);
    if (raw.size() % sizeof(T) != 0 || raw.empty())
        bail("array file is empty or not a whole number of entries");
    std::vector<T> out(raw.size() / sizeof(T));
    memcpy(out.data(), raw.data(), raw.size());
    return out;
}

struct Config {
    struct sockaddr_in server;
    int sources = 64;
    int callers = 256;
    int threads = 1;
    bool open_loop = false;
    bool tc_retry = false;
    int64_t warm_ns = 0, window_ns = 0, timeout_ns = 1000000000LL;
    const std::vector<Template> *templates = nullptr;
    const std::vector<uint32_t> *sequence = nullptr;
    const std::vector<uint64_t> *arrivals = nullptr;
    size_t entries = 1;             /* mix entries the templates name */
    std::vector<int64_t> cuts;      /* segment boundaries, ns into the window */
    int64_t t0 = 0;                 /* start of the warm-up */
};

enum Fail { F_TIMEOUT, F_RCODE, F_ANCOUNT, F_TCP, F_SEND, F_OVERFLOW,
            F_KINDS };
const char *kFailNames[F_KINDS] = {"timeout", "rcode", "ancount", "tcp",
                                   "send", "overflow"};
/* in the failures file, a query still out when its thread ended */
constexpr int64_t kUnanswered = F_KINDS;

struct Slot {
    bool in_flight = false;
    bool measured = false;
    uint16_t id = 0;
    uint16_t gen = 0;
    int tcp = -1;                   /* index into Worker::conns, or -1 */
    uint32_t entry = 0;             /* sequence entry, flag included */
    uint32_t pos = 0;               /* sequence position */
    int64_t ref_ns = 0;             /* due (open) or sent (closed) */
    int64_t sent_ns = 0;
};

struct Conn {
    int fd = -1;
    int sock = 0, slot = 0;
    std::string out;
    size_t out_off = 0;
    std::string in;
};

struct Capture {
    uint32_t pos, tmpl;
    uint8_t tcp;
    std::string wire;
};

struct Stats {
    uint64_t sent = 0, ok = 0, ok_in_window = 0, tc_retries = 0;
    uint64_t fails[F_KINDS] = {0};
    std::vector<uint32_t> lat, late;
    /* per mix entry; empty where the mix has one entry (it is `lat`) */
    std::vector<std::vector<uint32_t>> lat_entry;
    /* per segment of the window; empty where it is not cut (it is `lat`) */
    std::vector<std::vector<uint32_t>> lat_segment;
    std::vector<uint64_t> failed_segment;
    std::vector<uint32_t> inflight_samples;
    /* [start, end) between two clock reads kGapNs apart or more */
    std::vector<std::pair<int64_t, int64_t>> gaps;
    /* (due time, kind) of every measured query that failed, and the due
     * time of every measured send; from the window's first due time */
    std::vector<std::pair<int64_t, int64_t>> failures;
    std::vector<int64_t> sends;
    double cpu_user = 0, cpu_sys = 0;
    Stats(size_t entries, size_t cuts)
        : lat(kHistSize, 0), late(kHistSize, 0),
          lat_entry(entries > 1 ? entries : 0,
                    std::vector<uint32_t>(kHistSize, 0)),
          lat_segment(cuts ? cuts + 1 : 0,
                      std::vector<uint32_t>(kHistSize, 0)),
          failed_segment(cuts ? cuts + 1 : 0, 0) {}
};

std::atomic<uint64_t> g_next_pos{0};
std::atomic<size_t> g_captured{0};

double tv_s(const struct timeval &tv) {
    return (double)tv.tv_sec + (double)tv.tv_usec * 1e-6;
}

class Worker {
  public:
    Worker(const Config &cfg, int tid)
        : stats(cfg.entries, cfg.cuts.size()), cfg_(cfg), tid_(tid) {
        ep_ = epoll_create1(0);
        if (ep_ < 0) die("epoll_create1");
        int cap = cfg.open_loop ? kOpenSlots : kClosedSlots;
        for (int j = tid; j < cfg.sources; j += cfg.threads) {
            Sock s;
            snprintf(s.addr, sizeof(s.addr), "127.20.%d.%d", j / 250,
                     (j % 250) + 1);
            s.fd = socket(AF_INET, SOCK_DGRAM | SOCK_NONBLOCK, 0);
            if (s.fd < 0) die("socket");
            struct sockaddr_in src = source_addr(s.addr);
            if (bind(s.fd, (struct sockaddr *)&src, sizeof(src)) != 0)
                die("bind 127.20.x.y source");
            if (connect(s.fd, (const struct sockaddr *)&cfg.server,
                        sizeof(cfg.server)) != 0)
                die("connect");
            int rcvbuf = 1 << 20;
            (void)setsockopt(s.fd, SOL_SOCKET, SO_RCVBUF, &rcvbuf,
                             sizeof(rcvbuf));
            s.slots.resize((size_t)cap);
            for (int k = cap - 1; k >= 0; k--) s.free_slots.push_back(k);
            struct epoll_event ev;
            ev.events = EPOLLIN;
            ev.data.u64 = socks_.size();
            if (epoll_ctl(ep_, EPOLL_CTL_ADD, s.fd, &ev) != 0)
                die("epoll_ctl");
            socks_.push_back(std::move(s));
        }
        if (socks_.empty()) bail("more threads than sources");
    }

    void run() {
        const int64_t t_meas = cfg_.t0 + cfg_.warm_ns;
        const int64_t t_end = t_meas + cfg_.window_ns;
        while (now_ns() < cfg_.t0) usleep(200);
        /* room for every send now: no reallocation inside the window */
        stats.sends.reserve(cfg_.open_loop
                            ? cfg_.arrivals->size() / (size_t)cfg_.threads + 1
                            : (size_t)1 << 20);
        last_tick_ = now_ns();
        struct rusage ru0, ru1;
        bool ru_started = false, ru_done = false;
        int64_t last_sweep = cfg_.t0, last_sample = t_meas;
        uint64_t k = (uint64_t)tid_;          /* open loop: next arrival */
        if (!cfg_.open_loop)
            for (int c = tid_; c < cfg_.callers; c += cfg_.threads)
                send_next(now_ns(), now_ns(), t_meas, t_end);
        struct epoll_event evs[256];
        for (;;) {
            int64_t now = tick(now_ns());
            if (!ru_started && now >= t_meas) {
                getrusage(RUSAGE_THREAD, &ru0);
                ru_started = true;
            }
            if (!ru_done && now >= t_end) {
                getrusage(RUSAGE_THREAD, &ru1);
                ru_done = true;
            }
            int wait_ms = 20;
            if (cfg_.open_loop && now < t_end) {
                send_due(k, t_meas, t_end);
                /* an open-loop sender never sleeps: a 1 ms epoll_wait
                 * overslept by tens of ms on the chip host, which is
                 * the lateness the cell is there to see */
                wait_ms = 0;
            }
            int n = epoll_wait(ep_, evs, 256, wait_ms);
            if (n < 0 && errno != EINTR) die("epoll_wait");
            for (int i = 0; i < n; i++) {
                uint64_t tag = evs[i].data.u64;
                if (tag >> 63)
                    on_tcp((int)(tag & 0x7fffffff), evs[i].events, t_meas,
                           t_end);
                else
                    on_udp((size_t)tag, t_meas, t_end);
                /* a send that fell due while answers were being read
                 * does not wait for the rest of them */
                if (cfg_.open_loop) send_due(k, t_meas, t_end);
            }
            now = tick(now_ns());
            if (now - last_sweep >= 50000000LL) {
                last_sweep = now;
                sweep(now, t_meas, t_end);
            }
            if (now >= t_meas && now < t_end
                    && now - last_sample >= 100000000LL) {
                last_sample = now;
                stats.inflight_samples.push_back((uint32_t)in_flight_);
            }
            if (now >= t_end && (in_flight_ == 0
                                 || now >= t_end + cfg_.timeout_ns + 100000000LL))
                break;
        }
        for (const Sock &sk : socks_)
            for (const Slot &sl : sk.slots)
                if (sl.in_flight && sl.measured)
                    stats.failures.emplace_back(sl.ref_ns - t_meas,
                                                kUnanswered);
        if (!ru_done) getrusage(RUSAGE_THREAD, &ru1);
        stats.cpu_user = tv_s(ru1.ru_utime) - tv_s(ru0.ru_utime);
        stats.cpu_sys = tv_s(ru1.ru_stime) - tv_s(ru0.ru_stime);
        for (auto &s : socks_) close(s.fd);
        for (auto &c : conns_)
            if (c.fd >= 0) close(c.fd);
        close(ep_);
    }

    Stats stats;
    std::vector<Capture> captures;

  private:
    struct Sock {
        int fd = -1;
        char addr[32];
        std::vector<Slot> slots;
        std::vector<int> free_slots;
    };

    /* one compare a clock read of the loop: a sender that did not get
     * from one read to the next in kGapNs stood still meanwhile */
    int64_t tick(int64_t now) {
        if (now - last_tick_ >= kGapNs)
            stats.gaps.emplace_back(last_tick_, now);
        last_tick_ = now;
        return now;
    }

    /* a measured query failed: by kind, by segment, and for the harness */
    void count_failed(int fail, int64_t into) {
        stats.fails[fail]++;
        if (!stats.failed_segment.empty())
            stats.failed_segment[segment_of(into)]++;
        stats.failures.emplace_back(into, (int64_t)fail);
    }

    static struct sockaddr_in source_addr(const char *addr) {
        struct sockaddr_in src;
        memset(&src, 0, sizeof(src));
        src.sin_family = AF_INET;
        if (inet_pton(AF_INET, addr, &src.sin_addr) != 1)
            bail("bad source address");
        return src;
    }

    /* open loop: every query whose time has come, from arrival k on */
    void send_due(uint64_t &k, int64_t t_meas, int64_t t_end) {
        const std::vector<uint64_t> &arr = *cfg_.arrivals;
        int64_t now = tick(now_ns());
        while (k < arr.size() && cfg_.t0 + (int64_t)arr[k] <= now) {
            int64_t due = cfg_.t0 + (int64_t)arr[k];
            if (due >= t_end) return;
            send_query((uint32_t)k, due, now, t_meas);
            k += (uint64_t)cfg_.threads;
            now = tick(now_ns());
        }
        if (k >= arr.size()) bail("arrivals ran out in the window");
    }

    /* closed loop: the caller's next query, unless the window is over */
    void send_next(int64_t ref, int64_t now, int64_t t_meas,
                   int64_t t_end) {
        if (cfg_.open_loop || now >= t_end) return;
        uint64_t pos = g_next_pos.fetch_add(1, std::memory_order_relaxed);
        send_query((uint32_t)pos, ref, now, t_meas);
    }

    void send_query(uint32_t pos, int64_t ref, int64_t now,
                    int64_t t_meas) {
        const std::vector<uint32_t> &seq = *cfg_.sequence;
        uint32_t entry = seq[pos % seq.size()];
        bool measured = ref >= t_meas;
        if (measured) {
            stats.sent++;
            stats.sends.push_back(ref - t_meas);
        }
        /* the next source socket in turn that has a slot free */
        size_t s = rr_++ % socks_.size();
        for (size_t tries = 1; socks_[s].free_slots.empty(); tries++) {
            if (tries == socks_.size()) {
                if (measured) count_failed(F_OVERFLOW, ref - t_meas);
                return;
            }
            s = rr_++ % socks_.size();
        }
        Sock &sk = socks_[s];
        int si = sk.free_slots.back();
        sk.free_slots.pop_back();
        Slot &sl = sk.slots[(size_t)si];
        size_t cap = sk.slots.size();
        sl.gen = (uint16_t)((sl.gen + 1) % (65536 / cap));
        sl.id = (uint16_t)((size_t)si + cap * sl.gen);
        sl.in_flight = true;
        sl.measured = measured;
        sl.tcp = -1;
        sl.entry = entry;
        sl.pos = pos;
        sl.ref_ns = ref;
        sl.sent_ns = now;
        in_flight_++;
        const Template &t = (*cfg_.templates)[entry & ~kCaptureFlag];
        sendbuf_.assign(t.wire);
        sendbuf_[0] = (char)(sl.id >> 8);
        sendbuf_[1] = (char)(sl.id & 0xff);
        if (cfg_.open_loop && measured)
            stats.late[hist_bucket(now - ref)]++;
        if (send(sk.fd, sendbuf_.data(), sendbuf_.size(), 0) < 0) {
            /* the query never left: it failed, and the slot is free */
            finish(s, si, F_SEND, now, t_meas, INT64_MAX, false);
        }
    }

    /* a query is over: ok (fail < 0) or failed by kind */
    void finish(size_t s, int si, int fail, int64_t now, int64_t t_meas,
                int64_t t_end, bool next) {
        Sock &sk = socks_[s];
        Slot &sl = sk.slots[(size_t)si];
        if (sl.measured) {
            if (fail < 0) {
                stats.ok++;
                if (now <= t_end) stats.ok_in_window++;
                size_t bucket = hist_bucket(now - sl.ref_ns);
                stats.lat[bucket]++;
                if (!stats.lat_entry.empty())
                    stats.lat_entry[(*cfg_.templates)[
                        sl.entry & ~kCaptureFlag].entry][bucket]++;
                if (!stats.lat_segment.empty())
                    stats.lat_segment[segment_of(sl.ref_ns - t_meas)][bucket]++;
            } else {
                count_failed(fail, sl.ref_ns - t_meas);
            }
        }
        sl.in_flight = false;
        sl.tcp = -1;
        sk.free_slots.push_back(si);
        in_flight_--;
        if (next) send_next(now, now, t_meas, t_end);
    }

    /* which segment a query that was due *into* ns into the window is in */
    size_t segment_of(int64_t into) const {
        size_t k = 0;
        while (k < cfg_.cuts.size() && into >= cfg_.cuts[k]) k++;
        return k;
    }

    void keep(const Slot &sl, const unsigned char *wire, size_t len,
              bool tcp) {
        if (!sl.measured || !(sl.entry & kCaptureFlag)) return;
        if (g_captured.fetch_add(1, std::memory_order_relaxed)
                >= kCaptureCap)
            return;
        Capture c;
        c.pos = sl.pos;
        c.tmpl = sl.entry & ~kCaptureFlag;
        c.tcp = tcp ? 1 : 0;
        c.wire.assign((const char *)wire, len);
        captures.push_back(std::move(c));
    }

    /* -1 when the answer is what the template must get */
    int judge(const Slot &sl, const unsigned char *wire) const {
        const Template &t = (*cfg_.templates)[sl.entry & ~kCaptureFlag];
        if ((wire[3] & 0x0f) != t.rcode) return F_RCODE;
        unsigned an = ((unsigned)wire[6] << 8) | wire[7];
        if (an != t.ancount) return F_ANCOUNT;
        return -1;
    }

    void on_udp(size_t s, int64_t t_meas, int64_t t_end) {
        Sock &sk = socks_[s];
        unsigned char rbuf[65535];
        /* as many reads as queries are out on this socket (and one at
         * the least, for a late answer that nobody waits for): with one
         * out, one read and no second one that only says EAGAIN */
        size_t left = sk.slots.size() - sk.free_slots.size();
        for (left = left ? left : 1; left > 0; left--) {
            ssize_t got = recv(sk.fd, rbuf, sizeof(rbuf), MSG_DONTWAIT);
            if (got < 0) {
                if (errno == EINTR) continue;
                /* EAGAIN: drained; ECONNREFUSED and kin: the query
                 * that drew it will time out */
                return;
            }
            if (got < 12 || !(rbuf[2] & 0x80)) continue;
            unsigned id = ((unsigned)rbuf[0] << 8) | rbuf[1];
            int si = (int)(id % sk.slots.size());
            Slot &sl = sk.slots[(size_t)si];
            if (!sl.in_flight || sl.id != id || sl.tcp >= 0)
                continue;               /* late answer to a freed slot */
            int64_t now = now_ns();
            if ((rbuf[2] & 0x02) && cfg_.tc_retry) {
                if (sl.measured) stats.tc_retries++;
                start_tcp(s, si, now, t_meas, t_end);
                continue;
            }
            int fail = judge(sl, rbuf);
            /* TC=1 with no retry asked for: the truncated set is short */
            if (fail < 0) keep(sl, rbuf, (size_t)got, false);
            finish(s, si, fail, now, t_meas, t_end, true);
        }
    }

    void start_tcp(size_t s, int si, int64_t now, int64_t t_meas,
                   int64_t t_end) {
        Sock &sk = socks_[s];
        Slot &sl = sk.slots[(size_t)si];
        int fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
        if (fd < 0) {
            finish(s, si, F_TCP, now, t_meas, t_end, true);
            return;
        }
        int one = 1;
        (void)setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        struct sockaddr_in src = source_addr(sk.addr);
        (void)bind(fd, (struct sockaddr *)&src, sizeof(src));
        int rv = connect(fd, (const struct sockaddr *)&cfg_.server,
                         sizeof(cfg_.server));
        if (rv != 0 && errno != EINPROGRESS) {
            close(fd);
            finish(s, si, F_TCP, now, t_meas, t_end, true);
            return;
        }
        int ci = -1;
        for (size_t i = 0; i < conns_.size(); i++)
            if (conns_[i].fd < 0) { ci = (int)i; break; }
        if (ci < 0) {
            conns_.emplace_back();
            ci = (int)conns_.size() - 1;
        }
        Conn &c = conns_[(size_t)ci];
        c.fd = fd;
        c.sock = (int)s;
        c.slot = si;
        const Template &t = (*cfg_.templates)[sl.entry & ~kCaptureFlag];
        c.out.clear();
        c.out.push_back((char)(t.wire.size() >> 8));
        c.out.push_back((char)(t.wire.size() & 0xff));
        c.out.append(t.wire);
        c.out[2] = (char)(sl.id >> 8);
        c.out[3] = (char)(sl.id & 0xff);
        c.out_off = 0;
        c.in.clear();
        sl.tcp = ci;
        struct epoll_event ev;
        ev.events = EPOLLIN | EPOLLOUT;
        ev.data.u64 = (1ULL << 63) | (uint64_t)ci;
        if (epoll_ctl(ep_, EPOLL_CTL_ADD, fd, &ev) != 0) die("epoll_ctl");
    }

    void close_conn(int ci) {
        Conn &c = conns_[(size_t)ci];
        if (c.fd >= 0) close(c.fd);     /* close drops it from the set */
        c.fd = -1;
    }

    void on_tcp(int ci, uint32_t events, int64_t t_meas, int64_t t_end) {
        Conn &c = conns_[(size_t)ci];
        if (c.fd < 0) return;
        size_t s = (size_t)c.sock;
        int si = c.slot;
        Slot &sl = socks_[s].slots[(size_t)si];
        if ((events & EPOLLOUT) && c.out_off < c.out.size()) {
            ssize_t put = send(c.fd, c.out.data() + c.out_off,
                               c.out.size() - c.out_off, MSG_NOSIGNAL);
            if (put > 0) {
                c.out_off += (size_t)put;
                if (c.out_off == c.out.size()) {
                    struct epoll_event ev;
                    ev.events = EPOLLIN;
                    ev.data.u64 = (1ULL << 63) | (uint64_t)ci;
                    (void)epoll_ctl(ep_, EPOLL_CTL_MOD, c.fd, &ev);
                }
            } else if (put < 0 && errno != EAGAIN && errno != EINTR
                       && errno != ENOTCONN) {
                close_conn(ci);
                finish(s, si, F_TCP, now_ns(), t_meas, t_end, true);
                return;
            }
        }
        if (events & (EPOLLIN | EPOLLHUP | EPOLLERR)) {
            char rbuf[65536];
            for (;;) {
                ssize_t got = recv(c.fd, rbuf, sizeof(rbuf), MSG_DONTWAIT);
                if (got > 0) {
                    c.in.append(rbuf, (size_t)got);
                    continue;
                }
                if (got < 0 && errno == EINTR) continue;
                if (got < 0 && (errno == EAGAIN || errno == EWOULDBLOCK))
                    break;
                /* EOF or error before a whole answer */
                close_conn(ci);
                finish(s, si, F_TCP, now_ns(), t_meas, t_end, true);
                return;
            }
            if (c.in.size() >= 2) {
                size_t flen = ((size_t)(unsigned char)c.in[0] << 8)
                              | (unsigned char)c.in[1];
                if (c.in.size() >= 2 + flen) {
                    const unsigned char *w =
                        (const unsigned char *)c.in.data() + 2;
                    int64_t now = now_ns();
                    int fail = F_TCP;
                    if (flen >= 12 && !(w[2] & 0x02)
                            && ((((unsigned)w[0] << 8) | w[1]) == sl.id))
                        fail = judge(sl, w);
                    if (fail < 0) keep(sl, w, flen, true);
                    close_conn(ci);
                    finish(s, si, fail, now, t_meas, t_end, true);
                }
            }
        }
    }

    void sweep(int64_t now, int64_t t_meas, int64_t t_end) {
        for (size_t s = 0; s < socks_.size(); s++) {
            Sock &sk = socks_[s];
            for (size_t si = 0; si < sk.slots.size(); si++) {
                Slot &sl = sk.slots[si];
                if (!sl.in_flight || now - sl.sent_ns <= cfg_.timeout_ns)
                    continue;
                if (sl.tcp >= 0) close_conn(sl.tcp);
                finish(s, (int)si, F_TIMEOUT, now, t_meas, t_end, true);
            }
        }
    }

    const Config &cfg_;
    int tid_;
    int ep_ = -1;
    std::vector<Sock> socks_;
    std::vector<Conn> conns_;
    std::string sendbuf_;
    size_t rr_ = 0;
    long in_flight_ = 0;
    int64_t last_tick_ = 0;
};

void print_hist(FILE *f, const char *name,
                const std::vector<uint64_t> &h) {
    fprintf(f, "\"%s\": [", name);
    bool first = true;
    for (size_t i = 0; i < h.size(); i++) {
        if (h[i] == 0) continue;
        fprintf(f, "%s[%zu, %" PRIu64 "]", first ? "" : ", ", i, h[i]);
        first = false;
    }
    fprintf(f, "]");
}

}  // namespace

int main(int argc, char **argv) {
    const char *host = "127.0.0.1";
    const char *tmpl_path = nullptr, *seq_path = nullptr;
    const char *arr_path = nullptr, *cap_path = nullptr;
    const char *out_path = nullptr, *start_path = nullptr;
    const char *fail_path = nullptr, *sends_path = nullptr;
    int port = 0;
    double seconds = 10.0, warm = 0.0, timeout = 1.0;
    Config cfg;

    int c;
    while ((c = getopt(argc, argv, "H:p:t:q:a:c:o:d:W:T:C:S:j:Rg:s:f:n:")) != -1) {
        switch (c) {
        case 'H': host = optarg; break;
        case 'p': port = atoi(optarg); break;
        case 't': tmpl_path = optarg; break;
        case 'q': seq_path = optarg; break;
        case 'a': arr_path = optarg; break;
        case 'c': cap_path = optarg; break;
        case 'o': out_path = optarg; break;
        case 'd': seconds = atof(optarg); break;
        case 'W': warm = atof(optarg); break;
        case 'T': timeout = atof(optarg); break;
        case 'C': cfg.callers = atoi(optarg); break;
        case 'S': cfg.sources = atoi(optarg); break;
        case 'j': cfg.threads = atoi(optarg); break;
        case 'R': cfg.tc_retry = true; break;
        case 's': start_path = optarg; break;
        case 'f': fail_path = optarg; break;
        case 'n': sends_path = optarg; break;
        case 'g':
            for (const char *p = optarg; *p != '\0';) {
                char *end;
                double at = strtod(p, &end);
                if (end == p || at <= 0
                        || (!cfg.cuts.empty()
                            && (int64_t)(at * 1e9) <= cfg.cuts.back()))
                    bail("-g: seconds into the window, ascending");
                cfg.cuts.push_back((int64_t)(at * 1e9));
                p = *end == ',' ? end + 1 : end;
            }
            break;
        default:
            fprintf(stderr,
                    "usage: dnsblast -p port -t templates -q sequence "
                    "-d seconds [-W warm] [-T timeout] [-S sources] "
                    "[-C callers] [-j threads] [-a arrivals] "
                    "[-R] [-g cut,cut,...] [-s start file] [-c captures] "
                    "[-f failures] [-n sends] [-o out.json] [-H host]\n");
            return 2;
        }
    }
    if (port <= 0 || tmpl_path == nullptr || seq_path == nullptr)
        bail("-p, -t and -q are required");
    if (seconds <= 0 || warm < 0 || timeout <= 0) bail("bad -d/-W/-T");
    if (cfg.sources < 1 || cfg.sources > 4096) bail("-S in [1, 4096]");
    if (cfg.callers < cfg.threads
            || cfg.callers > cfg.sources * kClosedSlots)
        bail("-C in [threads, 4 x sources]");
    if (cfg.threads < 1 || cfg.threads > cfg.sources)
        bail("-j in [1, sources]");

    std::vector<Template> templates = load_templates(tmpl_path);
    for (const Template &t : templates)
        if ((size_t)t.entry + 1 > cfg.entries) cfg.entries = t.entry + 1u;
    std::vector<uint32_t> sequence = load_array<uint32_t>(seq_path);
    for (uint32_t e : sequence)
        if ((e & ~kCaptureFlag) >= templates.size())
            bail("sequence names a template that is not there");
    std::vector<uint64_t> arrivals;
    if (arr_path != nullptr) {
        arrivals = load_array<uint64_t>(arr_path);
        cfg.open_loop = true;
        if (arrivals.size() > sequence.size())
            bail("more arrivals than sequence entries");
    }
    memset(&cfg.server, 0, sizeof(cfg.server));
    cfg.server.sin_family = AF_INET;
    cfg.server.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &cfg.server.sin_addr) != 1)
        bail("bad host");
    cfg.warm_ns = (int64_t)(warm * 1e9);
    cfg.window_ns = (int64_t)(seconds * 1e9);
    cfg.timeout_ns = (int64_t)(timeout * 1e9);
    cfg.templates = &templates;
    cfg.sequence = &sequence;
    cfg.arrivals = &arrivals;

    std::vector<Worker *> workers;
    for (int t = 0; t < cfg.threads; t++)
        workers.push_back(new Worker(cfg, t));
    cfg.t0 = now_ns() + 20000000LL;
    if (start_path != nullptr) {
        /* written under another name and renamed: a reader that finds the
         * file finds the whole number */
        std::string tmp = std::string(start_path) + ".tmp";
        FILE *f = fopen(tmp.c_str(), "w");
        if (f == nullptr) die(start_path);
        fprintf(f, "%" PRId64 "\n", cfg.t0 + cfg.warm_ns);
        if (fclose(f) != 0 || rename(tmp.c_str(), start_path) != 0)
            die(start_path);
    }
    std::vector<std::thread> threads;
    for (Worker *w : workers) threads.emplace_back([w] { w->run(); });
    for (auto &t : threads) t.join();

    Stats total(0, 0);
    std::vector<uint64_t> lat(kHistSize, 0), late(kHistSize, 0);
    std::vector<std::vector<uint64_t>> lat_entry(
        cfg.entries, std::vector<uint64_t>(kHistSize, 0));
    const size_t segments = cfg.cuts.size() + 1;
    std::vector<std::vector<uint64_t>> lat_segment(
        segments, std::vector<uint64_t>(kHistSize, 0));
    std::vector<uint64_t> failed_segment(segments, 0);
    size_t samples = 0;
    for (Worker *w : workers) {
        total.sent += w->stats.sent;
        total.ok += w->stats.ok;
        total.ok_in_window += w->stats.ok_in_window;
        total.tc_retries += w->stats.tc_retries;
        for (int k = 0; k < F_KINDS; k++)
            total.fails[k] += w->stats.fails[k];
        for (size_t i = 0; i < kHistSize; i++) {
            lat[i] += w->stats.lat[i];
            late[i] += w->stats.late[i];
        }
        for (size_t e = 0; e < w->stats.lat_entry.size(); e++)
            for (size_t i = 0; i < kHistSize; i++)
                lat_entry[e][i] += w->stats.lat_entry[e][i];
        for (size_t g = 0; g < w->stats.lat_segment.size(); g++) {
            for (size_t i = 0; i < kHistSize; i++)
                lat_segment[g][i] += w->stats.lat_segment[g][i];
            failed_segment[g] += w->stats.failed_segment[g];
        }
        if (samples == 0 || w->stats.inflight_samples.size() < samples)
            samples = w->stats.inflight_samples.size();
    }

    if (cap_path != nullptr) {
        FILE *f = fopen(cap_path, "wb");
        if (f == nullptr) die(cap_path);
        for (Worker *w : workers)
            for (const Capture &cp : w->captures) {
                uint16_t len = (uint16_t)cp.wire.size();
                fwrite(&cp.pos, 4, 1, f);
                fwrite(&cp.tmpl, 4, 1, f);
                fwrite(&cp.tcp, 1, 1, f);
                fwrite(&len, 2, 1, f);
                fwrite(cp.wire.data(), 1, cp.wire.size(), f);
            }
        if (fclose(f) != 0) die("write captures");
    }

    if (fail_path != nullptr) {
        FILE *f = fopen(fail_path, "wb");
        if (f == nullptr) die(fail_path);
        for (Worker *w : workers)
            fwrite(w->stats.failures.data(), sizeof(w->stats.failures[0]),
                   w->stats.failures.size(), f);
        if (fclose(f) != 0) die("write failures");
    }
    bool any_gap = false;
    for (Worker *w : workers) any_gap = any_gap || !w->stats.gaps.empty();
    if (sends_path != nullptr && any_gap) {
        FILE *f = fopen(sends_path, "wb");
        if (f == nullptr) die(sends_path);
        for (Worker *w : workers)
            fwrite(w->stats.sends.data(), sizeof(int64_t),
                   w->stats.sends.size(), f);
        if (fclose(f) != 0) die("write sends");
    }

    FILE *out = out_path != nullptr ? fopen(out_path, "w") : stdout;
    if (out == nullptr) die(out_path);
    uint64_t failed = 0;
    for (int k = 0; k < F_KINDS; k++) failed += total.fails[k];
    fprintf(out, "{\"loop\": \"%s\", \"window_s\": %.6f, \"warm_s\": %.6f, "
            "\"threads\": %d, \"sources\": %d, \"callers\": %d, "
            "\"sent\": %" PRIu64 ", \"ok\": %" PRIu64 ", "
            "\"ok_in_window\": %" PRIu64 ", \"failed\": %" PRIu64 ", "
            "\"unanswered_at_end\": %" PRIu64 ", "
            "\"tc_retries\": %" PRIu64 ", \"fails\": {",
            cfg.open_loop ? "open" : "closed", seconds, warm, cfg.threads,
            cfg.sources, cfg.open_loop ? 0 : cfg.callers, total.sent,
            total.ok,
            total.ok_in_window, failed, total.sent - total.ok - failed,
            total.tc_retries);
    for (int k = 0; k < F_KINDS; k++)
        fprintf(out, "%s\"%s\": %" PRIu64, k ? ", " : "", kFailNames[k],
                total.fails[k]);
    /* the kinds' names in the failures file's numbering, and each thread's
     * gaps as [start, length], ns from the window's first due time */
    fprintf(out, "}, \"fail_kinds\": [");
    for (int k = 0; k < F_KINDS; k++) fprintf(out, "\"%s\", ", kFailNames[k]);
    fprintf(out, "\"unanswered_at_end\"], \"gap_least_ns\": %" PRId64
            ", \"gaps_ns\": [", kGapNs);
    for (size_t t = 0; t < workers.size(); t++) {
        fprintf(out, "%s[", t ? ", " : "");
        const auto &gaps = workers[t]->stats.gaps;
        for (size_t i = 0; i < gaps.size(); i++)
            fprintf(out, "%s[%" PRId64 ", %" PRId64 "]", i ? ", " : "",
                    gaps[i].first - cfg.t0 - cfg.warm_ns,
                    gaps[i].second - gaps[i].first);
        fprintf(out, "]");
    }
    fprintf(out, "], \"thread_cpu_s\": [");
    for (size_t t = 0; t < workers.size(); t++)
        fprintf(out, "%s[%.6f, %.6f]", t ? ", " : "",
                workers[t]->stats.cpu_user, workers[t]->stats.cpu_sys);
    /* queries in flight over all threads, every 100 ms of the window */
    fprintf(out, "], \"inflight\": [");
    for (size_t i = 0; i < samples; i++) {
        uint64_t sum = 0;
        for (Worker *w : workers) sum += w->stats.inflight_samples[i];
        fprintf(out, "%s%" PRIu64, i ? ", " : "", sum);
    }
    fprintf(out, "], \"hist_bits\": %d, ", kHistBits);
    print_hist(out, "latency_ns", lat);
    fprintf(out, ", ");
    print_hist(out, "late_ns", late);
    /* the latency histogram of each mix entry */
    fprintf(out, ", \"latency_ns_by_entry\": [");
    for (size_t e = 0; e < cfg.entries; e++) {
        fprintf(out, "%s{", e ? ", " : "");
        print_hist(out, "latency_ns", cfg.entries > 1 ? lat_entry[e] : lat);
        fprintf(out, "}");
    }
    /* and of each segment of the window, by the queries' due times, with
     * the bounds in seconds and how many of its queries failed */
    fprintf(out, "], \"latency_ns_by_segment\": [");
    for (size_t g = 0; g < segments; g++) {
        fprintf(out, "%s{\"from_s\": %.6f, \"to_s\": %.6f, \"failed\": %"
                PRIu64 ", ", g ? ", " : "",
                g ? (double)cfg.cuts[g - 1] / 1e9 : 0.0,
                g + 1 < segments ? (double)cfg.cuts[g] / 1e9 : seconds,
                segments > 1 ? failed_segment[g] : failed);
        print_hist(out, "latency_ns", segments > 1 ? lat_segment[g] : lat);
        fprintf(out, "}");
    }
    fprintf(out, "]}\n");
    if (out != stdout && fclose(out) != 0) die("write result");
    for (Worker *w : workers) delete w;
    return 0;
}
