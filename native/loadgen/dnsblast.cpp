/*
 * dnsblast — windowed DNS load generator (dnsperf-equivalent).
 *
 * The reference repo ships no load tool; its tests shell out to dig(1)
 * (reference test/dig.js:109-134), which cannot measure server capacity.
 * Load driven from Python competes, on a single-core machine, with the
 * server for the same CPU: the client's per-packet interpreter cost caps
 * the measurement.  This native client keeps the measurement overhead at
 * ~1-2us/query so the reported number is server capacity, not client
 * capacity.
 *
 * Protocol behavior:
 *   - window of W queries in flight over one connected UDP socket;
 *   - query wires are templates cycled round-robin with the 2-byte id
 *     rewritten per send (ids unique across the whole run, N <= 65536);
 *   - responses matched by id; rcode != NOERROR counts as an error;
 *   - queries unanswered for RETRY_AFTER are retransmitted (loopback UDP
 *     drops under bursts); retransmitted ids are excluded from latency.
 *
 * TCP modes (reference serves TCP on the same port,
 * lib/server.js:643-653):
 *   -m tcp    W queries in flight pipelined over -T persistent
 *             connections (RFC 1035 2-byte framing), responses matched
 *             by run-unique id;
 *   -m tcp1   one CONNECTION PER QUERY, W concurrent: latency covers
 *             connect + query + response + close — what a
 *             non-keep-alive TCP client experiences.
 *
 * Usage:
 *   dnsblast -p PORT [-H HOST] [-n QUERIES] [-w WINDOW] -t FILE
 *            [-m udp|tcp|tcp1] [-T CONNS] [-S SOURCES]
 * where FILE contains length-prefixed (u16 BE) DNS query wires to cycle.
 * Output: one JSON line {qps, elapsed_s, p50_us, p99_us, errors, retries}.
 *
 * -S SOURCES (UDP mode): spread the load over that many sockets, each
 * bound to its own 127.20.x.y loopback source address (Linux accepts
 * any 127/8 address unconfigured).  One socket = one mega-client, which
 * is exactly the flood shape per-client admission control sheds; the
 * recursion bench axes use -S so they measure forwarding under the
 * server's PRODUCTION admission limits instead of lifting them in
 * config.  If a source bind fails (non-Linux), the socket falls back to
 * the default source — the load still runs, just unspread.
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
#include <poll.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <string>
#include <vector>

namespace {

constexpr double kRetryAfter = 1.0;      /* seconds until retransmit */
constexpr double kRunTimeout = 300.0;    /* overall safety timeout */

double now_s() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

struct Outstanding {
    double sent_at = 0.0;
    bool in_flight = false;
    bool retried = false;
};

void die(const char *msg) {
    perror(msg);
    exit(1);
}

std::vector<std::string> load_templates(const char *path) {
    FILE *f = fopen(path, "rb");
    if (f == nullptr) die("open template file");
    std::vector<std::string> out;
    for (;;) {
        unsigned char hdr[2];
        size_t got = fread(hdr, 1, 2, f);
        if (got == 0) break;
        if (got != 2) { fprintf(stderr, "truncated template file\n"); exit(1); }
        size_t len = ((size_t)hdr[0] << 8) | hdr[1];
        std::string wire(len, '\0');
        if (fread(&wire[0], 1, len, f) != len) {
            fprintf(stderr, "truncated template file\n");
            exit(1);
        }
        if (len < 12) { fprintf(stderr, "template shorter than DNS header\n"); exit(1); }
        out.push_back(std::move(wire));
    }
    fclose(f);
    if (out.empty()) { fprintf(stderr, "no templates\n"); exit(1); }
    return out;
}

void emit_result(long n_queries, double elapsed,
                 std::vector<double> &latencies, long errors,
                 long retries) {
    std::sort(latencies.begin(), latencies.end());
    double p50 = 0.0, p99 = 0.0;
    if (!latencies.empty()) {
        p50 = latencies[latencies.size() / 2] * 1e6;
        p99 = latencies[(size_t)((double)latencies.size() * 0.99)] * 1e6;
    }
    printf("{\"qps\": %.1f, \"elapsed_s\": %.4f, \"p50_us\": %.1f, "
           "\"p99_us\": %.1f, \"errors\": %ld, \"retries\": %ld}\n",
           (double)n_queries / elapsed, elapsed, p50, p99, errors,
           retries);
}

int make_tcp_conn(const struct sockaddr_in *sa) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) die("socket");
    int one = 1;
    (void)setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    if (fcntl(fd, F_SETFL, O_NONBLOCK) != 0) die("fcntl");
    int rv = connect(fd, (const struct sockaddr *)sa, sizeof(*sa));
    if (rv != 0 && errno != EINPROGRESS) die("connect");
    return fd;
}

/* W queries pipelined over `nconns` persistent TCP connections. */
int run_tcp(const struct sockaddr_in *sa,
            const std::vector<std::string> &templates, long n_queries,
            int window, int nconns) {
    struct Conn {
        int fd = -1;
        std::string out;    /* unwritten framed queries */
        size_t out_off = 0;
        std::string in;     /* partial response frames */
    };
    if (nconns > window) nconns = window;
    std::vector<Conn> conns((size_t)nconns);
    for (auto &cn : conns) cn.fd = make_tcp_conn(sa);

    std::vector<Outstanding> state(65536);
    std::vector<double> latencies;
    latencies.reserve((size_t)n_queries);
    long next_idx = 0, received = 0, errors = 0;

    auto enqueue = [&](long idx) {
        const std::string &tmpl = templates[(size_t)idx % templates.size()];
        Conn &cn = conns[(size_t)idx % conns.size()];
        char hdr[2] = {(char)((tmpl.size() >> 8) & 0xff),
                       (char)(tmpl.size() & 0xff)};
        size_t base = cn.out.size();
        cn.out.append(hdr, 2);
        cn.out.append(tmpl);
        cn.out[base + 2] = (char)((idx >> 8) & 0xff);
        cn.out[base + 3] = (char)(idx & 0xff);
        state[(size_t)idx].sent_at = now_s();
        state[(size_t)idx].in_flight = true;
    };

    double t0 = now_s();
    for (int i = 0; i < window && next_idx < n_queries; i++)
        enqueue(next_idx++);

    std::vector<struct pollfd> pfds((size_t)nconns);
    char rbuf[65536];
    while (received < n_queries) {
        for (size_t i = 0; i < conns.size(); i++) {
            pfds[i].fd = conns[i].fd;
            pfds[i].events = POLLIN;
            if (conns[i].out_off < conns[i].out.size())
                pfds[i].events |= POLLOUT;
            pfds[i].revents = 0;
        }
        int rv = poll(pfds.data(), (nfds_t)pfds.size(), 250);
        if (now_s() - t0 > kRunTimeout) {
            fprintf(stderr, "dnsblast: tcp run timed out (%ld/%ld)\n",
                    received, n_queries);
            return 1;
        }
        if (rv <= 0) continue;
        for (size_t i = 0; i < conns.size(); i++) {
            Conn &cn = conns[i];
            if (pfds[i].revents & (POLLERR | POLLHUP)) {
                fprintf(stderr, "dnsblast: tcp connection died\n");
                return 1;
            }
            if ((pfds[i].revents & POLLOUT)
                    && cn.out_off < cn.out.size()) {
                ssize_t put = send(cn.fd, cn.out.data() + cn.out_off,
                                   cn.out.size() - cn.out_off,
                                   MSG_NOSIGNAL);
                if (put > 0) {
                    cn.out_off += (size_t)put;
                    if (cn.out_off == cn.out.size()) {
                        cn.out.clear();
                        cn.out_off = 0;
                    }
                } else if (put < 0 && errno != EAGAIN
                           && errno != EWOULDBLOCK && errno != EINTR) {
                    die("tcp send");
                }
            }
            if (pfds[i].revents & POLLIN) {
                ssize_t got = recv(cn.fd, rbuf, sizeof(rbuf),
                                   MSG_DONTWAIT);
                if (got < 0) {
                    if (errno == EAGAIN || errno == EWOULDBLOCK
                            || errno == EINTR)
                        continue;
                    die("tcp recv");
                }
                if (got == 0) {
                    fprintf(stderr, "dnsblast: server closed tcp\n");
                    return 1;
                }
                cn.in.append(rbuf, (size_t)got);
                size_t off = 0;
                while (cn.in.size() - off >= 2) {
                    size_t flen =
                        ((size_t)(unsigned char)cn.in[off] << 8)
                        | (unsigned char)cn.in[off + 1];
                    if (cn.in.size() - off - 2 < flen) break;
                    const unsigned char *resp =
                        (const unsigned char *)cn.in.data() + off + 2;
                    if (flen >= 4) {
                        unsigned qid = ((unsigned)resp[0] << 8) | resp[1];
                        Outstanding &o = state[qid];
                        if (o.in_flight) {
                            o.in_flight = false;
                            latencies.push_back(now_s() - o.sent_at);
                            if (resp[3] & 0x0f) errors++;
                            received++;
                            if (next_idx < n_queries)
                                enqueue(next_idx++);
                        }
                    }
                    off += 2 + flen;
                }
                if (off > 0) cn.in.erase(0, off);
            }
        }
    }
    double elapsed = now_s() - t0;
    for (auto &cn : conns) close(cn.fd);
    emit_result(n_queries, elapsed, latencies, errors, 0);
    return 0;
}

/* One connection per query, `window` concurrent: measures the full
 * connect+query+response+close cycle. */
int run_tcp1(const struct sockaddr_in *sa,
             const std::vector<std::string> &templates, long n_queries,
             int window) {
    struct Slot {
        int fd = -1;
        long idx = -1;
        double started = 0.0;
        bool sent = false;
        size_t out_off = 0;
        std::string out;
        std::string in;
    };
    if (window > 128) window = 128;   /* fd + accept-queue sanity */
    std::vector<Slot> slots((size_t)window);
    std::vector<double> latencies;
    latencies.reserve((size_t)n_queries);
    long next_idx = 0, received = 0, errors = 0;

    auto open_slot = [&](Slot &s) {
        if (next_idx >= n_queries) {
            s.fd = -1;
            return;
        }
        long idx = next_idx++;
        const std::string &tmpl = templates[(size_t)idx % templates.size()];
        s.fd = make_tcp_conn(sa);
        s.idx = idx;
        s.started = now_s();
        s.sent = false;
        s.out_off = 0;
        s.out.clear();
        char hdr[2] = {(char)((tmpl.size() >> 8) & 0xff),
                       (char)(tmpl.size() & 0xff)};
        s.out.append(hdr, 2);
        s.out.append(tmpl);
        s.out[2] = (char)((idx >> 8) & 0xff);
        s.out[3] = (char)(idx & 0xff);
        s.in.clear();
    };

    double t0 = now_s();
    for (auto &s : slots) open_slot(s);

    std::vector<struct pollfd> pfds((size_t)window);
    char rbuf[65536];
    while (received < n_queries) {
        size_t nfds = 0;
        for (auto &s : slots) {
            if (s.fd < 0) continue;
            pfds[nfds].fd = s.fd;
            pfds[nfds].events = (short)(POLLIN
                | (s.out_off < s.out.size() ? POLLOUT : 0));
            pfds[nfds].revents = 0;
            nfds++;
        }
        if (nfds == 0) break;
        int rv = poll(pfds.data(), (nfds_t)nfds, 250);
        if (now_s() - t0 > kRunTimeout) {
            fprintf(stderr, "dnsblast: tcp1 run timed out (%ld/%ld)\n",
                    received, n_queries);
            return 1;
        }
        if (rv <= 0) continue;
        size_t pi = 0;
        for (auto &s : slots) {
            if (s.fd < 0) continue;
            struct pollfd &p = pfds[pi++];
            if (p.revents & (POLLERR | POLLHUP)) {
                fprintf(stderr, "dnsblast: tcp1 connection died\n");
                return 1;
            }
            if ((p.revents & POLLOUT) && s.out_off < s.out.size()) {
                ssize_t put = send(s.fd, s.out.data() + s.out_off,
                                   s.out.size() - s.out_off,
                                   MSG_NOSIGNAL);
                if (put > 0) s.out_off += (size_t)put;
                else if (put < 0 && errno != EAGAIN
                         && errno != EWOULDBLOCK && errno != EINTR)
                    die("tcp1 send");
            }
            if (p.revents & POLLIN) {
                ssize_t got = recv(s.fd, rbuf, sizeof(rbuf),
                                   MSG_DONTWAIT);
                if (got == 0) {
                    /* peer EOF before a full response (cap refusal,
                     * abort): count it and recycle the slot — spinning
                     * on a readable-EOF fd would burn the run timeout */
                    errors++;
                    close(s.fd);
                    received++;
                    open_slot(s);
                    continue;
                }
                if (got > 0) s.in.append(rbuf, (size_t)got);
                if (s.in.size() >= 2) {
                    size_t flen =
                        ((size_t)(unsigned char)s.in[0] << 8)
                        | (unsigned char)s.in[1];
                    if (s.in.size() >= 2 + flen) {
                        const unsigned char *resp =
                            (const unsigned char *)s.in.data() + 2;
                        if (flen >= 4 && (resp[3] & 0x0f)) errors++;
                        latencies.push_back(now_s() - s.started);
                        received++;
                        close(s.fd);
                        open_slot(s);
                    }
                }
            }
        }
    }
    double elapsed = now_s() - t0;
    for (auto &s : slots)
        if (s.fd >= 0) close(s.fd);
    emit_result(n_queries, elapsed, latencies, errors, 0);
    return 0;
}

}  // namespace

int main(int argc, char **argv) {
    const char *host = "127.0.0.1";
    const char *tmpl_path = nullptr;
    const char *mode = "udp";
    int port = 0;
    long n_queries = 50000;
    int window = 64;
    int nconns = 8;
    int nsources = 1;

    int c;
    while ((c = getopt(argc, argv, "H:p:n:w:t:m:T:S:")) != -1) {
        switch (c) {
        case 'H': host = optarg; break;
        case 'p': port = atoi(optarg); break;
        case 'n': n_queries = atol(optarg); break;
        case 'w': window = atoi(optarg); break;
        case 't': tmpl_path = optarg; break;
        case 'm': mode = optarg; break;
        case 'T': nconns = atoi(optarg); break;
        case 'S': nsources = atoi(optarg); break;
        default:
            fprintf(stderr,
                    "usage: dnsblast -p port [-H host] [-n queries] "
                    "[-w window] [-m udp|tcp|tcp1] [-T conns] "
                    "[-S sources] -t templates\n");
            return 2;
        }
    }
    if (port <= 0 || tmpl_path == nullptr) {
        fprintf(stderr, "dnsblast: -p and -t are required\n");
        return 2;
    }
    if (n_queries < 1 || n_queries > 65536) {
        /* ids must stay unique across the run for unambiguous matching;
         * all three modes index 65536-slot state tables by query idx */
        fprintf(stderr, "dnsblast: -n must be in [1, 65536]\n");
        return 2;
    }
    if (window < 1) window = 1;
    if ((long)window > n_queries) window = (int)n_queries;
    if (nconns < 1) nconns = 1;
    if (nsources < 1) nsources = 1;
    if (nsources > 4096) nsources = 4096;  /* 127.20.x.y address budget */

    std::vector<std::string> templates = load_templates(tmpl_path);

    struct sockaddr_in sa;
    memset(&sa, 0, sizeof(sa));
    sa.sin_family = AF_INET;
    sa.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &sa.sin_addr) != 1) {
        fprintf(stderr, "dnsblast: bad host %s\n", host);
        return 2;
    }
    if (strcmp(mode, "tcp") == 0)
        return run_tcp(&sa, templates, n_queries, window, nconns);
    if (strcmp(mode, "tcp1") == 0)
        return run_tcp1(&sa, templates, n_queries, window);
    if (strcmp(mode, "udp") != 0) {
        fprintf(stderr, "dnsblast: unknown mode %s\n", mode);
        return 2;
    }

    /* -S: one socket per simulated client source (127.20.x.y); query
     * idx is pinned to socket idx % nsources so retransmits keep their
     * original 4-tuple and per-client accounting stays coherent */
    std::vector<int> fds((size_t)nsources, -1);
    for (int j = 0; j < nsources; j++) {
        int fd = socket(AF_INET, SOCK_DGRAM, 0);
        if (fd < 0) die("socket");
        if (nsources > 1) {
            struct sockaddr_in src;
            memset(&src, 0, sizeof(src));
            src.sin_family = AF_INET;
            char addr[32];
            snprintf(addr, sizeof(addr), "127.20.%d.%d", j / 250,
                     (j % 250) + 1);
            if (inet_pton(AF_INET, addr, &src.sin_addr) == 1)
                (void)bind(fd, (struct sockaddr *)&src, sizeof(src));
            /* bind failure: fall through unbound (non-Linux) */
        }
        if (connect(fd, (struct sockaddr *)&sa, sizeof(sa)) != 0)
            die("connect");
        int rcvbuf = 1 << 20;
        (void)setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &rcvbuf,
                         sizeof(rcvbuf));
        fds[(size_t)j] = fd;
    }

    std::vector<Outstanding> state(65536);
    std::vector<double> latencies;
    latencies.reserve((size_t)n_queries);
    long next_idx = 0, received = 0, errors = 0, retries = 0;
    std::string sendbuf;

    auto send_query = [&](long idx, bool is_retry) {
        const std::string &tmpl = templates[(size_t)idx % templates.size()];
        sendbuf.assign(tmpl);
        sendbuf[0] = (char)((idx >> 8) & 0xff);
        sendbuf[1] = (char)(idx & 0xff);
        Outstanding &o = state[(size_t)idx];
        o.sent_at = now_s();
        o.in_flight = true;
        if (is_retry) o.retried = true;
        /* best-effort like the Python client; drops are re-sent by the
         * retransmit sweep */
        (void)send(fds[(size_t)(idx % nsources)], sendbuf.data(),
                   sendbuf.size(), 0);
    };

    double t0 = now_s();
    for (int i = 0; i < window; i++) send_query(next_idx++, false);

    unsigned char rbuf[65535];
    double last_sweep = t0;
    std::vector<struct pollfd> pfds((size_t)nsources);
    while (received < n_queries) {
        for (size_t j = 0; j < fds.size(); j++) {
            pfds[j].fd = fds[j];
            pfds[j].events = POLLIN;
            pfds[j].revents = 0;
        }
        int rv = poll(pfds.data(), (nfds_t)pfds.size(), 250);
        double now = now_s();
        if (now - t0 > kRunTimeout) {
            fprintf(stderr, "dnsblast: run timed out (%ld/%ld answered)\n",
                    received, n_queries);
            return 1;
        }
        if (rv > 0) {
            for (size_t j = 0; j < fds.size() && received < n_queries;
                 j++) {
                if (!(pfds[j].revents & POLLIN)) continue;
                for (;;) {
                    ssize_t got = recv(fds[j], rbuf, sizeof(rbuf),
                                       MSG_DONTWAIT);
                    if (got < 0) {
                        if (errno == EAGAIN || errno == EWOULDBLOCK)
                            break;
                        if (errno == EINTR) continue;
                        die("recv");
                    }
                    if (got < 4) continue;
                    unsigned qid = ((unsigned)rbuf[0] << 8) | rbuf[1];
                    Outstanding &o = state[qid];
                    if (!o.in_flight) continue;  /* dup of a retransmit */
                    now = now_s();
                    o.in_flight = false;
                    if (!o.retried) latencies.push_back(now - o.sent_at);
                    if (rbuf[3] & 0x0f) errors++;
                    received++;
                    if (next_idx < n_queries) send_query(next_idx++, false);
                    if (received >= n_queries) break;
                }
            }
        }
        if (now - last_sweep >= 0.25) {
            last_sweep = now;
            for (long i = 0; i < next_idx; i++) {
                Outstanding &o = state[(size_t)i];
                if (o.in_flight && now - o.sent_at > kRetryAfter) {
                    retries++;
                    send_query(i, true);
                }
            }
        }
    }
    double elapsed = now_s() - t0;
    for (int fd : fds) close(fd);
    emit_result(n_queries, elapsed, latencies, errors, retries);
    return 0;
}
