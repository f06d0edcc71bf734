#!/usr/bin/env python3
"""chip_smoke: prove binder still starts and serves on the chip host.

binder dispatches nothing to the device (ROADMAP S9, PERF.md "Device
path: none"), so "on the chip" means: on the chip's host, with the chip
seen and named by a process that fails without it, the one piece of JAX
code the repo owns (``__graft_entry__.entry()``) run on it once as an
installation check, and the served path driven through its normal
entry points at the size the repo claims:

1. host facts (cores allowed, RAM, compiler, Python/JAX versions);
2. the chip, in a child process — the only process that imports jax;
3. ``make -B -C native`` from the files git would commit, so no stale
   extension that travelled with a copy is what runs;
4. ``python -u -m binder_tpu.main --shards 4`` in ``etc/config.json``'s
   production posture over a synthetic 1,000,000-name zone: asks from
   fresh sockets checked against the zone generator's formula, one
   mutation after ready read back from every worker, native-lane
   counters on every worker, one ``dnsblast`` pass, clean SIGTERM;
5. the JSON summary on stdout (and in ``summary.json``), then as the
   last line ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Any phase that fails exits non-zero; nothing is folded into a field.
Without a TPU the run fails at phase 2 unless ``--cpu`` is given, which
is for trying the command in a sandbox (with ``--hosts``/``--shards``
cut down) and is never chosen by the script itself.  Every number this
prints is a smoke observation, not a benchmark.
"""
import argparse
import importlib.metadata
import json
import os
import platform
import random
import re
import signal
import socket
import subprocess
import sys
import sysconfig
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
for _needed in ("binder_tpu/main.py", "native/Makefile", "etc/config.json",
                "__graft_entry__.py"):
    if not os.path.exists(os.path.join(ROOT, _needed)):
        sys.exit(f"chip_smoke: FAILED in start: {_needed} is not next to "
                 "chip_smoke.py: this is not a binder checkout")
sys.path.insert(0, ROOT)

from binder_tpu.dns import Message, Rcode, Type, make_query  # noqa: E402
from binder_tpu.main import resolve_shard_count  # noqa: E402

READY_TIMEOUT_S = 300.0
SETTLE_TIMEOUT_S = 600.0
#: the chaos clock starts once the whole group serves (main.py arms the
#: driver after ``supervisor.start()``), so this is "seconds after ready"
MUTATE_AT_S = 8
#: the correctness asks' source: outside the RRL allowlist below, so an
#: ordinary client's path (RRL judging it) is what gets checked
ASK_SOURCE = "127.0.1.1"
DNSBLAST_QUERIES = 50000
DNSBLAST_WINDOW = 64
#: dnsblast sockets (-S binds 127.20.x.y): many 4-tuples, so the
#: reuseport hash reaches every worker
DNSBLAST_SOURCES = 64


def fail(phase: str, why: str) -> None:
    sys.exit(f"chip_smoke: FAILED in {phase}: {why}")


def say(msg: str) -> None:
    # stderr: stdout carries the summary and the result line only
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# -- phase 2: the device child (the only process that touches jax) --

def device_child(cpu: bool) -> None:
    import numpy as np
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    want = "cpu" if cpu else "tpu"
    if devices[0].platform != want:
        fail("device", f"jax.devices()[0].platform is "
             f"{devices[0].platform!r}, need {want!r} (no accelerator; "
             "--cpu is the explicit sandbox mode)")
    from __graft_entry__ import entry

    fn, args = entry()
    t0 = time.monotonic()
    out = jax.block_until_ready(jax.jit(fn)(*args))
    first_call_s = time.monotonic() - t0
    if any(o.devices() != {devices[0]} for o in out):
        fail("device", "aggregation did not run on jax.devices()[0]")
    got = [float(o) for o in out]
    ref = [float(np.mean(args[0])), float(np.percentile(args[0], 50.0)),
           float(np.percentile(args[0], 99.0))]
    if not np.allclose(got, ref, rtol=1e-4):
        fail("device", f"aggregation {got} != numpy {ref}")
    print(json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "entry_aggregation": {"samples": int(args[0].size),
                              "mean_p50_p99": got, "numpy": ref,
                              "first_call_s": round(first_call_s, 3)},
    }))


def see_device(cpu: bool) -> dict:
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, "-u", os.path.abspath(__file__),
            "--device-child"] + (["--cpu"] if cpu else [])
    proc = subprocess.run(argv, env=env, cwd=ROOT, text=True,
                          stdout=subprocess.PIPE, timeout=600)
    if proc.returncode != 0:
        fail("device", f"device child exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- phase 1: host facts --

def host_facts() -> dict:
    def first_match(path: str, pat: str):
        with open(path) as f:
            m = re.search(pat, f.read(), re.M)
        return m.group(1).strip() if m else None

    gcc = subprocess.run(["gcc", "--version"], text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    return {
        "cpu_model": first_match("/proc/cpuinfo", r"^model name\s*:(.*)$"),
        "cpu_count": os.cpu_count(),
        "cpus_allowed": len(os.sched_getaffinity(0)),
        "ram_gib": round(int(first_match(
            "/proc/meminfo", r"^MemTotal:\s*(\d+) kB")) / (1 << 20), 1),
        "kernel": platform.release(),
        "hostname": platform.node(),
        "gcc": gcc.stdout.splitlines()[0] if gcc.returncode == 0 else None,
        "python": platform.python_version(),
        "jax": importlib.metadata.version("jax"),
        "shards_auto_would_be": resolve_shard_count({"shards": "auto"}),
    }


# -- phase 3: build from the files git would commit --

def build_native(out_dir: str) -> dict:
    t0 = time.time()
    with open(os.path.join(out_dir, "build.log"), "wb") as log:
        rc = subprocess.call(
            ["make", "-B", "-j", str(len(os.sched_getaffinity(0))),
             "-C", os.path.join(ROOT, "native")],
            stdout=log, stderr=subprocess.STDOUT)
    if rc != 0:
        with open(os.path.join(out_dir, "build.log")) as f:
            sys.stderr.write(f.read()[-4000:])
        fail("build", f"make -B -C native exited {rc}")
    ext = os.path.join(ROOT, "binder_tpu", "_binderfastio"
                       + sysconfig.get_config_var("EXT_SUFFIX"))
    for path in (ext, os.path.join(ROOT, "native/build/dnsblast"),
                 os.path.join(ROOT, "native/build/mbalancer")):
        if not os.path.exists(path) or os.path.getmtime(path) < t0 - 1:
            fail("build", f"{path} was not built (no compiler or no "
                 "Python.h? see build.log)")
    return {"seconds": round(time.time() - t0, 1),
            "extension": os.path.basename(ext)}


# -- phase 4: serve --

class Zone:
    """The synthetic zone's formula (binder_tpu/store/fake.py
    ``populate_synthetic``), restated here so expected answers come
    from a reference independent of the code under test."""

    def __init__(self, domain: str, hosts: int) -> None:
        self.domain = domain
        self.hosts = hosts
        self.racks = max(1, min(1024, hosts // 512))

    def rack(self, r: int) -> str:
        return f"r{r:04d}.zs.{self.domain}"

    def name(self, i: int) -> str:
        return f"h{i:06d}.{self.rack(i % self.racks)}"

    @staticmethod
    def addr(i: int) -> str:
        return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"

    @staticmethod
    def ptr(i: int) -> str:
        return (f"{i & 255}.{(i >> 8) & 255}.{(i >> 16) & 255}"
                ".10.in-addr.arpa")


class Server:
    """The supervisor process, its drained output, and what the output
    says: announce lines, and which worker pid answered which ask (the
    production posture logs every query with the serving pid and the
    client's source port)."""

    def __init__(self, config: str, shards: int, out_dir: str) -> None:
        self.log_path = os.path.join(out_dir, "server.log")
        self.spawned = time.monotonic()
        # -u: the announce lines must not sit in a block buffer; own
        # session: one killpg reaches the workers whatever happens
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "binder_tpu.main", "-f", config,
             "--shards", str(shards)],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            start_new_session=True)
        self.control = []           # parsed non-query log records
        self.ask_pid = {}           # ask source port -> serving pid
        self.query_lines = 0
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def _drain(self) -> None:
        probe = re.compile(rb'"client": ?"' + re.escape(ASK_SOURCE.encode())
                           + rb'"')
        with open(self.log_path, "wb") as log:
            for line in self.proc.stdout:
                is_query = b'"msg": "DNS query"' in line
                if is_query and not probe.search(line):
                    with self._lock:
                        self.query_lines += 1   # load pass: counted only
                    continue
                log.write(line)
                log.flush()
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue        # a traceback line: kept in the file
                with self._lock:
                    if is_query:
                        self.query_lines += 1
                        port = int(str(rec["port"]).split("/")[0])
                        self.ask_pid[port] = rec["pid"]
                    else:
                        self.control.append(rec)

    def wait_msg(self, pattern: str, count: int, timeout: float,
                 what: str) -> list:
        """Block until *count* control records match; the matches."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                hits = [m for m in (rx.search(str(r.get("msg", "")))
                                    for r in self.control) if m]
            if len(hits) >= count:
                return hits
            if self.proc.poll() is not None:
                fail("serve", f"server exited {self.proc.returncode} "
                     f"while waiting for {what} (see {self.log_path})")
            if time.monotonic() > deadline:
                fail("serve", f"no {what} within {timeout:.0f}s "
                     f"(see {self.log_path})")
            time.sleep(0.05)

    def pids_for(self, ports, timeout: float = 10.0) -> list:
        """The pid that served each ask, by the ask's source port, once
        the query log caught up.  (The kernel may hand two fresh
        sockets the same port; the later ask's line then stands for
        both.)"""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                missing = [p for p in ports if p not in self.ask_pid]
                if not missing:
                    return [self.ask_pid[p] for p in ports]
            if time.monotonic() > deadline:
                fail("serve", f"the query log never showed the asks "
                     f"from source ports {missing}")
            time.sleep(0.05)

    def background_s(self, what: str) -> list:
        """How long each worker's background *what* took, by the
        program's own ``<what> done: N names in X s`` log lines (none
        when the zone was small enough to do it inline before ready)."""
        with self._lock:
            msgs = [str(r.get("msg", "")) for r in self.control]
        return [float(m.group(1)) for m in (
            re.search(r"^%s done: \d+ names in ([\d.]+)s" % what, msg)
            for msg in msgs) if m]

    def kill_group(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


#: a service the way registrars write one: ``load_balancer`` members
#: (the synthetic zone's racks hold plain ``host`` children, which the
#: engine, like the reference, does not count as service members).
#: Forty SRV records cannot fit 512 bytes, so plain UDP must truncate.
SERVICE_MEMBERS = 40
SERVICE_PORT = 8080


def service_member(k: int) -> tuple:
    return f"lb{k:02d}", f"10.253.0.{k + 1}"


def write_config(out_dir: str, hosts: int) -> tuple:
    with open(os.path.join(ROOT, "etc", "config.json")) as f:
        cfg = json.load(f)
    base = "/" + "/".join(reversed(cfg["dnsDomain"].split("."))) + "/smoke"
    fixture = {base: {"type": "service", "service": {
        "srvce": "_smoke", "proto": "_tcp", "port": SERVICE_PORT}}}
    for k in range(SERVICE_MEMBERS):
        label, addr = service_member(k)
        fixture[f"{base}/{label}"] = {
            "type": "load_balancer", "load_balancer": {"address": addr}}
    fixture_path = os.path.join(out_dir, "fixture.json")
    with open(fixture_path, "w") as f:
        json.dump(fixture, f)
    cfg["store"] = {"backend": "fake", "fixture": fixture_path,
                    "synthetic": {"hosts": hosts}}
    cfg["port"] = 0
    # a measurement host is allowlisted the way docs/operations.md and
    # tools/chaos_smoke.py do it; ordinary clients (ASK_SOURCE) are not
    cfg["rrl"]["allowlist"] = ["127.20.0.0/16"]
    cfg["chaos"] = {"plan": f"at {MUTATE_AT_S} watch-storm n=8"}
    path = os.path.join(out_dir, "config.json")
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path, cfg["dnsDomain"]


def http_get(port: int, path: str) -> bytes:
    # a worker busy filling a million names answers a scrape in ~15 s
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as r:
        return r.read()


def worker_state(worker: dict) -> dict:
    """One worker's own ``/metrics`` and ``/status``.  The zone gauge
    is registered only when ``_binderfastio`` loaded; serves by the C
    lanes are the zone-table hits plus the answer-cache hits that the
    Python AnswerCache (``/status``) did not count itself."""
    text = http_get(worker["metrics_port"], "/metrics").decode()
    status = json.loads(http_get(worker["metrics_port"], "/status"))

    def total(name: str) -> float:
        return sum(float(v) for v in re.findall(
            r"^%s(?:\{[^}]*\})? ([0-9.eE+-]+)$" % re.escape(name), text,
            re.M))

    if not total("binder_zone_entries"):
        fail("serve", f"worker {worker['pid']} has no native zone table: "
             "the Python fallback is serving")
    return {"zone_entries": int(total("binder_zone_entries")),
            "native_serves": int(total("binder_zone_serves")
                                 + total("binder_answer_cache_hits")
                                 - status["answer_cache"]["hits"]),
            "store": status["store"]["backend"]}


def rss_mb(pid: int) -> float:
    with open(f"/proc/{pid}/status") as f:
        return round(int(re.search(r"^VmRSS:\s*(\d+) kB", f.read(),
                                   re.M).group(1)) / 1024, 1)


def ask_udp(port: int, wire: bytes):
    """One ask on a fresh socket — a new 4-tuple, so the reuseport hash
    draws a worker afresh.  Returns (reply bytes, source port)."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        sock.bind((ASK_SOURCE, 0))
        sock.connect(("127.0.0.1", port))
        sock.settimeout(2.0)
        for _ in range(3):
            sock.send(wire)
            try:
                return sock.recv(65535), sock.getsockname()[1]
            except socket.timeout:
                continue
        fail("serve", "an ask got no answer in 3 tries")
    finally:
        sock.close()


def ask_tcp(port: int, wire: bytes) -> bytes:
    with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
        s.sendall(len(wire).to_bytes(2, "big") + wire)
        buf = b""
        while len(buf) < 2 or len(buf) < 2 + int.from_bytes(buf[:2], "big"):
            chunk = s.recv(65536)
            if not chunk:
                fail("serve", "TCP connection closed mid-answer")
            buf += chunk
        return buf[2:]


class Checks:
    """Every comparison the serve phase makes; the first that does not
    hold ends the run."""

    def __init__(self) -> None:
        self.passed = {}

    def expect(self, kind: str, got, want, detail: str = "") -> None:
        if got != want:
            fail("serve", f"{kind} {detail}: got {got!r}, want {want!r}")
        self.passed[kind] = self.passed.get(kind, 0) + 1


def a_of(reply: bytes) -> tuple:
    msg = Message.decode(reply)
    return msg.rcode, [r.address for r in msg.answers]


def check_answers(udp: int, tcp: int, zone: Zone, rng, checks) -> list:
    """>=64 A asks and 16 PTR asks over the whole zone, each from a
    fresh socket, against the generator's formula; a service's SRV set
    (truncated over plain UDP, whole over the TCP retry); a plain TCP
    ask; an unknown name.  Returns the UDP asks' source ports."""
    ports = []
    for n in range(80):
        i = rng.randrange(zone.hosts)
        if n % 5 == 4:
            reply, sport = ask_udp(udp, make_query(
                zone.ptr(i), Type.PTR, qid=n + 2).encode())
            msg = Message.decode(reply)
            checks.expect("udp_ptr",
                          (msg.rcode, [r.target for r in msg.answers]),
                          (Rcode.NOERROR, [zone.name(i)]), zone.ptr(i))
        else:
            reply, sport = ask_udp(udp, make_query(
                zone.name(i), Type.A, qid=n + 2).encode())
            checks.expect("udp_a", a_of(reply),
                          (Rcode.NOERROR, [zone.addr(i)]), zone.name(i))
        ports.append(sport)

    service = f"smoke.{zone.domain}"
    srv_wire = make_query(f"_smoke._tcp.{service}", Type.SRV, qid=900,
                          edns_payload=None).encode()
    reply, _ = ask_udp(udp, srv_wire)
    checks.expect("srv_udp_truncated", Message.decode(reply).tc, True)
    msg = Message.decode(ask_tcp(tcp, srv_wire))
    members = [service_member(k) for k in range(SERVICE_MEMBERS)]
    checks.expect("srv_tcp_targets",
                  sorted((rec.target, rec.port) for rec in msg.answers),
                  sorted((f"{label}.{service}", SERVICE_PORT)
                         for label, _ in members))
    checks.expect("srv_tcp_glue",
                  sorted((rec.name, rec.address)
                         for rec in msg.additionals if rec.rtype == Type.A),
                  sorted((f"{label}.{service}", addr)
                         for label, addr in members))

    i = rng.randrange(zone.hosts)
    checks.expect("tcp_a", a_of(ask_tcp(tcp, make_query(
        zone.name(i), Type.A, qid=901).encode())),
        (Rcode.NOERROR, [zone.addr(i)]), zone.name(i))
    reply, _ = ask_udp(udp, make_query(f"nosuch.{zone.domain}", Type.A,
                                       qid=902).encode())
    checks.expect("unknown_name_refused", a_of(reply), (Rcode.REFUSED, []))
    return ports


def check_mutation(server: Server, udp: int, wire: bytes, pids: list,
                   checks) -> int:
    """Read-your-writes across the shard log: the chaos watch-storm
    wrote chaos{k} -> 10.254.k.(k+1) in the owner's store after ready;
    ask from fresh sockets until the query log shows every worker
    served the new answer.  Returns the asks it took."""
    server.wait_msg(r"^chaos: injected watch-storm", 1, MUTATE_AT_S + 30,
                    "chaos watch-storm")
    seen_from, asked = set(), 0
    deadline = time.monotonic() + 30
    while seen_from != set(pids):
        if time.monotonic() > deadline:
            fail("serve", "the mutation was read back from workers "
                 f"{sorted(seen_from)} only, of {pids}")
        batch = []
        for _ in range(8):
            reply, sport = ask_udp(udp, wire)
            checks.expect("mutation_read_back", a_of(reply),
                          (Rcode.NOERROR, ["10.254.5.6"]))
            batch.append(sport)
        seen_from |= set(server.pids_for(batch))
        asked += len(batch)
    return asked


def wait_settled(workers: list, hosts: int) -> None:
    """Above 20k names the native zone fill runs in the background
    after ready, and holds each worker's loop while it does.  The load
    pass wants the settled state: every host's zone entry in, and the
    zone gauges the same on two readings two seconds apart."""
    deadline = time.monotonic() + SETTLE_TIMEOUT_S
    last = None
    while True:
        states = [worker_state(w) for w in workers]
        entries = [s["zone_entries"] for s in states]
        if entries == last and min(entries) >= hosts:
            return
        if time.monotonic() > deadline:
            fail("serve", f"not settled after {SETTLE_TIMEOUT_S:.0f}s: "
                 f"{states}")
        last = entries
        time.sleep(2.0)


def load_pass(udp: int, zone: Zone, rng, out_dir: str) -> dict:
    """One dnsblast pass of the bench's shape over seeded names."""
    tmpl = os.path.join(out_dir, "queries.bin")
    with open(tmpl, "wb") as f:
        for i in rng.sample(range(zone.hosts), min(4096, zone.hosts)):
            wire = make_query(zone.name(i), Type.A, qid=0).encode()
            f.write(len(wire).to_bytes(2, "big") + wire)
    return json.loads(subprocess.run(
        [os.path.join(ROOT, "native/build/dnsblast"), "-p", str(udp),
         "-n", str(DNSBLAST_QUERIES), "-w", str(DNSBLAST_WINDOW),
         "-S", str(DNSBLAST_SOURCES), "-t", tmpl],
        stdout=subprocess.PIPE, text=True, timeout=330,
        check=True).stdout)


def serve(args, out_dir: str) -> dict:
    rng = random.Random(args.seed)
    config, domain = write_config(out_dir, args.hosts)
    zone = Zone(domain, args.hosts)
    checks = Checks()
    server = Server(config, args.shards, out_dir)
    try:
        udp = int(server.wait_msg(
            r"^UDP DNS service started on [\d.]+:(\d+)$", 1,
            READY_TIMEOUT_S, "announce line")[0].group(1))
        ready_s = time.monotonic() - server.spawned
        tcp = int(server.wait_msg(
            r"^TCP DNS service started on [\d.]+:(\d+)$", 1, 10,
            "TCP announce line")[0].group(1))
        mport = int(server.wait_msg(
            r"^metrics server started on port (\d+)$", 1, 10,
            "metrics announce line")[0].group(1))
        say(f"phase 4: {args.shards} shards ready in {ready_s:.1f}s "
            f"on udp/tcp {udp}/{tcp}")

        # the mutation has not happened yet: its name is not served
        chaos_wire = make_query(f"chaos5.{domain}", Type.A, qid=1).encode()
        checks.expect("pre_mutation_refused",
                      a_of(ask_udp(udp, chaos_wire)[0]), (Rcode.REFUSED, []))

        workers = json.loads(http_get(mport, "/status"))["shards"]["workers"]
        pids = sorted(w["pid"] for w in workers)
        checks.expect("distinct_worker_pids", len(set(pids)), args.shards)
        rss_ready = {"supervisor": rss_mb(server.proc.pid),
                     "workers": [rss_mb(p) for p in pids]}

        asks_by_worker = {}
        for pid in server.pids_for(check_answers(udp, tcp, zone, rng,
                                                 checks)):
            asks_by_worker[pid] = asks_by_worker.get(pid, 0) + 1
        mutation_asks = check_mutation(server, udp, chaos_wire, pids,
                                       checks)

        wait_settled(workers, args.hosts)
        settled_s = time.monotonic() - server.spawned
        before = {w["pid"]: worker_state(w) for w in workers}
        blast = load_pass(udp, zone, rng, out_dir)
        checks.expect("dnsblast_errors_zero", blast["errors"], 0)

        # proof the C lanes answered, not the Python fallback
        native = []
        for w in workers:
            state = worker_state(w)
            checks.expect("worker_on_replica_store", state["store"],
                          "ReplicaStore", f"pid {w['pid']}")
            served = (state["native_serves"]
                      - before[w["pid"]]["native_serves"])
            if served <= 0:
                fail("serve", f"worker {w['pid']}: the native lanes "
                     f"served {served} of the load pass")
            native.append({"pid": w["pid"], "native_serves": served,
                           "zone_entries": state["zone_entries"],
                           "rss_mb": rss_mb(w["pid"])})

        # SIGTERM: the supervisor exits and no worker survives
        server.proc.send_signal(signal.SIGTERM)
        try:
            rc = server.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            fail("serve", "supervisor ignored SIGTERM for 60s")
        checks.expect("supervisor_exit_0", rc, 0)
        time.sleep(0.2)
        orphans = [p for p in pids if os.path.exists(f"/proc/{p}")]
        checks.expect("no_orphan_pids", orphans, [])
    finally:
        server.kill_group()

    return {
        "names": args.hosts, "shards": args.shards, "domain": domain,
        "time_to_ready_s": round(ready_s, 1),
        "time_to_settled_s": round(settled_s, 1),
        "background_zone_fill_s": server.background_s("zone fill"),
        "rss_mb_at_ready": rss_ready,
        "worker_pids": pids, "worker_store": "ReplicaStore",
        "checks_passed": checks.passed,
        "asks_by_worker": asks_by_worker,
        "mutation_read_back_from": pids,
        "mutation_asks": mutation_asks,
        "native_lane": native,
        "query_log_lines": server.query_lines,
        "dnsblast": blast,
        "orphan_pids": orphans,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="explicit sandbox mode: accept (and require) "
                    "the CPU platform")
    ap.add_argument("--hosts", type=int, default=1_000_000,
                    help="zone size; smaller only for tiny CPU runs")
    ap.add_argument("--shards", type=int, default=4,
                    help="worker processes; fewer only for tiny CPU runs")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"), help="output directory")
    ap.add_argument("--device-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.device_child:
        device_child(args.cpu)
        return
    os.makedirs(args.out, exist_ok=True)
    # a `timeout` or a test harness ends us with SIGTERM: leave through
    # the finally blocks, so the server group is killed too
    signal.signal(signal.SIGTERM, lambda *_: fail("run", "got SIGTERM"))

    host = host_facts()
    say("phase 1: host " + json.dumps(host))
    device = see_device(args.cpu)
    say("phase 2: device " + json.dumps(device))
    build = build_native(args.out)
    say("phase 3: native build " + json.dumps(build))
    served = serve(args, args.out)

    if "jax" in sys.modules:
        fail("summary", "the parent process imported jax")
    summary = {
        "ok": True,
        "device": {"platform": device["platform"],
                   "kind": device["kind"], "count": device["count"]},
        "installation_check": {
            "what": "__graft_entry__.entry() under jax.jit vs NumPy; "
                    "not a layer of binder",
            "compile_cache_dir": device["compile_cache_dir"],
            **device["entry_aggregation"]},
        "host": host,
        "build": build,
        "serve": served,
        "device_work_on_served_path": "none",
        "assumed": {
            "names": "upstream documents no zone size (SURVEY 6); "
                     "1,000,000 is the repo's own claimed ceiling",
            "shards": "4 is what tests/test_shards.py covers; the "
                      "host's allowed cores are recorded, not assumed",
            "rrl_allowlist": "the load generator's sources "
                             "(127.20.0.0/16) only; asks come from "
                             + ASK_SOURCE + ", not allowlisted",
            "mutation": "the chaos watch-storm's 8 writes, "
                        f"{MUTATE_AT_S}s after ready",
        },
        "note": "every number here is a smoke observation, "
                "not a benchmark",
        "claim": None,
    }
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    # the result line: exactly these two keys, last on stdout
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)


if __name__ == "__main__":
    main()
