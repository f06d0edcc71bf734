#!/usr/bin/env python3
"""The benchmark's one command: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

A new process each time, nothing outliving it.  In order: the device child
names the chip (the run fails without one; ``--cpu`` is the rehearsal mode
and prints its numbers as a rehearsal, never as a result); ``make`` builds
``native/`` and the generator where they are not built yet; the zone, the
store fixture, the query templates, their sequence and the arrival schedule
are made from ``--seed``; the command that the configuration's ``entry``
names is spawned in ``etc/config.json``'s production posture (``python -m
binder_tpu.main --shards N``: a supervisor and its reuseport workers; or
``bin/binder-topology start``: N binders behind ``mbalancer``; the
configuration's ``topology`` says which *group* that makes, ``Group``) and
waited for until it is *settled*; a seeded sample of the cell's own
questions is asked from
fresh sockets (one of every entry of its mix and the zone's largest set
among them) and compared with ``reference.py``, the chaos plan's write is
read back from every worker; the generator warms up and then drives the
window, and where the workload names ``events`` each is delivered at its
offset inside it (a SIGHUP to the supervisor rolls every shard: the run then
waits for the roll's end and takes the group's new workers for what
follows); the answers it kept are compared with the reference, the sample
and the written names are asked once more; the queries that a stop of the
machine covers (every sender thread of the generator stood still at once,
for a quarter second or more) leave ``attempted`` and ``failed``; the
group's clean stop (SIGTERM to the supervisor, ``binder-topology stop``)
must end it with exit 0 and no orphan of any generation.  The last line of
stdout is the result object.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name: ``configs/<name>.json``,
``workloads/<name>.json``, ``layer_metrics/<name>.py`` (README.md).
"""
import argparse
import ctypes
import fcntl
import importlib.util
import json
import os
import random
import re
import shlex
import shutil
import signal
import socket
import struct
import subprocess
import sys
import sysconfig
import tempfile
import threading
import time
import urllib.request

import numpy as np

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import dnswire  # noqa: E402
import stats  # noqa: E402
from reference import Zone, compare, rng_for  # noqa: E402
from traffic import Traffic  # noqa: E402

READY_TIMEOUT_S = 240.0
SETTLE_TIMEOUT_S = 240.0
ROLL_TIMEOUT_S = 120.0      # after the window, for a roll's last line
#: the correctness asks' source: outside the RRL allowlist, so an ordinary
#: client's path (RRL judging it) is what gets checked
ASK_SOURCE = "127.0.1.1"
ASKS = 96                   # seeded sample asked before and after the window
GENERATOR = os.path.join(HERE, "loadgen", "build", "dnsblast")
#: what the generator writes, under the run's ``out`` directory, by flag
GENERATOR_OUT = {"-o": "generator.json", "-c": "captures.bin",
                 "-f": "failures.bin", "-n": "sends.bin",
                 "-s": "window_start"}
BREAKS = ("reference-address", "reference-declined", "reference-opt",
          "fixture-address", "skew-replica")
#: the two controls of the stop rule (``Stop``): no wrong run, ``correct``
#: is untouched; what they move is ``stops``, ``voided`` and ``failed``
STOP_BREAKS = ("machine-stop", "server-stop")
STOP_BREAK_S = 1.5


def fail(phase: str, why: str) -> None:
    sys.exit(f"benchmark: FAILED in {phase}: {why}")


def say(msg: str) -> None:
    print(f"benchmark: {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# -- the device child --

class DeviceChild:
    """``device.py`` in its own process: the only one that imports jax."""

    def __init__(self, cpu: bool, traced: bool) -> None:
        env = dict(os.environ)
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.join(HERE, "device.py"), ROOT,
             "cpu" if cpu else "tpu", "1" if traced else "0"],
            env=env, cwd=ROOT, text=True, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE)

    def _line(self, timeout: float) -> dict:
        box = []
        reader = threading.Thread(
            target=lambda: box.append(self.proc.stdout.readline()),
            daemon=True)
        reader.start()
        reader.join(timeout)
        if not box or not box[0].strip():
            self.kill()
            fail("device", "the device child named no device (exit "
                 f"{self.proc.poll()})")
        return json.loads(box[0])

    def device(self) -> dict:
        return self._line(600.0)

    def tell(self, word: str) -> None:
        self.proc.stdin.write(word + "\n")
        self.proc.stdin.flush()

    def traced_window(self) -> dict:
        return self._line(120.0)

    def finish(self) -> None:
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.kill()
            fail("device", "the device child did not exit")
        if rc != 0:
            fail("device", f"the device child exited {rc}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


# -- build --

def build(out_dir: str) -> None:
    """``make`` (no -B): the first run in a checkout builds, later runs
    find the build.  No Python fallback: a missing extension or generator
    fails the run."""
    jobs = str(len(os.sched_getaffinity(0)))
    with open(os.path.join(out_dir, "build.log"), "wb") as log:
        for target in (os.path.join(ROOT, "native"),
                       os.path.join(HERE, "loadgen")):
            rc = subprocess.call(["make", "-j", jobs, "-C", target],
                                 stdout=log, stderr=subprocess.STDOUT)
            if rc != 0:
                fail("build", f"make -C {target} exited {rc} (see "
                     f"{log.name})")
    ext = os.path.join(ROOT, "binder_tpu", "_binderfastio"
                       + sysconfig.get_config_var("EXT_SUFFIX"))
    for path in (ext, GENERATOR):
        if not os.path.exists(path):
            fail("build", f"{path} was not built")


# -- the group: what a configuration's ``entry`` starts --

QUERY_LINE = '"msg": "DNS query"'
#: what a member with a store of its own says when the plan's write is made
WRITE_LINE = r"^chaos: injected watch-storm"


def entry_argv(config: dict, config_path: str, rundir: str,
               base_port="") -> list:
    """The command a configuration's ``entry`` names, as it is spawned:
    split as a shell splits it, ``{config}`` the server config this run
    wrote, ``{rundir}`` the run directory a topology may keep its state
    in, ``{shards}`` the configuration's own key of that name,
    ``{base_port}`` the first of the ports this run found free for the
    members (``free_base_port``); a first word ``python`` is this
    interpreter."""
    words = [word.format(config=config_path, rundir=rundir,
                         shards=config["shards"], base_port=base_port)
             for word in shlex.split(config["entry"])]
    if words[0] == "python":
        words[0] = sys.executable
    return words


class Group:
    """The processes a configuration's ``entry`` starts, and what ``run``
    asks of them whatever the topology: the ports clients use
    (``wait_ready``), the members now (``members``), what stands in front
    of or above them (``scrape_front``), the control lines of their output
    (``find_msg``, ``wait_msg``, ``wait_written``), signals (``deliver``,
    ``signal_all``), a clean stop (``stop``, ``gone``) and a kill of
    everything (``kill``).

    The production posture logs every query, the native lanes' from C too:
    15 MB a second at the hosts cell's rate.  A reader that falls behind
    fills the pipe and every writer blocks in its next log write; a file
    would grow by half a gigabyte a window.  So every stream of output is a
    pipe made as large as the kernel allows and drained by a ``grep``
    process of its own, which drops the query lines; only the announce and
    chaos lines reach this (Python) process and ``server.log``."""

    #: seconds between the window's end and the closing scrape
    SCRAPE_LAG_S = 0.0
    #: where the correctness asks come from, in turn
    ask_sources = [ASK_SOURCE]
    def __init__(self, config: dict, config_path: str, out_dir: str) -> None:
        self.shards = int(config["shards"])
        self.out_dir = out_dir
        self.log_path = os.path.join(out_dir, "server.log")
        self.control = []               # parsed log records
        self._lock = threading.Lock()
        self._log = open(self.log_path, "wb")
        self._filters = []
        self._threads = []
        self.ready_s = None
        self.spawned = time.monotonic()

    def drain(self, stream, member=None) -> None:
        """Drain one stream of the group's output (a pipe's read end)
        through a ``grep`` of its own, from a thread of its own."""
        try:
            fcntl.fcntl(stream, fcntl.F_SETPIPE_SZ, 1 << 20)
        except OSError:
            pass                        # the default 64 KiB then
        grep = subprocess.Popen(
            ["grep", "--line-buffered", "-v", "-F", QUERY_LINE],
            stdin=stream, stdout=subprocess.PIPE)
        self._filters.append(grep)
        thread = threading.Thread(target=self._drain, daemon=True,
                                  args=(grep.stdout, member))
        thread.start()
        self._threads.append(thread)

    def _drain(self, lines, member) -> None:
        for line in lines:
            with self._lock:
                self._log.write(line)
                self._log.flush()
            try:
                rec = json.loads(line)
            except ValueError:
                continue                # a traceback line: in the file
            if not isinstance(rec, dict):
                continue
            rec["arrived"] = time.monotonic()
            rec["member"] = member
            with self._lock:
                self.control.append(rec)

    def matches(self, pattern: str) -> list:
        """``(match, when the record arrived here, member)`` of every
        record whose message matches, in order of arrival."""
        rx = re.compile(pattern)
        with self._lock:
            return [(m, rec["arrived"], rec["member"])
                    for rec in self.control
                    for m in [rx.search(str(rec.get("msg", "")))] if m]

    def find_msg(self, pattern: str):
        """(match, when the record arrived here) of the first record
        whose message matches, or None."""
        found = self.matches(pattern)
        return found[0][:2] if found else None

    def exited(self):
        """The exit code of a process whose end is the group's end, or
        None while the group stands."""
        raise NotImplementedError

    def wait_for(self, done, timeout: float, what: str, fatal: bool = True):
        """What *done* returns as soon as it is true; without that in time
        the run fails, or (not *fatal*) None comes back."""
        deadline = time.monotonic() + timeout
        while True:
            found = done()
            if found:
                return found
            if self.exited() is not None:
                fail("serve", f"server exited {self.exited()} "
                     f"while waiting for {what} (see {self.log_path})")
            if time.monotonic() > deadline:
                if not fatal:
                    return None
                fail("serve", f"no {what} within {timeout:.0f}s "
                     f"(see {self.log_path})")
            time.sleep(0.02)

    def wait_msg(self, pattern: str, timeout: float, what: str,
                 fatal: bool = True):
        """The match of the first record whose message matches."""
        found = self.wait_for(lambda: self.find_msg(pattern), timeout, what,
                              fatal)
        return found[0] if found else None

    def check(self, members: list) -> list:
        pids = {m["pid"] for m in members}
        if len(pids) != self.shards:
            fail("serve", f"{len(pids)} worker pids for "
                 f"{self.shards} shards")
        return members

    def end_drains(self, kill: bool) -> None:
        for grep in self._filters:
            if kill and grep.poll() is None:
                grep.kill()
            try:
                grep.wait(timeout=10)
            except subprocess.TimeoutExpired:   # a writer is still there
                grep.kill()
                grep.wait()
        for thread in self._threads:
            thread.join(5)


class SupervisorGroup(Group):
    """``binder_tpu.main --shards N``: one supervisor, one mutation log,
    N reuseport workers that share the supervisor's stdout.  The group is
    what the supervisor's ``/status`` says it is."""

    SCRAPE_LAG_S = 1.5      # the workers report to the supervisor at 1 Hz

    def __init__(self, config: dict, config_path: str, out_dir: str) -> None:
        super().__init__(config, config_path, out_dir)
        # -u: the announce lines must not sit in a block buffer; own
        # session: one killpg reaches the workers whatever happens
        self.proc = subprocess.Popen(
            entry_argv(config, config_path, out_dir),
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            start_new_session=True)
        self.drain(self.proc.stdout)
        self.proc.stdout.close()        # the filter holds it now
        self.mport = None

    def exited(self):
        return self.proc.poll()

    def wait_ready(self) -> tuple:
        """(UDP port, TCP port) of the group's reuseport sockets."""
        udp = int(self.wait_msg(
            r"^UDP DNS service started on [\d.]+:(\d+)$", READY_TIMEOUT_S,
            "announce line").group(1))
        self.ready_s = time.monotonic() - self.spawned
        tcp = int(self.wait_msg(
            r"^TCP DNS service started on [\d.]+:(\d+)$", 10,
            "TCP announce line").group(1))
        self.mport = int(self.wait_msg(
            r"^metrics server started on port (\d+)$", 10,
            "metrics announce line").group(1))
        return udp, tcp

    def members(self) -> list:
        """The group as the supervisor's ``/status`` has it now: a pid and
        a metrics port a shard.  Read again after a roll: a replaced worker
        is a new pid, a new port and counters from zero."""
        return self.check(json.loads(http_get(
            self.mport, "/status"))["shards"]["workers"])

    def scrape_front(self) -> dict:
        return {"supervisor": scrape(self.mport)}

    def front_pids(self) -> list:
        return []           # the supervisor is waited for by ``stop``

    def front_total(self, name: str) -> float:
        return stats.total(http_get(self.mport, "/metrics").decode(), name)

    def wait_written(self, timeout: float) -> None:
        """The one store is the supervisor's: one write, one line."""
        self.wait_msg(WRITE_LINE, timeout, "chaos watch-storm")

    @staticmethod
    def check_events(events: list) -> None:
        for event in events:
            if (event.get("signal") != "SIGHUP"
                    or event.get("to") != "supervisor"
                    or set(event) != {"at_s", "signal", "to"}):
                fail("start", f"event {event}: the one event built is "
                     '{"at_s": s, "signal": "SIGHUP", "to": "supervisor"}')

    def deliver(self, event: dict) -> None:
        self.proc.send_signal(getattr(signal, event["signal"]))

    def signal_all(self, sig: int) -> None:
        os.killpg(self.proc.pid, sig)   # its own session: pgid == pid

    def stop(self) -> int:
        """SIGTERM: the supervisor exits 0 and no worker survives."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            fail("serve", "supervisor ignored SIGTERM for 60s")

    def gone(self, pid: int) -> bool:
        return not os.path.exists(f"/proc/{pid}")

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self.end_drains(kill=True)
        self._log.close()


def process_state(pid: int):
    """The state letter of ``/proc/<pid>/stat``; None for no process."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0]
    except (OSError, IndexError):
        return None


PR_SET_CHILD_SUBREAPER = 36     # <linux/prctl.h>


def adopt_orphans() -> bool:
    """Make this process the one that inherits, and can wait for, the
    descendants whose parents end before them: ``binder-topology start``
    and ``instance_adjust`` leave the balancer and the instances behind
    (prctl PR_SET_CHILD_SUBREAPER).  False where the kernel refuses."""
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return False
    prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
    prctl.restype = ctypes.c_int
    return prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0) == 0


def read_int(path: str):
    """The number a pid or port file holds, or None."""
    try:
        with open(path) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


#: a UNIX socket's path holds 107 characters; the longest name a run
#: directory gives one is ``sockets/.balancer.stats``
SOCKET_PATH_MAX = 107


def free_base_port(count: int) -> int:
    """The first of *count* consecutive ports that are free, as is the port
    1000 above each (an instance's metrics server, ``main.py``).  Upstream's
    instances count up from 5301; two runs on one machine, or a process
    that an earlier run left, would meet there.  Drawn below the kernel's
    ephemeral range, so that no outgoing connection takes one between this
    look and the instances' binds."""
    def free(port: int) -> bool:
        for kind in (socket.SOCK_DGRAM, socket.SOCK_STREAM):
            with socket.socket(socket.AF_INET, kind) as sock:
                try:
                    sock.bind(("0.0.0.0", port))
                except OSError:
                    return False
        return True
    draw = random.SystemRandom()
    for _ in range(200):
        base = draw.randrange(20000, 30000)
        if all(free(base + n) and free(base + n + 1000)
               for n in range(count)):
            return base
    fail("start", f"no {count} consecutive free ports, each with the port "
         "1000 above it, between 20000 and 30000")


def open_fifo(path: str) -> tuple:
    """Make *path* a FIFO and open it, a reader first, so that no open
    blocks, then a writer of our own, so that the reader sees no end before
    we close it.  (read end, write end to keep)."""
    os.mkfifo(path)
    reader = os.open(path, os.O_RDONLY | os.O_NONBLOCK)
    keep = os.open(path, os.O_WRONLY)
    os.set_blocking(reader, True)
    return reader, keep


def balancer_stats(rundir: str) -> dict:
    """The balancer's state as its stats socket dumps it
    (``docs/balancer-protocol.md``): one connect, JSON until the end."""
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        sock.settimeout(10)
        sock.connect(os.path.join(rundir, "sockets", ".balancer.stats"))
        buf = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                return json.loads(buf)
            buf += chunk
    finally:
        sock.close()


def rundir_members(rundir: str, ports: list) -> list:
    """The instances as a run directory has them: ``metric_ports`` in the
    order of the instances' ports, ``state/binder-<port>.pid``."""
    with open(os.path.join(rundir, "metric_ports")) as f:
        mports = [int(p) for p in f.read().split()]
    return [{"shard": n, "metrics_port": mport, "pid": read_int(
        os.path.join(rundir, "state", f"binder-{port}.pid"))}
        for n, (port, mport) in enumerate(zip(ports, mports))]


class BalancerGroup(Group):
    """Upstream's topology, as ``bin/binder-topology start`` brings it up:
    N single-process binders, each with a store session of its own, behind
    one ``mbalancer`` on the clients' port, a UNIX socket a binder.  The
    group is what the run directory says it is: ``balancer.port``,
    ``balancer.pid``, ``metric_ports``, ``state/<name>.pid``,
    ``sockets/``; ``binder-topology stop`` ends it.

    ``instance_adjust`` gives each instance ``state/<name>.log`` as its
    stdout.  Before the start each of those is made a FIFO that this
    process drains as it drains a supervisor's pipe: no query line reaches
    the disk and no instance waits on a full pipe."""

    def __init__(self, config: dict, config_path: str, out_dir: str) -> None:
        super().__init__(config, config_path, out_dir)
        # the run directory goes with the run (``kill`` keeps its
        # ``balancer.log``); it lies where its sockets' paths are shortest
        self.rundir = tempfile.mkdtemp(prefix="run-", dir=min(
            (tempfile.gettempdir(), out_dir), key=len))
        if len(self.path("sockets", ".balancer.stats")) > SOCKET_PATH_MAX:
            fail("start", f"{self.rundir} is too long for a UNIX socket's "
                 f"path ({SOCKET_PATH_MAX} characters): set TMPDIR shorter")
        state = self.path("state")
        os.makedirs(state)
        # the balancer keeps a client's address with one backend (affinity
        # by host, new hosts in turn): the asks come from enough addresses,
        # none of them allowlisted, to reach every instance
        self.ask_sources = [f"127.0.1.{n + 1}"
                            for n in range(4 * self.shards)]
        adopt_orphans()
        base_port = free_base_port(self.shards)
        self.ports = [base_port + n for n in range(self.shards)]
        self._keep_open = []
        for n, port in enumerate(self.ports):
            reader, keep = open_fifo(os.path.join(state,
                                                  f"binder-{port}.log"))
            self._keep_open.append(keep)
            self.drain(reader, member=n)
            os.close(reader)
        argv = entry_argv(config, config_path, self.rundir, base_port)
        self.stop_argv = argv[:argv.index("start")] + [
            "stop", "-D", self.rundir]
        self.spawned = time.monotonic()
        with open(os.path.join(out_dir, "topology.log"), "wb") as log:
            self.proc = subprocess.Popen(
                argv, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        self.balancer_pid = None
        self.stopped = False

    def path(self, *parts: str) -> str:
        return os.path.join(self.rundir, *parts)

    def exited(self):
        """``binder-topology start`` exits 0 once the topology is up; any
        other code, or a balancer that is gone, is the group's end."""
        rc = self.proc.poll()
        if rc:
            return rc
        if self.balancer_pid and not self.stopped \
                and process_state(self.balancer_pid) in (None, "Z"):
            return "(the balancer is gone)"
        return None

    def wait_ready(self) -> tuple:
        """The balancer's port, for UDP and TCP alike, once ``start`` has
        ended (every instance online by ``instance_adjust -w``, the
        balancer's port written) and the balancer holds every backend."""
        self.wait_for(lambda: self.proc.poll() == 0, READY_TIMEOUT_S,
                      "the topology's start (see topology.log)")
        self.balancer_pid = read_int(self.path("balancer.pid"))
        port = read_int(self.path("balancer.port"))
        if not self.balancer_pid or not port:
            fail("serve", "the run directory names no balancer")

        def connected():
            try:
                backends = balancer_stats(self.rundir)["backends"]
            except (OSError, ValueError, KeyError):
                return False
            return sum(1 for b in backends if b["healthy"]) == self.shards
        self.wait_for(connected, 30, "the balancer to hold every backend")
        self.ready_s = time.monotonic() - self.spawned
        return port, port

    def members(self) -> list:
        return self.check(rundir_members(self.rundir, self.ports))

    def scrape_front(self) -> dict:
        return {"supervisor": dict(stats.FRESH),    # there is none
                "balancer": {"stats": balancer_stats(self.rundir),
                             "pid": self.balancer_pid,
                             "cpu_s": cpu_seconds(self.balancer_pid)}}

    def front_pids(self) -> list:
        return [self.balancer_pid]

    def wait_written(self, timeout: float) -> None:
        """A store an instance: the plan's write happens once in each, and
        each says so."""
        self.wait_for(
            lambda: len({member for _, _, member
                         in self.matches(WRITE_LINE)}) == self.shards,
            timeout, "chaos watch-storm of every instance")

    @staticmethod
    def check_events(events: list) -> None:
        if events:
            fail("start", "no event is built for a balancer topology: "
                 f"{events[0]}")

    def signal_all(self, sig: int) -> None:
        for pid in [self.balancer_pid] + [m["pid"] for m in self.members()]:
            os.kill(pid, sig)

    def stop(self) -> int:
        """``binder-topology stop``: the instances leave (each unlinks its
        socket on SIGTERM), then the balancer."""
        self.stopped = True
        with open(os.path.join(self.out_dir, "topology.log"), "ab") as log:
            rc = subprocess.call(self.stop_argv, cwd=ROOT, stdout=log,
                                 stderr=subprocess.STDOUT, timeout=120)
        deadline = time.monotonic() + 10
        while not self.gone(self.balancer_pid) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        return rc

    def gone(self, pid: int) -> bool:
        """A process that has ended: waited for where this process
        inherited it; where the kernel let us adopt nothing and the
        machine's init does not wait either, what is left of it is no
        process."""
        try:
            os.waitpid(pid, os.WNOHANG)
        except ChildProcessError:
            pass
        return process_state(pid) in (None, "Z")

    def kill(self) -> None:
        self.stopped = True
        # ``start`` with whatever it still runs and the balancer are one
        # process group, every instance is the leader of its own session
        left = [pid for pid in [read_int(self.path("balancer.pid"))] + [
            read_int(self.path("state", f"binder-{port}.pid"))
            for port in self.ports] if pid]
        for pgid in [self.proc.pid] + left:
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass                    # gone, or not a group's leader
        self.proc.wait()
        deadline = time.monotonic() + 5
        while not all(self.gone(pid) for pid in left) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        for fd in self._keep_open:
            os.close(fd)                # the drains see the end now
        self._keep_open = []
        self.end_drains(kill=False)
        self._log.close()
        if os.path.exists(self.path("balancer.log")):
            shutil.copy(self.path("balancer.log"), self.out_dir)
        shutil.rmtree(self.rundir, ignore_errors=True)


TOPOLOGIES = {"supervisor": SupervisorGroup, "balancer": BalancerGroup}


def http_get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as r:
        return r.read()


def scrape(port: int) -> dict:
    return {"metrics": http_get(port, "/metrics").decode(),
            "status": json.loads(http_get(port, "/status"))}


def cpu_seconds(pid: int):
    """User plus system time of a process so far (/proc/<pid>/stat); None
    for a process that is gone (a worker rolled away)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except (FileNotFoundError, ProcessLookupError):
        return None
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def scrape_all(group: Group, workers: list) -> dict:
    return dict(group.scrape_front(), at=time.monotonic(), workers=[
        dict(scrape(w["metrics_port"]), pid=w["pid"], shard=w["shard"],
             cpu_s=cpu_seconds(w["pid"])) for w in workers])


def wait_settled(workers: list, hosts: int) -> None:
    """Settled: every worker's precompile seed drained, the native zone
    table filled, and its gauge the same on two readings half a second
    apart (``chip_smoke.py wait_settled``)."""
    deadline = time.monotonic() + SETTLE_TIMEOUT_S
    last = None
    while True:
        seen = [scrape(w["metrics_port"]) for w in workers]
        entries = [int(stats.total(s["metrics"], "binder_zone_entries"))
                   for s in seen]
        left = [s["status"]["precompile"]["seed_remaining"] for s in seen]
        if not min(entries) and last is not None:
            fail("serve", "a worker has no native zone table: the Python "
                 "fallback is serving")
        if entries == last and min(entries) >= hosts and not any(left):
            return
        if time.monotonic() > deadline:
            fail("serve", f"not settled after {SETTLE_TIMEOUT_S:.0f}s: "
                 f"zone entries {entries}, seed remaining {left}")
        last = entries
        time.sleep(0.5)


# -- asks from fresh sockets --

def ask_udp(port: int, wire: bytes, source: str = ASK_SOURCE):
    """One ask on a fresh socket, a new 4-tuple, so the reuseport hash
    draws a worker afresh."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        sock.bind((source, 0))
        sock.connect(("127.0.0.1", port))
        sock.settimeout(2.0)
        for _ in range(3):
            sock.send(wire)
            try:
                return sock.recv(65535)
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


class Verdict:
    """Every number compared, beside its limit; ``correct`` is all of
    them inside their limits."""

    def __init__(self) -> None:
        self.rows = []
        self.examples = []

    def hold(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit))

    def wrong(self, what: str, problems: list) -> int:
        if problems and len(self.examples) < 8:
            self.examples.append(f"{what}: {'; '.join(problems)}")
        return 1 if problems else 0

    @property
    def correct(self) -> bool:
        return all(value <= limit for _, value, limit in self.rows)

    def lines(self) -> list:
        return [f"compared {name} = {value} (limit {limit})"
                + ("" if value <= limit else "  <-- outside")
                for name, value, limit in self.rows]

    def show(self) -> None:
        for line in self.lines() + [f"  e.g. {e}" for e in self.examples]:
            say(line)


def requests_completed(workers: list) -> list:
    """Each worker's own count of requests completed.  Between settle
    and the window, and after it, the harness is the only client: what a
    worker's count grows by is the asks that worker served."""
    return [stats.total(http_get(w["metrics_port"], "/metrics").decode(),
                        "binder_requests_completed") for w in workers]


def ask_sample(udp: int, tcp: int, zone, questions: list,
               verdict: Verdict, sources: list) -> int:
    """Ask each question from a fresh socket, the group's *sources* in
    turn (TC=1 retried over TCP, as a stub does), and compare with the
    reference; how many mismatched."""
    bad = 0
    for n, (qname, qtype, wire) in enumerate(questions):
        wire = bytes([n >> 8, n & 255]) + wire[2:]
        answer = dnswire.Answer(ask_udp(udp, wire,
                                        sources[n % len(sources)]))
        want = zone.expected(qname, qtype, dnswire.query_payload(wire))
        problems = compare(answer, qname, qtype, want, whole=not answer.tc,
                           truncated=answer.tc)
        if answer.tc and not problems:
            problems = compare(dnswire.Answer(ask_tcp(tcp, wire)), qname,
                               qtype, want)
        bad += verdict.wrong(f"ask {qname}/{qtype}", problems)
    return bad


def read_back(udp: int, zone, workers: list, verdict: Verdict,
              sources: list) -> tuple:
    """Read-your-writes: the written names asked from fresh sockets, the
    group's *sources* in turn, until every worker has served some of the
    asks; with every answer right, every worker gave the written answer.
    (workers that served none, mismatching answers, asks made)."""
    names = sorted(zone.written)
    start = requests_completed(workers)
    served, bad, asked = [0] * len(workers), 0, 0
    while min(served) < 1 and asked < 64 * len(workers):
        for _ in range(8):
            qname = names[asked % len(names)]
            reply = ask_udp(udp, dnswire.make_query(
                qname, dnswire.A, qid=asked), sources[asked % len(sources)])
            bad += verdict.wrong(f"written {qname}", compare(
                dnswire.Answer(reply), qname, dnswire.A,
                zone.expected(qname, dnswire.A)))
            asked += 1
        served = [now - was for now, was
                  in zip(requests_completed(workers), start)]
    return sum(1 for n in served if n < 1), bad, asked


def check_captures(path: str, traffic, zone, seconds: float,
                   verdict: Verdict) -> int:
    """The answers the generator kept from the window, each against the
    reference; every entry of the cell's mix has to be among them, and
    where the window is cut into segments enough of each segment's (an
    answer that came over TCP stands for a UDP answer that said TC=1).
    Returns how many were compared."""
    with open(path, "rb") as f:
        raw = f.read()
    workload = traffic.workload
    off = compared = bad = longest = 0
    by_entry = [0] * len(workload["mix"])
    # (a closed loop's sequence positions are no due times: not cut)
    cuts = [float(c) for c in workload.get("segments_at_s") or ()] \
        if traffic.arrivals is not None else []
    by_segment = [0] * (len(cuts) + 1)
    while off < len(raw):
        pos, tmpl, tcp, length = struct.unpack_from("<IIBH", raw, off)
        off += 11
        wire = raw[off:off + length]
        off += length
        qname, qtype = traffic.questions[tmpl]
        try:
            answer = dnswire.Answer(wire)
            problems = compare(
                answer, qname, qtype, zone.expected(
                    qname, qtype,
                    dnswire.query_payload(traffic.templates[tmpl][0])),
                truncated=bool(tcp) or answer.tc)
            longest = max(longest, len(answer.answers))
        except (ValueError, IndexError, struct.error) as e:
            problems = [f"undecodable answer: {e}"]
        bad += verdict.wrong(f"window {qname}/{qtype}", problems)
        compared += 1
        by_entry[traffic.templates[tmpl][3]] += 1
        if cuts:
            due_s = int(traffic.arrivals[pos]) / 1e9 - float(
                workload["warm_s"])
            by_segment[sum(1 for c in cuts if due_s >= c)] += 1
    verdict.hold("window_answers_mismatching", bad, 0)
    verdict.hold("mix_entries_with_no_window_answer_compared",
                 by_entry.count(0), 0)
    say(f"window answers compared: {compared} ({by_entry} by mix entry), "
        f"the longest with {longest} records")
    if cuts:
        # a segment has to hold its share of the answers kept, 200 at the
        # most: the comparison covers before, during and after an event
        edges = [0.0] + [min(c, seconds) for c in cuts] + [seconds]
        thin = sum(1 for n, lo, hi in zip(by_segment, edges, edges[1:])
                   if n < min(200, int(workload["capture_answers"])
                              * (hi - lo) / seconds / 2))
        verdict.hold("segments_with_too_few_answers_compared", thin, 0)
        say(f"by segment of the window: {by_segment}")
    return compared


# -- per-layer metrics: one reader file each --

def layer_readers() -> dict:
    out = {}
    directory = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(directory)):
        if not fname.endswith(".py"):
            continue
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + fname[:-3].replace("-", "_"),
            os.path.join(directory, fname))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        out[fname[:-3]] = module
    return out


def layer_values(manifest: dict, cell: str, ctx: dict) -> dict:
    """The traced run's metrics: every reader that finds something to
    read; for a cell that ``BENCHMARK.json`` lists, only the metrics it
    lists for that cell."""
    listed = any(w["name"] == cell for w in manifest["workloads"])
    declared = {m["name"]: m for m in manifest["per_layer"]}
    metrics = {}
    for name, module in layer_readers().items():
        if listed and (name not in declared or cell not in
                       declared[name].get("workloads", [cell])):
            continue
        value = module.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": module.UNIT}
    return metrics


def balancer_seconds(before: dict, after: dict) -> dict:
    """The balancer's own account of its packet path between the scrapes:
    its four exclusive stages (``stage_cycles`` of the stats socket, by its
    own TSC rate), each as ``balancer <stage>``, and ``balancer cpu``, its
    process's CPU seconds, which hold them."""
    b, a = before["balancer"], after["balancer"]
    out = {}
    if b["pid"] != a["pid"]:
        return out
    if None not in (b["cpu_s"], a["cpu_s"]):
        out["balancer cpu"] = a["cpu_s"] - b["cpu_s"]
    rate = a["stats"].get("cycles_per_us") or 0
    for stage, cell in (a["stats"].get("stage_cycles") or {}).items():
        was = (b["stats"].get("stage_cycles") or {}).get(stage) or {}
        if rate > 0:
            out[f"balancer {stage}"] = (
                cell["cycles"] - was.get("cycles", 0)) / rate / 1e6
    return out


def stage_seconds(before: dict, after: dict) -> list:
    """Host time by the program's own query stages over the window:
    the breakdown's ``idle_gaps`` (what the host did while the device
    idled), at most ten; behind a balancer, its own stages among them."""
    sums = balancer_seconds(before, after) if "balancer" in after else {}
    for b, a in stats.worker_pairs(before, after):
        was = {lab.get("stage"): v for lab, v in stats.samples(
            b["metrics"], "binder_query_stage_seconds_sum")}
        for lab, v in stats.samples(a["metrics"],
                                    "binder_query_stage_seconds_sum"):
            stage = lab.get("stage")
            sums[stage] = sums.get(stage, 0.0) + v - was.get(stage, 0.0)
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:10]
    return [[str(stage), seconds] for stage, seconds in top if seconds > 0]


def generator_argv(workload: dict, files: dict, udp: int, seconds: float,
                   gen_files: dict) -> list:
    argv = [GENERATOR, "-p", str(udp), "-d", str(seconds),
            "-W", str(workload["warm_s"]), "-T", str(workload["timeout_s"]),
            "-S", str(workload["sources"]),
            "-C", str(workload.get("callers", workload["threads"])),
            "-j", str(workload["threads"])]
    for flag, path in gen_files.items():
        argv += [flag, path]
    if workload.get("tc_retry"):
        argv.append("-R")
    if workload.get("segments_at_s"):
        argv += ["-g", ",".join(str(float(c))
                                for c in workload["segments_at_s"])]
    for flag, path in files.items():
        argv += [flag, path]
    return argv


def account_for_stops(g: dict, workload: dict, gen_files: dict) -> None:
    """Name the stops of the machine the generator lived through and leave
    the queries they cover out of the counts (``stats.py``, README.md "A
    stop of the machine").  Into the generator's object go ``stops``
    (``[start, length]`` in seconds from the window's first due time, every
    one of the generator's ``gap_least_ns`` or more), ``voided``
    (``queries``, ``of_them_failed``), ``attempted``, ``failed_by_kind``
    (what is left after voiding; their sum is the run's ``failed``), and
    each segment's ``failed`` becomes what is left of it.  The histograms,
    ``sent``, ``failed`` and ``fails`` stay the generator's own."""
    stops = stats.machine_stops(g["gaps_ns"], g["gap_least_ns"])
    spans = stats.covered_spans(stops, int(float(workload["timeout_s"])
                                           * 1e9), g["gap_least_ns"])
    failures = np.fromfile(gen_files["-f"], dtype="<i8").reshape(-1, 2)
    if len(failures) != g["failed"] + g["unanswered_at_end"]:
        fail("window", f"{len(failures)} failures on file for the "
             f"generator's {g['failed']} + {g['unanswered_at_end']}")
    sends = []
    if spans:
        # (the generator writes its sends' due times where a thread saw a
        # gap, and only a gap of every thread makes a span)
        sends = np.fromfile(gen_files["-n"], dtype="<i8")
        if len(sends) != g["sent"]:
            fail("window", f"{len(sends)} sends on file for the "
                 f"generator's {g['sent']}")
    # (the generator's own arithmetic for its -g cuts)
    cuts = [int(float(c) * 1e9) for c in workload.get("segments_at_s") or ()]
    account = stats.void_account(sends, failures, g["fail_kinds"], spans,
                                 cuts)
    g["stops"] = [[start / 1e9, length / 1e9] for start, length in stops]
    g["voided"] = {"queries": account["queries"],
                   "of_them_failed": account["of_them_failed"]}
    g["attempted"] = g["sent"] - account["queries"]
    g["failed_by_kind"] = account["failed_by_kind"]
    for segment, left in zip(g["latency_ns_by_segment"],
                             account["failed_by_segment"]):
        segment["failed"] = left


def describe_window(g: dict) -> None:
    """Say what the generator counted, with the sample count behind the
    percentiles."""
    lat, bits = g["latency_ns"], g["hist_bits"]
    say(f"window {g['window_s']:.1f}s {g['loop']} loop: sent {g['sent']}, "
        f"ok {g['ok']}, failed {g['failed']} {g['fails']}, TC retries "
        f"{g['tc_retries']}, unanswered at the end "
        f"{g['unanswered_at_end']}")
    say(f"stops of the machine ({g['gap_least_ns'] / 1e6:.0f} ms or more, "
        f"[start, length] s): {json.dumps(g['stops'])}; voided "
        f"{g['voided']['queries']} queries, {g['voided']['of_them_failed']} "
        f"of them failed; failed after that {g['failed_by_kind']}")
    if not lat:
        fail("window", "no query was answered in the window")
    p50_us, p99_us = (stats.hist_percentile(lat, bits, q) / 1e3
                      for q in (50, 99))
    n = stats.hist_count(lat)
    say(f"latency p50 {p50_us:.1f} us, p99 {p99_us:.1f} us over {n} "
        f"samples ({n // 100} beyond the 99th percentile)")
    if g["late_ns"]:
        say("sends left late by p50 %.1f us, p99 %.1f us" % tuple(
            stats.hist_percentile(g["late_ns"], bits, q) / 1e3
            for q in (50, 99)))
    if g["loop"] == "open" and len(g["inflight"]) >= 6:
        third = len(g["inflight"]) // 3
        say(f"in flight, mean of the window's first third "
            f"{sum(g['inflight'][:third]) / third:.1f}, of its last third "
            f"{sum(g['inflight'][-third:]) / third:.1f} (a backlog that "
            "grows shows here)")


# -- events inside the window --

def window_start(out_dir: str):
    """The window's first due time, seconds on CLOCK_MONOTONIC, as soon as
    the generator has written it (``-s``); None if it does not within a
    minute."""
    path = os.path.join(out_dir, GENERATOR_OUT["-s"])
    deadline = time.monotonic() + 60
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            return None
        time.sleep(0.002)
    with open(path) as f:
        return int(f.read()) / 1e9


class Stop:
    """The two controls of the stop rule (``--break``, README.md "A stop of
    the machine"), from a thread of their own: in mid-window SIGSTOP for
    ``STOP_BREAK_S`` seconds, then SIGCONT.  ``machine-stop`` stops the
    generator and every process of the server's group, as the sandbox stops:
    the run has to name that stop and void what it cost.  ``server-stop``
    stops the server's group alone: the generator's threads never paused,
    so no stop is named and every query lost stays failed; a stall of the
    program is never forgiven, whatever its length."""

    def __init__(self, which: str, group: Group, gen, out_dir: str,
                 seconds: float) -> None:
        self.pids = [gen.pid] if which == "machine-stop" else []
        self.group = group
        self.out_dir = out_dir
        self.at_s = seconds / 2 - STOP_BREAK_S / 2
        self.stopped_s = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        start = window_start(self.out_dir)
        if start is None:
            return
        time.sleep(max(0.0, start + self.at_s - time.monotonic()))
        began = time.monotonic()
        for pid in self.pids:
            os.kill(pid, signal.SIGSTOP)
        self.group.signal_all(signal.SIGSTOP)
        try:
            time.sleep(STOP_BREAK_S)
        finally:
            # the server first: it is up when the generator's backlog comes
            self.group.signal_all(signal.SIGCONT)
            for pid in self.pids:
                os.kill(pid, signal.SIGCONT)
        self.stopped_s = time.monotonic() - began

    def join(self) -> None:
        self._thread.join(90)
        if self.stopped_s is None:
            fail("window", "the --break stop was not delivered")
        say(f"--break: stopped {'generator and ' if self.pids else ''}"
            f"server group for {self.stopped_s:.3f}s from {self.at_s:.2f}s "
            "into the window")


class Events:
    """A workload's ``events``: a list, in time order, of objects with
    ``at_s`` (seconds from the first due time of the measured window) and
    one verb.  One verb is built, because one cell uses it: ``"signal":
    "SIGHUP", "to": "supervisor"`` (the zero-downtime roll of every
    shard), and the group says whether it takes it
    (``Group.check_events``).  Each is delivered from this object's own thread; ``left``
    says at which offset each really went."""

    def __init__(self, events: list, group: Group, out_dir: str) -> None:
        group.check_events(events)
        for n, event in enumerate(events):
            if n and float(event["at_s"]) < float(events[n - 1]["at_s"]):
                fail("start", "events are not in time order")
        self.events = events
        self.group = group
        self.out_dir = out_dir
        self.left = []
        self._thread = threading.Thread(target=self._deliver, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _deliver(self) -> None:
        start = window_start(self.out_dir)
        if start is None:
            return                      # join() then finds events undelivered
        for event in self.events:
            wait = start + float(event["at_s"]) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self.group.deliver(event)
            went = time.monotonic()
            self.left.append(dict(event, left_at_s=went - start,
                                  left_mono=went))

    def join(self) -> int:
        """Wait for the thread; how many events did not leave."""
        self._thread.join(10)
        return len(self.events) - len(self.left)

    def report(self) -> list:
        return [{k: v for k, v in e.items() if k != "left_mono"}
                for e in self.left]


# -- one run --

def write_server_config(config: dict, zone, out_dir: str, broken) -> str:
    cfg = load_json(os.path.join(ROOT, config["base_config"]))
    fixture = zone.fixture()
    racks = int(config.get("racks") or 0)
    if broken == "fixture-address" and zone.services:
        # the program's data altered under it: one member of the service
        # with the largest answer gets another address than the reference
        # knows
        service = max(zone.services, key=lambda s: len(s.members))
        base = "/" + "/".join(reversed(zone.domain.split(".")))
        node = fixture[f"{base}/{service.label}/{service.members[0][0]}"]
        node[service.kind]["address"] = "10.255.255.254"
    elif broken == "fixture-address":
        # a zone of hosts alone is made by the program's own formula, and
        # no fixture reaches a host's record: the program spreads the hosts
        # over one rack more than the reference knows, so that nearly every
        # name the cell asks is no host's or another's
        racks = zone.racks + 1
    fixture_path = os.path.join(out_dir, "fixture.json")
    with open(fixture_path, "w") as f:
        json.dump(fixture, f)
    cfg["store"] = {"backend": "fake", "fixture": fixture_path,
                    "synthetic": {"hosts": zone.hosts, "racks": racks,
                                  "subtree": zone.subtree}}
    cfg["port"] = 0
    for key, value in config.get("posture_overrides", {}).items():
        if isinstance(value, dict) and isinstance(cfg.get(key), dict):
            cfg[key].update(value)
        else:
            cfg[key] = value
    at = float(config["chaos"]["mutate_at_s"])
    plan = f"at {at} watch-storm n={int(config['chaos']['writes'])}"
    if broken == "skew-replica":
        # the control: one worker is cut off from the mutation log just
        # before the write, so read-your-writes does not hold on it
        plan = f"at {at - 0.5} skew-replica shard=0 frames=1000; " + plan
    cfg["chaos"] = {"plan": plan}
    path = os.path.join(out_dir, "config.json")
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path


def run(args) -> int:
    cells = os.path.join(ROOT, args.dir)
    workload = load_json(os.path.join(cells, "workloads",
                                      args.workload + ".json"))
    config = load_json(os.path.join(cells, "configs",
                                    workload["config"] + ".json"))
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    topology = config.get("topology", "supervisor")
    if topology not in TOPOLOGIES:
        fail("start", f"configuration {config['name']}: topology "
             f"{topology!r} is none of {sorted(TOPOLOGIES)}")
    if args.break_ == "skew-replica" and topology != "supervisor":
        fail("start", "--break skew-replica cuts a worker off the "
             "supervisor's mutation log: a balancer configuration has "
             "neither (every instance holds a store session of its own)")
    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    traced = args.trace == 1

    child = DeviceChild(args.cpu, traced)
    group = None
    try:
        build(out_dir)
        domain = load_json(os.path.join(
            ROOT, config["base_config"]))["dnsDomain"]
        zone = Zone(config, domain, args.seed)
        shards = int(config["shards"])
        group = TOPOLOGIES[topology](config, write_server_config(
            config, zone, out_dir, args.break_), out_dir)
        if args.break_ == "reference-address" and zone.services:
            service = max(zone.services, key=lambda s: len(s.members))
            service.members[0] = (service.members[0][0], "10.255.255.254")
        # the traffic is made while the server starts
        traffic = Traffic(workload, zone, args.seed, args.seconds)
        files = traffic.write(out_dir)
        if args.break_ == "reference-address" and not zone.services:
            # a zone of hosts alone: the reference is told another address
            # for the name that the window asks most
            most = int(np.bincount(
                traffic.sequence.astype(np.int64) & 0x7FFFFFFF).argmax())
            zone.altered[traffic.questions[most][0]] = "10.255.255.254"
        if args.break_ == "reference-declined":
            # the control of a declined type: the reference is told that
            # AAAA is answered, NOERROR without records (what a later PR
            # might serve for it), once the generator's files are written,
            # so that the answers are kept and it is the comparison that
            # fails
            zone.answered_empty = frozenset({dnswire.AAAA})
        if args.break_ == "reference-opt":
            # the control of an OPT record on every question: the
            # reference is told that no OPT record comes back
            zone.opt_echoed = False
        say(f"traffic: {len(traffic.templates)} templates, "
            f"{len(traffic.sequence)} sequence entries"
            + (f", {len(traffic.arrivals)} arrivals"
               if traffic.arrivals is not None else ""))

        udp, tcp = group.wait_ready()
        ready_s = group.ready_s
        verdict = Verdict()

        # the write has not happened yet: its names are not served
        before_write = 0
        for qname in sorted(zone.written)[:2]:
            before_write += verdict.wrong(
                f"before the write {qname}", compare(
                    dnswire.Answer(ask_udp(udp, dnswire.make_query(
                        qname, dnswire.A, qid=1))), qname, dnswire.A,
                    zone.expected(qname, dnswire.A)))
        workers = group.members()
        pids = {w["pid"] for w in workers} | set(group.front_pids())

        wait_settled(workers, zone.hosts)
        seed_s = time.monotonic() - group.spawned - ready_s
        say(f"{shards} shards ready in {ready_s:.1f}s, settled "
            f"{seed_s:.1f}s later")
        group.wait_written(float(config["chaos"]["mutate_at_s"]) + 30)
        zone.writes_done = True

        # a seeded sample of the cell's own questions, before the window,
        # with one of every mix entry and the zone's largest set in it
        picks = rng_for(args.seed, 6).choice(len(traffic.sequence),
                                             size=ASKS, replace=False)
        sample = [traffic.questions[tmpl] + (traffic.templates[tmpl][0],)
                  for tmpl in [int(traffic.sequence[pos]) & 0x7FFFFFFF
                               for pos in picks] + traffic.always_asked]

        def asks_and_read_back() -> tuple:
            start = requests_completed(workers)
            bad = ask_sample(udp, tcp, zone, sample, verdict,
                             group.ask_sources)
            unseen = sum(1 for now, was in zip(requests_completed(workers),
                                               start) if now <= was)
            return (bad, unseen) + read_back(udp, zone, workers, verdict,
                                             group.ask_sources)

        bad, unseen, silent, stale, asked = asks_and_read_back()
        say(f"before the window: {len(sample)} sampled asks, the write "
            f"read back in {asked} asks")

        device = child.device()
        if not traced:
            child.finish()
        scrape_before = scrape_after = None
        gen_files = {flag: os.path.join(out_dir, name)
                     for flag, name in GENERATOR_OUT.items()}
        for path in gen_files.values():
            if os.path.exists(path):
                os.remove(path)         # an earlier run's is not this run's
        argv = generator_argv(workload, files, udp, args.seconds, gen_files)
        events = None
        if workload.get("events"):
            events = Events(workload["events"], group, out_dir)
            aborts = group.front_total("binder_shard_roll_aborts_total")
        setup_s = time.monotonic() - T_START + float(workload["warm_s"])
        say(f"set-up {setup_s:.1f}s with the warm-up; the device child "
            f"named {device['kind']}")
        if traced:
            # the first scrape is taken before the warm-up, so that it
            # does not fall into the window (a scrape holds a worker's loop
            # for tens of milliseconds): the deltas cover warm-up and window
            scrape_before = scrape_all(group, workers)
        gen = subprocess.Popen(argv, cwd=out_dir)
        if events:
            events.start()
        stop = Stop(args.break_, group, gen, out_dir, args.seconds) \
            if args.break_ in STOP_BREAKS else None
        try:
            rc = gen.wait(timeout=args.seconds + 60)
        finally:
            if gen.poll() is None:
                gen.kill()
                gen.wait()
        if rc != 0:
            fail("window", f"the generator exited {rc}")
        if stop:
            stop.join()
        if traced:
            child.tell("stop")
        roll_s = None
        if events:
            # the event was a SIGHUP to the supervisor: wait (outside
            # every end-to-end metric) for its roll to end, then take the
            # group as it is now for the asks, the read-back and the scrape
            verdict.hold("events_not_delivered", events.join(), 0)
            ended = r"^rolling upgrade (complete|stopped)"
            group.wait_msg(ended, ROLL_TIMEOUT_S, "the roll's end",
                           fatal=False)
            done = group.find_msg(ended)        # (match, arrival) or None
            complete = bool(done) and done[0].group(1) == "complete"
            verdict.hold("roll_not_complete", 0 if complete else 1, 0)
            if complete and events.left:
                roll_s = done[1] - events.left[0]["left_mono"]
            was = {w["shard"]: w["pid"] for w in workers}
            workers = group.members()
            pids |= {w["pid"] for w in workers}
            verdict.hold("shards_not_rolled", sum(
                1 for w in workers if was.get(w["shard"]) == w["pid"]), 0)
            verdict.hold("roll_aborts", int(group.front_total(
                "binder_shard_roll_aborts_total") - aborts), 0)
            say("events: " + json.dumps(events.report()) + "; the roll "
                + (f"took {roll_s:.1f}s" if roll_s is not None
                   else "did not complete"))
        if traced:
            time.sleep(group.SCRAPE_LAG_S)
            scrape_after = scrape_all(group, workers)
            device.update(child.traced_window())
            child.finish()
        g = load_json(gen_files["-o"])
        account_for_stops(g, workload, gen_files)
        if traced:
            between = scrape_after["at"] - scrape_before["at"]
            say("worker CPU between the scrapes, % of a core: " + ", ".join(
                "replaced" if b is stats.FRESH or a["cpu_s"] is None
                else f"{100 * (a['cpu_s'] - b['cpu_s']) / between:.0f}"
                for b, a in stats.worker_pairs(scrape_before, scrape_after))
                + "; generator threads: " + ", ".join(
                    f"{100 * (user + system) / g['window_s']:.0f}"
                    for user, system in g["thread_cpu_s"]))

        # after the window: the kept answers, the sample and the write again
        # (after a roll: from the group's new workers)
        compared = check_captures(gen_files["-c"], traffic, zone,
                                  args.seconds, verdict)
        bad2, unseen2, silent2, stale2, _ = asks_and_read_back()
        verdict.hold("asks_mismatching_before_window", bad, 0)
        verdict.hold("asks_mismatching_after_window", bad2, 0)
        verdict.hold("workers_not_seen_answering", max(unseen, unseen2), 0)
        verdict.hold("written_names_served_before_the_write",
                     before_write, 0)
        verdict.hold("workers_not_serving_the_write",
                     max(silent, silent2), 0)
        verdict.hold("written_names_mismatching", stale + stale2, 0)
        verdict.hold("window_answers_wrong_rcode_or_count",
                     g["fails"]["rcode"] + g["fails"]["ancount"], 0)
        verdict.hold("window_answers_not_compared",
                     0 if compared >= min(
                         200, int(workload["capture_answers"]) // 4) else 1,
                     0)

        # the clean stop: exit 0 and no process of the group survives
        rc = group.stop()
        time.sleep(0.2)
        orphans = [p for p in sorted(pids) if not group.gone(p)]
        say(f"orphan check over {len(pids)} worker pids"
            + (" (both generations)" if events else ""))
        verdict.hold("supervisor_exit_code", rc, 0)
        verdict.hold("orphan_processes", len(orphans), 0)
    finally:
        if group is not None:
            group.kill()
        child.kill()

    if "jax" in sys.modules:
        fail("summary", "the parent process imported jax")
    say(f"whole run {time.monotonic() - T_START:.1f}s")
    verdict.show()
    describe_window(g)
    values = {"setup_s": (setup_s, "s"),
              "answers_per_s": (g["ok_in_window"] / g["window_s"],
                                "answers/s")}
    for q in (50, 90, 99):
        values[f"p{q}_us"] = (stats.hist_percentile(
            g["latency_ns"], g["hist_bits"], q) / 1e3, "us")
    if traced:
        metrics = layer_values(manifest, args.workload, {
            "before": scrape_before, "after": scrape_after, "generator": g,
            "workload": workload, "mix_rcodes": traffic.rcodes_by_entry(),
            "events": events.report() if events else [],
            "harness": {"ready_s": ready_s, "seed_s": seed_s,
                        "setup_s": setup_s, "roll_s": roll_s}})
    else:
        metrics = {name: {"value": values[name][0], "unit": values[name][1]}
                   for name in workload["end_to_end"]}
    # what no stop of the machine covers; none of the three that follow
    # enters ``correct``
    result = {"correct": verdict.correct, "attempted": g["attempted"],
              "failed": sum(g["failed_by_kind"].values()),
              "voided": g["voided"],
              "stops": sorted(sorted(g["stops"], key=lambda s: -s[1])[:20]),
              "failed_by_kind": g["failed_by_kind"],
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {
            "device_ops": device.pop("device_ops", []),
            "idle_gaps": stage_seconds(scrape_before, scrape_after)}
        if events:
            result["breakdown"]["replaced_workers"] = stats.replaced_workers(
                scrape_before, scrape_after)
    if events:
        result["events"] = events.report()
    # every number compared beside its limit, as the benchmark's contract
    # asks: the line's last key, and the run's last lines on standard error
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in verdict.rows}
    print("\n".join(f"benchmark: {line}" for line in [
        f"{key} {json.dumps(result[key])}"
        for key in ("voided", "stops", "failed_by_kind")] + verdict.lines()),
        file=sys.stderr, flush=True)
    if args.cpu:
        # a rehearsal: no number of a CPU run goes out under a device
        # metric's name, and no result line
        say("REHEARSAL on the CPU (not a measurement): "
            + json.dumps(result))
        return 0 if verdict.correct else 1
    print(json.dumps(result), flush=True)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal on the CPU platform: refused as a "
                    "measurement, prints no result line")
    ap.add_argument("--dir", default="benchmark",
                    help="where configs/ and workloads/ are looked up "
                    "(the tests keep a tiny cell of their own)")
    ap.add_argument("--break", dest="break_", choices=BREAKS + STOP_BREAKS,
                    help="a deliberately wrong run, for the tests and the "
                    "control: correct must come out false; or (machine-stop, "
                    "server-stop) a stop in mid-window: a control of the "
                    "stop rule, which leaves correct alone")
    args = ap.parse_args()
    for needed in ("BENCHMARK.json", "binder_tpu/main.py", "native/Makefile",
                   "etc/config.json", "__graft_entry__.py"):
        if not os.path.exists(os.path.join(ROOT, needed)):
            fail("start", f"{needed} is missing: not a binder checkout")
    # a `timeout` or the driver ends us with SIGTERM: leave through the
    # finally blocks, so the server group is killed too
    signal.signal(signal.SIGTERM, lambda *_: fail("run", "got SIGTERM"))
    sys.exit(run(args))


if __name__ == "__main__":
    main()
