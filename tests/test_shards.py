"""Shard-mode tier-1 suite: supervisor lifecycle + answer parity.

What ISSUE 6 pins here:

- ``--shards N`` serves correct answers from N distinct PIDs behind
  ONE kernel-balanced UDP port, with exactly one store session total
  (the supervisor's) — workers run ``ReplicaStore`` and never touch
  the store;
- crashed-shard respawn with snapshot catch-up: a SIGKILLed worker is
  respawned by the supervisor and converges on mutations that landed
  while it was dead;
- SIGTERM drain leaves no orphan worker PIDs;
- answer byte-parity (modulo ID) between N=1 and N=4 across the
  record shapes (host A, PTR, REFUSED policy, rotated service sets);
- the ``binder_shard_*`` exposition passes
  ``tools/lint.py validate_shard_metrics`` (this is the family's
  tier-1 wiring, like the tcp validator);
- the chaos DSL's ``shard-kill`` action parses and dispatches to the
  driver's ``shard_target``.

The suite boots REAL worker subprocesses (``python -m binder_tpu.main
--shard-worker``) under an in-process supervisor, so what is tested is
the production process topology, not a simulation.
"""
import asyncio
import json
import os
import signal
import socket
import sys
import tempfile
import time
import urllib.request

import pytest

from binder_tpu.chaos import ChaosDriver, FaultPlan
from binder_tpu.dns import Message, Rcode, Type, make_query
from binder_tpu.main import run as binder_run
from tools.lint import (validate_ledger_metrics, validate_shard_metrics,
                        validate_status_snapshot)

DOMAIN = "shard.test"

FIXTURE = {
    **{f"/test/shard/w{i}":
       {"type": "host", "host": {"address": f"10.50.0.{i + 1}"}}
       for i in range(4)},
    "/test/shard/svc": {
        "type": "service",
        "service": {"srvce": "_http", "proto": "_tcp", "port": 8080}},
    **{f"/test/shard/svc/m{i}":
       {"type": "load_balancer",
        "load_balancer": {"address": f"10.50.1.{i + 1}"}}
       for i in range(3)},
}

#: the parity shapes: single-answer wires must be byte-identical
#: modulo ID; rotated sets compare as sorted answer summaries
SINGLE_ANSWER_QUERIES = [
    ("w0.shard.test", Type.A),           # host A
    ("w3.shard.test", Type.A),
    ("1.0.50.10.in-addr.arpa", Type.PTR),  # reverse
    ("nosuch.shard.test", Type.A),       # miss -> REFUSED policy
    ("w0.other.test", Type.A),           # out-of-suffix -> REFUSED
    ("w0.shard.test", Type.TXT),         # NODATA shape
]
ROTATED_QUERIES = [
    ("svc.shard.test", Type.A),
    ("_http._tcp.svc.shard.test", Type.SRV),
]


async def boot(tmpdir: str, shards: int):
    """Boot a shard supervisor (fake owner store + fixture) with REAL
    worker subprocesses; returns the supervisor."""
    fixture = os.path.join(tmpdir, "fixture.json")
    with open(fixture, "w") as f:
        json.dump(FIXTURE, f)
    options = {
        "dnsDomain": DOMAIN, "datacenterName": "dc0",
        "host": "127.0.0.1", "port": 0, "queryLog": False,
        "expiry": 60000, "size": 10000,
        "store": {"backend": "fake", "fixture": fixture},
        "shards": shards,
    }
    return await binder_run(options)


async def ask_fresh(port: int, name: str, qtype: int, qid: int,
                    timeout: float = 3.0) -> bytes:
    """One query on a fresh socket — a new source port, so the
    reuseport hash gets a fresh draw across the worker group."""
    loop = asyncio.get_running_loop()
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setblocking(False)
    sock.connect(("127.0.0.1", port))
    try:
        for _ in range(3):
            sock.send(make_query(name, qtype, qid=qid).encode())
            try:
                return await asyncio.wait_for(
                    loop.sock_recv(sock, 4096), timeout)
            except asyncio.TimeoutError:
                continue
        raise AssertionError(f"no answer for {name} in 3 tries")
    finally:
        sock.close()


async def wait_for(predicate, timeout: float = 10.0, what: str = ""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what or predicate}")


def worker_status(sup, shard: int) -> dict:
    mport = sup.links[shard].hello["metrics_port"]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/status", timeout=5) as r:
        return json.loads(r.read())


async def collect_answers(port: int, samples: int = 18):
    """Normalized answer shapes over many fresh sockets (both parity
    sides sample the same way)."""
    singles = {}
    for name, qtype in SINGLE_ANSWER_QUERIES:
        wires = set()
        for s in range(6):
            data = await ask_fresh(port, name, qtype,
                                   qid=(hash((name, s)) & 0x7FFF) + 1)
            wires.add(b"\x00\x00" + data[2:])   # modulo ID
        singles[(name, qtype)] = wires
    rotated = {}
    for name, qtype in ROTATED_QUERIES:
        shapes = set()
        for s in range(samples):
            data = await ask_fresh(port, name, qtype, qid=s + 1)
            msg = Message.decode(data)
            shapes.add((msg.rcode,
                        tuple(sorted(str(a) for a in msg.answers)),
                        len(msg.answers)))
        rotated[(name, qtype)] = shapes
    return singles, rotated


class TestShardServing:
    def test_two_pids_one_port_one_session(self, tmp_path):
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                port = sup.udp_port
                # correct answers over many fresh flows on ONE port
                for s in range(24):
                    data = await ask_fresh(port, f"w{s % 4}.{DOMAIN}",
                                           Type.A, qid=s + 1)
                    msg = Message.decode(data)
                    assert msg.rcode == Rcode.NOERROR
                    assert msg.answers[0].address == \
                        f"10.50.0.{s % 4 + 1}"
                # N distinct worker PIDs, none of them the supervisor
                pids = {sup._pid(i) for i in range(2)}
                assert len(pids) == 2
                assert os.getpid() not in pids
                # exactly ONE store session in the whole topology: the
                # supervisor's; workers run ReplicaStore (no store
                # client at all) off the one mutation log
                assert sup.store.session_establishments == 1
                for i in range(2):
                    snap = worker_status(sup, i)
                    assert snap["store"]["backend"] == "ReplicaStore"
                    assert snap["service"]["pid"] == sup._pid(i)
                    assert snap["mirror"]["ready"] is True
                # every shard answered (the kernel spread the flows):
                # per-shard requests fold comes from 1 Hz stats frames
                await wait_for(
                    lambda: all(sup._requests_total.get(i, 0) > 0
                                for i in range(2)),
                    timeout=10, what="per-shard request folds")
            finally:
                await sup.drain()

        asyncio.run(run())

    def test_shard_metrics_exposition(self, tmp_path):
        """Tier-1 wiring for tools/lint.py validate_shard_metrics: the
        live supervisor's scrape passes, and the validator actually
        detects a broken exposition (a family with no samples)."""
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                text = sup.collector.expose()
                assert validate_shard_metrics(text) == []
                broken = "\n".join(
                    line for line in text.splitlines()
                    if not line.startswith("binder_shard_up"))
                errs = validate_shard_metrics(broken)
                assert any("binder_shard_up" in e for e in errs)
                # per-shard series must carry the shard label
                unlabeled = text.replace('shard="0"', 'notshard="0"')
                errs = validate_shard_metrics(unlabeled)
                assert any("shard" in e and "label" in e for e in errs)
                # the supervisor snapshot names every worker
                snap = sup.snapshot()
                assert snap["shards"]["count"] == 2
                assert len(snap["shards"]["workers"]) == 2
                assert all(w["pid"] for w in snap["shards"]["workers"])
            finally:
                await sup.drain()

        asyncio.run(run())


    def test_the_time_ledger_and_the_stall_rings_reach_every_process(
            self, tmp_path):
        """main.py hands every loop the timing selector: a worker's
        scrape carries the whole ledger (``loop-idle`` with it) and its
        ``/status`` ``io`` and ``loop.stalls``; the supervisor, which
        serves no query, still times its own loop's wait and keeps a
        ring, so the rings line up on the shared clock."""
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                for s in range(8):
                    await ask_fresh(sup.udp_port, "w0.shard.test", Type.A,
                                    qid=900 + s)
                loop = asyncio.get_running_loop()
                for shard in range(2):
                    mport = sup.links[shard].hello["metrics_port"]

                    def scrape(port=mport):
                        with urllib.request.urlopen(
                                f"http://127.0.0.1:{port}/metrics",
                                timeout=5) as r:
                            return r.read().decode()

                    text = await loop.run_in_executor(None, scrape)
                    assert validate_ledger_metrics(text) == []
                    status = await loop.run_in_executor(
                        None, worker_status, sup, shard)
                    assert validate_status_snapshot(status) == []
                    assert isinstance(status["loop"]["stalls"], list)
                    assert status["io"]["recv_calls"] >= 0
                snap = sup.snapshot()
                assert isinstance(snap["loop"]["stalls"], list)
                assert snap["loop"]["samples"] >= 0
            finally:
                await sup.drain()

        asyncio.run(run())


class TestShardLifecycle:
    def test_respawn_with_snapshot_catchup(self, tmp_path):
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                port = sup.udp_port
                pid0 = sup._pid(0)
                gen_before = sup.cache.gen
                assert sup.kill_shard(0) == pid0
                # supervisor respawns with a fresh incarnation
                await wait_for(
                    lambda: sup._pid(0) not in (None, pid0)
                    and sup.links[0].hello is not None,
                    timeout=15, what="shard respawn")
                assert sup.respawns[0] == 1
                # a mutation AFTER the crash: the respawned worker's
                # snapshot predates it, so convergence proves the
                # delta feed re-attached, not just the snapshot
                sup.store.put_json(
                    "/test/shard/w0",
                    {"type": "host", "host": {"address": "10.50.9.9"}})
                assert sup.cache.gen > gen_before   # owner monotonic

                async def all_converged():
                    for s in range(12):
                        data = await ask_fresh(port, f"w0.{DOMAIN}",
                                               Type.A, qid=500 + s)
                        msg = Message.decode(data)
                        if not msg.answers or \
                                msg.answers[0].address != "10.50.9.9":
                            return False
                    return True

                deadline = time.monotonic() + 10
                while not await all_converged():
                    assert time.monotonic() < deadline, \
                        "respawned group never converged on the " \
                        "post-crash mutation"
                    await asyncio.sleep(0.2)
            finally:
                await sup.drain()

        asyncio.run(run())

    def test_sigterm_drain_leaves_no_orphans(self, tmp_path):
        async def run():
            sup = await boot(str(tmp_path), 2)
            pids = [sup._pid(i) for i in range(2)]
            procs = [sup.links[i].proc for i in range(2)]
            await sup.drain()
            # every worker exited AND was reaped (no zombies: poll()
            # returns the code only after a successful waitpid)
            for proc in procs:
                assert proc.poll() is not None
            for pid in pids:
                with pytest.raises(ProcessLookupError):
                    os.kill(pid, 0)
            # drain is terminal: nothing respawns afterwards
            await asyncio.sleep(1.2)
            assert not sup.links

        asyncio.run(run())


class TestShardParity:
    def test_answers_identical_n1_vs_n4(self, tmp_path):
        """Byte parity (modulo ID) between N=1 and N=4 for the
        single-answer shapes, set parity for the rotated service
        shapes — N processes must be indistinguishable from one."""
        async def run():
            with tempfile.TemporaryDirectory() as d1:
                sup = await boot(d1, 1)
                try:
                    singles1, rotated1 = await collect_answers(
                        sup.udp_port)
                finally:
                    await sup.drain()
            with tempfile.TemporaryDirectory() as d4:
                sup = await boot(d4, 4)
                try:
                    assert len({sup._pid(i) for i in range(4)}) == 4
                    singles4, rotated4 = await collect_answers(
                        sup.udp_port)
                    assert sup.store.session_establishments == 1
                finally:
                    await sup.drain()
            for key in singles1:
                assert singles1[key] == singles4[key], \
                    f"answer wires differ for {key}"
                assert len(singles1[key]) == 1, \
                    f"single-answer shape {key} was not deterministic"
            for key in rotated1:
                assert rotated1[key] == rotated4[key], \
                    f"rotated answer shapes differ for {key}"

        asyncio.run(run())


class _StubProc:
    """Stands in for a worker Popen on an in-process link: alive, no
    PID of interest."""

    pid = 0

    def poll(self):
        return None


class TestLargeSnapshotAttach:
    """ISSUE 7 satellite: shard snapshot attach against a LARGE
    (>=50k-name) mirror.  In-process links (real socketpairs, real
    ReplicaStores, the real chunked pump) instead of worker
    subprocesses, so what is measured is the snapshot protocol at
    scale, not 50k names of process-boot overhead."""

    NAMES = 50000

    def test_50k_snapshot_heartbeats_convergence_parity(self):
        from binder_tpu.metrics.collector import MetricsCollector
        from binder_tpu.resolver.engine import Resolver, render_plan
        from binder_tpu.shard import ReplicaStore
        from binder_tpu.shard.supervisor import ShardLink, ShardSupervisor
        from binder_tpu.store import FakeStore, MirrorCache
        from binder_tpu.store.fake import populate_synthetic

        def render(cache, qname):
            plan = Resolver(cache, dns_domain=DOMAIN).plan(qname, Type.A)
            return render_plan(qname, Type.A, plan)

        async def run():
            store = FakeStore()
            populate_synthetic(store, DOMAIN, self.NAMES)
            cache = MirrorCache(store, DOMAIN)
            store.start_session()
            n_owner = len(cache.nodes)
            assert n_owner >= self.NAMES

            sup = ShardSupervisor(
                options={"shards": 2, "host": "127.0.0.1", "port": 0,
                         "dnsDomain": DOMAIN},
                store=store, cache=cache,
                collector=MetricsCollector())
            loop = asyncio.get_running_loop()
            sup._loop = loop

            replicas = []
            for i in range(2):
                sup_end, worker_end = socket.socketpair()
                sup_end.setblocking(False)
                link = ShardLink(i, _StubProc(), sup_end)
                sup.links[i] = link
                sup._send_snapshot(link)
                replicas.append(ReplicaStore(worker_end, i))
            # the pump must NOT have materialized the whole zone in the
            # link buffers (chunked streaming, not an eager build)
            assert all(len(lk.wbuf) <= sup.SNAP_HIGH_WATER + (1 << 20)
                       for lk in sup.links.values())

            futs = [loop.run_in_executor(None, r.read_snapshot, 120.0)
                    for r in replicas]
            # heartbeats + a mid-snapshot mutation while the snapshot
            # streams: both must interleave cleanly into the stream
            racks = max(1, min(1024, self.NAMES // 512))
            moved = f"h000123.r{123 % racks:04d}.zs.{DOMAIN}"
            ticks = 0
            mutated = False
            while not all(f.done() for f in futs):
                sup._tick()
                ticks += 1
                if not mutated and ticks >= 2:
                    store.put_json(
                        f"/test/shard/zs/r{123 % racks:04d}/h000123",
                        {"type": "host",
                         "host": {"address": "10.88.88.88"}})
                    mutated = True
                await asyncio.sleep(0.02)
            counts = [await f for f in futs]
            assert all(c == lk.snap_sent for c, lk in
                       zip(counts, sup.links.values()))
            assert mutated and ticks >= 2

            for r, c in zip(replicas, counts):
                # heartbeats kept flowing DURING snapshot streaming:
                # beyond the node frames, the replica applied the
                # leading state frame plus at least one mid-stream
                # heartbeat/delta
                assert r.frames_applied >= c + 2
                assert r.is_connected()

            # convergence: a worker-side mirror over each replica
            # reproduces the owner's view exactly
            mirrors = []
            for r in replicas:
                rc = MirrorCache(r, DOMAIN)
                mirrors.append(rc)
                assert len(rc.nodes) == len(cache.nodes)
                assert len(rc.rev_lookup) == len(cache.rev_lookup)
                assert rc.lookup(moved).data["host"]["address"] \
                    == "10.88.88.88"

            # N=1 vs N=2 byte parity modulo ID: both replicas render
            # byte-identical answers to the owner for sampled names
            # (render IDs are 0 on all sides)
            step = max(1, self.NAMES // 7)
            for i in range(0, self.NAMES, step):
                qname = f"h{i:06d}.r{i % racks:04d}.zs.{DOMAIN}"
                want = render(cache, qname)
                for rc in mirrors:
                    assert render(rc, qname) == want, qname

            for r in replicas:
                r.close()
            for lk in sup.links.values():
                sup._close_link(lk)

        asyncio.run(run())


def _spawn_stopped(sup):
    """A ``_spawn_link`` whose worker is SIGSTOPped at birth: alive,
    never exits, never moves — the quiet worker."""
    spawn = sup._spawn_link

    def spawn_stopped(i, port, role="serving"):
        link = spawn(i, port, role=role)
        os.kill(link.proc.pid, signal.SIGSTOP)
        return link

    return spawn_stopped


class TestStartBoundedByProgress:
    """ISSUE 21 repair: the supervisor's wait for a worker's hello is
    bounded by absence of progress, not by a total — a fixed 30 s made
    ``--shards N`` unstartable over a million-name zone."""

    NAMES = 50000

    def _supervisor(self, names: int):
        from binder_tpu.metrics.collector import MetricsCollector
        from binder_tpu.shard.supervisor import ShardSupervisor
        from binder_tpu.store import FakeStore, MirrorCache
        from binder_tpu.store.fake import populate_synthetic

        store = FakeStore()
        populate_synthetic(store, DOMAIN, names)
        cache = MirrorCache(store, DOMAIN)
        store.start_session()
        return ShardSupervisor(
            options={"shards": 1, "host": "127.0.0.1", "port": 0,
                     "dnsDomain": DOMAIN, "queryLog": False},
            store=store, cache=cache, collector=MetricsCollector())

    def test_attach_longer_than_the_window_still_starts(self):
        async def run():
            sup = self._supervisor(self.NAMES)
            # well under the attach's total; a small high-water keeps
            # the unapplied tail (the one stretch neither end reports)
            # short, so the gaps are the 250 ms progress cadence
            sup.WORKER_QUIET_S = 0.75
            sup.SNAP_HIGH_WATER = 256 << 10
            try:
                await sup.start()
                link = sup.links[0]
                took = link.progress_at - link.spawned_mono
                assert took > sup.WORKER_QUIET_S, took
                racks = max(1, min(1024, self.NAMES // 512))
                i = self.NAMES - 1
                data = await ask_fresh(
                    sup.udp_port,
                    f"h{i:06d}.r{i % racks:04d}.zs.{DOMAIN}", Type.A,
                    qid=61)
                assert Message.decode(data).answers[0].address == \
                    f"10.{i >> 16}.{(i >> 8) & 255}.{i & 255}"
            finally:
                await sup.drain()

        asyncio.run(run())

    def test_quiet_worker_still_fails_the_start(self):
        async def run():
            sup = self._supervisor(8)
            sup._spawn_link = _spawn_stopped(sup)
            sup.WORKER_QUIET_S = 0.5
            t0 = time.monotonic()
            try:
                with pytest.raises(TimeoutError, match="no progress"):
                    await sup.start()
                assert time.monotonic() - t0 < 5.0
            finally:
                for link in list(sup.links.values()):
                    sup._kill_link(link)
                await sup.drain()

        asyncio.run(run())

    def test_dead_worker_fails_the_start(self):
        async def run():
            sup = self._supervisor(8)
            spawn = sup._spawn_link

            def spawn_dead(i, port, role="serving"):
                link = spawn(i, port, role=role)
                link.proc.kill()
                return link

            sup._spawn_link = spawn_dead
            try:
                with pytest.raises(TimeoutError, match="exited"):
                    await sup.start()
            finally:
                await sup.drain()

        asyncio.run(run())


class TestShardAuto:
    def test_auto_resolves_to_allowed_cores_capped(self, monkeypatch):
        from binder_tpu.config.options import parse_options
        from binder_tpu.main import MAX_AUTO_SHARDS, resolve_shard_count
        opts = parse_options(["--shards", "auto", "-f",
                              "etc/config.json"])
        assert opts["shards"] == "auto"
        # the cores this process may run on, not the machine's
        assert resolve_shard_count(opts) == \
            len(os.sched_getaffinity(0)) >= 1
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: {4, 5})
        monkeypatch.setattr(os, "cpu_count", lambda: 64)
        assert resolve_shard_count(opts) == 2
        # a very wide host stops at the reference's per-zone cap
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(208)))
        assert resolve_shard_count(opts) == MAX_AUTO_SHARDS == 32
        # explicit counts and the unset default pass through untouched
        assert resolve_shard_count({"shards": 3}) == 3
        assert resolve_shard_count({}) == 0


class TestMissingExtensionWarning:
    def test_startup_says_once_when_fastio_is_absent(self, monkeypatch,
                                                     caplog):
        import logging

        from binder_tpu import main, server
        log = logging.getLogger("binder.test.nofastio")
        with caplog.at_level(logging.WARNING, logger=log.name):
            main.warn_if_no_fastio(log)     # built: nothing to say
            assert not caplog.records
            monkeypatch.setattr(server, "_fastio", None)
            main.warn_if_no_fastio(log)
        assert [r.levelname for r in caplog.records] == ["WARNING"]
        assert "_binderfastio" in caplog.text
        assert "Python lanes" in caplog.text


class TestChaosShardKill:
    def test_dsl_parses_and_dispatches(self):
        plan = FaultPlan.parse("at 0.5 shard-kill shard=1\n"
                               "at 1.0 shard-kill")
        assert [(t, a) for t, a, _ in plan.timeline] == \
            [(0.5, "shard-kill"), (1.0, "shard-kill")]
        killed = []
        driver = ChaosDriver(plan, shard_target=killed.append)
        driver.apply("shard-kill", {"shard": 1})
        driver.apply("shard-kill", {})
        assert killed == [1, -1]

    def test_no_target_is_skipped_not_fatal(self):
        driver = ChaosDriver(FaultPlan())
        driver.apply("shard-kill", {"shard": 0})   # must not raise
        assert [a for _, a in driver.applied] == ["shard-kill"]


class TestChaosRollAndFlood:
    """ISSUE 19 satellite: the ``worker-roll`` and ``rrl-flood`` chaos
    actions parse and dispatch (the live end-to-end exercise is
    ``tools/population_smoke.py`` phase B)."""

    def test_worker_roll_parses_and_dispatches(self):
        plan = FaultPlan.parse("at 0.5 worker-roll shard=1\n"
                               "at 1.0 worker-roll")
        assert [(t, a) for t, a, _ in plan.timeline] == \
            [(0.5, "worker-roll"), (1.0, "worker-roll")]
        rolled = []
        driver = ChaosDriver(plan, roll_target=rolled.append)
        driver.apply("worker-roll", {"shard": 1})
        driver.apply("worker-roll", {})
        assert rolled == [1, -1]

    def test_rrl_flood_sends_from_hostile_prefixes(self):
        """rrl-flood binds real sockets in the hostile /24s and fires
        decodable queries at the UDP target — the same source prefixes
        tools/hostile.py floods from, so RRL judges them alike."""
        from binder_tpu.chaos.plan import FLOOD_PREFIXES
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.settimeout(2.0)
        try:
            port = recv.getsockname()[1]
            driver = ChaosDriver(
                FaultPlan(),
                udp_target=("127.0.0.1", port, f"w0.{DOMAIN}"))
            driver.apply("rrl-flood", {"n": 32})
            srcs, data = set(), b""
            for _ in range(32):
                data, addr = recv.recvfrom(4096)
                srcs.add(addr[0].rsplit(".", 1)[0])
            # flood traffic really arrives FROM the hostile prefixes
            assert srcs <= set(FLOOD_PREFIXES) and len(srcs) >= 2
            msg = Message.decode(data)
            assert msg.questions[0].name == f"w0.{DOMAIN}"
        finally:
            recv.close()

    def test_no_target_is_skipped_not_fatal(self):
        driver = ChaosDriver(FaultPlan())
        driver.apply("worker-roll", {})            # must not raise
        driver.apply("rrl-flood", {"n": 4})        # must not raise
        assert [a for _, a in driver.applied] == \
            ["worker-roll", "rrl-flood"]


class TestRollingOps:
    """ISSUE 19 tentpole: zero-downtime drain-and-replace.  The
    incumbent keeps serving until the replacement is snapshot-caught-up
    and reuseport-bound; only then is it drained."""

    def test_roll_shard_drain_and_replace(self, tmp_path):
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                port = sup.udp_port
                pid0 = sup._pid(0)
                old_proc = sup.links[0].proc
                assert await sup.roll_shard(0)
                assert sup._pid(0) not in (None, pid0)
                # the incumbent exited AND was reaped
                assert old_proc.poll() is not None
                assert sup.rolls[0] == 1 and sup.roll_aborts == 0
                assert sup.respawns[0] == 0     # a roll is not a crash
                snap = sup.snapshot()
                assert snap["shards"]["rolls_total"] == 1
                assert snap["shards"]["rolling_shard"] is None
                # a mutation AFTER the roll: the replacement's delta
                # feed is live, not just its snapshot
                sup.store.put_json(
                    "/test/shard/w1",
                    {"type": "host", "host": {"address": "10.50.7.7"}})

                async def converged():
                    for s in range(8):
                        data = await ask_fresh(port, f"w1.{DOMAIN}",
                                               Type.A, qid=700 + s)
                        msg = Message.decode(data)
                        if not msg.answers or \
                                msg.answers[0].address != "10.50.7.7":
                            return False
                    return True

                deadline = time.monotonic() + 10
                while not await converged():
                    assert time.monotonic() < deadline, \
                        "rolled group never converged on the " \
                        "post-roll mutation"
                    await asyncio.sleep(0.2)
            finally:
                await sup.drain()

        asyncio.run(run())

    def test_request_roll_group_and_busy_absorbed(self, tmp_path):
        async def run():
            sup = await boot(str(tmp_path), 2)
            try:
                pids = {i: sup._pid(i) for i in range(2)}
                task = sup.request_roll()
                assert task is not None
                # an overlapping request is absorbed, not interleaved
                # (two rolls racing promotions for one shard slot)
                assert sup.request_roll() is None
                assert await task
                for i in range(2):
                    assert sup._pid(i) not in (None, pids[i])
                assert sup.rolls == {0: 1, 1: 1}
                assert sup.roll_aborts == 0
                # answers still flow from the new incarnation
                data = await ask_fresh(sup.udp_port, f"w0.{DOMAIN}",
                                       Type.A, qid=41)
                assert Message.decode(data).answers
            finally:
                await sup.drain()

        asyncio.run(run())

    def test_roll_abort_keeps_incumbent_serving(self, tmp_path):
        """A replacement that goes quiet before hello aborts the roll
        with the incumbent untouched — a bad build or config must not
        take down a serving shard."""
        async def run():
            sup = await boot(str(tmp_path), 1)
            try:
                pid0 = sup._pid(0)
                sup._spawn_link = _spawn_stopped(sup)
                sup.WORKER_QUIET_S = 0.5
                assert not await sup.roll_shard(0)
                assert sup.roll_aborts == 1 and sup.rolls[0] == 0
                assert sup._pid(0) == pid0
                data = await ask_fresh(sup.udp_port, f"w0.{DOMAIN}",
                                       Type.A, qid=51)
                assert Message.decode(data).answers
            finally:
                await sup.drain()

        asyncio.run(run())


class TestDcsFanout:
    """ISSUE 19 satellite: the ``/dcs`` subtree fans through the
    owner->worker mutation log (``pnode``/``pgone`` frames), so a
    worker's DcRegistry sees membership changes that happen AFTER it
    attached — pre-attach state rides the snapshot, post-attach joins
    and leaves ride the delta feed."""

    def test_worker_sees_dc_join_after_attach(self):
        from binder_tpu.federation.registry import DcRegistry
        from binder_tpu.metrics.collector import MetricsCollector
        from binder_tpu.shard import ReplicaStore
        from binder_tpu.shard.supervisor import ShardLink, ShardSupervisor
        from binder_tpu.store import FakeStore, MirrorCache

        async def run():
            store = FakeStore()
            for path, data in FIXTURE.items():
                store.put_json(path, data)
            # dc1 joins BEFORE the worker attaches: snapshot path
            store.put_json("/dcs/dc1", {"zones": ["east"],
                                        "peers": ["10.9.9.1:53"]})
            cache = MirrorCache(store, DOMAIN)
            store.start_session()

            sup = ShardSupervisor(
                options={"shards": 1, "host": "127.0.0.1", "port": 0,
                         "dnsDomain": DOMAIN},
                store=store, cache=cache, collector=MetricsCollector())
            loop = asyncio.get_running_loop()
            sup._loop = loop
            sup_end, worker_end = socket.socketpair()
            sup_end.setblocking(False)
            link = ShardLink(0, _StubProc(), sup_end)
            sup.links[0] = link
            sup._send_snapshot(link)
            replica = ReplicaStore(worker_end, 0)
            fut = loop.run_in_executor(None, replica.read_snapshot, 30.0)
            while not fut.done():
                sup._tick()
                await asyncio.sleep(0.02)
            await fut

            # the worker's registry comes up with the pre-attach
            # membership — delivered by the snapshot, not a store read
            reg = DcRegistry(replica, self_name="dc0")
            reg.start()
            assert set(reg.records) == {"dc1"}
            assert reg.records["dc1"]["zones"] == ["east"]
            changes = []
            reg.on_change(lambda: changes.append(dict(reg.records)))

            replica.start(loop)     # non-blocking delta feed

            # a DC that joins AFTER attach must reach the worker
            store.put_json("/dcs/dc2", {"zones": ["west"],
                                        "peers": ["10.9.9.2:53"]})
            deadline = time.monotonic() + 5
            while "dc2" not in reg.records:
                assert time.monotonic() < deadline, \
                    "post-attach dc-join never reached the worker"
                await asyncio.sleep(0.02)
            assert reg.records["dc2"]["peers"] == ["10.9.9.2:53"]
            assert reg.joins >= 1 and changes

            # ... and so must a leave (pgone -> children watch fires)
            store.rmr("/dcs/dc2")
            deadline = time.monotonic() + 5
            while "dc2" in reg.records:
                assert time.monotonic() < deadline, \
                    "post-attach dc-leave never reached the worker"
                await asyncio.sleep(0.02)
            assert reg.leaves >= 1
            assert set(reg.records) == {"dc1"}

            replica.close()
            sup._close_link(link)

        asyncio.run(run())


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
