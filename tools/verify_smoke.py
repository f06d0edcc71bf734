#!/usr/bin/env python3
"""Serving-plane verification smoke: the checker catches what it must.

Two harnesses back to back (ISSUE 16 acceptance):

**In-process** — boots a full binder (fake store + the verify
subsystem) and runs two phases:

- *clean soak*: continuous churn + queries; the incremental checker
  and the sampled audit must evaluate real work (checks advance, audit
  passes complete, the propagation stages ``mirror-apply`` and
  ``native-install`` observe) while firing ZERO violations — a
  checker that cries wolf on a healthy binder is worse than none; the
  scrape passes ``validate_verify_metrics`` and the snapshot passes
  ``validate_status_snapshot``; process RSS growth stays bounded;
- *scripted corruption*: chaos ``drop-reverse`` (map corruption that
  fires NO invalidation — only the audit can see it), then one audit
  cycle.  The corruption must be detected within that single cycle,
  and the violation must surface all three ways at once:
  ``verify-violation`` flight event, the
  ``binder_verify_violations_total{invariant}`` counter, and the
  ``recent_violations`` table in ``/status verify``.

**Subprocess** — a real N=2 shard supervisor with a scripted
``skew-replica`` fault (one delta frame suppressed to one worker, still
folded into the owner's digest roll) followed by a mutation storm: the
replica-digest invariant must flag the divergence at the next digest
frame (supervisor ``/status shards.digest_violations`` and the
``invariant="replica-digest"`` counter), serving must continue, and
SIGTERM must drain with no orphan PIDs.

Run via ``make verify-smoke`` (30 s) or set ``BINDER_VERIFY_SECONDS``.
Prints one JSON summary line; exit 0 == all invariants held.
"""
import asyncio
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from binder_tpu.chaos import ChaosDriver, FaultPlan  # noqa: E402
from binder_tpu.dns import Message, Rcode, Type, make_query  # noqa: E402
from binder_tpu.introspect import FlightRecorder, Introspector  # noqa: E402
from binder_tpu.metrics.collector import MetricsCollector  # noqa: E402
from binder_tpu.server import BinderServer  # noqa: E402
from binder_tpu.store import FakeStore, MirrorCache  # noqa: E402
from tools.lint import (validate_status_snapshot,  # noqa: E402
                        validate_verify_metrics)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOMAIN = "verify.test"
SKEW_DOMAIN = "verifyskew.test"
SHARDS = 2

#: in-process RSS growth bound over the whole soak+corruption run —
#: the checker/tracer reservoirs are all deque-bounded, so growth past
#: this is a leak, not workload
RSS_GROWTH_LIMIT_KB = 96 * 1024


class Violation(Exception):
    pass


def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def _invariant_counter(text: str, name: str, invariant: str) -> float:
    pat = (r'^%s\{[^}]*invariant="%s"[^}]*\} ([0-9.eE+-]+)$'
           % (re.escape(name), re.escape(invariant)))
    m = re.search(pat, text, re.M)
    return float(m.group(1)) if m else 0.0


async def _ask(port, name, qtype, qid, timeout=2.0):
    loop = asyncio.get_running_loop()
    fut = loop.create_future()

    class Proto(asyncio.DatagramProtocol):
        def connection_made(self, transport):
            transport.sendto(make_query(name, qtype, qid=qid).encode())

        def datagram_received(self, data, addr):
            if not fut.done():
                fut.set_result(data)

    transport, _ = await loop.create_datagram_endpoint(
        Proto, remote_addr=("127.0.0.1", port))
    try:
        return Message.decode(await asyncio.wait_for(fut, timeout))
    finally:
        transport.close()


# -- in-process: clean soak + scripted table corruption --

async def _run_inprocess(duration: float) -> dict:
    collector = MetricsCollector()
    recorder = FlightRecorder(capacity=1024)
    store = FakeStore(recorder=recorder)
    cache = MirrorCache(store, DOMAIN, collector=collector,
                        recorder=recorder)
    for i in range(8):
        store.put_json(f"/test/verify/w{i}",
                       {"type": "host",
                        "host": {"address": f"10.60.0.{i + 1}"}})
    for i in range(4):
        # churn names: each slot owns a /24 so address moves never
        # collide across names (ptr-coherence must stay clean)
        store.put_json(f"/test/verify/c{i}",
                       {"type": "host",
                        "host": {"address": f"10.60.{i + 1}.1"}})
    store.put_json("/test/verify/svc",
                   {"type": "service",
                    "service": {"srvce": "_http", "proto": "_tcp",
                                "port": 80}})
    for i in range(3):
        store.put_json(f"/test/verify/svc/m{i}",
                       {"type": "host",
                        "host": {"address": f"10.60.9.{i + 1}"}})
    store.start_session()

    # query_log on (without the JSON log ring) stands the native tier
    # down (_fastpath_active), so every query surfaces in Python; the
    # zone drain still re-pushes each churned name (native-install)
    server = BinderServer(
        zk_cache=cache, dns_domain=DOMAIN, datacenter_name="dc0",
        host="127.0.0.1", port=0, collector=collector, query_log=True,
        flight_recorder=recorder,
        verify={"auditIntervalSeconds": 0.05})
    await server.start()
    intro = Introspector(server=server, recorder=recorder,
                         name="verify-smoke")
    intro.set_loop(asyncio.get_running_loop())
    vf = server._verify
    rss0 = _rss_kb()
    stats = {"queries": 0, "mutations": 0}
    snapshot_errs = []
    try:
        # -- phase 1: clean soak (churn + queries, zero violations) --
        loop = asyncio.get_running_loop()
        t_end = loop.time() + duration
        i = 0
        while loop.time() < t_end:
            i += 1
            store.put_json(
                f"/test/verify/c{i % 4}",
                {"type": "host",
                 "host": {"address":
                          f"10.60.{i % 4 + 1}.{i % 250 + 1}"}})
            stats["mutations"] += 1
            msg = await _ask(server.udp_port, f"w{i % 8}.{DOMAIN}",
                             Type.A, qid=(i % 0xFFFF) + 1)
            if msg.rcode != Rcode.NOERROR or not msg.answers:
                raise Violation(f"bad answer for w{i % 8}: "
                                f"rcode {msg.rcode}")
            stats["queries"] += 1
            if i % 5 == 0:
                await _ask(server.udp_port, f"c{i % 4}.{DOMAIN}",
                           Type.A, qid=20000 + i % 1000)
            if i % 7 == 0:
                await _ask(server.udp_port, f"svc.{DOMAIN}",
                           Type.A, qid=30000 + i % 1000)
            if i % 31 == 0:
                errs = validate_status_snapshot(intro.snapshot())
                if errs:
                    snapshot_errs.extend(errs)
            await asyncio.sleep(duration / 400.0)
        if snapshot_errs:
            raise Violation(f"status snapshot: {snapshot_errs[:3]}")

        fired = {k: v for k, v in vf.violations.items() if v}
        if fired:
            raise Violation(f"clean soak fired violations: {fired}")
        if not sum(vf.checks.values()):
            raise Violation("checker evaluated no invariants")
        for inv in ("ptr-coherence", "dangling-srv"):
            if not vf.checks[inv]:
                raise Violation(f"invariant {inv} never checked")
        if vf.audit_passes < 1:
            raise Violation("background audit never completed a pass")
        prop = vf.tracer.introspect()
        if not prop["observed"]:
            raise Violation("no propagation stages observed")
        for stage in ("mirror-apply", "native-install"):
            if not prop["stages"][stage]["count"]:
                raise Violation(f"propagation stage {stage} never "
                                f"observed under churn")
        errs = validate_verify_metrics(collector.expose())
        if errs:
            raise Violation(f"verify metrics: {errs[:3]}")

        # -- phase 2: scripted corruption, detected within ONE cycle --
        plan = FaultPlan(seed=3).at(0.05, "drop-reverse")
        driver = ChaosDriver(plan, store=store, verify_target=server,
                             recorder=recorder)
        await driver.run()
        vf.audit_cycle()
        if vf.violations["ptr-coherence"] < 1:
            raise Violation("drop-reverse not detected within one "
                            "audit cycle")
        # the violation -> flight event -> metrics -> /status round trip
        if recorder.by_type.get("verify-violation", 0) < 1:
            raise Violation("violations missing from the flight "
                            "recorder")
        if _invariant_counter(
                collector.expose(), "binder_verify_violations_total",
                "ptr-coherence") < 1:
            raise Violation("violations counter for ptr-coherence did "
                            "not advance")
        snap = intro.snapshot()
        recent = {v["invariant"]
                  for v in snap["verify"]["recent_violations"]}
        if "ptr-coherence" not in recent:
            raise Violation(f"/status recent_violations missing "
                            f"invariants: has {sorted(recent)}")
        errs = validate_status_snapshot(snap)
        if errs:
            raise Violation(f"status snapshot mid-violation: "
                            f"{errs[:3]}")

        growth = _rss_kb() - rss0
        if growth > RSS_GROWTH_LIMIT_KB:
            raise Violation(f"RSS grew {growth} KiB over the run "
                            f"(limit {RSS_GROWTH_LIMIT_KB})")
        stats.update({
            "checks": dict(vf.checks),
            "violations_detected": dict(vf.violations),
            "skipped": sum(vf.skipped.values()),
            "audit_passes": vf.audit_passes,
            "propagation_observed": prop["observed"],
            "rss_growth_kb": growth,
        })
        return stats
    finally:
        await server.stop()


# -- subprocess: skew-replica vs the digest frames --

SKEW_FIXTURE = {
    f"/test/verifyskew/w{i}":
    {"type": "host", "host": {"address": f"10.61.0.{i + 1}"}}
    for i in range(8)
}


async def _run_skew(duration: float) -> dict:
    from tools.shard_smoke import (_ask_fresh, _drain_stdout,
                                   _pid_alive, _scrape, _status)
    from tools.shard_smoke import Violation as ShardViolation
    tmpdir = tempfile.mkdtemp(prefix="verify-smoke-")
    fixture = os.path.join(tmpdir, "fixture.json")
    config = os.path.join(tmpdir, "config.json")
    with open(fixture, "w") as f:
        json.dump(SKEW_FIXTURE, f)
    skew_at = max(1.5, duration * 0.2)
    storm_at = skew_at + 0.8
    with open(config, "w") as f:
        json.dump({
            "dnsDomain": SKEW_DOMAIN, "datacenterName": "dc0",
            "host": "127.0.0.1", "queryLog": False,
            "store": {"backend": "fake", "fixture": fixture},
            "shards": SHARDS,
            # suppress ONE delta frame to shard 0 (still hashed into
            # the owner's roll), then a storm: the very next digest
            # frame must flag the divergence
            "chaos": {"plan":
                      f"at {skew_at:.1f} skew-replica shard=0 frames=1;"
                      f" at {storm_at:.1f} watch-storm n=20"},
        }, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "binder_tpu.main", "-f", config,
         "-p", "0"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    stats = {}
    try:
        buf = b""
        deadline = time.time() + 30
        port = mport = None
        while time.time() < deadline:
            chunk = os.read(proc.stdout.fileno(), 4096)
            if not chunk:
                raise Violation("supervisor exited during startup")
            buf += chunk
            m = re.search(rb"UDP DNS service started on "
                          rb"[\d.]+:(\d+)\"", buf)
            if m:
                port = int(m.group(1))
                mm = re.search(
                    rb"metrics server started on port (\d+)\"", buf)
                mport = int(mm.group(1)) if mm else None
                break
        if port is None or mport is None:
            raise Violation("supervisor did not report its ports")
        os.set_blocking(proc.stdout.fileno(), False)

        # the divergence must be detected before the window closes
        snap = None
        deadline = time.monotonic() + duration
        while time.monotonic() < deadline:
            _drain_stdout(proc)
            snap = _status(mport)
            if snap["shards"]["digest_violations"] >= 1:
                break
            await asyncio.sleep(0.25)
        else:
            checks = (snap["shards"]["digest_checks"]
                      if snap is not None else None)
            raise Violation(f"replica-digest divergence never "
                            f"detected (digest checks: {checks})")
        if snap["shards"]["digest_checks"] < 1:
            raise Violation("no digest frames were ever compared")
        text = _scrape(mport)
        if _invariant_counter(text, "binder_verify_violations_total",
                              "replica-digest") < 1:
            raise Violation("replica-digest violations counter did "
                            "not advance on the supervisor scrape")

        # divergence detected, serving continues
        data = await _ask_fresh(port, f"w0.{SKEW_DOMAIN}", Type.A,
                                qid=777)
        msg = Message.decode(data)
        if msg.rcode != Rcode.NOERROR or not msg.answers:
            raise Violation("serving broke after the skew incident")

        # SIGTERM drain: no orphan worker PIDs
        pids = [w["pid"] for w in snap["shards"]["workers"]
                if w["pid"]]
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            raise Violation("supervisor did not exit on SIGTERM")
        deadline = time.monotonic() + 5
        orphans = list(pids)
        while orphans and time.monotonic() < deadline:
            orphans = [p for p in orphans if _pid_alive(p)]
            await asyncio.sleep(0.1)
        if orphans:
            raise Violation(f"orphan worker pid(s) after drain: "
                            f"{orphans}")
        stats.update({
            "digest_checks": snap["shards"]["digest_checks"],
            "digest_violations": snap["shards"]["digest_violations"],
        })
        return stats
    except ShardViolation as e:
        raise Violation(str(e))
    finally:
        if proc.poll() is None:
            proc.kill()
        try:
            proc.wait(timeout=10)
        except Exception:
            pass


def run_smoke(duration: float = None) -> dict:
    if duration is None:
        duration = float(os.environ.get("BINDER_VERIFY_SECONDS", "30"))
    stats = asyncio.run(_run_inprocess(max(3.0, duration * 0.5)))
    stats["skew_incident"] = asyncio.run(
        _run_skew(max(6.0, duration * 0.35)))
    stats["duration_s"] = duration
    return stats


def main() -> int:
    try:
        stats = run_smoke()
    except Violation as e:
        print(json.dumps({"verify_smoke": "FAIL", "violation": str(e)}))
        return 1
    print(json.dumps({"verify_smoke": "ok", **stats}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
