#!/usr/bin/env python3
"""population-smoke: million-client realism as a CI gate.

Two phases, each against a REAL ``binder_tpu.main`` subprocess:

**Phase A — population vs RRL v2 (single process).**  Runs the
population model (``tools/population.py``: Zipf name/identity
popularity, NAT'd resolver farms concentrated in two /24s, a spoofed
overlay, ramped offered load, TCP retry on slip/timeout) against a
server with deliberately low RRL limits, ``adaptive: true``, and the
eyeball cohort's /16 allowlisted.  Asserts:

- **goodput floor**: the NAT'd farm cohort's end-to-end goodput
  (UDP answers + TCP-retry recoveries over sent) stays above the
  smoke floor even though the farm prefixes ARE rate-limited;
- **FP ceiling**: the measured RRL false-positive rate (legit farm
  queries lost and never recovered) stays under the ceiling — the
  adaptive buckets' whole job;
- **adaptation engaged**: ``binder_rrl_adaptations_total`` >= 1 (the
  farms' TCP retries earned a bigger bucket) while the spoofed
  overlay still shows drops (``binder_rrl_dropped_total`` > 0);
- **allowlist honored**: ``binder_rrl_allowlisted_total`` > 0 and the
  exposition passes the extended ``validate_rrl_metrics``.

**Phase B — zero-downtime rolling operations (2-shard supervisor).**
Mid-incident (a scripted ``rrl-flood`` burst), the chaos DSL's
``worker-roll`` rolls every shard; once ``rolls_total`` reaches 2 the
smoke sends SIGHUP (the config-reload entry point) to roll them all
again.  A closed-loop allowlisted probe runs across both rolls.
Asserts:

- **zero query loss**: no probe query is ever lost (and first-try
  timeouts stay within a freak-packet tolerance) across 4 rolls;
- **drain-and-replace end to end**: every worker PID changed, twice;
  ``binder_shard_rolls_total`` == 2 per shard, zero aborts; workers
  logged "quiesced clean" (in-flight served out before exit); shard
  0's promotion completed before shard 1's replacement spawned (rolls
  are sequential by construction);
- the supervisor scrape passes the extended
  ``validate_shard_metrics`` (roll counters present from scrape 1).

``BINDER_POPULATION_SECONDS`` overrides the total budget (default 30;
``make ci`` trims to 10).  Prints one JSON summary line; exit 0 ==
all held.  Run via ``make population-smoke``.
"""
import json
import os
import re
import select
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from binder_tpu.dns.wire import Type, make_query  # noqa: E402
from tools.population import run_population  # noqa: E402
from tools.lint import (validate_rrl_metrics,  # noqa: E402
                        validate_shard_metrics,
                        validate_status_snapshot)

DOMAIN = "popsmoke.test"
DURATION = float(os.environ.get("BINDER_POPULATION_SECONDS", "30"))
SHARDS = 2
#: low enough that the farm /24s trip RRL fast, high enough that one
#: adaptation step visibly relieves them
RRL_RPS, RRL_BURST = 60, 120
#: smoke floors/ceilings (the gate only refuses regressions to "RRL
#: starves farms")
GOODPUT_FLOOR = 0.5
FP_CEILING = 0.10
#: freak-packet tolerance for first-try probe timeouts across 4 rolls
#: (a loaded CI host, not the hand-over: no socket is closed in a roll
#: since PR 45); LOST queries get zero tolerance
ROLL_RETRY_TOLERANCE = 3


class Violation(Exception):
    pass


def _write_config(tmpdir, *, shards=None, chaos=None, allowlist=()):
    fixture = {f"/test/popsmoke/w{i}":
               {"type": "host", "host": {"address": f"10.77.0.{i + 1}"}}
               for i in range(16)}
    fixture_path = os.path.join(tmpdir, "fixture.json")
    with open(fixture_path, "w") as f:
        json.dump(fixture, f)
    cfg = {
        "dnsDomain": DOMAIN, "datacenterName": "dc0",
        "host": "127.0.0.1", "queryLog": False,
        "store": {"backend": "fake", "fixture": fixture_path},
        "rrl": {"responsesPerSecond": RRL_RPS, "burst": RRL_BURST,
                "slipRatio": 2, "maxBuckets": 512,
                "adaptive": True, "adaptEvidence": 3,
                "allowlist": list(allowlist)},
    }
    if shards:
        cfg["shards"] = shards
    if chaos:
        cfg["chaos"] = chaos
    config_path = os.path.join(tmpdir, "config.json")
    with open(config_path, "w") as f:
        json.dump(cfg, f)
    return config_path


def _boot(config):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "binder_tpu.main", "-f", config,
         "-p", "0"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    buf = b""
    deadline = time.time() + 30
    while time.time() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [],
                                    max(0.0, deadline - time.time()))
        if not ready:
            break
        chunk = os.read(proc.stdout.fileno(), 65536)
        if not chunk:
            raise Violation("server exited during startup")
        buf += chunk
        m = re.search(rb"UDP DNS service started on [\d.]+:(\d+)\"", buf)
        mm = re.search(rb"metrics server started on port (\d+)\"", buf)
        if m and mm:
            os.set_blocking(proc.stdout.fileno(), False)
            return proc, int(m.group(1)), int(mm.group(1)), buf
    raise Violation("server did not report its ports in time")


def _drain_stdout(proc, buf):
    try:
        while True:
            chunk = os.read(proc.stdout.fileno(), 65536)
            if not chunk:
                return buf
            buf += chunk
    except (BlockingIOError, InterruptedError, OSError):
        pass
    return buf


def _scrape(mport, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}{path}", timeout=5) as r:
        return r.read().decode()


def _metric(text, name):
    total = 0.0
    for m in re.finditer(rf"^{name}(?:{{[^}}]*}})? ([0-9.eE+-]+)$",
                         text, re.M):
        total += float(m.group(1))
    return total


def _stop(proc):
    if proc is None:
        return
    try:
        proc.terminate()
        proc.wait(timeout=10)
    except Exception:
        try:
            proc.kill()
            proc.wait(timeout=10)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Phase A


def phase_population(duration: float) -> dict:
    tmpdir = tempfile.mkdtemp(prefix="pop_smoke_a_")
    # allowlist the DIRECT (eyeball) cohort's /16: those sources skip
    # RRL pre-decode; the farm prefixes are deliberately NOT listed —
    # they must earn relief through the adaptive path
    config = _write_config(tmpdir, allowlist=("127.10.0.0/16",))
    proc = None
    try:
        proc, port, mport, _ = _boot(config)
        report = run_population(
            "127.0.0.1", port, duration=duration, domain=DOMAIN,
            names=[f"w{i}.{DOMAIN}" for i in range(16)],
            identities=100_000, qps_floor=300, qps_peak=1500,
            spoof_share=0.2)
        if proc.poll() is not None:
            raise Violation("server died under population load")

        goodput = report["farm_goodput_ratio"]
        if goodput < GOODPUT_FLOOR:
            raise Violation(f"farm goodput {goodput} under floor "
                            f"{GOODPUT_FLOOR}")
        fp = report["rrl_false_positive_rate"]
        if fp > FP_CEILING:
            raise Violation(f"RRL false-positive rate {fp} over "
                            f"ceiling {FP_CEILING}")

        text = _scrape(mport, "/metrics")
        errs = validate_rrl_metrics(text)
        if errs:
            raise Violation(f"rrl metrics: {errs[:3]}")
        if _metric(text, "binder_rrl_dropped_total") <= 0:
            raise Violation("spoof overlay was never dropped")
        if _metric(text, "binder_rrl_adaptations_total") < 1:
            raise Violation("adaptive buckets never engaged (no "
                            "TCP-retry evidence consumed)")
        if _metric(text, "binder_rrl_allowlisted_total") <= 0:
            raise Violation("allowlisted eyeball cohort never counted")
        status = json.loads(_scrape(mport, "/status"))
        errs = validate_status_snapshot(status)
        if errs:
            raise Violation(f"status snapshot: {errs[:3]}")
        rrl_status = (status.get("policy") or {}).get("rrl") or {}
        return {
            "population": report["population"],
            "farm_goodput_ratio": goodput,
            "rrl_false_positive_rate": fp,
            "identity_outcomes": report["identity_outcomes"],
            "cohorts": {c: row["sent"]
                        for c, row in report["cohorts"].items()},
            "rrl": {
                "dropped": _metric(text, "binder_rrl_dropped_total"),
                "adaptations": _metric(text,
                                       "binder_rrl_adaptations_total"),
                "adapted_buckets": _metric(text,
                                           "binder_rrl_adapted_buckets"),
                "allowlisted": _metric(text,
                                       "binder_rrl_allowlisted_total"),
                "false_positives": _metric(
                    text, "binder_rrl_false_positives_total"),
                "status_adapted": rrl_status.get("adapted_buckets"),
            },
        }
    finally:
        _stop(proc)


# ---------------------------------------------------------------------------
# Phase B


def _probe_once(port, qid, timeout=1.5):
    """One closed-loop query; returns tries used (1..3) or raises."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.connect(("127.0.0.1", port))
    sock.settimeout(timeout)
    wire = make_query(f"w{qid % 16}.{DOMAIN}", Type.A,
                      qid=(qid % 65535) + 1).encode()
    try:
        for attempt in range(1, 4):
            sock.send(wire)
            try:
                reply = sock.recv(65535)
            except socket.timeout:
                continue
            if len(reply) >= 12 and (reply[3] & 0xF) == 0:
                return attempt
        return 0      # lost entirely
    finally:
        sock.close()


def phase_rolling(duration: float) -> dict:
    tmpdir = tempfile.mkdtemp(prefix="pop_smoke_b_")
    flood_at = max(1.0, duration * 0.15)
    roll_at = max(1.5, duration * 0.25)
    config = _write_config(
        tmpdir, shards=SHARDS,
        # the mid-incident script: a spoofed burst trips RRL, then the
        # DSL's worker-roll drains-and-replaces every shard under it
        chaos={"plan": f"at {flood_at:.1f} rrl-flood n=400; "
                       f"at {roll_at:.1f} worker-roll"},
        allowlist=("127.0.0.0/24",))
    proc = None
    try:
        proc, port, mport, buf = _boot(config)
        status = json.loads(_scrape(mport, "/status"))
        pids0 = [w["pid"] for w in status["shards"]["workers"]]
        if len(set(pids0)) != SHARDS:
            raise Violation(f"expected {SHARDS} worker pids, {pids0}")

        stats = {"queries": 0, "retried": 0, "lost": 0}
        sighup_sent = False
        pids1 = []
        deadline = time.monotonic() + duration + 25.0
        i = 0
        while time.monotonic() < deadline:
            i += 1
            tries = _probe_once(port, i)
            stats["queries"] += 1
            if tries == 0:
                stats["lost"] += 1
            elif tries > 1:
                stats["retried"] += 1
            if i % 10 == 0:
                buf = _drain_stdout(proc, buf)
                snap = json.loads(_scrape(mport, "/status"))
                rolls = snap["shards"]["rolls_total"]
                if rolls >= SHARDS and not sighup_sent:
                    # chaos roll done: exercise the config-reload
                    # entry point on the same live group
                    pids1 = [w["pid"]
                             for w in snap["shards"]["workers"]]
                    proc.send_signal(signal.SIGHUP)
                    sighup_sent = True
                elif rolls >= 2 * SHARDS:
                    break
            time.sleep(max(0.005, duration / 400.0))
        buf = _drain_stdout(proc, buf)

        snap = json.loads(_scrape(mport, "/status"))
        sh = snap["shards"]
        if sh["rolls_total"] < 2 * SHARDS:
            raise Violation(f"only {sh['rolls_total']} rolls completed "
                            f"(want {2 * SHARDS}: chaos + SIGHUP)")
        if sh["roll_aborts"]:
            raise Violation(f"{sh['roll_aborts']} roll step(s) aborted")
        pids2 = [w["pid"] for w in sh["workers"]]
        if set(pids2) & set(pids0) or (pids1 and set(pids2) & set(pids1)):
            raise Violation(f"worker pids survived a roll: "
                            f"{pids0} -> {pids1} -> {pids2}")
        if stats["lost"]:
            raise Violation(f"{stats['lost']} probe quer(ies) lost "
                            f"across {sh['rolls_total']} rolls")
        if stats["retried"] > ROLL_RETRY_TOLERANCE:
            raise Violation(f"{stats['retried']} probe retries across "
                            f"rolls (tolerance {ROLL_RETRY_TOLERANCE})")

        # drain-and-replace evidence, from the workers' own mouths:
        # every drained incumbent served out its in-flight before exit
        quiesced = buf.count(b"quiesced clean")
        if quiesced < 2 * SHARDS:
            raise Violation(f"only {quiesced} clean quiesces logged "
                            f"(want {2 * SHARDS})")
        # sequential rolls: shard 0's cycle completed before shard 1's
        # replacement was even spawned
        first_done = buf.find(b"shard 0 rolled: pid")
        second_spawn = buf.find(b"shard 1 replacement spawned")
        if first_done == -1 or second_spawn == -1 \
                or second_spawn < first_done:
            raise Violation("rolls were not sequential (shard 1 "
                            "replacement before shard 0 promotion)")

        text = _scrape(mport, "/metrics")
        errs = validate_shard_metrics(text)
        if errs:
            raise Violation(f"shard metrics: {errs[:3]}")
        if _metric(text, "binder_shard_rolls_total") < 2 * SHARDS:
            raise Violation("binder_shard_rolls_total under-counts")

        # the flood engaged RRL inside at least one worker (folded
        # rrl drops surface in the supervisor's shard aggregates)
        if _metric(text, "binder_shard_rrl_dropped") <= 0:
            raise Violation("rrl-flood never engaged the workers' RRL")

        stats.update({
            "rolls_total": sh["rolls_total"],
            "roll_aborts": sh["roll_aborts"],
            "pids": {"boot": pids0, "after_chaos_roll": pids1,
                     "after_sighup_roll": pids2},
            "quiesced_clean": quiesced,
        })
        return stats
    finally:
        _stop(proc)


def main() -> int:
    try:
        a = phase_population(max(5.0, DURATION * 0.5))
        b = phase_rolling(max(6.0, DURATION * 0.5))
    except Violation as e:
        print(json.dumps({"population_smoke": "FAIL",
                          "violation": str(e)}))
        return 1
    print(json.dumps({"population_smoke": "ok", "duration_s": DURATION,
                      "population": a, "rolling": b}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
