#!/usr/bin/env python3
"""Zone-scale smoke: the million-name representation's invariants, end
to end, as a CI gate (ISSUE 7).

Builds a synthetic mirror at a small CONTROL size and at the smoke size
(``BINDER_ZONE_NAMES``, default 100k; ``make ci`` runs a trimmed 20k),
applies a mutation burst + watch storm through the real
mirror → invalidate → drop chain (tools/zone_probe.py), and
asserts:

- single-name rebuild latency is independent of zone size
  (p50 at the smoke size within ``LAT_RATIO_MAX`` of the control —
  O(delta), not O(zone));
- the ask after every mutation serves the bytes of a fresh engine
  render of the new data, never the dropped answer (answers stay
  engine-parity through the compact representation);
- the watch storm's names all serve their new answers again;
- the chunked session rebuild never stalls the event loop past the
  loop-lag watchdog threshold, and lookups keep serving throughout;
- the in-process metrics surface passes ``validate_mirror_metrics``
  (TYPE + label pins for the ``binder_mirror_*`` family).

Prints one JSON summary line; exit 0 == all invariants held.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from binder_tpu.metrics.collector import MetricsCollector  # noqa: E402
from binder_tpu.server import BinderServer  # noqa: E402
from binder_tpu.store import FakeStore, MirrorCache  # noqa: E402
from tools.lint import validate_mirror_metrics  # noqa: E402
from tools.zone_probe import probe  # noqa: E402

CONTROL = int(os.environ.get("BINDER_ZONE_CONTROL", "2000"))
SMOKE = int(os.environ.get("BINDER_ZONE_NAMES", "100000"))
#: p50 mutation latency at the smoke size may be at most this multiple
#: of the control's — generous against CI noise while still failing
#: loudly on anything O(zone) (a linear path would show up as ~SMOKE /
#: CONTROL, i.e. 50x)
LAT_RATIO_MAX = 4.0
#: the loop-lag watchdog's stall threshold (introspect/watchdog.py)
STALL_THRESHOLD_MS = 250.0


def scrape_mirror_metrics() -> list:
    """Build a collector-wired server over a small mirror and validate
    the binder_mirror_* / zone-scale exposition pins."""
    collector = MetricsCollector()
    store = FakeStore()
    store.put_json("/com/smoke/web",
                   {"type": "host", "host": {"address": "10.0.0.1"}})
    cache = MirrorCache(store, "smoke.com", collector=collector)
    store.start_session()
    BinderServer(zk_cache=cache, dns_domain="smoke.com",
                 collector=collector, cache_size=16)
    return validate_mirror_metrics(collector.expose())


def main() -> int:
    failures = []
    results = {"control_names": CONTROL, "smoke_names": SMOKE}

    control = probe(CONTROL, mutations=100,
                    storm=max(100, CONTROL // 4))
    smoke = probe(SMOKE, mutations=150, storm=max(500, SMOKE // 20))
    results["control"] = control
    results["smoke"] = smoke

    ratio = smoke["mutation_p50_us"] / max(1e-9,
                                           control["mutation_p50_us"])
    results["mutation_p50_ratio"] = round(ratio, 2)
    if ratio > LAT_RATIO_MAX:
        failures.append(
            f"mutation latency scales with zone size: p50 "
            f"{smoke['mutation_p50_us']}us at {SMOKE} names vs "
            f"{control['mutation_p50_us']}us at {CONTROL} "
            f"(ratio {ratio:.1f} > {LAT_RATIO_MAX})")

    parity = control["parity_failures"] + smoke["parity_failures"]
    if parity:
        failures.append(f"{parity} answer(s) served after a mutation "
                        "diverged from a fresh engine render")

    if smoke["rebuild_max_loop_lag_ms"] > STALL_THRESHOLD_MS:
        failures.append(
            f"chunked rebuild stalled the loop "
            f"{smoke['rebuild_max_loop_lag_ms']}ms "
            f"(watchdog threshold {STALL_THRESHOLD_MS}ms)")
    if smoke["rebuild_miss_mid"]:
        failures.append(
            f"{smoke['rebuild_miss_mid']} lookup(s) went dark during "
            "the chunked rebuild (serving must continue)")
    if smoke["rebuild_chunks"] < 2:
        failures.append("rebuild at smoke size did not chunk")

    # storm recovered (every name re-resolved) — pin the figure
    results["storm_recovery_s"] = smoke["storm_recovery_s"]

    lint_errs = scrape_mirror_metrics()
    if lint_errs:
        failures.append("mirror metrics exposition: "
                        + "; ".join(lint_errs[:5]))

    results["failures"] = failures
    results["ok"] = not failures
    print(json.dumps(results))
    if failures:
        for f in failures:
            print("zone-smoke FAIL:", f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
