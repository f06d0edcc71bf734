#!/usr/bin/env python3
"""Zone-scale probe: mirror RSS/build/mutation-latency at N names.

The measurement half of ISSUE 7's ``zone_scale`` axis, shared by ``make
zone-smoke`` (tools/zone_smoke.py) and by tests/test_zone_scale.py; run
as a script, one process a zone size keeps measurements out of each
other's RSS.

Builds a synthetic zone (``store.fake.populate_synthetic``) in a fake
store, mirrors it, wires the answer cache to the mirror's invalidation
feed the way BinderServer does, and measures:

- store/mirror build wall time and RSS delta (→ bytes per name);
- single-name mutation latency: mirror → drop of the served answer
  (p50/p99 over a sample spread across the zone), with a byte-parity
  check of the next ask's wire (a lazy resolve) against a fresh engine
  render of the new data;
- watch-storm recovery: a burst of mutations against served names,
  time until every one of them serves its new answer again;
- chunked session rebuild: wall time, chunk count, the worst
  event-loop stall observed while it streamed, and proof that lookups
  kept serving mid-rebuild;
- interned-name pool stats.

Usage:  python tools/zone_probe.py <names> [mutations] [storm]
Prints one JSON line.
"""
import asyncio
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from binder_tpu.resolver.answer_cache import AnswerCache  # noqa: E402
from binder_tpu.resolver.engine import Resolver, render_plan  # noqa: E402
from binder_tpu.dns.wire import Type  # noqa: E402
from binder_tpu.store import FakeStore, MirrorCache  # noqa: E402
from binder_tpu.store.fake import populate_synthetic  # noqa: E402
from binder_tpu.store.names import POOL  # noqa: E402

DOMAIN = "bench.zone"


def rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


def host_path(i: int, racks: int) -> str:
    return f"/zone/bench/zs/r{i % racks:04d}/h{i:06d}"


def host_name(i: int, racks: int) -> str:
    return f"h{i:06d}.r{i % racks:04d}.zs.{DOMAIN}"


class Harness:
    """The Python lanes' answer path of BinderServer, minus transports:
    an AnswerCache + Resolver fed by the mirror's per-name invalidation
    events, so a store mutation exercises the REAL mirror → drop chain
    and the next ask is a lazy resolve."""

    def __init__(self, cache: MirrorCache, cache_size: int = 65536):
        self.cache = cache
        self.answer_cache = AnswerCache(size=cache_size,
                                        intern=cache.canon)
        self.resolver = Resolver(cache, dns_domain=DOMAIN)
        cache.on_invalidate(self._on_invalidate)

    def _on_invalidate(self, tags) -> None:
        for tag in tags:
            self.answer_cache.invalidate_tag(tag)

    def served_wire(self, qname: str) -> bytes:
        """What the ladder serves for the name's A: the cached wire,
        else a resolve whose wire is cached under the name's tag."""
        key, epoch = (Type.A, qname), self.cache.epoch
        wire = self.answer_cache.get(key, epoch)
        if wire is None:
            wire = self.engine_wire(qname)
            self.answer_cache.put(key, epoch, wire, tag=qname)
        return wire

    #: a first ask: the name has a served answer for a mutation to drop
    prime = served_wire

    def engine_wire(self, qname: str) -> bytes:
        return render_plan(qname, Type.A,
                           self.resolver.plan(qname, Type.A))


def probe(n: int, mutations: int = 200, storm: int = 2000) -> dict:
    racks = max(1, min(1024, n // 512))
    out = {"names": n, "racks": racks}

    gc.collect()
    rss0 = rss_kb()
    t0 = time.perf_counter()
    store = FakeStore()
    populate_synthetic(store, DOMAIN, n, racks=racks)
    out["store_build_s"] = round(time.perf_counter() - t0, 3)
    gc.collect()
    rss1 = rss_kb()
    out["store_rss_kb"] = rss1 - rss0

    t0 = time.perf_counter()
    cache = MirrorCache(store, DOMAIN)
    store.start_session()
    out["mirror_build_s"] = round(time.perf_counter() - t0, 3)
    gc.collect()
    rss2 = rss_kb()
    out["mirror_rss_kb"] = rss2 - rss1
    out["mirror_rss_per_name_bytes"] = round(
        (rss2 - rss1) * 1024 / max(1, n), 1)
    out["mirror_nodes"] = len(cache.nodes)

    h = Harness(cache)

    # single-name mutation -> dropped answer, sampled across the
    # zone; the timing is the full synchronous mirror -> invalidate ->
    # drop chain and nothing else
    step = max(1, n // max(1, mutations))
    sample = list(range(0, n, step))[:mutations]
    for i in sample:
        h.prime(host_name(i, racks))
    lat_us = []
    parity_failures = 0
    for j, i in enumerate(sample):
        addr = f"10.200.{(j >> 8) & 255}.{j & 255}"
        body = json.dumps({"type": "host",
                           "host": {"address": addr}}).encode()
        t0 = time.perf_counter()
        store.set_data(host_path(i, racks), body)
        lat_us.append((time.perf_counter() - t0) * 1e6)
        name = host_name(i, racks)
        if h.served_wire(name) != h.engine_wire(name):
            parity_failures += 1
    lat_us.sort()
    out["mutation_p50_us"] = round(lat_us[len(lat_us) // 2], 1)
    out["mutation_p99_us"] = round(
        lat_us[min(len(lat_us) - 1, int(len(lat_us) * 0.99))], 1)
    out["mutation_samples"] = len(sample)
    out["parity_failures"] = parity_failures

    # the chunked rebuild needs a live event loop (its bounded chunks
    # are the thing being measured)
    async def loop_phase():
        res = {}
        burst = min(storm, n)
        step_b = max(1, n // max(1, burst))
        burst_idx = list(range(0, n, step_b))[:burst]
        for i in burst_idx:
            h.prime(host_name(i, racks))
        t0 = time.perf_counter()
        for j, i in enumerate(burst_idx):
            store.set_data(
                host_path(i, racks),
                b'{"type": "host", "host": {"address": "10.201.%d.%d"}}'
                % ((j >> 8) & 255, j & 255))
        res["storm_mutate_s"] = round(time.perf_counter() - t0, 3)
        for i in burst_idx:
            h.served_wire(host_name(i, racks))
        res["storm_recovery_s"] = round(time.perf_counter() - t0, 3)
        res["storm_burst"] = len(burst_idx)

        # chunked session rebuild: serving continues, loop stays live
        loop = asyncio.get_running_loop()
        stalls = {"max": 0.0}
        probe_name = host_name(burst_idx[0], racks)
        served = {"mid": 0, "miss": 0}
        done = {"v": False}

        async def sampler():
            while not done["v"]:
                t = loop.time()
                await asyncio.sleep(0.002)
                lag = loop.time() - t - 0.002
                if lag > stalls["max"]:
                    stalls["max"] = lag
                if cache.rebuild_pending():
                    if cache.lookup(probe_name) is not None:
                        served["mid"] += 1
                    else:
                        served["miss"] += 1

        task = asyncio.ensure_future(sampler())
        t0 = time.perf_counter()
        chunks0 = cache.rebuild_chunks
        store.expire_session()
        while cache.rebuild_pending():
            await asyncio.sleep(0.001)
        res["rebuild_s"] = round(time.perf_counter() - t0, 3)
        res["rebuild_chunks"] = cache.rebuild_chunks - chunks0
        done["v"] = True
        await task
        res["rebuild_max_loop_lag_ms"] = round(stalls["max"] * 1000, 2)
        res["rebuild_served_mid"] = served["mid"]
        res["rebuild_miss_mid"] = served["miss"]
        return res

    out.update(asyncio.run(loop_phase()))
    out["pool"] = POOL.stats()
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    n = int(argv[0]) if argv else 100000
    mutations = int(argv[1]) if len(argv) > 1 else 200
    storm = int(argv[2]) if len(argv) > 2 else 2000
    print(json.dumps(probe(n, mutations, storm)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
