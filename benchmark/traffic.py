"""The one general traffic generator: a workload file's parameters, a zone
and a seed in; query templates, the sequence in which they are sent and (for
an open loop) the due time of every send out, as the files the load generator
reads.

A traffic mix is data (``benchmark/workloads/<name>.json``):

- ``mix``: entries of ``share``, ``qtype`` (A, PTR, SRV) and ``target``
  (``host``, ``service`` or ``member``: a member of the drawn service,
  uniform among its members);
- ``distribution``: ``{"kind": "zipfian", "constant": 0.99}`` or
  ``{"kind": "uniform"}``, over popularity ranks; which name holds which
  rank is a seeded permutation, so the hot set is not the first racks;
- ``edns_share``/``edns_payload``/``rd_share``: the posture of each query;
- ``loop``: ``closed`` (``callers`` of them, over ``sources`` sockets) or
  ``open`` (``rate_per_s``, Poisson arrivals, optional ``burst`` of
  ``on_s``/``off_s``/``factor``: the rate is *factor* times higher during
  each on-stretch, with the mean kept at ``rate_per_s``).
"""
import struct

import numpy as np

from dnswire import QTYPES, encode_name
from reference import Zone, rng_for

CAPTURE_FLAG = 0x80000000
#: how many sends of the template with the largest answer are marked to be
#: kept, besides the seeded sample: the longest is always compared
LONGEST_KEPT = 32


def rank_cdf(n: int, distribution: dict) -> np.ndarray:
    kind = distribution["kind"]
    if kind == "zipfian":
        weights = np.arange(1, n + 1, dtype=np.float64) \
            ** -float(distribution["constant"])
    elif kind == "uniform":
        weights = np.ones(n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    cdf = np.cumsum(weights)
    return cdf / cdf[-1]


def draw_ranks(rng, n: int, distribution: dict, size: int) -> np.ndarray:
    """*size* popularity ranks, 0 the most asked."""
    return np.minimum(np.searchsorted(rank_cdf(n, distribution),
                                      rng.random(size)), n - 1)


def arrival_times(rng, rate: float, span_s: float, burst=None) -> np.ndarray:
    """Poisson due times in nanoseconds from 0 to past *span_s*."""
    n = int(rate * span_s * 1.1) + 1000
    unit = np.cumsum(rng.exponential(1.0, size=n))     # rate-1 process
    if not burst:
        t = unit / rate
    else:
        on, off, factor = (float(burst[k])
                           for k in ("on_s", "off_s", "factor"))
        low = rate * (on + off) / (on * factor + off)
        # expected arrivals up to each boundary of the on/off cycles
        edges, counts = [0.0], [0.0]
        while edges[-1] < span_s * 1.2 + on + off:
            edges += [edges[-1] + on, edges[-1] + on + off]
            counts += [counts[-1] + on * low * factor,
                       counts[-1] + on * low * factor + off * low]
        t = np.interp(unit, counts, edges)
    if t[-1] < span_s:
        raise ValueError("arrival schedule came out short")
    return (t * 1e9).astype(np.uint64)


class Traffic:
    """Templates, sequence and schedule of one cell under one seed."""

    def __init__(self, workload: dict, zone: Zone, seed: int,
                 seconds: float) -> None:
        self.workload = workload
        span = float(workload["warm_s"]) + seconds
        self.open_loop = workload["loop"] == "open"
        per_s = float(workload["rate_per_s"] if self.open_loop
                      else workload["sequence_per_s"])
        self.arrivals = None
        if self.open_loop:
            self.arrivals = arrival_times(rng_for(seed, 4), per_s, span + 1,
                                          workload.get("burst"))
            size = len(self.arrivals)
        else:
            size = int(per_s * span)
        rng = rng_for(seed, 2)
        mix = workload["mix"]
        shares = np.array([float(m["share"]) for m in mix])
        part = rng.choice(len(mix), size=size, p=shares / shares.sum())
        host_by_rank = rng_for(seed, 3).permutation(zone.hosts)
        item = np.zeros(size, dtype=np.int64)
        member = np.zeros(size, dtype=np.int64)
        for k, m in enumerate(mix):
            where = np.flatnonzero(part == k)
            if m["target"] == "host":
                item[where] = host_by_rank[draw_ranks(
                    rng, zone.hosts, workload["distribution"], len(where))]
            else:
                ranks = draw_ranks(rng, len(zone.by_rank),
                                   workload["distribution"], len(where))
                item[where] = ranks
                if m["target"] == "member":
                    sizes = np.array([len(s.members)
                                      for s in zone.by_rank])
                    member[where] = (rng.random(len(where))
                                     * sizes[ranks]).astype(np.int64)
        edns = rng.random(size) < float(workload["edns_share"])
        rd = rng.random(size) < float(workload["rd_share"])
        key = ((((part.astype(np.int64) << 24 | item) << 12 | member) << 1
                | edns) << 1 | rd)
        _, first, inverse = np.unique(key, return_index=True,
                                         return_inverse=True)
        self.questions = []             # per template: (qname, qtype)
        self.templates = []             # per template: (wire, rcode, ancount)
        payload = int(workload["edns_payload"])
        opt = b"\0" + struct.pack(">HHIH", 41, payload, 0, 0)
        for i in first:
            m = mix[int(part[i])]
            qname, ancount = self._question(zone, m, int(item[i]),
                                            int(member[i]))
            qtype = QTYPES[m["qtype"]]
            wire = struct.pack(">HHHHHH", 0, 0x0100 if rd[i] else 0, 1, 0, 0,
                               1 if edns[i] else 0) \
                + encode_name(qname) + struct.pack(">HH", qtype, 1) \
                + (opt if edns[i] else b"")
            self.questions.append((qname, qtype))
            self.templates.append((wire, 0, ancount))
        sequence = inverse.astype(np.uint32)
        # the sample whose answers are kept and compared: drawn from the
        # seed, and the longest answer's template always among them
        kept = rng_for(seed, 5).random(size) < (
            float(workload["capture_answers"])
            / (float(workload["expect_per_s"]) * seconds))
        # (the longest among those sent early, so that it is sent at all)
        longest = max(np.unique(sequence[:max(1, size // 10)]),
                      key=lambda t: self.templates[t][2])
        at = np.flatnonzero(sequence == longest)
        kept[at[:LONGEST_KEPT]] = True
        self.sequence = sequence | (kept.astype(np.uint32)
                                    * np.uint32(CAPTURE_FLAG))

    @staticmethod
    def _question(zone: Zone, m: dict, item: int, member: int):
        """(qname, answers the reference gives) for one drawn query."""
        if m["target"] == "host":
            name, addr = zone.host_name(item), zone.host_addr(item)
            if m["qtype"] == "PTR":
                return zone.reverse_name(addr), 1
            return name, 1
        service = zone.by_rank[item]
        name = f"{service.label}.{zone.domain}"
        if m["target"] == "member":
            label, addr = service.members[member]
            if m["qtype"] == "PTR":
                return zone.reverse_name(addr), 1
            return f"{label}.{name}", 1
        if m["qtype"] == "SRV":
            return f"{zone.srvce}.{zone.proto}.{name}", len(service.members)
        return name, len(service.members)

    def write(self, directory: str) -> dict:
        """The generator's input files; their paths by option letter."""
        paths = {"-t": f"{directory}/templates.bin",
                 "-q": f"{directory}/sequence.bin"}
        with open(paths["-t"], "wb") as f:
            for wire, rcode, ancount in self.templates:
                f.write(struct.pack(">HBH", len(wire), rcode, ancount)
                        + wire)
        self.sequence.astype("<u4").tofile(paths["-q"])
        if self.arrivals is not None:
            paths["-a"] = f"{directory}/arrivals.bin"
            self.arrivals.astype("<u8").tofile(paths["-a"])
        return paths
