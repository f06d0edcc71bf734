"""The one general traffic generator: a workload file's parameters, a zone
and a seed in; query templates, the sequence in which they are sent and (for
an open loop) the due time of every send out, as the files the load generator
reads.

A traffic mix is data (``benchmark/workloads/<name>.json``):

- ``mix``: entries of ``share``, ``qtype`` (A, PTR, SRV, AAAA) and
  ``target``: ``host``; ``service``; ``member``, a member of the drawn
  service, uniform among its members; ``absent``, a name of the hosts' own
  label shape whose index lies past the zone's last host.  What a question
  must get (rcode, answer count) is the reference's word for it, never
  written here: an AAAA question is declined (NOTIMP), ``SRV`` of a
  ``host`` asks ``_srvce._proto.<host>`` and gets NODATA, an ``absent``
  name is REFUSED;
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
#: how many asks for the zone's largest set are laid over the drawn
#: sequence, evenly from its start to its end, and marked to be kept besides
#: the seeded sample: the longest answer the zone can give is compared in
#: every run, whatever ranks the seed drew and at whatever rate a closed
#: loop walks the sequence
LONGEST_KEPT = 32
#: how many of each mix entry's sends, spread as evenly, are marked to be
#: kept: every kind of answer the mix holds is among those compared
KIND_KEPT = 64


def spread_over(where: np.ndarray, n: int) -> np.ndarray:
    """At most *n* of the positions *where*, evenly from first to last."""
    if len(where) <= n:
        return where
    return where[np.linspace(0, len(where) - 1, n).astype(np.int64)]


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
            if m["target"] in ("host", "absent"):
                # (an absent name is the drawn host's index past the zone)
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
        longest_at = self._lay_longest(zone, mix, part, item)
        key = ((((part.astype(np.int64) << 24 | item) << 12 | member) << 1
                | edns) << 1 | rd)
        _, first, inverse = np.unique(key, return_index=True,
                                         return_inverse=True)
        self.questions = []             # per template: (qname, qtype)
        self.templates = []             # (wire, rcode, ancount, mix entry)
        payload = int(workload["edns_payload"])
        opt = b"\0" + struct.pack(">HHIH", 41, payload, 0, 0)
        for i in first:
            m = mix[int(part[i])]
            qname, rcode, ancount = self._question(
                zone, m, int(item[i]), int(member[i]))
            qtype = QTYPES[m["qtype"]]
            wire = struct.pack(">HHHHHH", 0, 0x0100 if rd[i] else 0, 1, 0, 0,
                               1 if edns[i] else 0) \
                + encode_name(qname) + struct.pack(">HH", qtype, 1) \
                + (opt if edns[i] else b"")
            self.questions.append((qname, qtype))
            self.templates.append((wire, rcode, ancount, int(part[i])))
        sequence = inverse.astype(np.uint32)
        # the sample whose answers are kept and compared: drawn from the
        # seed; the zone's largest set and some sends of every mix entry
        # always among them
        kept = rng_for(seed, 5).random(size) < (
            float(workload["capture_answers"])
            / (float(workload["expect_per_s"]) * seconds))
        kept[longest_at] = True
        sends_of = [np.flatnonzero(part == k) for k in range(len(mix))]
        for where in sends_of:
            kept[spread_over(where, KIND_KEPT)] = True
        self.sequence = sequence | (kept.astype(np.uint32)
                                    * np.uint32(CAPTURE_FLAG))
        #: templates the asks from fresh sockets always hold: one of each
        #: mix entry, and the zone's largest set where the mix asks sets
        self.always_asked = sorted(
            {int(sequence[at[0]]) for at in sends_of if len(at)}
            | {int(t) for t in sequence[longest_at[:1]]})

    def rcodes_by_entry(self) -> list:
        """Per mix entry, the rcodes its templates expect, sorted."""
        out = [set() for _ in self.workload["mix"]]
        for _wire, rcode, _ancount, entry in self.templates:
            out[entry].add(rcode)
        return [sorted(rcodes) for rcodes in out]

    @staticmethod
    def _lay_longest(zone: Zone, mix: list, part, item) -> np.ndarray:
        """Lay ``LONGEST_KEPT`` asks for the zone's largest set over the
        drawn sequence, among the sends of the first mix entry that asks
        whole sets; their positions (none where the mix asks no set or
        the zone has no service).  Sizes are fixed by rank, so this is the
        same work under every seed."""
        sets = [k for k, m in enumerate(mix) if m["target"] == "service"
                and m["qtype"] in ("A", "SRV")]
        if not sets or not zone.services:
            return np.zeros(0, dtype=np.int64)
        largest = max(zone.services, key=lambda s: len(s.members))
        at = spread_over(np.flatnonzero(part == sets[0]), LONGEST_KEPT)
        item[at] = zone.by_rank.index(largest)
        return at

    @staticmethod
    def _question(zone: Zone, m: dict, item: int, member: int):
        """(qname, rcode, answer count) for one drawn query: the name by
        the mix entry's target and type, what it must get by the
        reference."""
        target, qtype = m["target"], m["qtype"]
        if target in ("host", "absent"):
            if target == "absent":
                item += zone.hosts
            qname, addr = zone.host_name(item), zone.host_addr(item)
        elif target in ("service", "member"):
            service = zone.by_rank[item]
            qname, addr = f"{service.label}.{zone.domain}", None
            if target == "member":
                qname = f"{service.members[member][0]}.{qname}"
                addr = service.members[member][1]
        else:
            raise ValueError(f"unknown target {target!r}")
        if qtype == "PTR":
            if addr is None:
                raise ValueError("a service has no address to ask PTR of")
            qname = zone.reverse_name(addr)
        elif qtype == "SRV":
            qname = f"{zone.srvce}.{zone.proto}.{qname}"
        want = zone.expected(qname, QTYPES[qtype])
        return qname, want["rcode"], len(want["answers"])

    def write(self, directory: str) -> dict:
        """The generator's input files; their paths by option letter."""
        paths = {"-t": f"{directory}/templates.bin",
                 "-q": f"{directory}/sequence.bin"}
        with open(paths["-t"], "wb") as f:
            for wire, rcode, ancount, entry in self.templates:
                f.write(struct.pack(">HBHB", len(wire), rcode, ancount,
                                    entry) + wire)
        self.sequence.astype("<u4").tofile(paths["-q"])
        if self.arrivals is not None:
            paths["-a"] = f"{directory}/arrivals.bin"
            self.arrivals.astype("<u8").tofile(paths["-a"])
        return paths
