"""The plain reference: what an authoritative server over this zone answers.

It works from the *description* of the zone (the configuration file's sizes
and the seed) and imports nothing of ``binder_tpu``.  The host part follows
the synthetic generator's documented formula for every seed; the services
part (labels, sizes, member labels and addresses, which service holds which
popularity rank) is drawn from the seed here, and written out as the store
fixture the program is started on: data in, answers out.

Semantics, from TritonDataCenter/binder ``lib/server.js`` as the program's
documentation restates them: A of a host-like record is its one address; A
of a service is its members' addresses; SRV ``_srvce._proto.<service>`` is
one record per member (priority 0, weight 10, the service's port) with the
member's A as glue; PTR is the owner of the address; SRV on a name that is
not a service is NODATA (no answer, one SOA owned by that name in the
authority section, its TTL and minimum the record's 30 s:
``lib/server.js:276-292``); a name the zone lacks is REFUSED
(``lib/server.js:227-246``); a type other than A, SRV and PTR is NOTIMP,
decided by the type alone before any look at the name
(``lib/server.js:491-506``).  An answer without records has every section
empty, but for NODATA's SOA.  TTL 30.  Sets compare without order: rotation
is the server's to choose.

EDNS (RFC 6891): an answer to a question that carried an OPT record carries
exactly one, version 0, in its additional section (6.1.1: "if a query
message with an OPT record is received, the response MUST include an OPT");
an answer to a question without one carries none.  A UDP answer may say
TC=1 only where the whole answer passes the payload the question
advertised (512 without an OPT), and goes whole only where it fits.  The
whole answer's size is the reference's own plain wire: every owner name
compressed against the question's name (RFC 1035 4.1.4), an SRV target
spelled out (RFC 2782: no compression in that field), the OPT's 11 bytes.
"""
import re

import numpy as np

from dnswire import (A, NOERROR, NOTIMP, NXDOMAIN, PTR, REFUSED, SOA, SRV,
                     encode_name)

TTL = 30
#: registrar record types that count as service members (binder
#: lib/server.js:352-360); one is drawn per service
MEMBER_TYPES = ("load_balancer", "moray_host", "redis_host", "rr_host",
                "ops_host")


def rng_for(seed: int, stream: int):
    """One independent stream per purpose, all from ``--seed``."""
    return np.random.default_rng([int(seed), stream])


class Service:
    __slots__ = ("label", "rank", "size_class", "kind", "members")

    def __init__(self, label, rank, size_class, kind, members):
        self.label = label
        self.rank = rank                # 1 = most asked
        self.size_class = size_class
        self.kind = kind
        self.members = members          # [(label, address)]


class Zone:
    """The zone a configuration and a seed describe."""

    def __init__(self, config: dict, domain: str, seed: int,
                 services=None) -> None:
        self.domain = domain
        self.hosts = int(config["hosts"])
        self.racks = int(config.get("racks") or 0) \
            or max(1, min(1024, self.hosts // 512))
        self.subtree = config.get("subtree", "zs")
        spec = config["services"]
        self.srvce, self.proto = spec["srvce"], spec["proto"]
        self.port = int(spec["port"])
        self.services = services if services is not None \
            else self._draw_services(spec, seed)
        self.by_label = {s.label: s for s in self.services}
        self.by_rank = sorted(self.services, key=lambda s: s.rank)
        self.member_owner = {}          # address -> member's full name
        for s in self.services:
            for label, addr in s.members:
                self.member_owner[addr] = f"{label}.{s.label}.{domain}"
        #: names the chaos plan writes after ready: name -> address, and
        #: whether the write has happened yet
        self.written = {f"chaos{k}.{domain}": f"10.254.{k}.{k + 1}"
                        for k in range(int(config["chaos"]["writes"]))}
        self.writes_done = False
        #: types the reference takes for answered without records where
        #: the deployment declines them: empty but under the control
        #: ``--break reference-declined``
        self.answered_empty = frozenset()
        #: whether an answer to a question with an OPT record carries one:
        #: true but under the control ``--break reference-opt``
        self.opt_echoed = True
        #: empty but under the control ``--break reference-address`` in a
        #: zone without services: a host's name -> another address
        self.altered = {}
        self._host_rx = re.compile(
            r"^h(\d{6})\.r(\d{4})\.%s\.%s$" % (re.escape(self.subtree),
                                                re.escape(domain)))

    # -- the host part: store/fake.py populate_synthetic's formula --

    def host_name(self, i: int) -> str:
        return (f"h{i:06d}.r{i % self.racks:04d}.{self.subtree}."
                f"{self.domain}")

    @staticmethod
    def host_addr(i: int) -> str:
        return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"

    @staticmethod
    def reverse_name(addr: str) -> str:
        return ".".join(reversed(addr.split("."))) + ".in-addr.arpa"

    # -- the services part: drawn from the seed --

    @staticmethod
    def _class_of(rank: int, spec: dict) -> dict:
        """The size class is fixed by the popularity rank, not drawn."""
        place = (rank - 1) % int(spec["rank_period"]) + 1
        for cls in spec["classes"]:
            if place in cls.get("ranks_in_period", ()):
                return cls
        return next(c for c in spec["classes"]
                    if "ranks_in_period" not in c)

    def _draw_services(self, spec: dict, seed: int) -> list:
        """Sizes are fixed by rank for every seed (the class by the
        rank's place in its period, the count by the rank's turn in its
        class, spread over the class's range), so that the share of
        truncated answers is the same work under every seed.  The seed
        draws which label holds which rank, the labels, the member
        types and the addresses."""
        rng = rng_for(seed, 1)
        count = int(spec["count"])
        sizes, turn = {}, {}
        for rank in range(1, count + 1):
            cls = self._class_of(rank, spec)
            lo, hi = cls["members"]
            j = turn.get(cls["name"], 0)
            turn[cls["name"]] = j + 1
            sizes[rank] = (cls["name"], lo + (j * 7919) % (hi - lo + 1))
        ranks = rng.permutation(count) + 1
        tags = rng.choice(1 << 24, size=count, replace=False)
        total = sum(n for _, n in sizes.values())
        # member addresses: distinct, in 10.128.0.0/10, away from the hosts
        slots = rng.choice(1 << 22, size=total, replace=False)
        labels = rng.choice(1 << 32, size=total, replace=False)
        kinds = rng.integers(len(MEMBER_TYPES), size=count)
        out, used = [], 0
        for k in range(count):
            size_class, n = sizes[int(ranks[k])]
            members = []
            for m in range(used, used + n):
                a = int(slots[m])
                members.append((f"{int(labels[m]):08x}",
                                f"10.{128 + (a >> 16)}.{(a >> 8) & 255}."
                                f"{a & 255}"))
            used += n
            out.append(Service(f"svc-{int(tags[k]):06x}", int(ranks[k]),
                               size_class, MEMBER_TYPES[int(kinds[k])],
                               members))
        return out

    def fixture(self) -> dict:
        """The store fixture the program starts on: znode path -> record,
        as registrars write them."""
        base = "/" + "/".join(reversed(self.domain.split(".")))
        out = {}
        for s in self.services:
            out[f"{base}/{s.label}"] = {"type": "service", "service": {
                "srvce": self.srvce, "proto": self.proto,
                "port": self.port}}
            for label, addr in s.members:
                out[f"{base}/{s.label}/{label}"] = {
                    "type": s.kind, s.kind: {"address": addr}}
        return out

    # -- the resolver --

    def expected(self, qname: str, qtype: int, payload=None) -> dict:
        """``{"rcode", "answers", "glue", "nodata", "opt", "payload"}``
        for one question; answers and glue are sorted lists of ``(type,
        rdata)`` and ``(name, address)``; ``nodata`` is the owner of the
        SOA that a NODATA answer carries, else ``None``.  *payload* is
        what the question's OPT record advertised, 0 where it had none:
        ``opt`` is how many OPT records the answer carries, ``payload``
        the size a UDP answer may have.  A caller that does not say
        (None) gets None for both, and ``compare`` then holds the answer
        to neither."""
        if payload is None:
            return dict(self._records(qname, qtype), opt=None, payload=None)
        return dict(self._records(qname, qtype),
                    opt=1 if payload and self.opt_echoed else 0,
                    payload=int(payload or 512))

    def _records(self, qname: str, qtype: int) -> dict:
        qname = qname.lower().rstrip(".")

        def empty(rcode):
            return {"rcode": rcode, "answers": [], "glue": [],
                    "nodata": None}

        def ok(answers, glue=(), nodata=None):
            return {"rcode": NOERROR, "answers": sorted(answers),
                    "glue": sorted(glue), "nodata": nodata}

        if qtype not in (A, SRV, PTR):
            # routed by type first: the name is never looked at
            return ok([]) if qtype in self.answered_empty \
                else empty(NOTIMP)
        refused = empty(REFUSED)
        if qtype == PTR:
            m = re.match(r"^(\d+)\.(\d+)\.(\d+)\.(\d+)\.in-addr\.arpa$",
                         qname)
            if not m:
                return refused
            d, c, b, a = (int(x) for x in m.groups())
            addr = f"{a}.{b}.{c}.{d}"
            owner = self.member_owner.get(addr)
            if owner is None and a == 10:
                i = (b << 16) | (c << 8) | d
                if i < self.hosts:
                    owner = self.host_name(i)
            if owner is None and self.writes_done:
                owner = next((n for n, w in self.written.items()
                              if w == addr), None)
            return ok([(PTR, owner)]) if owner else refused

        name, want_srv = qname, None
        if qtype == SRV:
            m = re.match(r"^(_[^_.]*)\.(_[^_.]*)\.(.+)$", qname)
            if not m:
                return refused
            want_srv, name = (m.group(1), m.group(2)), m.group(3)
        if not name.endswith("." + self.domain):
            return refused
        address = self._address_of(name)
        if address is not None:         # host-like
            if want_srv:
                return ok([], nodata=name)
            return ok([(A, address)])
        service = self.by_label.get(name[:-len(self.domain) - 1])
        if service is None:
            return refused
        if not want_srv:
            return ok([(A, addr) for _, addr in service.members])
        if want_srv != (self.srvce, self.proto):
            return empty(NXDOMAIN)
        targets = [(f"{label}.{name}", addr)
                   for label, addr in service.members]
        return ok([(SRV, (0, 10, self.port, t)) for t, _ in targets],
                  glue=targets)

    def _address_of(self, name: str):
        if name in self.altered:
            return self.altered[name]
        m = self._host_rx.match(name)
        if m:
            i = int(m.group(1))
            if i < self.hosts and i % self.racks == int(m.group(2)):
                return self.host_addr(i)
            return None
        if name in self.written:
            return self.written[name] if self.writes_done else None
        labels = name[:-len(self.domain) - 1].split(".")
        if len(labels) == 2 and labels[1] in self.by_label:
            for label, addr in self.by_label[labels[1]].members:
                if label == labels[0]:
                    return addr
        return None


def whole_size(qname: str, want: dict) -> int:
    """Bytes of the reference's own wire of the whole answer (the module's
    docstring says how it is laid out)."""
    question = qname.lower().rstrip(".").split(".")

    def owner(name: str) -> int:
        labels = name.lower().rstrip(".").split(".")
        shared = 0
        while (shared < min(len(labels), len(question))
               and labels[-1 - shared] == question[-1 - shared]):
            shared += 1
        spelled = sum(1 + len(lab) for lab in labels[:len(labels) - shared])
        return spelled + (2 if shared else 1)

    def rdata(rtype: int, value) -> int:
        if rtype == A:
            return 4
        if rtype == PTR:
            return len(encode_name(value))
        return 6 + len(encode_name(value[3]))           # SRV

    return (12 + len(encode_name(qname)) + 4 + (11 if want["opt"] else 0)
            + sum(2 + 10 + rdata(rtype, value)
                  for rtype, value in want["answers"])
            + sum(owner(name) + 10 + 4 for name, _ in want["glue"]))


def compare(answer, qname: str, qtype: int, want: dict,
            whole: bool = True, truncated=None) -> list:
    """What is wrong with a decoded answer, as a list of strings (empty:
    it is what the reference gives).  *whole* is false for a UDP answer
    with TC=1, where only the header and the OPT record can be held to
    anything.  *truncated* says whether the UDP answer to this question
    said TC=1 (None: not known, as for an answer asked over TCP alone):
    it may only where the whole answer passes the advertised payload.
    The OPT and payload rows are held only where *want* says what the
    question carried (``Zone.expected`` with its *payload* given)."""
    wrong = []
    if answer.question != (qname.lower(), qtype):
        wrong.append(f"question echoed as {answer.question}")
    if answer.rcode != want["rcode"]:
        wrong.append(f"rcode {answer.rcode}, reference {want['rcode']}")
        return wrong
    if want.get("opt") is None:
        pass                # the caller did not say what the question had
    elif len(answer.opts) != want["opt"]:
        wrong.append(f"{len(answer.opts)} OPT records, reference "
                     f"{want['opt']}")
    elif any(opt[0] != 2 or opt[1] != "" or opt[3] or opt[4]
             for opt in answer.opts):
        wrong.append(f"OPT (section, owner, payload, extended rcode, "
                     f"version) {answer.opts}, reference in the additional "
                     "section, owned by the root, 0, 0")
    limit = want.get("payload")
    if truncated and limit is not None:
        size = whole_size(qname, want)
        if size <= limit:
            wrong.append(f"TC=1 over UDP where the reference's whole answer "
                         f"of {size} bytes fits the {limit} advertised")
    if truncated is False and limit is not None and answer.size > limit:
        wrong.append(f"{answer.size} bytes whole over UDP past the {limit} "
                     "advertised")
    if not whole:
        return wrong
    if answer.tc:
        wrong.append("TC=1 on an answer taken as whole")
    got = sorted((rtype, rdata) for _, rtype, _, rdata in answer.answers)
    if got != want["answers"]:
        wrong.append(f"answers {got[:4]}{'...' if len(got) > 4 else ''} "
                     f"({len(got)}), reference {want['answers'][:4]} "
                     f"({len(want['answers'])})")
    if any(name != qname.lower() for name, _, _, _ in answer.answers):
        wrong.append("an answer is owned by another name")
    glue = sorted((name, rdata) for name, rtype, _, rdata
                  in answer.additionals if rtype == A)
    if glue != want["glue"]:
        wrong.append(f"glue {len(glue)} records, reference "
                     f"{len(want['glue'])}")
    ttls = {ttl for _, _, ttl, _ in answer.answers + answer.additionals}
    if ttls - {TTL}:
        wrong.append(f"TTLs {sorted(ttls)}, reference {TTL}")
    if want["nodata"]:
        soa = [(name, ttl, rdata[-1]) for name, rtype, ttl, rdata
               in answer.authorities if rtype == SOA]
        if soa != [(want["nodata"], TTL, TTL)]:
            wrong.append(f"NODATA with the SOAs (owner, TTL, minimum) "
                         f"{soa}, reference one of {want['nodata']}, "
                         f"{TTL}, {TTL}")
    if not want["answers"]:
        # an answer without records is its header and question, and for
        # NODATA the one SOA
        extra = len(answer.authorities) - (1 if want["nodata"] else 0) \
            + len(answer.additionals)
        if extra:
            wrong.append(f"{extra} records in the authority and additional "
                         "sections of an answer without records")
    return wrong
