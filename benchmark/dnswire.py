"""The benchmark's own DNS wire codec: query encoding and answer decoding.

Nothing here comes from ``binder_tpu``: what the benchmark sends and how it
reads what came back must not move when the program's codec does.  RFC 1035
messages, RFC 6891 OPT in queries and, kept apart from the records, in
answers; the record types the zone serves (A, PTR, SRV, SOA) are decoded,
anything else is kept as raw rdata.  AAAA is a type
the benchmark asks and the zone declines (NOTIMP): a libc stub sends one
beside every A.
"""
import socket
import struct

A, PTR, SOA, AAAA, SRV, OPT = 1, 12, 6, 28, 33, 41
QTYPES = {"A": A, "PTR": PTR, "SRV": SRV, "AAAA": AAAA}
NOERROR, SERVFAIL, NXDOMAIN, NOTIMP, REFUSED = 0, 2, 3, 4, 5


def encode_name(name: str) -> bytes:
    out = b""
    for label in name.rstrip(".").split("."):
        raw = label.encode("ascii")
        if not 0 < len(raw) < 64:
            raise ValueError(f"bad label in {name!r}")
        out += bytes([len(raw)]) + raw
    return out + b"\0"


def make_query(name: str, qtype: int, qid: int = 0, rd: bool = False,
               edns_payload=None) -> bytes:
    """One question; an OPT record advertising *edns_payload* if given."""
    flags = 0x0100 if rd else 0
    wire = struct.pack(">HHHHHH", qid, flags, 1, 0, 0,
                       1 if edns_payload else 0)
    wire += encode_name(name) + struct.pack(">HH", qtype, 1)
    if edns_payload:
        wire += b"\0" + struct.pack(">HHIH", OPT, edns_payload, 0, 0)
    return wire


def query_payload(wire: bytes):
    """The UDP payload size a query's OPT record advertises, or 0 for a
    query without one (one question, the OPT its only additional)."""
    if not int.from_bytes(wire[10:12], "big"):
        return 0
    _, off = _name(wire, 12)
    off += 4
    _, off = _name(wire, off)
    rtype, payload = struct.unpack(">HH", wire[off:off + 4])
    if rtype != OPT:
        raise ValueError("a query's additional record is not an OPT")
    return payload


def _name(wire: bytes, off: int):
    """(name, offset after it), following compression pointers."""
    labels, end, hops = [], None, 0
    while True:
        n = wire[off]
        if n & 0xC0 == 0xC0:
            if end is None:
                end = off + 2
            off = ((n & 0x3F) << 8) | wire[off + 1]
            hops += 1
            if hops > 64:
                raise ValueError("compression loop")
        elif n == 0:
            return ".".join(labels), (end if end is not None else off + 1)
        else:
            labels.append(wire[off + 1:off + 1 + n].decode("ascii"))
            off += 1 + n


class Answer:
    """A decoded response: header fields and the three sections as lists
    of ``(name, type, ttl, rdata)``; rdata is an address, a name, a
    ``(priority, weight, port, target)`` tuple, an SOA's ``(mname, rname,
    serial, refresh, retry, expire, minimum)``, or bytes.  OPT records are
    no records of the zone: they are kept apart in ``opts``, as ``(section
    0-2, owner, payload size, extended rcode, version)``; ``size`` is the
    length of the wire."""

    __slots__ = ("qid", "tc", "rcode", "question", "answers", "authorities",
                 "additionals", "opts", "size")

    def __init__(self, wire: bytes) -> None:
        (self.qid, flags, qd, an, ns, ar) = struct.unpack(">HHHHHH",
                                                          wire[:12])
        if not flags & 0x8000:
            raise ValueError("not a response")
        self.tc = bool(flags & 0x0200)
        self.rcode = flags & 0x0F
        self.size = len(wire)
        self.opts = []
        off = 12
        self.question = None
        for _ in range(qd):
            qname, off = _name(wire, off)
            qtype, _qclass = struct.unpack(">HH", wire[off:off + 4])
            off += 4
            self.question = (qname.lower(), qtype)
        sections = []
        for section, count in enumerate((an, ns, ar)):
            recs = []
            for _ in range(count):
                name, off = _name(wire, off)
                rtype, rclass, ttl, rdlen = struct.unpack(
                    ">HHIH", wire[off:off + 10])
                off += 10
                rdata = wire[off:off + rdlen]
                if len(rdata) != rdlen:
                    raise ValueError("rdata runs past the message")
                if rtype == A and rdlen == 4:
                    rdata = socket.inet_ntoa(rdata)
                elif rtype == PTR:
                    rdata = _name(wire, off)[0].lower()
                elif rtype == SRV:
                    prio, weight, port = struct.unpack(">HHH", rdata[:6])
                    rdata = (prio, weight, port,
                             _name(wire, off + 6)[0].lower())
                elif rtype == SOA:
                    mname, at = _name(wire, off)
                    rname, at = _name(wire, at)
                    rdata = (mname.lower(), rname.lower()) \
                        + struct.unpack(">IIIII", wire[at:at + 20])
                off += rdlen
                if rtype == OPT:
                    self.opts.append((section, name, rclass, ttl >> 24,
                                      (ttl >> 16) & 255))
                else:
                    recs.append((name.lower(), rtype, ttl, rdata))
            sections.append(recs)
        self.answers, self.authorities, self.additionals = sections
