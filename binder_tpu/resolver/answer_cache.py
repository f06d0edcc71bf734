"""Encoded-answer cache with per-name (tag) invalidation.

The modern incarnation of the reference's legacy cache flags (``-s size``
default 10000, ``-a expiry`` default 60000 ms — reference
``main.js:34-38``, ``README.md:40-44``): resolvers re-ask the same handful
of names continuously, so the fully-encoded response bytes are cached, keyed
on the decoded fields the response depends on (transport semantics,
RD, question, EDNS presence/payload — see ``BinderServer._on_query``;
raw-wire keying would let per-packet EDNS options mint unbounded keys).
Stored values are opaque to this class — the server stores ``(wire,
answers_summary, additional_summary)`` tuples so cache hits keep full
query-log detail.

Correctness properties:
- every entry records the mirror cache's *epoch* (bumped on full
  rebuilds/session events), so a hit can never survive a re-mirror;
- every entry carries a *dependency tag* — the store lookup domain (or
  PTR qname) its answer derives from; a mirrored mutation invalidates
  exactly the tags it touched (``MirrorCache.invalidate``), so one
  churning record no longer evicts every cached answer;
- round-robin is preserved: each miss stores another shuffle variant (up
  to ``variants_cap``), and hits cycle through the collected variants; a
  truncated UDP answer (TC=1: header, question, OPT echo) shows no
  rotation and is one variant, served from its second sight on;
- entries expire after ``expiry_ms`` regardless (defense in depth);
- negative answers (NXDOMAIN, and NODATA — NOERROR with no answers) are
  cached like positives but accounted separately (``negative`` flag,
  ``neg_entries``/``neg_hits`` in ``stats()``), so a miss flood of
  nonexistent names is visibly absorbed here instead of hitting the
  resolver engine;
- SERVFAIL and recursion-produced responses are NEVER cached (the callers
  decide; see ``BinderServer._on_query`` — SERVFAIL means the store is
  unavailable or a record is garbage, conditions that must re-check on
  every query).
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Set

from binder_tpu.store import names as _names


class AnswerCache:
    __slots__ = ("size", "expiry_s", "variants_cap", "_entries",
                 "_by_tag", "hits", "misses", "invalidations",
                 "neg_hits", "_intern")

    def __init__(self, size: int = 10000, expiry_ms: int = 60000,
                 variants_cap: int = 8, intern=None) -> None:
        # canonicalizer for tag/qname strings entering the long-lived
        # indexes: query-decoded names dedup against the mirror's own
        # domain objects (MirrorCache.canon) or the process-wide pool,
        # so a name is ONE object no matter how many layers index it
        self._intern = intern if intern is not None \
            else _names.intern_name
        self.size = size
        self.expiry_s = expiry_ms / 1000.0
        self.variants_cap = variants_cap
        # key -> [epoch, created, next_variant_idx, [value, ...],
        #         complete, tag, pushed, negative]
        self._entries: Dict[object, list] = {}
        # dependency tag -> keys whose answers derive from it
        self._by_tag: Dict[str, Set[object]] = {}
        self.hits = 0
        self.misses = 0
        self.invalidations = 0
        self.neg_hits = 0

    def _drop(self, key, e) -> None:
        del self._entries[key]
        keys = self._by_tag.get(e[5])
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_tag[e[5]]

    def get(self, key, epoch: int) -> Optional[object]:
        if self.size <= 0:
            return None
        e = self._entries.get(key)
        if e is None:
            self.misses += 1
            return None
        if e[0] != epoch or time.monotonic() - e[1] > self.expiry_s:
            self._drop(key, e)
            self.misses += 1
            return None
        variants = e[3]
        if not e[4] and len(variants) < self.variants_cap:
            # rotatable answer set: keep resolving until we've collected
            # enough shuffle variants for fair rotation
            self.misses += 1
            return None
        idx = e[2]
        e[2] = (idx + 1) % len(variants)
        self.hits += 1
        if e[7]:
            self.neg_hits += 1
        return variants[idx]

    def put(self, key, epoch: int, value: object,
            rotatable: bool = False, tag: Optional[str] = None,
            negative: bool = False) -> bool:
        """Record a freshly resolved value.  ``rotatable`` says that
        another resolve of this key may give other bytes (the wire
        carries a set of several records, shuffled a resolve): such an
        entry serves no hit before it holds ``variants_cap`` of them.  A
        wire that left truncated carries no record, whatever set was
        rendered for it, and ``BinderServer._on_query`` stores it not
        rotatable: one variant, complete from its first sight.  ``tag`` is
        the store name the answer depends on (defaults handled by the
        caller);
        ``negative`` marks NXDOMAIN/NODATA answers for the separate
        accounting (never SERVFAIL — callers must not put those at
        all).  Returns True exactly when the entry
        just became *complete* (non-rotatable, or the full variant set
        collected): from then on ``get`` serves it, and its first hit
        promotes it to the native fast path (``take_push``)."""
        if self.size <= 0:
            return False
        e = self._entries.get(key)
        if e is not None and e[0] == epoch:
            if len(e[3]) < self.variants_cap:
                e[3].append(value)
                return not e[4] and len(e[3]) == self.variants_cap
            return False
        if e is not None:
            self._drop(key, e)          # stale epoch: replace cleanly
        if len(self._entries) >= self.size:
            # evict oldest insertion (dicts preserve insertion order)
            old_key = next(iter(self._entries))
            self._drop(old_key, self._entries[old_key])
        if tag is not None:
            tag = self._intern(tag)
        self._entries[key] = [epoch, time.monotonic(), 0, [value],
                              not rotatable, tag, False, negative]
        self._by_tag.setdefault(tag, set()).add(key)
        return not rotatable

    def take_push(self, key, epoch: int):
        """Claim a complete entry for promotion to the native fast
        path: returns ``(variant_values, tag)`` exactly once (marking
        the entry pushed), else None.  Promotion happens on an entry's
        FIRST HIT, not at resolve time — one-shot names (the cache-cold
        workload) then never pay the native push cost, while any name
        asked twice is native from its third query on."""
        e = self._entries.get(key)
        if e is None or e[0] != epoch or e[6] or not (
                e[4] or len(e[3]) >= self.variants_cap):
            return None
        e[6] = True
        return e[3], e[5]

    def invalidate_tag(self, tag: str) -> int:
        """Drop every entry whose answer derives from ``tag``; returns
        how many were dropped."""
        keys = self._by_tag.pop(tag, None)
        if not keys:
            return 0
        n = 0
        for key in keys:
            if self._entries.pop(key, None) is not None:
                n += 1
        self.invalidations += n
        return n

    def remaining_ttl_ms(self, key, epoch: int) -> Optional[float]:
        """Milliseconds until this entry's time expiry — a late-completed
        rotatable entry must carry its *remaining* lifetime into the
        native fast path, not a fresh full window."""
        e = self._entries.get(key)
        if e is None or e[0] != epoch:
            return None
        return max(0.0, (self.expiry_s - (time.monotonic() - e[1]))
                   * 1000.0)

    def stats(self) -> dict:
        """Occupancy + economics for the introspection snapshot
        (binder_tpu/introspect/status.py `answer_cache` section)."""
        hits, misses = self.hits, self.misses
        total = hits + misses
        return {
            "size": self.size,
            "entries": len(self._entries),
            "hits": hits,
            "misses": misses,
            "hit_ratio": (hits / total) if total else 0.0,
            "invalidations": self.invalidations,
            "expiry_ms": self.expiry_s * 1000.0,
            "neg_hits": self.neg_hits,
        }

    def clear(self) -> None:
        self._entries.clear()
        self._by_tag.clear()
