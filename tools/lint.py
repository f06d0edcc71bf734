#!/usr/bin/env python3
"""First-party Python lint gate (the jsstyle/javascriptlint analog).

The reference gates CI on vendored linters (`make check` runs jsstyle +
javascriptlint, reference Jenkinsfile:37-40, deps/jsstyle,
deps/javascriptlint); this image ships no Python linter, so this tool
implements the high-signal, zero-false-positive subset used by `make
check`.  Zero findings is the passing state; every rule here is cheap to
satisfy and each finding is a real smell:

  unused-import        imported name never referenced in the module
  import-shadowed      def/class rebinds an imported name
  bare-except          `except:` catches SystemExit/KeyboardInterrupt
  duplicate-dict-key   constant key repeated in a dict literal
  f-string-no-placeholder  f-prefix on a string with no {…}
  is-literal           `is` / `is not` against a str/number literal
  mutable-default      def f(x=[]) / f(x={}) / f(x=set())
  assert-tuple         assert (cond, "msg") — always true

Usage: python tools/lint.py <paths...>   (directories are walked for .py
files; explicit files are linted regardless of extension so bin/ scripts
can be covered).
"""
import ast
import os
import re
import sys


class Finding:
    def __init__(self, path, line, rule, msg):
        self.path = path
        self.line = line
        self.rule = rule
        self.msg = msg

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def iter_strings(node):
    """All string constants syntactically inside `node` (docstrings and
    __all__ entries count as usage for re-export barrels)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub.value


class Linter(ast.NodeVisitor):
    def __init__(self, path, tree, source):
        self.path = path
        self.tree = tree
        self.source = source
        self.findings = []

    def add(self, node, rule, msg):
        self.findings.append(Finding(self.path, node.lineno, rule, msg))

    def run(self):
        self.check_imports()
        self.visit(self.tree)
        return self.findings

    # ---- unused imports / shadowing (module scope) ----

    def check_imports(self):
        # __init__.py imports are re-export surface (the lib/index.js
        # barrel pattern); "unused" is their whole point
        barrel = os.path.basename(self.path) == "__init__.py"
        imported = {}   # name -> (node, reported_name)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    imported.setdefault(name, (node, a.asname or a.name))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    name = a.asname or a.name
                    imported.setdefault(name, (node, name))

        used = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Name):
                used.add(node.id)
            elif isinstance(node, ast.Attribute):
                # handled via the Name at the base of the chain
                pass
        # names mentioned in strings count (docstring references, __all__,
        # typing forward refs)
        strings = set()
        for s in iter_strings(self.tree):
            if len(s) < 200:
                for tok in s.replace(",", " ").replace("'", " ").split():
                    strings.add(tok.strip("\"`()[]{}.:;"))

        redefined = set()
        # module-level defs only: a method or nested function named like
        # an import does not rebind the module-level name
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                if node.name in imported:
                    redefined.add(node.name)
                    self.add(node, "import-shadowed",
                             f"definition of {node.name!r} shadows an "
                             f"import of the same name")

        if barrel:
            return
        for name, (node, reported) in imported.items():
            if name.startswith("_") or name in redefined:
                continue
            if name not in used and name not in strings:
                self.add(node, "unused-import",
                         f"{reported!r} imported but unused")

    # ---- node-local rules ----

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self.add(node, "bare-except",
                     "bare `except:` also catches SystemExit/"
                     "KeyboardInterrupt; use `except Exception:`")
        self.generic_visit(node)

    def visit_Dict(self, node):
        seen = {}
        for k in node.keys:
            if isinstance(k, ast.Constant):
                try:
                    hash(k.value)
                except TypeError:
                    continue
                if k.value in seen:
                    self.add(k, "duplicate-dict-key",
                             f"duplicate dict key {k.value!r}")
                seen[k.value] = True
        self.generic_visit(node)

    def visit_JoinedStr(self, node):
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self.add(node, "f-string-no-placeholder",
                     "f-string has no placeholders")
        self.generic_visit(node)

    def visit_FormattedValue(self, node):
        # format specs (f"{x:>3}") are themselves JoinedStr nodes holding
        # only Constants; don't descend or every spec is a false positive
        self.visit(node.value)

    def visit_Compare(self, node):
        # chained comparisons: op[i] compares comparators[i-1] (or .left
        # for i == 0) with comparators[i]
        lefts = [node.left] + list(node.comparators[:-1])
        for left, op, comp in zip(lefts, node.ops, node.comparators):
            if isinstance(op, (ast.Is, ast.IsNot)):
                operands = [comp, left]
                for o in operands:
                    if isinstance(o, ast.Constant) and isinstance(
                            o.value, (str, int, float, bytes)) and \
                            not isinstance(o.value, bool):
                        self.add(node, "is-literal",
                                 "`is` comparison with a literal; "
                                 "use == / !=")
                        break
        self.generic_visit(node)

    def _check_defaults(self, node):
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in ("list", "dict", "set")
                    and not d.args and not d.keywords):
                self.add(d, "mutable-default",
                         "mutable default argument; use None and "
                         "initialize inside")

    def visit_FunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_Assert(self, node):
        if isinstance(node.test, ast.Tuple) and node.test.elts:
            self.add(node, "assert-tuple",
                     "assert on a non-empty tuple is always true "
                     "(did you mean `assert cond, msg`?)")
        self.generic_visit(node)


def lint_file(path):
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
    except (OSError, UnicodeDecodeError) as e:
        return [Finding(path, 0, "unreadable", str(e))]
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "syntax-error", e.msg)]
    return Linter(path, tree, source).run()


# ---- Prometheus text-exposition validator ----
#
# The scrape endpoint (binder_tpu/metrics/collector.py expose()) hand-
# renders the text format version 0.0.4; a formatting bug there is
# invisible to every unit test that greps for a substring but breaks
# real Prometheus ingestion silently.  validate_exposition() checks the
# whole grammar plus the semantic invariants a hand-rolled histogram
# can violate: cumulative buckets must be non-decreasing in `le` order,
# the +Inf bucket must exist and equal `_count`, `_sum`/`_count` must
# both be present per label set, counters must be finite and
# non-negative, every sample must belong to a declared # TYPE family,
# and no (name, labelset) may repeat.  Returns a list of
# "line N: message" strings; empty list == valid.  Wired into tier-1
# via tests/test_attribution.py against MetricsCollector.expose().

_METRIC_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*$")
_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _parse_label_block(block, errs, lineno):
    """`k="v",k2="v2"` (no surrounding braces) -> tuple of (k, v) pairs,
    validating names, quoting, and escape sequences."""
    pairs = []
    i, n = 0, len(block)
    while i < n:
        j = block.find("=", i)
        if j < 0:
            errs.append(f"line {lineno}: malformed label block "
                        f"{block[i:]!r}")
            return tuple(pairs)
        name = block[i:j]
        if not _LABEL_NAME_RE.match(name):
            errs.append(f"line {lineno}: bad label name {name!r}")
        if j + 1 >= n or block[j + 1] != '"':
            errs.append(f"line {lineno}: label {name!r} value not quoted")
            return tuple(pairs)
        k = j + 2
        val = []
        while k < n:
            c = block[k]
            if c == "\\":
                if k + 1 >= n or block[k + 1] not in ('\\', '"', 'n'):
                    errs.append(f"line {lineno}: bad escape in label "
                                f"{name!r}")
                    return tuple(pairs)
                val.append({"\\": "\\", '"': '"', "n": "\n"}[block[k + 1]])
                k += 2
            elif c == '"':
                break
            else:
                val.append(c)
                k += 1
        else:
            errs.append(f"line {lineno}: unterminated label value for "
                        f"{name!r}")
            return tuple(pairs)
        pairs.append((name, "".join(val)))
        i = k + 1
        if i < n:
            if block[i] != ",":
                errs.append(f"line {lineno}: expected ',' between labels")
                return tuple(pairs)
            i += 1
    return tuple(pairs)


def _parse_value(tok, errs, lineno, what="value"):
    if tok in ("+Inf", "-Inf", "Inf", "NaN"):
        return float(tok.replace("Inf", "inf").replace("NaN", "nan"))
    try:
        return float(tok)
    except ValueError:
        errs.append(f"line {lineno}: unparseable {what} {tok!r}")
        return None


def validate_exposition(text):
    """Validate Prometheus text format 0.0.4.  Returns error strings
    ("line N: msg"); an empty list means the exposition is valid."""
    errs = []
    if text and not text.endswith("\n"):
        errs.append("line 0: exposition must end with a newline")
    types = {}          # family name -> declared type
    helps = set()
    samples = {}        # (sample name, label tuple) -> (lineno, value)
    family_of = {}      # sample name -> family (for suffix resolution)
    order = []          # (family, labels-without-le, le, value, lineno)
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line != line.strip():
            errs.append(f"line {lineno}: leading/trailing whitespace")
            line = line.strip()
            if not line:
                continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 2 and parts[1] in ("HELP", "TYPE"):
                if len(parts) < 3 or not _METRIC_NAME_RE.match(parts[2]):
                    errs.append(f"line {lineno}: malformed {parts[1]}")
                    continue
                name = parts[2]
                if parts[1] == "TYPE":
                    kind = parts[3].strip() if len(parts) > 3 else ""
                    if kind not in _TYPES:
                        errs.append(f"line {lineno}: unknown TYPE "
                                    f"{kind!r} for {name}")
                    if name in types:
                        errs.append(f"line {lineno}: duplicate TYPE "
                                    f"for {name}")
                    if any(fam == name for fam in family_of.values()):
                        errs.append(f"line {lineno}: TYPE for {name} "
                                    "after its samples")
                    types[name] = kind
                else:
                    if name in helps:
                        errs.append(f"line {lineno}: duplicate HELP "
                                    f"for {name}")
                    helps.add(name)
            continue   # other comments are free-form
        # sample line: name[{labels}] value [timestamp]
        brace = line.find("{")
        if brace >= 0:
            close = line.rfind("}")
            if close < brace:
                errs.append(f"line {lineno}: unbalanced braces")
                continue
            name = line[:brace]
            labels = _parse_label_block(line[brace + 1:close], errs,
                                        lineno)
            rest = line[close + 1:].split()
        else:
            toks = line.split()
            name, labels, rest = toks[0], (), toks[1:]
        if not _METRIC_NAME_RE.match(name):
            errs.append(f"line {lineno}: bad metric name {name!r}")
            continue
        if len(rest) not in (1, 2):
            errs.append(f"line {lineno}: expected 'name value "
                        "[timestamp]'")
            continue
        value = _parse_value(rest[0], errs, lineno)
        if len(rest) == 2 and _parse_value(
                rest[1], errs, lineno, "timestamp") is None:
            continue
        # resolve the family: histogram/summary samples carry suffixes
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[:len(name) - len(suffix)]
            if name.endswith(suffix) and types.get(base) in (
                    "histogram", "summary"):
                family = base
                break
        if family not in types:
            errs.append(f"line {lineno}: sample {name!r} has no "
                        "preceding # TYPE")
        family_of[name] = family
        key = (name, labels)
        if key in samples:
            errs.append(f"line {lineno}: duplicate sample {name}"
                        f"{dict(labels)!r} (first at line "
                        f"{samples[key][0]})")
        samples[key] = (lineno, value)
        kind = types.get(family)
        if kind == "counter" and value is not None and \
                not (value >= 0.0 and value == value and
                     value != float("inf")):
            errs.append(f"line {lineno}: counter {name} value {rest[0]} "
                        "not a finite non-negative number")
        if kind == "histogram" and name.endswith("_bucket"):
            le = dict(labels).get("le")
            if le is None:
                errs.append(f"line {lineno}: histogram bucket without "
                            "le label")
            else:
                bare = tuple(p for p in labels if p[0] != "le")
                order.append((family, bare, le, value, lineno))
    # histogram semantics per (family, label set)
    series = {}
    for family, bare, le, value, lineno in order:
        series.setdefault((family, bare), []).append((le, value, lineno))
    for (family, bare), cells in series.items():
        prev = None
        inf_val = None
        for le, value, lineno in cells:
            lef = _parse_value(le, errs, lineno, "le bound")
            if lef is None or value is None:
                continue
            if prev is not None and lef <= prev[0]:
                errs.append(f"line {lineno}: {family} buckets out of "
                            f"le order ({le!r} after {prev[1]!r})")
            if prev is not None and value < prev[2]:
                errs.append(f"line {lineno}: {family} cumulative bucket "
                            f"count decreases at le={le!r}")
            prev = (lef, le, value)
            if lef == float("inf"):
                inf_val = value
        if inf_val is None:
            errs.append(f"{family}{dict(bare)!r}: no le=\"+Inf\" bucket")
        cnt = samples.get((family + "_count", bare))
        if cnt is None:
            errs.append(f"{family}{dict(bare)!r}: missing _count")
        elif inf_val is not None and cnt[1] != inf_val:
            errs.append(f"line {cnt[0]}: {family}_count {cnt[1]:g} != "
                        f"+Inf bucket {inf_val:g}")
        if (family + "_sum", bare) not in samples:
            errs.append(f"{family}{dict(bare)!r}: missing _sum")
    return errs


# ---- introspection snapshot-schema validator ----
#
# The /status endpoint (binder_tpu/introspect/status.py) is consumed by
# bin/bstat and by operators' jq one-liners; a silently dropped or
# retyped field breaks both without failing any substring-grepping
# test.  validate_status_snapshot() pins the schema: required sections,
# required keys per section, and value types (None allowed only where
# the schema says nullable).  Returns "path: message" strings; empty
# list == valid.  Wired into tier-1 via tests/test_introspect.py
# against a live HTTP endpoint, and into `make status-smoke`.

_NUM = (int, float)
# section -> {key: (types, nullable)}
_SNAPSHOT_SCHEMA = {
    "service": {
        "name": (str, False), "pid": (int, False),
        "version": (int, False), "uptime_seconds": (_NUM, False),
        "generated_at": (_NUM, False),
    },
    "store": {
        "backend": (str, True), "state": (str, False),
        "connected": (bool, False),
        "disconnected_seconds": (_NUM, True),
        "session_establishments": (int, False),
        "transitions": (list, False),
    },
    "mirror": {
        "ready": (bool, False), "domain": (str, True),
        "generation": (int, False), "epoch": (int, False),
        "nodes": (int, False), "names": (int, False),
        "reverse_entries": (int, False),
        "interned_names": (int, False),
        "staleness_seconds": (_NUM, True),
        "last_rebuild_age_seconds": (_NUM, True),
        "rebuild": (dict, False),
    },
    "answer_cache": {
        "size": (int, False), "entries": (int, False),
        "hits": (int, False), "misses": (int, False),
        "hit_ratio": (_NUM, False), "invalidations": (int, False),
        "expiry_ms": (_NUM, False), "neg_hits": (int, False),
        "type_row_serves": (int, False),
        "zone_put_skips": (dict, False),
    },
    "inflight": {
        "count": (int, False), "queries": (list, False),
    },
    "tcp": {
        "open_conns": (int, False), "max_conns": (int, False),
        "idle_timeout_seconds": (_NUM, False),
        "max_write_buffer": (int, False),
        "cap_refusals": (int, False), "accepts": (int, False),
        "fast_serves": (int, False), "native_serves": (int, False),
        "promotions": (int, False),
        "oneshot_closes": (int, False), "idle_timeouts": (int, False),
        "slow_reader_drops": (int, False),
        "coalesced_writes": (int, False),
        "coalesced_frames": (int, False), "half_closes": (int, False),
        "rst_drops": (int, False), "udp_truncated": (int, False),
    },
}
# the counts behind the time ledger's socket and log stages
_IO_SCHEMA = {
    "recv_calls": (int, False), "recv_empty": (int, False),
    "recv_chained": (int, False),
    "recv_datagrams": (int, False), "recv_batch_cells": (list, False),
    "send_calls": (int, False), "send_datagrams": (int, False),
    "send_drops": (dict, False),
    "log_writes": (int, False), "log_lines": (int, False),
    "log_lines_direct": (int, False), "log_bytes": (int, False),
}
#: who can lose a UDP answer to a full send buffer
#: (binder_udp_send_drops_total{lane}, /status io.send_drops)
_SEND_DROP_LANES = ("native", "python", "balancer")
_SESSION_STATES = ("never-connected", "connected", "degraded", "expired",
                   "closed")
_INFLIGHT_KEYS = ("trace", "name", "type", "client", "protocol",
                  "age_ms", "phase", "phases")
_TRANSITION_KEYS = ("t_wall", "age_seconds", "from", "to", "reason")


def _check_keys(obj, schema, where, errs):
    for key, (types, nullable) in schema.items():
        if key not in obj:
            errs.append(f"{where}: missing key {key!r}")
            continue
        val = obj[key]
        if val is None:
            if not nullable:
                errs.append(f"{where}.{key}: null not allowed")
            continue
        if not isinstance(val, types):
            errs.append(f"{where}.{key}: expected "
                        f"{getattr(types, '__name__', types)}, got "
                        f"{type(val).__name__}")


def validate_status_snapshot(snap):
    """Validate an introspection snapshot (parsed JSON).  Returns error
    strings; an empty list means the snapshot is schema-complete."""
    errs = []
    if not isinstance(snap, dict):
        return [f"snapshot: expected object, got {type(snap).__name__}"]
    for section, schema in _SNAPSHOT_SCHEMA.items():
        sub = snap.get(section)
        if not isinstance(sub, dict):
            errs.append(f"{section}: missing or not an object")
            continue
        _check_keys(sub, schema, section, errs)
    # nullable top-level sections must still be PRESENT (consumers key
    # on them to know the feature is off, not mistyped)
    for section in ("recursion", "precompile", "verify", "loop",
                    "flight_recorder", "policy", "io"):
        if section not in snap:
            errs.append(f"{section}: key must be present (null when "
                        "the subsystem is off)")
        elif snap[section] is not None and not isinstance(
                snap[section], dict):
            errs.append(f"{section}: expected object or null")
    store = snap.get("store")
    if isinstance(store, dict):
        if store.get("state") not in _SESSION_STATES:
            errs.append(f"store.state: unknown state "
                        f"{store.get('state')!r}")
        for i, tr in enumerate(store.get("transitions") or []):
            if not isinstance(tr, dict):
                errs.append(f"store.transitions[{i}]: not an object")
                continue
            for key in _TRANSITION_KEYS:
                if key not in tr:
                    errs.append(f"store.transitions[{i}]: missing "
                                f"{key!r}")
    infl = snap.get("inflight")
    if isinstance(infl, dict) and isinstance(infl.get("queries"), list):
        if infl.get("count") != len(infl["queries"]):
            errs.append("inflight.count != len(inflight.queries)")
        for i, q in enumerate(infl["queries"]):
            if not isinstance(q, dict):
                errs.append(f"inflight.queries[{i}]: not an object")
                continue
            for key in _INFLIGHT_KEYS:
                if key not in q:
                    errs.append(f"inflight.queries[{i}]: missing "
                                f"{key!r}")
    loop = snap.get("loop")
    if isinstance(loop, dict):
        for key in ("interval_seconds", "stall_threshold_seconds",
                    "samples", "stall_events", "stalls",
                    "last_lag_seconds", "max_lag_seconds"):
            if key not in loop:
                errs.append(f"loop: missing {key!r}")
        # the ring of stall instants on the shared monotonic clock
        stalls = loop.get("stalls")
        if not isinstance(stalls, list):
            errs.append("loop.stalls: expected a list of instants")
        else:
            for i, st in enumerate(stalls):
                if not (isinstance(st, dict)
                        and isinstance(st.get("t_mono"), _NUM)
                        and isinstance(st.get("lag_s"), _NUM)):
                    errs.append(f"loop.stalls[{i}]: expected "
                                "{t_mono, lag_s} numbers")
            times = [st["t_mono"] for st in stalls
                     if isinstance(st, dict) and "t_mono" in st]
            if times != sorted(times):
                errs.append("loop.stalls: t_mono not ascending")
    io = snap.get("io")
    if isinstance(io, dict):
        _check_keys(io, _IO_SCHEMA, "io", errs)
        drops = io.get("send_drops")
        if isinstance(drops, dict):
            for lane in _SEND_DROP_LANES:
                if not isinstance(drops.get(lane), int):
                    errs.append(f"io.send_drops: lane {lane!r} missing "
                                f"or not an int")
    fr = snap.get("flight_recorder")
    if isinstance(fr, dict):
        for key in ("capacity", "recorded", "dropped", "by_type",
                    "events"):
            if key not in fr:
                errs.append(f"flight_recorder: missing {key!r}")
        seqs = [ev.get("seq") for ev in fr.get("events") or []
                if isinstance(ev, dict)]
        if seqs != sorted(seqs):
            errs.append("flight_recorder.events: seq not ascending")
    mirror = snap.get("mirror")
    if isinstance(mirror, dict) and isinstance(mirror.get("rebuild"),
                                               dict):
        for key in ("pending", "chunks", "last_duration_seconds"):
            if key not in mirror["rebuild"]:
                errs.append(f"mirror.rebuild: missing {key!r}")
    # the one key the benchmark harness still waits on (ROADMAP D13)
    pc = snap.get("precompile")
    if isinstance(pc, dict) and "seed_remaining" not in pc:
        errs.append("precompile: missing 'seed_remaining'")
    vf = snap.get("verify")
    if isinstance(vf, dict):
        for key in ("enabled", "checks", "violations", "skipped",
                    "queue_depth", "audit", "recent_violations",
                    "propagation"):
            if key not in vf:
                errs.append(f"verify: missing {key!r}")
        audit = vf.get("audit")
        if isinstance(audit, dict):
            for key in ("passes", "pending", "interval_seconds",
                        "sample"):
                if key not in audit:
                    errs.append(f"verify.audit: missing {key!r}")
        prop = vf.get("propagation")
        if isinstance(prop, dict):
            for key in ("observed", "stages", "slowest"):
                if key not in prop:
                    errs.append(f"verify.propagation: missing {key!r}")
    pol = snap.get("policy")
    if isinstance(pol, dict):
        for key in ("degradation", "admission", "rrl", "breakers_open"):
            if key not in pol:
                errs.append(f"policy: missing {key!r}")
        deg = pol.get("degradation")
        if isinstance(deg, dict):
            for key in ("state", "state_since_seconds",
                        "max_staleness_seconds",
                        "stale_ttl_clamp_seconds", "exhausted_action",
                        "mirror_staleness_seconds", "stale_served",
                        "withheld", "transitions"):
                if key not in deg:
                    errs.append(f"policy.degradation: missing {key!r}")
            if deg.get("state") not in ("fresh", "stale-serving",
                                        "stale-exhausted", None):
                errs.append(f"policy.degradation.state: unknown state "
                            f"{deg.get('state')!r}")
        adm = pol.get("admission")
        if isinstance(adm, dict):
            for key in ("max_inflight", "inflight", "recursion_rate",
                        "recursion_burst", "clients_tracked", "shed"):
                if key not in adm:
                    errs.append(f"policy.admission: missing {key!r}")
        rrl = pol.get("rrl")
        if isinstance(rrl, dict):
            for key in ("enabled", "responses_per_second", "burst",
                        "slip_ratio", "buckets", "hot", "responses",
                        "slipped", "dropped", "evictions",
                        "allowlist", "allowlisted", "adaptive",
                        "adapted_buckets", "adaptations",
                        "false_positives"):
                if key not in rrl:
                    errs.append(f"policy.rrl: missing {key!r}")
    return errs


# ---- degradation / chaos metrics validator ----
#
# The degradation policy engine's whole point is that failure behavior
# is *observable*: binder_degraded_state is what the alert rules watch,
# binder_breaker_state is how an operator sees a dead peer being
# routed around, binder_shed_total is the only record of refused load.
# An exporter bug dropping any of them makes a degraded binder look
# healthy — the exact silent failure this PR exists to kill.
# validate_degradation_metrics() checks a scrape exposition for the
# full family set with the right TYPEs, the label pins the dashboards
# key on, and at least one sample each (every series is materialized
# at registration, so absence is always a bug).  Wired into tier-1 via
# tests/test_chaos.py and into `make chaos-smoke`.

_DEGRADATION_FAMILIES = {
    "binder_degraded_state": "gauge",
    "binder_breaker_state": "gauge",
    "binder_shed_total": "counter",
    "binder_stale_served_total": "counter",
    "binder_stale_withheld_total": "counter",
}
#: label values that must exist from scrape 1 (family -> label -> values)
_DEGRADATION_LABEL_PINS = {
    "binder_shed_total": ("reason", ("inflight-overflow",
                                     "recursion-ratelimit")),
    "binder_breaker_state": ("peer", ("(max)",)),
}


def validate_degradation_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    degradation/shedding family set (correct TYPE declarations, pinned
    label values, at least one sample each).  Returns error strings;
    empty == valid.  Scope: a FULLY configured binder — degradation +
    admission blocks on AND recursion configured (the breaker family
    registers with the recursion layer; a binder without upstreams has
    nothing to break and legitimately lacks it)."""
    errs = list(validate_exposition(text))
    types = {}
    labels_seen = {}    # family -> {label name -> set(values)}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            brace = line.find("{")
            name = line[:brace] if brace >= 0 else parts[0]
            fam_labels = labels_seen.setdefault(name, {})
            if brace >= 0:
                close = line.rfind("}")
                for lname, lval in _parse_label_block(
                        line[brace + 1:close], [], 0):
                    fam_labels.setdefault(lname, set()).add(lval)
            else:
                fam_labels.setdefault(None, set()).add("")
    for family, kind in _DEGRADATION_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in labels_seen:
            errs.append(f"{family}: no samples in exposition")
    for family, (label, values) in _DEGRADATION_LABEL_PINS.items():
        have = labels_seen.get(family, {}).get(label, set())
        for val in values:
            if val not in have:
                errs.append(f"{family}: missing pinned series "
                            f"{label}={val!r}")
    return errs


# ---- TCP stream-lane metrics validator ----
#
# The stream lane's performance story is only auditable through its
# counters: fast_serves vs promotions names whether the accept fast
# path is actually carrying the one-shot population, and the drop
# counters (idle / slow-reader / cap) are the only record of shed
# connections.  validate_tcp_metrics() checks a scrape exposition for
# the full binder_tcp_* family with the right TYPEs and at least one
# sample each (every series is materialized at registration, so absence
# is always an exporter bug).  Wired into tier-1 via
# tests/test_tcp_stream.py and into `make tcp-smoke`.

_TCP_FAMILIES = {
    "binder_tcp_accepts": "counter",
    "binder_tcp_fast_serves": "counter",
    "binder_tcp_native_serves": "counter",
    "binder_tcp_promotions": "counter",
    "binder_tcp_oneshot_closes": "counter",
    "binder_tcp_idle_timeouts": "counter",
    "binder_tcp_slow_reader_drops": "counter",
    "binder_tcp_coalesced_writes": "counter",
    "binder_tcp_coalesced_frames": "counter",
    "binder_tcp_half_closes": "counter",
    "binder_tcp_rst_drops": "counter",
    "binder_tcp_cap_refusals": "counter",
    "binder_tcp_open_conns": "gauge",
    # what sends clients to the lane: UDP answers that left with TC=1
    "binder_truncated_responses": "counter",
}


def validate_tcp_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_tcp_*`` family (correct TYPE declarations and at least one
    sample each).  Returns error strings; empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    sampled = set()
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            sampled.add(parts[0].split("{", 1)[0])
    for family, kind in _TCP_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in sampled:
            errs.append(f"{family}: no samples in exposition")
    return errs


# -- shard-mode metrics (binder_tpu/shard, docs/observability.md) ------
#
# The supervisor aggregates N workers into the binder_shard_* family:
# per-shard series MUST carry a `shard` label (an unlabeled sample
# would silently sum incomparable processes in PromQL), every family
# must have the right TYPE, and every series must exist from scrape 1
# (the supervisor registers all N label sets at startup, so absence is
# always an exporter bug).  Wired into tier-1 via tests/test_shards.py
# and into `make shard-smoke`.

_SHARD_FAMILIES = {
    "binder_shards": ("gauge", False),
    "binder_shard_up": ("gauge", True),
    "binder_shard_pid": ("gauge", True),
    "binder_shard_generation": ("gauge", True),
    "binder_shard_ready": ("gauge", True),
    "binder_shard_respawns": ("counter", True),
    "binder_shard_requests": ("counter", True),
    "binder_shard_rolls_total": ("counter", True),
    "binder_shard_roll_aborts_total": ("counter", False),
    "binder_shard_roll_inflight_total": ("counter", False),
    "binder_shard_roll_unserved_total": ("counter", False),
}

#: a rolled shard's phases, one series each from scrape 1
_SHARD_ROLL_PHASES = ("attach", "fill", "drain")


def validate_shard_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_shard_*`` family: correct TYPE declarations, at least one
    sample each, and a ``shard`` label on every per-shard series.
    Returns error strings; empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    samples = {}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            name, _, labels = parts[0].partition("{")
            samples.setdefault(name, []).append(labels)
    for family, (kind, per_shard) in _SHARD_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in samples:
            errs.append(f"{family}: no samples in exposition")
        elif per_shard:
            for labels in samples[family]:
                # parse actual label NAMES ("notshard" must not pass a
                # substring check for "shard")
                names = {pair.partition("=")[0]
                         for pair in labels.partition("}")[0].split(",")
                         if pair}
                if "shard" not in names:
                    errs.append(f"{family}: sample missing the "
                                f"`shard` label")
                    break
    family = "binder_shard_roll_phase_seconds"
    if types.get(family) != "histogram":
        errs.append(f"{family}: declared {types.get(family)!r}, "
                    "expected 'histogram'")
    for phase in _SHARD_ROLL_PHASES:
        if not any(f'phase="{phase}"' in labels
                   for labels in samples.get(family + "_count", ())):
            errs.append(f"{family}: no series for phase {phase!r}")
    return errs


# -- mirror / zone-scale metrics (ISSUE 7, docs/observability.md) ------
#
# The million-name story is told by the binder_mirror_* family (name
# count, interned-pool size, chunked-rebuild progress/duration) plus
# binder_udp_late_drops_total (late responses dropped at a full socket
# buffer — the drop path that used to be a silent debug line).  Every
# family must carry the right TYPE and at least one sample, and none of
# the per-binder series may carry stray labels (an accidental label
# would split the one-series-per-process contract PromQL dashboards sum
# over).  Wired into tier-1 via tests/test_zone_scale.py and into
# `make zone-smoke`.

_MIRROR_FAMILIES = {
    "binder_mirror_staleness_seconds": "gauge",
    "binder_mirror_names": "gauge",
    "binder_mirror_interned_names": "gauge",
    "binder_mirror_rebuild_pending": "gauge",
    "binder_mirror_rebuild_seconds": "gauge",
    "binder_mirror_rebuild_chunks": "counter",
    "binder_udp_late_drops_total": "counter",
}

#: labels the collector's static set may legitimately add to every
#: series; anything else on a mirror-family sample is a pin violation
_MIRROR_ALLOWED_LABELS = frozenset(
    ("datacenter", "instance", "server", "service", "port"))


def validate_mirror_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_mirror_*`` / zone-scale family (plus the late-drop
    counter): correct TYPE declarations, at least one sample each, and
    no labels beyond the collector's static set.  Returns error
    strings; empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    samples = {}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            name, _, labels = parts[0].partition("{")
            samples.setdefault(name, []).append(labels)
    for family, kind in _MIRROR_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in samples:
            errs.append(f"{family}: no samples in exposition")
            continue
        for labels in samples[family]:
            names = {pair.partition("=")[0]
                     for pair in labels.partition("}")[0].split(",")
                     if pair}
            stray = names - _MIRROR_ALLOWED_LABELS
            if stray:
                errs.append(f"{family}: unexpected label(s) "
                            f"{sorted(stray)}")
                break
    return errs


# -- federation metrics (ISSUE 11, docs/federation.md) ----------------
#
# The multi-DC story is told by the binder_federation_* family (registry
# size, per-DC forward counts, the foreign-answer cache's stale/withheld
# split, budget clamps, failover convergence) plus the recursion
# single-flight counter.  Forward counts are the only per-DC series and
# must carry the `dc` label; everything else is one series per process.
# Wired into tier-1 via tests/test_federation.py and into
# `make federation-smoke`.

_FEDERATION_FAMILIES = {
    "binder_federation_dcs": ("gauge", False),
    "binder_federation_convergence_seconds": ("gauge", False),
    "binder_federation_forwards_total": ("counter", True),
    "binder_federation_foreign_hits_total": ("counter", False),
    "binder_federation_foreign_stale_served_total": ("counter", False),
    "binder_federation_foreign_withheld_total": ("counter", False),
    "binder_federation_budget_clamped_total": ("counter", False),
    "binder_federation_failovers_total": ("counter", False),
    "binder_recursion_coalesced_total": ("counter", False),
}


def validate_federation_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_federation_*`` family (plus the recursion single-flight
    counter): correct TYPE declarations, at least one sample each, a
    ``dc`` label on every forward-count series, and no labels beyond
    the collector's static set elsewhere.  Returns error strings;
    empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    samples = {}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            name, _, labels = parts[0].partition("{")
            samples.setdefault(name, []).append(labels)
    for family, (kind, per_dc) in _FEDERATION_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in samples:
            errs.append(f"{family}: no samples in exposition")
            continue
        for labels in samples[family]:
            # parse actual label NAMES ("notdc" must not pass a
            # substring check for "dc")
            names = {pair.partition("=")[0]
                     for pair in labels.partition("}")[0].split(",")
                     if pair}
            if per_dc:
                if "dc" not in names:
                    errs.append(f"{family}: sample missing the "
                                f"`dc` label")
                    break
            else:
                stray = names - _MIRROR_ALLOWED_LABELS
                if stray:
                    errs.append(f"{family}: unexpected label(s) "
                                f"{sorted(stray)}")
                    break
    return errs


# -- RRL / hostile-traffic metrics (ISSUE 12, docs/operations.md) -----
#
# The hostile-internet posture is told by the binder_rrl_* family
# (responses admitted / slipped / dropped / bucket evictions, live
# bucket count, the `active` flood flag) plus the
# binder_shed_total{reason="response-ratelimit"} series the drops feed.
# Wired into tier-1 via tests/test_hostile.py and into
# `make hostile-smoke`.

_RRL_FAMILIES = {
    "binder_rrl_responses_total": "counter",
    "binder_rrl_slipped_total": "counter",
    "binder_rrl_dropped_total": "counter",
    "binder_rrl_evictions_total": "counter",
    "binder_rrl_allowlisted_total": "counter",
    "binder_rrl_adaptations_total": "counter",
    "binder_rrl_false_positives_total": "counter",
    "binder_rrl_buckets": "gauge",
    "binder_rrl_active": "gauge",
    "binder_rrl_adapted_buckets": "gauge",
}


def validate_rrl_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_rrl_*`` family plus the response-ratelimit shed series:
    correct TYPE declarations, at least one sample each, and no labels
    beyond the collector's static set.  Returns error strings;
    empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    samples = {}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            name, _, labels = parts[0].partition("{")
            samples.setdefault(name, []).append(labels)
    for family, kind in _RRL_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in samples:
            errs.append(f"{family}: no samples in exposition")
            continue
        for labels in samples[family]:
            names = {pair.partition("=")[0]
                     for pair in labels.partition("}")[0].split(",")
                     if pair}
            stray = names - _MIRROR_ALLOWED_LABELS
            if stray:
                errs.append(f"{family}: unexpected label(s) "
                            f"{sorted(stray)}")
                break
    # the drop path must surface in the shared shed accounting too:
    # operators alert on binder_shed_total, not per-family counters
    if not any(parts and parts[0].startswith("binder_shed_total{")
               and 'reason="response-ratelimit"' in parts[0]
               for parts in (ln.split() for ln in text.splitlines())
               if parts and not parts[0].startswith("#")):
        errs.append('binder_shed_total: missing the '
                    'reason="response-ratelimit" series')
    return errs


# -- serving-plane verification metrics (ISSUE 16) --------------------
#
# The checker's whole value is that silence is never ambiguous: every
# invariant's check/violation/skip series must exist from scrape 1
# (zero-seeded at registration), and the propagation histogram must
# carry every datapath stage before the first mutation.  An exporter
# bug dropping a series would make "no violations" indistinguishable
# from "not checking" — the exact failure the family exists to rule
# out.  Wired into tier-1 via tests/test_verify.py and into
# `make verify-smoke`.

_VERIFY_FAMILIES = {
    "binder_verify_checks_total": "counter",
    "binder_verify_violations_total": "counter",
    "binder_verify_skipped_total": "counter",
    "binder_verify_queue_depth": "gauge",
    "binder_propagation_seconds": "histogram",
}
#: the invariant catalog (binder_tpu/verify/checker.py INVARIANTS) —
#: every value pinned on all three counters; the skip counter also
#: carries the queue-shed series
_VERIFY_INVARIANTS = ("dangling-srv", "ptr-coherence", "replica-digest")
#: the propagation stage catalog (binder_tpu/verify/tracer.py STAGES)
_VERIFY_STAGES = ("mirror-apply", "shard-frame", "replica-apply",
                  "native-install")


def validate_verify_metrics(text):
    """Validate that a Prometheus exposition carries the complete
    ``binder_verify_*`` family plus the per-stage propagation
    histogram: correct TYPE declarations, at least one sample each,
    every invariant pinned on the three counters (queue-shed on the
    skip counter), and every stage pinned on the histogram.  Returns
    error strings; empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    labels_seen = {}    # family -> {label name -> set(values)}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            brace = line.find("{")
            name = line[:brace] if brace >= 0 else parts[0]
            # histogram series expose under <fam>_bucket/_sum/_count
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) \
                        and name[:-len(suffix)] in _VERIFY_FAMILIES:
                    name = name[:-len(suffix)]
                    break
            fam_labels = labels_seen.setdefault(name, {})
            if brace >= 0:
                close = line.rfind("}")
                for lname, lval in _parse_label_block(
                        line[brace + 1:close], [], 0):
                    fam_labels.setdefault(lname, set()).add(lval)
            else:
                fam_labels.setdefault(None, set()).add("")
    for family, kind in _VERIFY_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
        if family not in labels_seen:
            errs.append(f"{family}: no samples in exposition")
    for family in ("binder_verify_checks_total",
                   "binder_verify_violations_total",
                   "binder_verify_skipped_total"):
        have = labels_seen.get(family, {}).get("invariant", set())
        for inv in _VERIFY_INVARIANTS:
            if inv not in have:
                errs.append(f"{family}: missing pinned series "
                            f"invariant={inv!r}")
    if "queue-shed" not in labels_seen.get(
            "binder_verify_skipped_total", {}).get("invariant", set()):
        errs.append("binder_verify_skipped_total: missing pinned "
                    "series invariant='queue-shed'")
    have = labels_seen.get(
        "binder_propagation_seconds", {}).get("stage", set())
    for stage in _VERIFY_STAGES:
        if stage not in have:
            errs.append(f"binder_propagation_seconds: missing pinned "
                        f"series stage={stage!r}")
    return errs


# -- the time ledger (ISSUE 24, docs/observability.md) ----------------
#
# A worker's second is accounted for by the leaf stages of
# binder_query_stage_seconds (loop-idle, udp-recv, native-serve,
# udp-send, log-write, log-line and the stream lane's tcp-accept,
# tcp-recv, tcp-send, tcp-close) beside the per-query stages, with the
# socket and log counters that give them their denominators.  The
# benchmark's per-layer readers (benchmark/layer_metrics/) key on these
# names and labels, so each family must carry the right TYPE, and the
# pinned label values must be there.  Wired into tier-1 via
# tests/test_ledger.py.

_LEDGER_FAMILIES = {
    "binder_query_stage_seconds": "histogram",
    "binder_udp_datagrams": "counter",
    "binder_udp_batch_size": "histogram",
    # drains made with no select before them (benchmark:
    # udp_chained_share), and the answers a full send buffer cost, by
    # the lane that sent them (benchmark: udp_send_drops; runbook
    # "Performance notes")
    "binder_udp_chained_drains_total": "counter",
    "binder_udp_send_drops_total": "counter",
    "binder_answer_cache_hits": "counter",
    # the native serves that are neither: the zone table's, and of
    # those the type row's (benchmark: type_declined_native_share)
    "binder_zone_serves": "counter",
    "binder_zone_type_serves": "counter",
    # what the zone table refused to hold (runbook "Large zones")
    "binder_zone_put_skips": "counter",
    "binder_query_log_bytes": "counter",
    "binder_query_log_lines": "counter",
    "binder_truncated_renders": "counter",
    # the event span: the parent of the leaves, a family of its own
    "binder_loop_event_seconds": "histogram",
}
_LEDGER_STAGES = ("loop-idle", "udp-recv", "native-serve", "udp-send",
                  "log-write", "log-line",
                  "tcp-accept", "tcp-recv", "tcp-send", "tcp-close",
                  "tcp-register", "query-ingress")
_LEDGER_LABELS = {
    "binder_loop_event_seconds_count": (
        "lane", ("udp", "tcp", "balancer", "deferred")),
    "binder_udp_datagrams": ("dir", ("in", "out")),
    "binder_udp_send_drops_total": ("lane", _SEND_DROP_LANES),
    "binder_answer_cache_hits": ("tier", ("native", "python")),
    "binder_query_log_lines": ("path", ("direct", "logging")),
    "binder_zone_put_skips": ("reason", ("size", "bytes")),
}


def validate_ledger_metrics(text):
    """Validate that a Prometheus exposition carries the time ledger:
    the families with their TYPEs, every leaf stage as a series of the
    stage histogram, and the pinned ``dir`` / ``tier`` / ``path`` /
    ``lane`` label values.
    Returns error strings; empty == valid."""
    errs = list(validate_exposition(text))
    types = {}
    labels_seen = {}
    for line in text.splitlines():
        parts = line.split()
        if line.startswith("# TYPE") and len(parts) >= 4:
            types[parts[2]] = parts[3]
        elif line and not line.startswith("#") and parts:
            name, _, labels = parts[0].partition("{")
            for pair in labels.partition("}")[0].split(","):
                key, _, val = pair.partition("=")
                if key:
                    labels_seen.setdefault(name, {}).setdefault(
                        key, set()).add(val.strip('"'))
    for family, kind in _LEDGER_FAMILIES.items():
        if family not in types:
            errs.append(f"{family}: missing # TYPE declaration")
        elif types[family] != kind:
            errs.append(f"{family}: declared {types[family]!r}, "
                        f"expected {kind!r}")
    have = labels_seen.get(
        "binder_query_stage_seconds_count", {}).get("stage", set())
    for stage in _LEDGER_STAGES:
        if stage not in have:
            errs.append(f"binder_query_stage_seconds: missing leaf "
                        f"stage={stage!r}")
    for family, (label, values) in _LEDGER_LABELS.items():
        have = labels_seen.get(family, {}).get(label, set())
        for value in values:
            if value not in have:
                errs.append(f"{family}: missing pinned series "
                            f"{label}={value!r}")
    return errs


def is_python_script(path):
    if path.endswith(".py"):
        return True
    try:
        with open(path, "rb") as f:
            head = f.read(64)
        return head.startswith(b"#!") and b"python" in head.splitlines()[0]
    except OSError:
        return False


def collect(paths):
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git", "build")]
                for fn in sorted(files):
                    full = os.path.join(root, fn)
                    if is_python_script(full):
                        out.append(full)
        else:
            if is_python_script(p):
                out.append(p)
    return out


def main(argv):
    paths = argv or ["binder_tpu", "tests", "bin", "tools",
                     "chip_smoke.py", "__graft_entry__.py"]
    files = collect(paths)
    if not files:
        print("lint: no files found", file=sys.stderr)
        return 2
    findings = []
    for path in files:
        findings.extend(lint_file(path))
    for f in findings:
        print(f)
    if findings:
        print(f"lint: {len(findings)} finding(s) in {len(files)} files",
              file=sys.stderr)
        return 1
    print(f"lint: ok ({len(files)} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
