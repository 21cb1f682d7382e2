"""Declarative closure-gated rule specs + the shared walker (ISSUE 14).

Every closure-gated rule is a :class:`ClosureRule`: scopes (which files
seed roots), root function names, a forbidden-construct kind, and an
allowlist tag.  One engine resolves the roots' CROSS-MODULE transitive
call closure (tools/analyzer/index.py) and walks each reached function
for the rule's forbidden constructs — so a host sync or per-entry
pickle moved into a helper one file away can no longer escape the gate
(the pre-ISSUE-14 checkers only followed same-module calls).

Rules here (the doc-of-record for codes is tools/lint.py's docstring):

  RA02  engine step hot loop: no np.asarray/.item() host syncs
  RA04  soak dispatch loops + sampler/recorder/tuner/driver-poll tick
        paths: no blocking device->host syncs
  RA08  ingress coalescer + mesh ingress pump: no per-session Python
        loops / dict allocation
  RA09  wire reader sweep path: same, extended to the socket path
  RA10  classic replication hot paths: no per-entry encode/WAL submit
        inside loops, and (the ISSUE 18 codec family) no raw
        ``pickle.dumps`` ANYWHERE in an append/AER/WAL/segment/sweep
        closure — object payloads must ride the codec's tagged
        fallback (ra_tpu.codec.encode_fallback) so every stored or
        shipped byte stays versioned and decodable

Findings are RAW (unsuppressed): tools/analyzer/audit.py applies the
``# raNN-ok`` line allowlists and audits them for staleness.  Tag
FAMILIES: RA02/RA04 are one host-sync family and RA08/RA09 one
per-row-Python family — a line a cross-module closure reaches from two
gates carries ONE documented tag, and the audit accepts either.
"""
from __future__ import annotations

import ast
import os

__all__ = ["Finding", "CLOSURE_RULES", "evaluate_closure_rules",
           "TAG_FAMILIES", "family_codes", "FILE_RULES",
           "evaluate_file_rules"]


class Finding:
    __slots__ = ("path", "line", "code", "msg", "roots")

    def __init__(self, path, line, code, msg, roots=()):
        self.path = path
        self.line = line
        self.code = code
        self.msg = msg
        # provenance: module paths of the rule roots (spawn sites, lock
        # sites, closure seeds) this finding was reached from.  The
        # engine evaluates the WHOLE program so scoped runs match the
        # full run's raw pool (the audit depends on that); the caller
        # then reports a finding only when its path OR one of its roots
        # is a lint target — linting fixture A must not surface sibling
        # B's findings, while a cross-module escape rooted in A still
        # lands wherever the construct lives.
        self.roots = tuple(roots)

    def key(self):
        return (self.path, self.line, self.code, self.msg)

    def render(self):
        return f"{self.path}:{self.line}: {self.code} {self.msg}"


#: allowlist-tag families: a tag from any rule in the family suppresses
#: (and keeps live, for the audit) a finding from any other member —
#: RA02/RA04 police the same host-sync bug class from different roots,
#: RA08/RA09 the same per-row-Python class.
TAG_FAMILIES = (
    ("RA02", "RA04"),
    ("RA08", "RA09"),
    ("RA03",),
    ("RA10",),
    ("RA11",),
    ("RA12",),
    ("RA13",),
    ("RA14",),
    ("RA15",),
    ("RA16",),
)


def family_codes(code):
    for fam in TAG_FAMILIES:
        if code in fam:
            return fam
    return (code,)


# -- scopes ---------------------------------------------------------------

class Scope:
    """Selects root functions inside matching target files."""

    def __init__(self, roots, basenames=None, parent=None, dirname=None):
        self.roots = frozenset(roots)
        self.basenames = frozenset(basenames) if basenames else None
        self.parent = parent      # required parent directory name
        self.dirname = dirname    # any path component (e.g. "wire")

    def matches(self, path):
        base = os.path.basename(path)
        if self.basenames is not None and base not in self.basenames:
            return False
        if self.parent is not None and \
                os.path.basename(os.path.dirname(path)) != self.parent:
            return False
        if self.dirname is not None:
            parts = os.path.normpath(path).split(os.sep)
            if self.dirname not in parts[:-1]:
                return False
        return True


class ClosureRule:
    def __init__(self, code, kind, scopes, msg_ctx):
        self.code = code
        self.kind = kind          # "sync" | "loops" | "per_entry"
        self.scopes = scopes
        self.msg_ctx = msg_ctx    # human name of the gated path


#: a pump's confirm-only program is on the serve loop as a dispatch is:
#: its one readback is a documented sync, nothing else in it may sync
_HOT_STEP_FUNCS = frozenset({"step", "_step", "submit", "uniform_step",
                             "superstep", "_superstep", "submit_block",
                             "uniform_superstep", "confirm_only"})
_SAMPLER_HOT_FUNCS = frozenset({"tick", "_start_sample", "_harvest",
                                "note"})

CLOSURE_RULES = [
    ClosureRule("RA02", "sync_ra02",
                [Scope(_HOT_STEP_FUNCS,
                       basenames={"lockstep.py", "durable.py"})],
                "hot-loop"),
    ClosureRule("RA04", "sync",
                [Scope(_SAMPLER_HOT_FUNCS, basenames={"telemetry.py"}),
                 Scope({"record"}, basenames={"blackbox.py"}),
                 Scope({"tick"}, basenames={"autotune.py"}),
                 # ISSUE 20: the driver's read observer is a sampler
                 # tick — it must only touch COMPLETED async read-aux
                 # copies, never force a device sync of its own
                 Scope({"_observe_reads"}, basenames={"lockstep.py"}),
                 # ISSUE 28: the driver's poll() runs on the dispatch
                 # path after every launch and at every ingress
                 # harvest — NON-BLOCKING by contract: it converts only
                 # handles that is_ready(), so its one np.asarray (in
                 # _take) carries the family's documented reason and
                 # nothing else in its closure may sync
                 Scope({"poll"}, basenames={"lockstep.py"})],
                "sampler tick-path"),
    ClosureRule("RA08", "loops",
                [Scope({"offer", "pop_block", "pop_rows"},
                       basenames={"coalesce.py"}),
                 Scope({"ingress_submit_wave"}, basenames={"mesh.py"}),
                 # ISSUE 20: the read admission/reply lane — per-WAVE
                 # vectorized, no per-session Python on the hot path
                 Scope({"submit_reads", "_pop_read_block",
                        "_harvest_reads", "_emit_read_replies"},
                       basenames={"__init__.py"}, parent="ingress")],
                "coalescer"),
    ClosureRule("RA09", "loops",
                [Scope({"sweep"}, dirname="wire"),
                 # ISSUE 20: READ_REPLY egress — one frame per
                 # connection per wave, never per read
                 Scope({"_on_reads_served", "collect_read_replies"},
                       basenames={"server.py"}, dirname="wire")],
                "wire sweep"),
    ClosureRule("RA10", "per_entry",
                [Scope({"_send_items", "_wire_form"},
                       basenames={"tcp.py"}),
                 Scope({"write", "append_batch", "_put_batch", "_put",
                        "flush_mem_to_segments"},
                       basenames={"durable.py"}, parent="log"),
                 Scope({"_write_batch"}, basenames={"wal.py"},
                       parent="log"),
                 Scope({"flush"}, basenames={"segment.py"},
                       parent="log"),
                 Scope({"sweep"}, dirname="wire"),
                 Scope({"_leader_aer_reply", "_evaluate_quorum"},
                       basenames={"server.py"}, parent="core")],
                "classic hot path"),
]

#: soak dispatch-loop scope (RA04's loop-shaped half): any loop in
#: these files that dispatches engine work is a measured region
_BENCH_FILES = frozenset({"soak.py"})
_DISPATCH_ATTRS = frozenset({"step", "superstep", "uniform_step",
                             "uniform_superstep", "submit"})
#: ``drain`` is new with ISSUE 14: a driver/sampler drain is a full
#: pipeline barrier, the strongest sync of all — the pre-engine gate
#: missed it
_SYNC_ATTRS = frozenset({"block_until_ready", "committed_total", "item",
                         "drain"})
_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp,
               ast.SetComp, ast.DictComp, ast.GeneratorExp)
_RA10_ENCODE_NAMES = frozenset({"dumps", "encode_command"})
_RA10_SYNC_NAMES = frozenset({"fsync", "fdatasync"})


# -- forbidden-construct walkers -----------------------------------------

def _walk_sync(fi, code, ctx, out, attrs=_SYNC_ATTRS,
               msg_tail="blocks the dispatch loop the path rides; "
                        "gate on is_ready() or mark the line "
                        "'# ra04-ok: why'"):
    path = fi.module.path
    for sub in ast.walk(fi.node):
        if not isinstance(sub, ast.Call):
            continue
        fn = sub.func
        if not isinstance(fn, ast.Attribute):
            continue
        if fn.attr in attrs and not sub.args:
            out.append(Finding(path, sub.lineno, code,
                               f".{fn.attr}() in {ctx} {fi.name}() "
                               + msg_tail))
        elif fn.attr == "asarray" and \
                isinstance(fn.value, ast.Name) and fn.value.id == "np":
            out.append(Finding(path, sub.lineno, code,
                               f"np.asarray() in {ctx} {fi.name}() "
                               + msg_tail))


def _walk_sync_ra02(fi, code, ctx, out):
    _walk_sync(fi, code, ctx, out, attrs=frozenset({"item"}),
               msg_tail="forces a device->host sync; move it to a "
                        "documented readback point or mark the line "
                        "'# ra02-ok: why'")


def _walk_loops(fi, code, ctx, out):
    path = fi.module.path
    mark = f"# {code.lower()}-ok: why"
    for sub in ast.walk(fi.node):
        if isinstance(sub, _LOOP_NODES):
            out.append(Finding(
                path, sub.lineno, code,
                f"Python loop in {ctx} hot path {fi.name}() — per-row "
                "iteration turns the vectorized path back into "
                "per-command host work; vectorize (argsort/fancy "
                f"indexing) or mark the line '{mark}'"))
        elif isinstance(sub, ast.Dict):
            out.append(Finding(
                path, sub.lineno, code,
                f"dict allocation in {ctx} hot path {fi.name}(); "
                f"preallocate outside the hot path or mark the line "
                f"'{mark}'"))
        elif isinstance(sub, ast.Call) and \
                isinstance(sub.func, ast.Name) and sub.func.id == "dict":
            out.append(Finding(
                path, sub.lineno, code,
                f"dict() allocation in {ctx} hot path {fi.name}(); "
                f"preallocate outside the hot path or mark the line "
                f"'{mark}'"))


def _call_name(call):
    f = call.func
    return f.attr if isinstance(f, ast.Attribute) else \
        f.id if isinstance(f, ast.Name) else None


def _is_encoder(fi):
    for sub in ast.walk(fi.node):
        if isinstance(sub, ast.Call) and \
                _call_name(sub) in _RA10_ENCODE_NAMES:
            return True
    return False


def _is_raw_pickle(call):
    """``pickle.dumps(...)`` or a bare ``dumps``/``_dumps`` alias call
    (the codec's own module-level alias shape) — the construct the
    ISSUE 18 codec family bans from hot closures outside the codec's
    tagged fallback."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr == "dumps" and isinstance(f.value, ast.Name) \
            and f.value.id == "pickle"
    return isinstance(f, ast.Name) and f.id in ("dumps", "_dumps")


def _walk_per_entry(idx, fi, code, ctx, out, encoder_names):
    """RA10: per-entry encode / WAL submit inside a loop, including a
    call to a helper (same-module by name, or cross-module resolved)
    that itself encodes."""
    path = fi.module.path
    seen = set()
    for loop in ast.walk(fi.node):
        if not isinstance(loop, _LOOP_NODES):
            continue
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call) or id(sub) in seen:
                continue
            cname = _call_name(sub)
            f = sub.func
            if cname in _RA10_SYNC_NAMES or (
                    cname in ("write", "write_many") and
                    isinstance(f, ast.Attribute) and
                    isinstance(f.value, ast.Attribute) and
                    f.value.attr == "wal"):
                seen.add(id(sub))
                out.append(Finding(
                    path, sub.lineno, code,
                    f"per-entry WAL submit/sync ({cname}) inside a "
                    f"loop in {ctx} {fi.name}() — use the group-commit "
                    "fan-in (write_many) outside the loop or mark the "
                    "line '# ra10-ok: why'"))
            elif cname in _RA10_ENCODE_NAMES or \
                    cname in encoder_names or \
                    any(_is_encoder(c) for c in idx.resolve_call(fi, sub)):
                seen.add(id(sub))
                out.append(Finding(
                    path, sub.lineno, code,
                    f"per-entry encode ({cname}) inside a loop in "
                    f"{ctx} {fi.name}() — batch-encode outside the "
                    "loop (one pickle per frame/run) or mark the line "
                    "'# ra10-ok: why'"))
    # the codec family (ISSUE 18): raw pickle ANYWHERE in the closure,
    # loop or not — a hot-path object-encode that bypasses the codec's
    # tagged fallback ships unversioned bytes to the WAL/wire/segments
    for sub in ast.walk(fi.node):
        if not isinstance(sub, ast.Call) or id(sub) in seen:
            continue
        if _is_raw_pickle(sub):
            seen.add(id(sub))
            out.append(Finding(
                path, sub.lineno, code,
                f"raw pickle.dumps in {ctx} closure {fi.name}() — "
                "object payloads must ride the codec's tagged "
                "fallback (ra_tpu.codec.encode_fallback) so every "
                "stored/shipped byte stays versioned and decodable, "
                "or mark the line '# ra10-ok: why'"))


_WALKERS = {
    "sync": _walk_sync,
    "sync_ra02": _walk_sync_ra02,
    "loops": _walk_loops,
}


def _rule_roots(idx, rule):
    # roots come from EVERY indexed source module, not just the lint
    # targets: a scoped run (--changed, one file) must evaluate the
    # same whole-program pool the full run does, or a tag in a changed
    # helper reads as stale when the root module didn't change (the
    # audit false-failure loop, review finding)
    roots = []
    per_module_names = {}
    for mod in idx.by_path.values():
        if mod.in_tests:
            continue
        names = set()
        for scope in rule.scopes:
            if scope.matches(mod.path):
                names |= scope.roots
        if not names:
            continue
        per_module_names[mod.path] = names
        for n in names:
            roots.extend(mod.func_defs.get(n, []))
    return roots, per_module_names


def evaluate_closure_rules(idx):
    """RAW findings from every declarative closure rule plus the
    bench dispatch-loop half of RA04."""
    out = []
    for rule in CLOSURE_RULES:
        roots, per_module = _rule_roots(idx, rule)
        if not roots:
            continue
        # per-ROOT-MODULE closures so each finding carries exactly the
        # root modules that reach it: stamping the whole rule's root
        # set would make a scoped run report findings only reachable
        # from a DIFFERENT root module (review finding — linting
        # telemetry.py must not surface a mesh-only escape)
        reached_by = {}   # id(fi) -> set of root module paths
        closure = {}
        for mpath, names in per_module.items():
            mod = idx.by_path[mpath]
            mod_roots = []
            for n in names:
                mod_roots.extend(mod.func_defs.get(n, []))
            for fid, fi in idx.closure(mod_roots).items():
                closure[fid] = fi
                reached_by.setdefault(fid, set()).add(mpath)
        if rule.kind == "per_entry":
            # same-module helper-encoder names (legacy superset: bare
            # attr-name matching catches unresolvable self-ish calls)
            encoder_names_by_mod = {}
            for fi in closure.values():
                mpath = fi.module.path
                if mpath not in encoder_names_by_mod:
                    names = set()
                    for defs in fi.module.func_defs.values():
                        for d in defs:
                            if _is_encoder(d):
                                names.add(d.name)
                    encoder_names_by_mod[mpath] = names
        walker = _WALKERS.get(rule.kind)
        for fid, fi in closure.items():
            fi_out = []
            if rule.kind == "per_entry":
                _walk_per_entry(idx, fi, rule.code, rule.msg_ctx,
                                fi_out,
                                encoder_names_by_mod[fi.module.path])
            else:
                walker(fi, rule.code, rule.msg_ctx, fi_out)
            root_paths = tuple(sorted(reached_by[fid]))
            for f in fi_out:
                f.roots = root_paths
            out.extend(fi_out)
    out.extend(_evaluate_bench_loops(idx))
    # dedup: overlapping scopes/roots may reach one function twice
    uniq = {}
    for f in out:
        uniq.setdefault(f.key(), f)
    return list(uniq.values())


def _evaluate_bench_loops(idx):
    """RA04's dispatch-loop half: direct syncs inside a soak loop that
    dispatches engine work, PLUS syncs anywhere in the resolvable
    call closure of helpers the loop body invokes (the cross-module
    escape ISSUE 14 closes)."""
    out = []
    tail = ("inside a bench dispatch loop forces a device->host sync "
            "that serializes the measured pipeline; harvest async "
            "readbacks instead or mark the line '# ra04-ok: why' "
            "(window boundary)")
    for mod in idx.by_path.values():
        if mod.in_tests:
            continue
        if os.path.basename(mod.path) not in _BENCH_FILES:
            continue
        seen = set()
        helper_roots = []
        mod_out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.While, ast.For, ast.AsyncFor)):
                continue
            body = list(node.body) + list(node.orelse)
            calls = [sub for stmt in body for sub in ast.walk(stmt)
                     if isinstance(sub, ast.Call)
                     and isinstance(sub.func, ast.Attribute)]
            if not any(c.func.attr in _DISPATCH_ATTRS for c in calls):
                continue
            for c in calls:
                if id(c) in seen:
                    continue
                seen.add(id(c))
                attr = c.func.attr
                if attr in ("item", "committed_total") and c.args:
                    continue
                if attr in _SYNC_ATTRS:
                    mod_out.append(Finding(mod.path, c.lineno, "RA04",
                                           f".{attr}() " + tail))
                elif attr == "asarray" and \
                        isinstance(c.func.value, ast.Name) and \
                        c.func.value.id == "np":
                    mod_out.append(Finding(mod.path, c.lineno, "RA04",
                                           "np.asarray() " + tail))
            # cross-module half: helpers the measured loop calls by
            # name — a sync moved into one must not escape the gate
            owner = _enclosing_func(mod, node)
            if owner is None:
                continue
            for stmt in body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Name):
                        helper_roots.extend(
                            idx.resolve_call(owner, sub))
        if helper_roots:
            for fi in idx.closure(helper_roots).values():
                if fi.node is None:
                    continue
                _walk_sync(fi, "RA04",
                           "a helper reached from a bench dispatch "
                           "loop:", mod_out)
        for f in mod_out:
            f.roots = (mod.path,)
        out.extend(mod_out)
    return out


def _enclosing_func(mod, node):
    """FuncInfo whose body (transitively) contains ``node``."""
    for defs in mod.func_defs.values():
        for fi in defs:
            for sub in ast.walk(fi.node):
                if sub is node:
                    return fi
    return None


# -- declarative per-file rules (RA05/RA06/RA07, migrated from
#    tools/lint.py so ONE engine evaluates every rule — ISSUE 15) --------

class FileRule:
    """A per-file contract: scope (which modules it applies to) + a
    walker ``check(mod, ctx) -> [Finding]``.  Evaluated over EVERY
    indexed module (tests exempt per rule), not just lint targets —
    the same whole-program-pool principle as the closure rules, so a
    scoped run feeds the audit the same raw findings the full run
    does."""

    def __init__(self, code, check, basenames=None, dirnames=None,
                 all_source=False):
        self.code = code
        self.check = check
        self.basenames = frozenset(basenames) if basenames else None
        self.dirnames = frozenset(dirnames) if dirnames else None
        self.all_source = all_source

    def matches(self, mod):
        if mod.in_tests:
            return False
        if self.basenames is not None and \
                os.path.basename(mod.path) in self.basenames:
            return True
        if self.dirnames is not None and os.path.basename(
                os.path.dirname(mod.path)) in self.dirnames:
            return True
        if self.basenames is not None or self.dirnames is not None:
            return False
        return self.all_source


class _FileRuleCtx:
    """Shared resolution context: doc text and event-registry keys are
    resolved NEXT TO the checked file first (self-contained fixtures),
    else from the repo — cached per path."""

    def __init__(self, repo):
        self.repo = repo
        self._doc_cache = {}
        self._keys_cache = {}

    def _read_adjacent(self, path, rel, repo_rel=None):
        """Text of a collaborator file: the copy NEXT TO the checked
        file wins (self-contained fixtures), else the repo's canonical
        location (``repo_rel``, defaulting to ``rel`` off the repo
        root).  ONE resolution helper — the doc, telemetry-overview
        and event-registry lookups all ride it (review finding: three
        hand-rolled copies of the same fallback)."""
        cand = os.path.join(os.path.dirname(path), *rel)
        if not os.path.exists(cand) and self.repo:
            cand = os.path.join(self.repo, *(repo_rel or rel))
        if not os.path.exists(cand):
            return None
        try:
            with open(cand, encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None

    def doc_text(self, path):
        key = os.path.dirname(path)
        if key not in self._doc_cache:
            self._doc_cache[key] = self._read_adjacent(
                path, ("docs", "OBSERVABILITY.md"))
        return self._doc_cache[key]

    def telemetry_text(self, path):
        return self._read_adjacent(path, ("telemetry.py",),
                                   ("ra_tpu", "telemetry.py"))

    def registry_keys(self, path):
        """Keys of blackbox.EVENT_REGISTRY (adjacent-first)."""
        key = os.path.dirname(path)
        if key in self._keys_cache:
            return self._keys_cache[key]
        out = None
        src = self._read_adjacent(path, ("blackbox.py",),
                                  ("ra_tpu", "blackbox.py"))
        if src is not None:
            try:
                tree = ast.parse(src)
            except SyntaxError:
                tree = None
            if tree is not None:
                for node in tree.body:
                    if isinstance(node, ast.Assign) and \
                            len(node.targets) == 1 and \
                            isinstance(node.targets[0], ast.Name) and \
                            node.targets[0].id == "EVENT_REGISTRY" and \
                            isinstance(node.value, ast.Dict):
                        out = {k.value for k in node.value.keys
                               if isinstance(k, ast.Constant)
                               and isinstance(k.value, str)}
        self._keys_cache[key] = out
        return out


def _check_field_registry(mod, ctx):
    """RA05 — the field-group registry contract (metrics.py): a counter
    field FIELD_REGISTRY does not list escapes the registry parity
    test, and one docs/OBSERVABILITY.md does not name is a number
    nobody can interpret — both flagged at the definition site."""
    out = []
    doc_text = ctx.doc_text(mod.path)
    groups = {}
    registry_names = set()
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        name = node.targets[0].id
        if name.endswith("_FIELDS") and isinstance(node.value, ast.Tuple):
            fields = [e.value for e in node.value.elts
                      if isinstance(e, ast.Constant)
                      and isinstance(e.value, str)]
            groups[name] = (node, fields)
        elif name == "FIELD_REGISTRY" and isinstance(node.value, ast.Dict):
            for v in node.value.values:
                if isinstance(v, ast.Name):
                    registry_names.add(v.id)
    for name, (node, fields) in groups.items():
        if name not in registry_names:
            out.append(Finding(
                mod.path, node.lineno, "RA05",
                f"counter-field tuple {name} is not listed in "
                "FIELD_REGISTRY; the registry parity test cannot "
                "cover it"))
        if doc_text is not None:
            missing = [f for f in fields if f"`{f}`" not in doc_text]
            if missing:
                out.append(Finding(
                    mod.path, node.lineno, "RA05",
                    f"{name} fields undocumented in "
                    f"docs/OBSERVABILITY.md: {missing[:6]}"))
    return out


def _check_event_registry_use(mod, ctx):
    """RA06 (emit half) — every string-constant event type passed to
    the recorder (record(...)/blackbox.record/RECORDER.record) or a
    module-level tracer site (trace.span/trace.phase_span) must be a
    blackbox.EVENT_REGISTRY key.  Tracer OBJECT spans (t.span) are
    exempt — the registry governs the repo's own instrumentation
    vocabulary."""
    keys = ctx.registry_keys(mod.path)
    if keys is None:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        via = None
        if isinstance(fn, ast.Name) and fn.id == "record":
            via = "record"
        elif isinstance(fn, ast.Attribute) and fn.attr == "record" and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id in ("blackbox", "RECORDER"):
            via = f"{fn.value.id}.record"
        elif isinstance(fn, ast.Attribute) and \
                fn.attr in ("span", "phase_span") and \
                isinstance(fn.value, ast.Name) and fn.value.id == "trace":
            via = f"trace.{fn.attr}"
        if via is None:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value not in keys:
            out.append(Finding(
                mod.path, node.lineno, "RA06",
                f"event type {arg.value!r} emitted via {via}() is not "
                "in blackbox.EVENT_REGISTRY; register and document it "
                "(docs/OBSERVABILITY.md) or ra_trace/ra_top cannot "
                "interpret it"))
    return out


def _check_event_registry_doc(mod, ctx):
    """RA06 (doc half, blackbox.py only): every EVENT_REGISTRY key must
    be backticked in docs/OBSERVABILITY.md."""
    out = []
    doc_text = ctx.doc_text(mod.path)
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "EVENT_REGISTRY" and \
                isinstance(node.value, ast.Dict):
            keys = [k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)]
            if doc_text is not None:
                missing = [k for k in keys if f"`{k}`" not in doc_text]
                if missing:
                    out.append(Finding(
                        mod.path, node.lineno, "RA06",
                        "EVENT_REGISTRY keys undocumented in "
                        f"docs/OBSERVABILITY.md: {missing[:6]}"))
    return out


def _tunable_knobs(tree):
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "TUNABLE_KNOBS" and \
                isinstance(node.value, ast.Tuple):
            return [(node, e.value) for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)]
    return []


def _check_autotune_contract(mod, ctx):
    """RA07 — the autotuner contract (autotune.py, ISSUE 9): every
    TUNABLE_KNOBS knob stamped in the engine_pipeline overview
    (telemetry.py next to the file, else the repo's) and documented;
    a knob-mutating function without a registered record(...) event is
    a silent knob turn.  The tick-path no-host-sync half rides the
    RA04 closure gate."""
    out = []
    tree = mod.tree
    path = mod.path
    doc_text = ctx.doc_text(path)
    keys = ctx.registry_keys(path)
    knobs = _tunable_knobs(tree)
    knob_names = {k for _n, k in knobs}
    tel_text = ctx.telemetry_text(path)
    for node, knob in knobs:
        if tel_text is not None and f'"{knob}"' not in tel_text \
                and f"'{knob}'" not in tel_text:
            out.append(Finding(
                path, node.lineno, "RA07",
                f"tunable knob {knob!r} is not stamped in the "
                "engine_pipeline overview (telemetry.py engine "
                "source); a knob the overview does not carry turns "
                "invisibly"))
        if doc_text is not None and f"`{knob}`" not in doc_text:
            out.append(Finding(
                path, node.lineno, "RA07",
                f"tunable knob {knob!r} undocumented in "
                "docs/OBSERVABILITY.md"))
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        mutates = None
        for sub in ast.walk(node):
            targets = []
            if isinstance(sub, ast.Assign):
                targets = sub.targets
            elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                targets = [sub.target]
            for t in targets:
                if isinstance(t, ast.Subscript):
                    base = t.value
                    name = base.attr if isinstance(base, ast.Attribute) \
                        else base.id if isinstance(base, ast.Name) \
                        else None
                    if name == "knobs":
                        mutates = sub
                elif isinstance(t, ast.Attribute) and \
                        t.attr in knob_names:
                    mutates = sub
        if mutates is None:
            continue
        recorded = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and sub.args and \
                    isinstance(sub.args[0], ast.Constant) and \
                    isinstance(sub.args[0].value, str):
                fn = sub.func
                name = fn.id if isinstance(fn, ast.Name) else \
                    fn.attr if isinstance(fn, ast.Attribute) else None
                if name == "record" and \
                        (keys is None or sub.args[0].value in keys):
                    recorded = True
        if not recorded:
            out.append(Finding(
                path, mutates.lineno, "RA07",
                f"{node.name}() mutates an autotuner knob without "
                "emitting a registered record(...) event — silent "
                "knob turns are unreconstructable (register the "
                "decision in EVENT_REGISTRY)"))
    return out


#: control-plane calls whose presence makes a While loop a RETRY loop
#: (RA16): commit/query submission, reliable RPC, and pacing sleeps —
#: the verbs a placement/failover escalation loop is built from
_RA16_RETRY_CALLS = frozenset({
    "process_command", "consistent_query", "local_query", "node_call",
    "reliable_node_call", "send_rpc", "sleep", "attempt"})
_RA16_BOUND = ("deadline", "attempt", "tries", "remaining", "budget",
               "retry", "giveup")


def _ra16_local_walk(root):
    """Nodes of ``root`` excluding nested function/lambda bodies (each
    function is judged exactly once, against ITS loops)."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))


def _ra16_idents(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _ra16_has_bound(name_iter):
    return any(b in n.lower() for n in name_iter for b in _RA16_BOUND)


def _check_retry_bounds(mod, ctx):
    """RA16 — no silent infinite retry in the placement/failover
    control plane: a While loop that submits commands / reliable RPCs
    / pacing sleeps must (a) carry deadline-or-attempt bound evidence
    (bound names in the loop test, or a bound-guarded break/raise in
    the body) and (b) live in a function that emits a REGISTERED
    ``record(...)`` event — the give-up a post-mortem can grep for.
    An unbounded escalation loop against a dead peer is exactly how a
    failover wedges forever with nothing in the flight recorder."""
    keys = ctx.registry_keys(mod.path) or set()
    out = []
    funcs = [n for n in ast.walk(mod.tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in funcs:
        gives_up = False
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) and sub.args and \
                    isinstance(sub.args[0], ast.Constant) and \
                    sub.args[0].value in keys:
                f = sub.func
                name = f.id if isinstance(f, ast.Name) else \
                    f.attr if isinstance(f, ast.Attribute) else None
                if name == "record":
                    gives_up = True
                    break
        for loop in _ra16_local_walk(fn):
            if not isinstance(loop, ast.While):
                continue
            retry = None
            for sub in ast.walk(loop):
                if isinstance(sub, ast.Call):
                    f = sub.func
                    name = f.id if isinstance(f, ast.Name) else \
                        f.attr if isinstance(f, ast.Attribute) else None
                    if name in _RA16_RETRY_CALLS:
                        retry = name
                        break
            if retry is None:
                continue
            bounded = _ra16_has_bound(_ra16_idents(loop.test))
            if not bounded:
                for sub in ast.walk(loop):
                    if isinstance(sub, ast.If) and \
                            _ra16_has_bound(_ra16_idents(sub.test)) and \
                            any(isinstance(s, (ast.Break, ast.Raise,
                                               ast.Return))
                                for b in sub.body for s in ast.walk(b)):
                        bounded = True
                        break
            if not bounded:
                out.append(Finding(
                    mod.path, loop.lineno, "RA16",
                    f"{fn.name}(): retry loop around {retry}() has no "
                    "deadline/bounded-attempt evidence (no bound name "
                    "in the loop test, no bound-guarded break/raise) — "
                    "an unreachable peer wedges this control-plane "
                    "loop forever"))
            elif not gives_up:
                out.append(Finding(
                    mod.path, loop.lineno, "RA16",
                    f"{fn.name}(): bounded retry loop around "
                    f"{retry}() never emits a registered record(...) "
                    "give-up event — exhaustion is invisible to the "
                    "flight recorder (register one in EVENT_REGISTRY "
                    "and emit it on the give-up path)"))
    return out


def _check_rpc_deadlines(mod, ctx):
    """RA16 (ISSUE 19 extension) — every control-plane RPC call site
    in the placement package must carry an EXPLICIT deadline: a
    ``node_call``/``reliable_node_call`` without a timeout=/deadline
    keyword rides the callee's default budget, which is invisible at
    the call site — the escalation loop that owns the call can no
    longer reason about its own deadline arithmetic (a 60 s hidden
    default inside a 10 s commit window is how a 'bounded' failover
    overshoots its bound)."""
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else \
            f.attr if isinstance(f, ast.Attribute) else None
        if name not in ("node_call", "reliable_node_call"):
            continue
        if any(kw.arg and ("timeout" in kw.arg.lower()
                           or "deadline" in kw.arg.lower())
               for kw in node.keywords):
            continue
        out.append(Finding(
            mod.path, node.lineno, "RA16",
            f"{name}() call site without an explicit timeout=/deadline "
            "keyword — placement-package RPC calls must state their "
            "own deadline budget (a hidden callee default breaks the "
            "caller's deadline arithmetic)"))
    return out


FILE_RULES = [
    FileRule("RA05", _check_field_registry, basenames={"metrics.py"}),
    FileRule("RA06", _check_event_registry_use, all_source=True),
    FileRule("RA06", _check_event_registry_doc,
             basenames={"blackbox.py"}),
    FileRule("RA07", _check_autotune_contract,
             basenames={"autotune.py"}),
    FileRule("RA16", _check_retry_bounds, dirnames={"placement"}),
    FileRule("RA16", _check_rpc_deadlines, dirnames={"placement"}),
]


def evaluate_file_rules(idx, repo=None):
    """RAW findings from the declarative per-file rules over every
    indexed (non-test) module."""
    ctx = _FileRuleCtx(repo)
    out = []
    for mod in idx.by_path.values():
        for rule in FILE_RULES:
            if rule.matches(mod):
                out.extend(rule.check(mod, ctx))
    uniq = {}
    for f in out:
        uniq.setdefault(f.key(), f)
    return list(uniq.values())
