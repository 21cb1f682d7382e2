"""Self-contained static gate — the dialyzer/xref/elvis role of the
reference's CI (/root/reference/rebar.config:30-44), implemented over
the stdlib ``ast`` because this image ships no ruff/mypy/flake8 and
installing tools is off the table.

Since ISSUE 14 the closure-gated rules are evaluated by the
whole-program engine in ``tools/analyzer/`` (AST index + CROSS-MODULE
call graph): a host sync or per-entry pickle moved into a helper one
file away no longer escapes its gate.  Since ISSUE 15 the engine also
gates the JIT PLANE (RA13 trace hazards / RA14 donation lifetime /
RA15 pytree schema, ``tools/analyzer/jitplane.py``) and evaluates the
per-file registry rules (RA05/RA06/RA07, and since ISSUE 17 the
RA16 placement retry-bound rule) as declarative FILE_RULES in
``tools/analyzer/rules.py``.  This module keeps the CLI and output
contract (``path:line: CODE msg`` + ``lint: N files, M findings``)
and the cheap generic checks (syntax/F/B/E/W + RA01/RA03); the engine
owns every other rule plus the suppression audit.

Checks (cheap, high-signal, zero-config):

  syntax        file must parse
  F401          module-level import never referenced (``__init__.py``
                re-export files and ``# noqa`` lines exempt)
  B006          mutable default argument (list/dict/set literals or
                constructors)
  E722          bare ``except:``
  F631          assert on a non-empty tuple literal (always true)
  F632          ``is``/``is not`` comparison against a str/number literal
  F541          f-string without any placeholder
  F601          duplicate constant key in a dict literal
  F811          redefinition of a function/class in the same scope
                (property setters/overloads exempt)
  W101          unreachable statement after return/raise/break/continue
  RA01          (api.py only) node-lifecycle verbs must ride the
                reliable control-plane RPC layer (transport/rpc.py):
                a direct one-shot `.send(...)`/`.remote_call(...)`
                inside a lifecycle function is the silent-loss bug
                class ISSUE 2 removed — route through node_call
  RA02          (engine lockstep.py/durable.py) no `np.asarray(...)`/
                `.item()` host syncs anywhere in the CROSS-MODULE
                transitive call closure of the step hot-loop functions
                (step/_step/submit/superstep/submit_block/...) — a
                forced device sync there serializes the XLA pipeline;
                documented readback points carry an `# ra02-ok: <why>`
                line comment
  RA03          (files in a `log/` directory only) no swallow-only
                `except OSError:`/`except Exception:` (body is just
                `pass`) around durability-bearing I/O calls — a
                silently eaten disk error is the confirmed-but-not-
                durable bug class ISSUE 4 removed; audited sites carry
                `# ra03-ok: <why>` (plus a DISK_FAULT_FIELDS counter)
  RA04          (soak.py measured dispatch loops, telemetry.py
                sampler tick path, blackbox.py recorder emit path,
                autotune.py controller tick path, lockstep.py driver
                _observe_reads() and poll() — non-blocking by
                contract: they convert only readbacks that
                is_ready()) no blocking device->host
                syncs — block_until_ready/.item()/np.asarray/
                committed_total — anywhere in the cross-module closure;
                window-boundary syncs carry `# ra04-ok: <why>`.
                RA02/RA04 are one allowlist FAMILY: a line two closures
                reach carries one documented tag, either code's
  RA05          (metrics.py only) every module-level `*_FIELDS` tuple
                must be in FIELD_REGISTRY and every field documented in
                docs/OBSERVABILITY.md
  RA06          (repo source, tests exempt) every trace/flight-recorder
                event type emitted anywhere must be a key of
                blackbox.EVENT_REGISTRY, and (blackbox.py) every
                registry key documented in docs/OBSERVABILITY.md
  RA07          (autotune.py only) every TUNABLE_KNOBS knob stamped in
                the engine_pipeline overview + documented; every
                knob-mutating function emits a registered record(...)
                event — no silent knob turns
  RA08          (ingress coalesce.py offer/pop_block, mesh.py
                ingress_submit_wave) the block-build hot path stays
                vectorized across its whole cross-module closure: no
                per-session Python loops, no dict allocation;
                `# ra08-ok: <why>` allowlists (family with RA09)
  RA09          (files in a `wire/` directory) the reader sweep path:
                zero per-frame/per-command Python across the closure;
                per-CONNECTION work carries `# ra09-ok: <why>`
  RA10          (classic replication hot paths: tcp.py _send_items,
                log/durable.py write/append_batch/_put_batch,
                core/server.py _leader_aer_reply/_evaluate_quorum) no
                per-entry pickle/encode_command and no per-entry WAL
                submit/fsync inside loops, including encodes moved
                into helpers (cross-module resolved);
                `# ra10-ok: <why>` allowlists deliberate singles
  RA11          (package code, tests exempt) lock-order cycles: the
                analyzer harvests `with self._lock:` acquisitions
                (threading.Lock/RLock/Condition attributes, plus
                `# ra11-lock: Class.attr` for dynamically passed
                locks), builds the global acquisition-order graph over
                the cross-module call closure, and flags every edge on
                a cycle — the ABBA deadlock class the PR 13 review
                caught by hand (`log/durable.py` _lock vs _io_lock,
                io-then-log is the documented order; INTERNALS §15).
                `# ra11-ok: <why>` allowlists a reviewed edge
  RA12          (package code, tests exempt) thread roles: functions
                reachable from `threading.Thread(target=...)` spawn
                sites run on WORKER threads and must not touch the
                device — jax.*/jnp.*/lax.* calls, device_put,
                block_until_ready — the PR 11 mesh deadlock (an encode
                worker enqueuing multi-device work against an
                in-flight pjit), as a lint.  Host materialization
                (np.asarray of ready values, copy_to_host_async) is
                the sanctioned pattern; deliberate device ops carry
                `# ra12-ok: <why>` naming the host-materialized inputs
  RA13          (package code, tests exempt) trace hazards: inside the
                harvested TRACED closures (functions reaching jax.jit/
                pjit entry points and lax.scan/cond/while_loop bodies,
                incl. through the _build_jit-style wrapper's fn param
                and subclass overrides of resolved methods), no Python
                `if`/`while`/`assert` on tracer-typed values, no
                host-world calls (time.*/random.*/print/open, np.* on
                traced values), no `.item()`/float()/int()/bool()
                casts of traced values.  Positional params are tracers;
                keyword-only params and static_argnames are config.
                The sanctioned cond_concrete-style concreteness probe
                carries `# ra13-ok: <why>`
  RA14          (package code, tests exempt) donation lifetime: at a
                call site of a donation-enabled jitted callable
                (jax.jit(..., donate_argnums=...) — direct, or via a
                factory like _build_jit), reading the donated argument
                AFTER the call without rebinding is flagged (donated
                buffers are invalidated); and a NamedTuple pytree
                construction passing ONE buffer binding as two leaves
                (or splatting it across all leaves) is the PR 6
                "donate same buffer twice" bug as a rule.
                `# ra14-ok: <why>` allowlists
  RA15          (package code, tests exempt) pytree/sharding/checkpoint
                schema: the state schema derives from the NamedTuple
                class annotating state_shardings' state param; (a)
                every field must be covered by the shardings dispatch
                (generic `._fields` iteration or by name; stale
                by-name arms flagged), (b) the schema module's
                CHECKPOINT_FIELD_DEFAULTS registry must name every
                field (and nothing else) and restore() must consult
                it — forward-compat: an old archive restores with
                declared defaults instead of stranding a durable dir,
                (c) every staged superstep-block key
                (shardings.get("n_new")) must exist in
                superstep_block_shardings.  `# ra15-ok: <why>`
  RA16          (files in a `placement/` directory only) retry/
                escalation loops in the failover control plane: a
                While loop around process_command / consistent_query /
                reliable RPC / pacing sleep must carry deadline-or-
                bounded-attempt evidence (bound name in the loop test,
                or a bound-guarded break/raise) AND live in a function
                that emits a registered `record(...)` give-up event —
                an unbounded escalation loop against a dead peer is
                how a failover wedges forever with nothing in the
                flight recorder.  `# ra16-ok: <why>` allowlists
  AUDIT         every `raNN-ok` comment tag on a line its rule family
                no longer flags is itself an error — allowlists can't
                rot (tags inside string literals are ignored:
                suppressions are COMMENTS, tokenize decides)

Usage::

  python tools/lint.py [paths...]   # defaults to the repo source roots
  python tools/lint.py --changed    # only files differing from HEAD
  python tools/lint.py --json       # machine-readable findings
  python tools/lint.py --report     # grouped human report

Exits nonzero with one line per finding.
"""
from __future__ import annotations

import ast
import os
import subprocess
import sys
import time
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from analyzer import (  # noqa: E402 (path bootstrap above)
    apply_suppressions, audit_suppressions, run_analysis)
from analyzer.report import render_json, render_report  # noqa: E402
from analyzer.rules import Finding  # noqa: E402

DEFAULT_TARGETS = ["ra_tpu", "tools", "tests", "chip_smoke.py"]

_MUTABLE_CALLS = {"list", "dict", "set", "bytearray", "deque",
                  "defaultdict", "OrderedDict", "Counter"}


def _is_mutable_default(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        fn = node.func
        name = fn.id if isinstance(fn, ast.Name) else \
            fn.attr if isinstance(fn, ast.Attribute) else None
        return name in _MUTABLE_CALLS
    return False


def _decorator_exempts_redef(dec: ast.AST) -> bool:
    # @x.setter / @x.deleter / @overload / @singledispatchmethod.register
    if isinstance(dec, ast.Attribute):
        return True
    if isinstance(dec, ast.Name) and dec.id in ("overload",):
        return True
    if isinstance(dec, ast.Call):
        return _decorator_exempts_redef(dec.func)
    return False


_TERMINAL = (ast.Return, ast.Raise, ast.Break, ast.Continue)

#: api-layer node-LIFECYCLE verbs: cross-node start/restart/stop/delete
#: must ride the reliable RPC layer (at-most-once retries, typed
#: failures) — a raw one-shot transport call from any of these is the
#: race that loses a control-plane call to a restarting peer
_LIFECYCLE_VERBS = frozenset({
    "node_call", "start_cluster", "start_server", "restart_server",
    "stop_server", "force_delete_server",
})
_ONE_SHOT_SENDS = frozenset({"send", "remote_call"})


#: RA03 — durability-bearing I/O calls: an exception from one of these
#: inside the log layer carries a durability verdict and must never be
#: swallowed bare (fsyncgate: a confirmed write whose fsync error was
#: eaten is silent data loss)
_DURABILITY_CALLS = frozenset({"fsync", "fdatasync", "pwrite", "write",
                               "write_batch", "sync"})
_SWALLOWED_EXCS = frozenset({"OSError", "Exception", "IOError",
                             "EnvironmentError"})


def _handler_names(handler: ast.ExceptHandler) -> set:
    t = handler.type
    names = []
    if isinstance(t, ast.Name):
        names = [t.id]
    elif isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    return set(names)


def _check_log_io_swallow(tree: ast.Module, err) -> None:
    """RA03: in log-layer files, forbid pass-only except OSError/
    Exception handlers whose try body performs durability-bearing I/O
    (allowlist via `# ra03-ok:` on the except line)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        io_calls = set()
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    fn = sub.func
                    name = fn.attr if isinstance(fn, ast.Attribute) \
                        else fn.id if isinstance(fn, ast.Name) else None
                    if name in _DURABILITY_CALLS:
                        io_calls.add(name)
        if not io_calls:
            continue
        for handler in node.handlers:
            if not (_handler_names(handler) & _SWALLOWED_EXCS):
                continue
            body = handler.body
            if len(body) == 1 and isinstance(body[0], ast.Pass):
                err(handler, "RA03",
                    "swallow-only except around durability I/O "
                    f"({'/'.join(sorted(io_calls))}); route the error "
                    "through the degradation ladder or mark the line "
                    "'# ra03-ok: why' with a DISK_FAULT_FIELDS counter")


def _check_lifecycle_rpc(tree: ast.Module, err) -> None:
    """RA01: inside lifecycle verbs, forbid direct one-shot transport
    calls (they must go through the reliable RPC layer)."""
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in _LIFECYCLE_VERBS:
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _ONE_SHOT_SENDS:
                err(sub, "RA01",
                    f"lifecycle verb {node.name}() uses one-shot "
                    f".{sub.func.attr}(); route through the reliable "
                    "RPC layer (transport/rpc.py)")


def check_file(path: str) -> list:
    """RAW per-file findings (suppressions applied by the caller)."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError as exc:
        # the historical output contract spells this "path:N: syntax:
        # msg" — the colon rides in the code so Finding.render keeps it
        return [Finding(path, exc.lineno or 0, "syntax:",
                        str(exc.msg))]
    findings: list = []
    # format specs (the ':03d' in f"{i:03d}") are themselves JoinedStr
    # nodes with constant-only parts — never F541 candidates
    spec_ids = {id(n.format_spec) for n in ast.walk(tree)
                if isinstance(n, ast.FormattedValue)
                and n.format_spec is not None}

    def err(node: ast.AST, code: str, msg: str) -> None:
        findings.append(Finding(path, getattr(node, "lineno", 0),
                                code, msg))

    if os.path.basename(path) == "api.py":
        _check_lifecycle_rpc(tree, err)
    if os.path.basename(os.path.dirname(path)) == "log":
        _check_log_io_swallow(tree, err)
    # RA05 (field registry), RA06 (event registry) and RA07 (autotuner
    # knob contract) are evaluated by the analyzer engine's declarative
    # FILE_RULES (tools/analyzer/rules.py) since ISSUE 15 — one engine
    # owns every rule; this module keeps the cheap generic checks.

    # -- F401: unused module-level imports ------------------------------
    if os.path.basename(path) != "__init__.py":
        used = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                used.add(node.id)
            elif isinstance(node, ast.Attribute):
                pass  # base resolves through a Name anyway
        # names referenced in __all__ strings count as used
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__":
                        for elt in ast.walk(node.value):
                            if isinstance(elt, ast.Constant) and \
                                    isinstance(elt.value, str):
                                used.add(elt.value)
        for node in tree.body:
            if isinstance(node, ast.ImportFrom) and \
                    node.module == "__future__":
                continue
            if isinstance(node, ast.Import):
                for alias in node.names:
                    bound = (alias.asname or
                             alias.name.split(".")[0])
                    if bound not in used:
                        err(node, "F401",
                            f"'{alias.name}' imported but unused")
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    if bound not in used:
                        err(node, "F401",
                            f"'{alias.name}' imported but unused")

    for node in ast.walk(tree):
        # -- B006 mutable defaults --------------------------------------
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in list(node.args.defaults) + \
                    [d for d in node.args.kw_defaults if d is not None]:
                if _is_mutable_default(default):
                    err(default, "B006",
                        f"mutable default argument in {node.name}()")
        # -- E722 bare except -------------------------------------------
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            err(node, "E722", "bare 'except:'")
        # -- F631 assert on tuple ---------------------------------------
        if isinstance(node, ast.Assert) and \
                isinstance(node.test, ast.Tuple) and node.test.elts:
            err(node, "F631", "assert on a non-empty tuple is always true")
        # -- F632 is-literal --------------------------------------------
        if isinstance(node, ast.Compare):
            for op, comp in zip(node.ops, node.comparators):
                if isinstance(op, (ast.Is, ast.IsNot)) and \
                        isinstance(comp, ast.Constant) and \
                        isinstance(comp.value, (str, int, float, bytes)) \
                        and not isinstance(comp.value, bool):
                    err(node, "F632",
                        "'is' comparison with a literal; use ==")
        # -- F541 placeholder-less f-string -----------------------------
        if isinstance(node, ast.JoinedStr) and id(node) not in spec_ids \
                and not any(isinstance(v, ast.FormattedValue)
                            for v in node.values):
            err(node, "F541", "f-string without placeholders")
        # -- F601 duplicate dict keys -----------------------------------
        if isinstance(node, ast.Dict):
            seen: set = set()
            for key in node.keys:
                if isinstance(key, ast.Constant):
                    try:
                        if key.value in seen:
                            err(key, "F601",
                                f"duplicate dict key {key.value!r}")
                        seen.add(key.value)
                    except TypeError:
                        pass
        # -- F811 redefinition in one scope + W101 unreachable ----------
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            defs: dict = {}
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    decs = getattr(stmt, "decorator_list", [])
                    if any(_decorator_exempts_redef(d) for d in decs):
                        continue
                    if stmt.name in defs:
                        err(stmt, "F811",
                            f"redefinition of '{stmt.name}' "
                            f"(first at line {defs[stmt.name]})")
                    defs[stmt.name] = stmt.lineno
        for field in ("body", "orelse", "finalbody"):
            body = getattr(node, field, None)
            if isinstance(body, list):
                for i, stmt in enumerate(body[:-1]):
                    if isinstance(stmt, _TERMINAL):
                        err(body[i + 1], "W101",
                            "unreachable code after "
                            f"{type(stmt).__name__.lower()}")
                        break
    return findings


def _collect_files(targets: list, missing: list = None) -> list:
    files: list = []
    for t in targets:
        p = os.path.join(REPO, t) if not os.path.isabs(t) else t
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".pytest_cache")]
                files += [os.path.join(root, n) for n in names
                          if n.endswith(".py")]
        elif p.endswith(".py") and os.path.exists(p):
            files.append(p)
        elif missing is not None:
            # a typo'd/nonexistent explicit target must fail LOUDLY —
            # a gate that silently lints nothing reports green on a
            # misconfiguration (review finding)
            missing.append(t)
    return sorted(set(files))


def _default_source_files() -> list:
    """The repo's source roots minus tests — what single-file
    invocations index so cross-module edges resolve the same way the
    full run resolves them."""
    return _collect_files(["ra_tpu", "tools", "chip_smoke.py"])


def _changed_targets() -> Optional[list]:
    """Files differing from HEAD (staged, unstaged, untracked) — the
    fast local loop (`tools/lint.py --changed`).  Returns None when
    git itself fails: silently widening to the full default target set
    would hand the user findings for files they never touched (the
    same silent-misconfiguration class as a typo'd target)."""
    names: set = set()
    for cmd in (["git", "-C", REPO, "diff", "--name-only", "HEAD"],
                ["git", "-C", REPO, "ls-files", "--others",
                 "--exclude-standard"]):
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if out.returncode != 0:
            return None
        names.update(x.strip() for x in out.stdout.splitlines()
                     if x.strip())
    return sorted(n for n in names if n.endswith(".py")
                  and os.path.exists(os.path.join(REPO, n)))


def main(argv: list) -> int:
    flags = {a for a in argv if a.startswith("--")}
    paths = [a for a in argv if not a.startswith("--")]
    unknown = flags - {"--json", "--report", "--changed"}
    if unknown:
        print(f"lint: unknown flags {sorted(unknown)}", file=sys.stderr)
        return 2
    if "--changed" in flags:
        if paths:
            # explicit paths would be silently discarded — a user
            # scoping the fast loop to a subtree must not get results
            # for unrelated files with no warning
            print("lint: --changed takes no explicit targets",
                  file=sys.stderr)
            return 2
        targets = _changed_targets()
        if targets is None:
            print("lint: --changed could not read the git diff; "
                  "run without --changed for a full pass",
                  file=sys.stderr)
            return 2
        if not targets:
            print("lint: 0 files, 0 findings")
            return 0
    else:
        targets = paths or DEFAULT_TARGETS
    t0 = time.monotonic()
    missing: list = []
    files = _collect_files(targets, missing)
    if missing:
        for m in missing:
            print(f"lint: no such target: {m}", file=sys.stderr)
        return 2
    raw: list = []
    for f in files:
        raw += check_file(f)
    engine_raw, _idx = run_analysis(
        files, repo=REPO, default_sources=_default_source_files())
    seen = {x.key() for x in raw}
    engine_raw = [x for x in engine_raw if x.key() not in seen]
    # the engine evaluates the WHOLE indexed program so a scoped run
    # (--changed, one file) produces the same raw pool as the full run
    # — that pool feeds the audit, or a tag in a changed helper would
    # read as stale whenever its closure ROOT didn't change (review
    # finding).  REPORT only findings attributable to the targets: the
    # finding's own file, or a rule root that reaches it (so the
    # cross-module escape rooted in a linted file still surfaces
    # wherever the construct lives, but linting fixture A never
    # reports sibling B's independent findings).
    target_set = set(files)
    raw_full = raw + engine_raw
    raw += [x for x in engine_raw
            if x.path in target_set
            or any(r in target_set for r in x.roots)]
    active, suppressed = apply_suppressions(raw)
    active += audit_suppressions(files, raw_full)
    active.sort(key=lambda f: (f.path, f.line, f.code))
    elapsed = time.monotonic() - t0
    if "--json" in flags:
        print(render_json(files, active, suppressed, elapsed))
    elif "--report" in flags:
        print(render_report(files, active, suppressed, elapsed,
                            repo=REPO))
    else:
        for f in active:
            print(f.render())
        print(f"lint: {len(files)} files, {len(active)} findings")
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
