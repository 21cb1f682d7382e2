"""Static-analysis gate — the dialyzer/xref/elvis role of the
reference's CI (/root/reference/rebar.config:30-44).  The image ships
no ruff/mypy, so tools/lint.py implements the checks over stdlib ast;
this test keeps the tree clean and the checker honest."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "lint.py")


def run_lint(*args):
    return subprocess.run([sys.executable, LINT, *args],
                          capture_output=True, text=True, timeout=120)


@pytest.fixture(scope="session")
def full_lint():
    """ONE whole-repo pass for every "module is clean" pin below.  Each
    lint start rebuilds the whole-program index (~5-8 s), and the pins
    used to start one per module: ~45 starts, a third of the tier-1
    budget, for findings the full pass already holds."""
    r = run_lint("--json")
    assert r.returncode in (0, 1), r.stdout + r.stderr
    return json.loads(r.stdout)


def findings_in(full, *targets):
    """The full pass's active findings that live in ``targets`` (repo
    files or directories), rendered as lint prints them — so a pin
    still asserts that its rule's code is absent from its module's
    output, and a regression names the rule."""
    paths = [os.path.join(REPO, *t.split("/")) for t in targets]
    return "\n".join(
        f"{f['path']}:{f['line']}: {f['code']} {f['msg']}"
        for f in full["findings"]
        if any(f["path"] == p or f["path"].startswith(p + os.sep)
               for p in paths))


def test_repo_is_lint_clean(full_lint):
    assert full_lint["findings"] == [], full_lint["findings"]


def test_checker_detects_each_rule(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import os
        import sys

        print(sys.argv)

        def f(x=[]):
            try:
                pass
            except:
                pass
            assert (x, "oops")
            if x is "lit":
                return f"nothing"
            return {1: "a", 1: "b"}
            print("unreachable")

        def f():
            pass
    """))
    r = run_lint(str(bad))
    out = r.stdout
    assert r.returncode == 1
    for code in ("F401", "B006", "E722", "F631", "F632", "F541",
                 "F601", "F811", "W101"):
        assert code in out, (code, out)
    # 'sys' is used; only 'os' may be flagged unused
    assert "'sys' imported but unused" not in out


def test_checker_forbids_one_shot_sends_in_lifecycle_verbs(tmp_path):
    """RA01: api-layer lifecycle verbs must ride the reliable RPC layer
    (transport/rpc.py) — a direct router.send/remote_call from one is
    the silent-loss race ISSUE 2 removed.  Applies to files named
    api.py only; non-lifecycle functions keep their one-shot sends."""
    bad = tmp_path / "api.py"
    bad.write_text(textwrap.dedent("""\
        def stop_server(server_id, router):
            router.send("?", server_id, object())

        def restart_server(server_id, router):
            return router.remote_call(server_id, object())

        def trigger_election(server_id, router):
            router.send("?", server_id, object())  # not a lifecycle verb
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA01") == 2, r.stdout
    assert "stop_server" in r.stdout and "restart_server" in r.stdout
    assert "trigger_election" not in r.stdout
    # the same content under another module name is not gated
    other = tmp_path / "helpers.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA01" not in r.stdout


def test_api_module_is_ra01_clean(full_lint):
    """The real api.py passes the lifecycle-RPC gate (covered by the
    repo-wide run too; pinned separately so a regression names the
    rule)."""
    out = findings_in(full_lint, "ra_tpu/api.py")
    assert "RA01" not in out, out


def test_checker_forbids_host_syncs_in_engine_hot_loop(tmp_path):
    """RA02: np.asarray/.item() inside the engine step hot-loop
    functions force a device->host sync that serializes the XLA
    pipeline.  Applies to files named lockstep.py/durable.py only;
    `# ra02-ok:` allowlists a documented readback point."""
    bad = tmp_path / "lockstep.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        def step(self, n_new):
            host = np.asarray(n_new)
            flag = self.state.term[0].item()
            return host, flag

        def _step(state, n_new):
            ok = np.asarray(n_new)  # ra02-ok: host-provided mask
            return ok

        def overview(self):
            return np.asarray(self.state.commit)  # not a hot-loop fn
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA02") == 2, r.stdout
    assert "np.asarray" in r.stdout and ".item()" in r.stdout
    # the same content under a non-engine module name is not gated
    other = tmp_path / "helpers.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA02" not in r.stdout


def test_engine_modules_are_ra02_clean(full_lint):
    """The real engine hot loop passes the host-sync gate (covered by
    the repo-wide run too; pinned separately so a regression names the
    rule)."""
    for mod in ("lockstep.py", "durable.py"):
        out = findings_in(full_lint, "ra_tpu/engine/" + mod)
        assert "RA02" not in out, (mod, out)


def test_checker_forbids_swallowed_io_errors_in_log_layer(tmp_path):
    """RA03: pass-only except OSError/Exception around durability I/O
    (fsync/pwrite/write/sync) in log/ files is the silent-loss bug
    class ISSUE 4 removed; `# ra03-ok:` allowlists an audited site.
    Applies to files inside a directory named log/ only."""
    logdir = tmp_path / "log"
    logdir.mkdir()
    bad = logdir / "wal.py"
    bad.write_text(textwrap.dedent("""\
        import os

        def flush(fd, buf):
            try:
                os.write(fd, buf)
                os.fsync(fd)
            except OSError:
                pass

        def sync2(io, fd):
            try:
                io.sync(fd, 2)
            except Exception:  # ra03-ok: audited, counter bumped in caller
                pass

        def close_quiet(fd):
            try:
                os.close(fd)       # not durability-bearing: no finding
            except OSError:
                pass

        def handled(fd, buf):
            try:
                os.pwrite(fd, buf, 0)
            except OSError:
                raise RuntimeError("escalate")  # routed, not swallowed
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA03") == 1, r.stdout
    assert ":7:" in r.stdout, r.stdout  # the except line of flush()
    # the same content outside a log/ directory is not gated
    other = tmp_path / "wal.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA03" not in r.stdout


def test_log_layer_is_ra03_clean(full_lint):
    """The real log layer passes the swallowed-IO-error gate (covered
    by the repo-wide run too; pinned separately so a regression names
    the rule)."""
    for mod in ("wal.py", "segment.py", "durable.py", "snapshot.py",
                "faults.py", "memory.py"):
        out = findings_in(full_lint, "ra_tpu/log/" + mod)
        assert "RA03" not in out, (mod, out)


def test_checker_forbids_host_syncs_in_bench_dispatch_loops(tmp_path):
    """RA04: block_until_ready/.item()/np.asarray/committed_total inside
    a soak dispatch loop serializes the measured pipeline (ISSUE 5).
    Applies to files named soak.py only; `# ra04-ok:` allowlists
    window-boundary syncs; loops that dispatch nothing are not gated."""
    bad = tmp_path / "soak.py"
    bad.write_text(textwrap.dedent("""\
        import time
        import numpy as np

        def run(eng, n_new, payloads):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 1.0:
                eng.step(n_new, payloads)
                eng.block_until_ready()
                total = eng.committed_total()
                flag = eng.state.term[0].item()
                host = np.asarray(eng.state.commit)
            return total, flag, host

        def run_windowed(eng, n_new, payloads, rb):
            for _ in range(100):
                eng.superstep(n_new, payloads)
                while len(rb) > 4:
                    np.asarray(rb.popleft())  # ra04-ok: window boundary
            eng.block_until_ready()

        def postprocess(rows):
            # no dispatch in this loop: host-side math is not gated
            out = []
            for r in rows:
                out.append(np.asarray(r).sum().item())
            return out
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 4, r.stdout
    for frag in (".block_until_ready()", ".committed_total()",
                 ".item()", "np.asarray()"):
        assert frag in r.stdout, (frag, r.stdout)
    # the same content under another module name is not gated
    other = tmp_path / "helpers.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA04" not in r.stdout


def test_bench_files_are_ra04_clean(full_lint):
    """The real soak measured loops pass the dispatch-loop sync gate
    (covered by the repo-wide run too; pinned separately so a
    regression names the rule)."""
    out = findings_in(full_lint, "tools/soak.py")
    assert "RA04" not in out, out


def test_checker_false_positive_guards(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(textwrap.dedent("""\
        from __future__ import annotations
        import json  # noqa: F401

        @property
        def x(self):
            return 1

        @x.setter
        def x(self, v):
            pass

        def g(i):
            return f"{i:03d}"
    """))
    r = run_lint(str(ok))
    assert r.returncode == 0, r.stdout


def test_checker_enforces_field_registry(tmp_path):
    """RA05: a counter-field tuple missing from FIELD_REGISTRY, or with
    fields undocumented in docs/OBSERVABILITY.md, is flagged at the
    definition site.  Applies to files named metrics.py only."""
    bad = tmp_path / "metrics.py"
    bad.write_text(textwrap.dedent("""\
        WAL_FIELDS = ("syncs", "batches")

        ORPHAN_FIELDS = ("zz_not_documented_anywhere",)

        FIELD_REGISTRY = {"wal": WAL_FIELDS}
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA05") == 2, r.stdout
    assert "ORPHAN_FIELDS is not listed" in r.stdout
    assert "zz_not_documented_anywhere" in r.stdout
    # WAL_FIELDS is registered and its fields are documented: clean
    assert "WAL_FIELDS" not in r.stdout
    # the same content under another module name is not gated
    other = tmp_path / "helpers.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA05" not in r.stdout


def test_metrics_module_is_ra05_clean(full_lint):
    """The real registry passes the parity gate: every *_FIELDS tuple
    is in FIELD_REGISTRY and documented in docs/OBSERVABILITY.md."""
    out = findings_in(full_lint, "ra_tpu/metrics.py")
    assert "RA05" not in out, out


def test_checker_gates_telemetry_sampler_path(tmp_path):
    """RA04 (sampler extension): blocking syncs inside the telemetry
    sampler's tick-path functions (tick/_start_sample/_harvest) are
    flagged — the sampler rides the dispatch loop, so its tick path
    obeys the same no-host-sync contract as the bench loops.  Applies
    to files named telemetry.py only."""
    bad = tmp_path / "telemetry.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        class S:
            def tick(self):
                self.engine.block_until_ready()
                v = self.handle.item()
                return v

            def _harvest(self):
                host = np.asarray(self.handle)  # ra04-ok: ready-gated
                return host

            def drain(self):
                return np.asarray(self.handle)  # not a tick-path fn
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 2, r.stdout
    assert ".block_until_ready()" in r.stdout and ".item()" in r.stdout
    assert "drain" not in r.stdout
    # the same content under another module name is not gated
    other = tmp_path / "other.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA04" not in r.stdout


def test_telemetry_module_is_ra04_clean(full_lint):
    """The real sampler tick path passes the no-host-sync gate."""
    out = findings_in(full_lint, "ra_tpu/telemetry.py")
    assert "RA04" not in out, out


def test_checker_enforces_event_registry(tmp_path):
    """RA06: an event type emitted via record()/blackbox.record/
    RECORDER.record or a module-level trace.span/trace.phase_span that is
    not a key of blackbox.EVENT_REGISTRY is flagged; Tracer OBJECT
    spans (t.span) and non-constant types are exempt; tests are exempt
    by path."""
    bb = tmp_path / "blackbox.py"
    bb.write_text('EVENT_REGISTRY = {"wal.fsync": "doc"}\n')
    bad = tmp_path / "instrumented.py"
    bad.write_text(textwrap.dedent("""\
        from blackbox import RECORDER, record
        import trace

        def f(t, name):
            record("wal.fsync", ms=1)        # registered: clean
            record("zz.bogus", x=1)          # RA06
            RECORDER.record("zz.worse")      # RA06
            record(name)                     # non-constant: exempt
            with trace.span("zz.span"):      # RA06
                pass
            with t.span("anything"):         # Tracer object: exempt
                pass
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA06") == 3, r.stdout
    assert "zz.bogus" in r.stdout and "zz.worse" in r.stdout
    assert "zz.span" in r.stdout
    # the same content under a tests/ path is exempt
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "helper.py").write_text(bad.read_text())
    (tmp_path / "tests" / "blackbox.py").write_text(bb.read_text())
    r = run_lint(str(tdir / "helper.py"))
    assert "RA06" not in r.stdout, r.stdout


def test_checker_enforces_event_registry_doc_half(tmp_path):
    """RA06 (doc half): blackbox.py's EVENT_REGISTRY keys must be
    backticked in docs/OBSERVABILITY.md (resolved next to the file
    first, like RA05's doc resolution)."""
    d = tmp_path / "docs"
    d.mkdir()
    (d / "OBSERVABILITY.md").write_text("only `wal.fsync` is here\n")
    bb = tmp_path / "blackbox.py"
    bb.write_text('EVENT_REGISTRY = {"wal.fsync": "d", '
                  '"zz.undocumented": "d"}\n')
    r = run_lint(str(bb))
    assert r.returncode == 1
    assert "RA06" in r.stdout and "zz.undocumented" in r.stdout


def test_checker_gates_recorder_emit_path(tmp_path):
    """RA04 extension: host syncs inside blackbox.py's record()
    closure are flagged — the recorder rides dispatch loops."""
    bad = tmp_path / "blackbox.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        EVENT_REGISTRY = {"wal.fsync": "d"}

        class R:
            def record(self, etype, **fields):
                self._stash(fields)
                self.handle.block_until_ready()
                return np.asarray(fields["x"])

            def _stash(self, fields):
                return fields["x"].item()

            def dump(self):
                return np.asarray(self.rings)  # not on the emit path
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 3, r.stdout
    assert "dump" not in r.stdout


def test_checker_enforces_autotune_contract(tmp_path):
    """RA07 (ISSUE 9): TUNABLE_KNOBS must be stamped in the
    engine_pipeline overview (telemetry.py next to the file) and
    documented in docs/OBSERVABILITY.md; a knob-mutating function
    without a registered record(...) event is a silent knob turn.
    Applies to files named autotune.py only."""
    (tmp_path / "telemetry.py").write_text(
        'PIPE = {"superstep_k": 1}\n')
    d = tmp_path / "docs"
    d.mkdir()
    (d / "OBSERVABILITY.md").write_text("`superstep_k` is documented\n")
    (tmp_path / "blackbox.py").write_text(
        'EVENT_REGISTRY = {"tune.decision": "d"}\n')
    bad = tmp_path / "autotune.py"
    bad.write_text(textwrap.dedent("""\
        from blackbox import record

        TUNABLE_KNOBS = ("superstep_k", "zz_ghost_knob")

        class T:
            def good_set(self, v):
                self.knobs["superstep_k"] = v
                record("tune.decision", new=v)

            def silent_set(self, v):
                self.knobs["superstep_k"] = v      # RA07: no event

            def unregistered_set(self, v):
                self.superstep_k = v
                record("zz.not.registered", new=v)  # RA07: bogus type
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    out = r.stdout
    # ghost knob: not stamped in telemetry.py AND not documented
    assert out.count("zz_ghost_knob") == 2, out
    assert "not stamped in the" in out and "undocumented" in out
    assert "silent_set" in out and "unregistered_set" in out
    assert "good_set" not in out
    assert out.count("RA07") == 4, out
    # the same content under another module name is not gated
    other = tmp_path / "controller.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA07" not in r.stdout


def test_checker_gates_autotune_tick_path(tmp_path):
    """RA04 extension: host syncs reachable from the controller's
    tick() closure are flagged — the tuner runs between dispatches."""
    (tmp_path / "telemetry.py").write_text("PIPE = {}\n")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text("nothing\n")
    bad = tmp_path / "autotune.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        class T:
            def tick(self):
                self._decide()
                return self.handle.item()

            def _decide(self):
                return np.asarray(self.state.commit)

            def overview(self):
                return np.asarray(self.rings)  # not on the tick path
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 2, r.stdout
    assert ".item()" in r.stdout and "np.asarray" in r.stdout
    assert "overview" not in r.stdout


def test_autotune_module_is_ra07_and_ra04_clean(full_lint):
    """The real controller passes both gates (covered by the repo-wide
    run too; pinned separately so a regression names the rule)."""
    out = findings_in(full_lint, "ra_tpu/autotune.py")
    assert "RA07" not in out and "RA04" not in out, out


def test_blackbox_module_is_ra06_and_ra04_clean(full_lint):
    """The real recorder and every instrumented module pass the gates
    (covered by the repo-wide run too; pinned so a regression names
    the rule)."""
    out = findings_in(full_lint, "ra_tpu/blackbox.py")
    assert "RA06" not in out and "RA04" not in out, out
    for mod in ("ra_tpu/api.py", "ra_tpu/core/server.py",
                "ra_tpu/log/wal.py", "ra_tpu/transport/rpc.py",
                "ra_tpu/engine/durable.py", "ra_tpu/engine/lockstep.py"):
        out = findings_in(full_lint, mod)
        assert "RA06" not in out, (mod, out)


def test_checker_enforces_coalescer_hot_path(tmp_path):
    """RA08 (ISSUE 10): Python loops and dict allocation inside the
    ingress coalescer's block-build hot path (offer/pop_block + the
    same-module helpers they reach) are flagged; `# ra08-ok:` lines
    and non-hot functions are exempt; other filenames are not gated."""
    import textwrap
    bad = tmp_path / "coalesce.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        class W:
            def offer(self, lanes, payloads, handles):
                for ln in lanes:                      # RA08: loop
                    self.fill[ln] += 1
                meta = {"rows": len(lanes)}           # RA08: dict
                return self._scatter(lanes), meta

            def _scatter(self, lanes):
                return dict(enumerate(lanes))         # RA08: via helper

            def pop_block(self):
                takes = [int(t) for t in self.fill]   # RA08: comp loop
                return takes

            def ready(self):
                # NOT hot: loops here are control-plane work
                return any(f > 0 for f in [1, 2])
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    out = r.stdout
    assert out.count("RA08") == 4, out
    assert "offer()" in out and "pop_block()" in out \
        and "_scatter()" in out
    assert "ready()" not in out
    # allowlisted lines pass
    fixed = bad.read_text() \
        .replace("for ln in lanes:", "for ln in lanes:  # ra08-ok: tiny") \
        .replace('meta = {"rows": len(lanes)}',
                 'meta = {"rows": len(lanes)}  # ra08-ok: once') \
        .replace("return dict(enumerate(lanes))",
                 "return dict(enumerate(lanes))  # ra08-ok: cold") \
        .replace("takes = [int(t) for t in self.fill]",
                 "takes = [int(t) for t in self.fill]  # ra08-ok: k")
    bad.write_text(fixed)
    r = run_lint(str(bad))
    assert "RA08" not in r.stdout, r.stdout
    # the same content under another module name is not gated
    other = tmp_path / "window.py"
    other.write_text(textwrap.dedent("""\
        class W:
            def offer(self, lanes):
                return {ln: 1 for ln in lanes}
    """))
    r = run_lint(str(other))
    assert "RA08" not in r.stdout


@pytest.mark.parametrize("hot", ["pop_rows", "pop_block", "offer"])
def test_checker_gates_every_coalescer_pop(tmp_path, hot):
    """RA08 (ISSUE 26): the flat pop is block-build hot path like the
    dense one and ``offer`` — a per-lane loop in any of them is
    flagged, and the same body under a control-plane name is not."""
    bad = tmp_path / "coalesce.py"
    body = textwrap.dedent("""\
        class W:
            def @NAME@(self):
                rows = [self.buf[n, :t] for n, t in enumerate(self.fill)]
                return rows
    """)
    bad.write_text(body.replace("@NAME@", hot))
    r = run_lint(str(bad))
    assert r.returncode == 1 and r.stdout.count("RA08") == 1, r.stdout
    assert f"{hot}()" in r.stdout
    bad.write_text(body.replace("@NAME@", "overview"))
    assert "RA08" not in run_lint(str(bad)).stdout


def test_flat_block_staging_keys_have_shardings(full_lint):
    """RA15(c) on the real tree (ISSUE 26): the flat block's staged
    keys (rows, row_base, take) are read by the driver and every one
    has its entry in superstep_block_shardings."""
    import ast
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = ast.parse(open(os.path.join(
        root, "ra_tpu", "engine", "lockstep.py")).read())
    read = {n.args[0].value for n in ast.walk(tree)
            if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute) and n.func.attr == "get"
            and isinstance(n.func.value, ast.Attribute)
            and n.func.value.attr == "shardings"}
    assert {"rows", "row_base", "take", "n_new", "payloads"} <= read
    for mod in ("ra_tpu/engine/lockstep.py", "ra_tpu/parallel/mesh.py",
                "ra_tpu/ingress/__init__.py"):
        assert "RA15" not in findings_in(full_lint, mod)
    import jax

    from ra_tpu.parallel.mesh import lane_mesh, superstep_block_shardings
    sh = superstep_block_shardings(lane_mesh(jax.devices()[:1]))
    assert read <= set(sh)
    assert sh["rows"].spec == jax.sharding.PartitionSpec()
    assert sh["row_base"].spec == sh["take"].spec \
        == jax.sharding.PartitionSpec("lanes")


def test_ingress_fields_carry_the_flat_counters():
    """Registry parity for the counters ISSUE 26, 27, 32, 35 and 37 add:
    in INGRESS_FIELDS (so in every plane's counters and the
    Observatory's ``ingress`` source) and documented (RA05 gates the doc
    half)."""
    from ra_tpu.metrics import FIELD_REGISTRY, INGRESS_FIELDS
    assert INGRESS_FIELDS[-8:] == ("flat_blocks", "flat_rows_padded",
                                   "lane_capped_rows", "read_blocks",
                                   "read_served_rows", "read_refused_rows",
                                   "read_zero_blocks", "slow_pumps")
    assert FIELD_REGISTRY["ingress"] is INGRESS_FIELDS
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(root, "docs", "OBSERVABILITY.md")).read()
    for f in INGRESS_FIELDS:
        assert f"| `{f}` |" in doc, f


def test_ingress_coalescer_is_ra08_clean(full_lint):
    """The real coalescer's hot path is loop- and dict-free (covered by
    the repo-wide run too; pinned so a regression names the rule)."""
    out = findings_in(full_lint, "ra_tpu/ingress/coalesce.py")
    assert "RA08" not in out, out


def test_checker_gates_mesh_ingress_pump_path(tmp_path):
    """RA08 (mesh extension, ISSUE 11): per-session Python loops/dict
    allocation in the mesh-side ingress pump path (ingress_submit_wave
    + closure) are flagged; non-pump functions are exempt."""
    bad = tmp_path / "mesh.py"
    bad.write_text(textwrap.dedent("""\
        def ingress_submit_wave(plane, handles, seqnos, payloads):
            for h in handles:                     # RA08: per-session
                plane.touch(h)
            return _meta(handles)

        def _meta(handles):
            return {"rows": len(handles)}         # RA08: via helper

        def lane_mesh(devices):
            # control-plane setup: loops here are fine
            return [d for d in devices]
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA08") == 2, r.stdout
    assert "ingress_submit_wave" in r.stdout and "_meta" in r.stdout
    assert "lane_mesh" not in r.stdout
    other = tmp_path / "pump.py"
    other.write_text(bad.read_text())
    r = run_lint(str(other))
    assert "RA08" not in r.stdout


def test_checker_enforces_wire_sweep_path(tmp_path):
    """RA09 (ISSUE 12): Python loops and dict allocation inside the
    wire reader sweep path (sweep + the same-module helpers it
    reaches) are flagged — per-frame Python there is the RA08 bug
    class extended to the socket path.  `# ra09-ok:` allowlists
    per-CONNECTION work; non-sweep functions and other directories
    are not gated."""
    wdir = tmp_path / "wire"
    wdir.mkdir()
    bad = wdir / "server.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np

        class L:
            def sweep(self):
                rows = [r for r in self.rbuf]         # RA09: loop
                meta = {"rows": len(rows)}            # RA09: dict
                return self._fanout(rows), meta

            def _fanout(self, rows):
                for r in rows:                        # RA09: via helper
                    self.send(r)

            def overview(self):
                # NOT on the sweep path: control-plane loops are fine
                return {k: v for k, v in self.counters.items()}
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    out = r.stdout
    assert out.count("RA09") == 3, out
    assert "sweep()" in out and "_fanout()" in out
    assert "overview" not in out
    # allowlisted per-connection lines pass
    fixed = bad.read_text() \
        .replace("rows = [r for r in self.rbuf]",
                 "rows = [r for r in self.rbuf]  # ra09-ok: test") \
        .replace('meta = {"rows": len(rows)}',
                 'meta = {"rows": len(rows)}  # ra09-ok: once') \
        .replace("for r in rows:",
                 "for r in rows:  # ra09-ok: per-connection write")
    bad.write_text(fixed)
    r = run_lint(str(bad))
    assert "RA09" not in r.stdout, r.stdout
    # the same content OUTSIDE a wire/ directory is not gated
    other = tmp_path / "server.py"
    other.write_text(textwrap.dedent("""\
        class L:
            def sweep(self):
                return [r for r in self.rbuf]
    """))
    r = run_lint(str(other))
    assert "RA09" not in r.stdout


def test_wire_package_is_ra09_clean(full_lint):
    """The real wire sweep path is loop- and dict-free outside its
    allowlisted per-connection sites (covered by the repo-wide run
    too; pinned so a regression names the rule)."""
    wdir = os.path.join(REPO, "ra_tpu", "wire")
    for name in sorted(os.listdir(wdir)):
        if name.endswith(".py"):
            out = findings_in(full_lint, "ra_tpu/wire/" + name)
            assert "RA09" not in out, (name, out)


def test_checker_enforces_classic_hot_path(tmp_path):
    """RA10 (ISSUE 13 + the ISSUE 18 codec family): per-entry
    pickle.dumps/encode_command and per-entry WAL submits inside loops
    in the classic replication hot paths are flagged — including a
    pickle moved into a same-module helper called from the loop — AND
    any raw pickle.dumps anywhere in the closure (loop or not) that
    bypasses the codec's tagged fallback; `# ra10-ok:` allowlists
    deliberate sites; unscoped filenames are not gated."""
    bad = tmp_path / "tcp.py"
    bad.write_text(textwrap.dedent("""\
        import pickle

        class R:
            def _send_items(self, peer, items):
                buf = bytearray()
                for item in items:
                    buf += pickle.dumps(item)       # RA10: per-item
                    buf += self._encode_item(item)  # RA10: via helper
                return bytes(buf)

            def _encode_item(self, item):
                return pickle.dumps(item)           # RA10: raw pickle

            def _wire_form(self, to, msg, src):
                # the codec family: no loop, still a hot closure
                return pickle.dumps(msg)            # RA10: raw pickle

            def overview(self):
                # not on the sender path: per-item work is fine here
                return [pickle.dumps(x) for x in (1, 2)]
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA10") == 4, r.stdout
    assert "_send_items" in r.stdout
    assert "encode_fallback" in r.stdout    # the codec-family message
    assert "overview" not in r.stdout
    # allowlisted lines pass
    fixed = bad.read_text() \
        .replace("buf += pickle.dumps(item)       # RA10: per-item",
                 "buf += pickle.dumps(item)  # ra10-ok: singles") \
        .replace("buf += self._encode_item(item)  # RA10: via helper",
                 "buf += self._encode_item(item)  # ra10-ok: fallback") \
        .replace("return pickle.dumps(item)           # RA10: raw pickle",
                 "return pickle.dumps(item)  # ra10-ok: envelope") \
        .replace("return pickle.dumps(msg)            # RA10: raw pickle",
                 "return pickle.dumps(msg)  # ra10-ok: envelope")
    bad.write_text(fixed)
    r = run_lint(str(bad))
    assert "RA10" not in r.stdout, r.stdout
    # log/durable.py: per-entry WAL submits in the batch-append path,
    # plus the helper encoder's own raw pickle (the codec family)
    logdir = tmp_path / "log"
    logdir.mkdir()
    dlog = logdir / "durable.py"
    dlog.write_text(textwrap.dedent("""\
        def encode_command(cmd):
            import pickle
            return pickle.dumps(cmd)

        class D:
            def write(self, entries):
                for e in entries:
                    payload = encode_command(e)     # RA10: per-entry
                    self.wal.write(self.uid, e, payload)  # RA10: WAL
    """))
    r = run_lint(str(dlog))
    assert r.returncode == 1
    assert r.stdout.count("RA10") == 3, r.stdout
    assert "per-entry WAL submit" in r.stdout
    assert "raw pickle.dumps" in r.stdout
    # the same content under another parent dir is not gated
    other = tmp_path / "durable.py"
    other.write_text(dlog.read_text())
    r = run_lint(str(other))
    assert "RA10" not in r.stdout
    # an unscoped filename with the same sender content is not gated
    free = tmp_path / "sender.py"
    free.write_text(textwrap.dedent("""\
        import pickle

        class R:
            def _send_items(self, peer, items):
                return [pickle.dumps(i) for i in items]
    """))
    r = run_lint(str(free))
    assert "RA10" not in r.stdout


def test_classic_hot_paths_are_ra10_clean(full_lint):
    """The real sender loop, batch-append, WAL batch-writer, segment
    flush, codec, and commit-advance closures pass the per-entry +
    raw-pickle gate (covered by the repo-wide run too; pinned
    separately so a regression names the rule)."""
    for mod in ("ra_tpu/transport/tcp.py", "ra_tpu/log/durable.py",
                "ra_tpu/log/wal.py", "ra_tpu/log/segment.py",
                "ra_tpu/codec.py", "ra_tpu/core/server.py",
                "ra_tpu/wire/server.py"):
        out = findings_in(full_lint, mod)
        assert "RA10" not in out, (mod, out)


def test_mesh_module_is_ra04_and_ra08_clean(full_lint):
    """The real mesh driver passes both gates (covered by the repo-wide
    run too; pinned separately so a regression names the rule)."""
    out = findings_in(full_lint, "ra_tpu/parallel/mesh.py")
    assert "RA04" not in out and "RA08" not in out, out


# ---------------------------------------------------------------------------
# ISSUE 14 — the whole-program analyzer (tools/analyzer/): cross-module
# closures, RA11 lock-order cycles, RA12 thread roles, the suppression
# audit, and the CLI additions (--changed/--json/--report).
# ---------------------------------------------------------------------------

def test_checker_catches_cross_module_escape(tmp_path):
    """The tentpole regression: a host sync moved into a helper ONE
    MODULE AWAY is flagged.  The pre-ISSUE-14 gate walked only the
    same-module call closure, so this exact shape escaped every rule —
    the finding below lands in helpers.py, a file the old checker
    could never attribute a sampler-path finding to."""
    pkg = tmp_path / "plane"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helpers.py").write_text(textwrap.dedent("""\
        import numpy as np

        def pull(handle):
            return np.asarray(handle)
    """))
    (pkg / "telemetry.py").write_text(textwrap.dedent("""\
        from .helpers import pull

        class S:
            def tick(self):
                return pull(self.handle)
    """))
    r = run_lint(str(pkg / "telemetry.py"))
    assert r.returncode == 1
    assert "RA04" in r.stdout, r.stdout
    assert "helpers.py" in r.stdout and "pull" in r.stdout, r.stdout


def test_checker_resolves_ra_type_annotation_seams(tmp_path):
    """`# ra-type: Class` on an attribute assignment types the seam, so
    the closure walks through dynamically passed collaborators (the
    light-annotation half of ISSUE 14 — lockstep's `_dur` bridge and
    the WAL shard's `bridge` use exactly this)."""
    bad = tmp_path / "lockstep.py"
    bad.write_text(textwrap.dedent("""\
        class Bridge:
            def work(self):
                return self.h.item()

        class Eng:
            def __init__(self, bridge):
                self.bridge = bridge  # ra-type: Bridge

            def step(self):
                self.bridge.work()
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert "RA02" in r.stdout and "work" in r.stdout, r.stdout
    # without the annotation the seam is opaque: no finding (the
    # analyzer only follows provable edges)
    bad.write_text(bad.read_text().replace("  # ra-type: Bridge", ""))
    r = run_lint(str(bad))
    assert "RA02" not in r.stdout, r.stdout


def test_checker_detects_lock_order_cycle(tmp_path):
    """RA11: an ABBA pair — a-then-b on one path, b-then-a (through a
    helper call) on another — is a lock-order cycle; both directions
    are named.  A consistent hierarchy passes clean."""
    pkg = tmp_path / "store"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "store.py"
    mod.write_text(textwrap.dedent("""\
        import threading


        class Store:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def put(self):
                with self._a:
                    with self._b:
                        pass

            def flush(self):
                with self._b:
                    self._refresh()

            def _refresh(self):
                with self._a:
                    pass
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA11") == 2, r.stdout
    assert "Store._a" in r.stdout and "Store._b" in r.stdout
    # consistent a-then-b everywhere: clean
    mod.write_text(textwrap.dedent("""\
        import threading


        class Store:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def put(self):
                with self._a:
                    with self._b:
                        pass

            def flush(self):
                with self._a:
                    self._refresh()

            def _refresh(self):
                with self._b:
                    pass
    """))
    r = run_lint(str(pkg))
    assert "RA11" not in r.stdout, r.stdout


def test_checker_pins_the_fetch_term_abba_shape(tmp_path):
    """The exact shape RA11 caught LIVE in log/durable.py (ISSUE 14):
    a term lookup whose tail falls through to the io lock, called while
    the log lock is held, against a flush path that holds io-then-log.
    The PR 13 review fixed this class on the append path by hand; the
    analyzer found three surviving sites (_wal_notify/set_last_index/
    handle_written) — fixed in this PR and pinned clean below."""
    pkg = tmp_path / "logpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "durlog.py"
    mod.write_text(textwrap.dedent("""\
        import threading


        class Log:
            def __init__(self):
                self._lock = threading.RLock()
                self._io_lock = threading.Lock()

            def fetch_term(self, idx):
                with self._lock:
                    got = idx
                return self._segment_read(got)

            def _segment_read(self, idx):
                with self._io_lock:
                    return idx

            def handle_written(self, evt):
                with self._lock:
                    return self.fetch_term(evt)

            def flush(self):
                with self._io_lock:
                    with self._lock:
                        pass
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert "RA11" in r.stdout, r.stdout
    assert "Log._io_lock" in r.stdout and "Log._lock" in r.stdout
    # `# ra11-ok:` allowlists reviewed edges (both directions tagged)
    fixed = mod.read_text() \
        .replace("return self.fetch_term(evt)",
                 "return self.fetch_term(evt)  # ra11-ok: reviewed") \
        .replace("with self._lock:\n                pass",
                 "with self._lock:  # ra11-ok: reviewed\n"
                 "                pass")
    mod.write_text(fixed)
    r = run_lint(str(pkg))
    assert "RA11" not in r.stdout, r.stdout


def test_log_layer_is_ra11_clean(full_lint):
    """The real log layer holds the documented io-then-log order with
    no cycle — the PR 13 ABBA class cannot reland (ISSUE 14
    acceptance pin; the three fixed sites live in durable.py)."""
    out = findings_in(full_lint, "ra_tpu/log")
    assert "RA11" not in out, out
    out = findings_in(full_lint, "ra_tpu/log/durable.py")
    assert "RA11" not in out, out


def test_checker_ra11_lock_annotation_names_dynamic_locks(tmp_path):
    """`# ra11-lock: Name` names a dynamically passed lock so its
    acquisitions join the order graph (the small annotation ISSUE 14
    specifies for locks the resolver cannot type)."""
    pkg = tmp_path / "w"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "worker.py").write_text(textwrap.dedent("""\
        import threading


        class W:
            def __init__(self, shared):
                self._own = threading.Lock()
                self._shared = shared

            def a(self):
                with self._own:
                    with self._shared:  # ra11-lock: Pool.biglock
                        pass

            def b(self):
                with self._shared:  # ra11-lock: Pool.biglock
                    with self._own:
                        pass
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA11") == 2, r.stdout
    assert "Pool.biglock" in r.stdout and "W._own" in r.stdout


def test_checker_detects_worker_thread_device_ops(tmp_path):
    """RA12: jax.*/jnp.* calls, device_put and block_until_ready in the
    transitive closure of a threading.Thread target are flagged — the
    PR 11 mesh deadlock (an encode worker enqueuing device work against
    an in-flight pjit), as a lint.  Non-worker functions and
    non-package files are exempt."""
    pkg = tmp_path / "eng"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "shard.py"
    mod.write_text(textwrap.dedent("""\
        import threading

        import jax
        import jax.numpy as jnp


        class Shard:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

            def _run(self):
                self._work()

            def _work(self):
                a = jnp.ones(3)
                jax.device_put(a)
                a.block_until_ready()

            def overview(self):
                return jnp.zeros(1)
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA12") == 3, r.stdout
    assert "_work" in r.stdout and "overview" not in r.stdout
    assert "jnp.ones" in r.stdout and "jax.device_put" in r.stdout
    assert ".block_until_ready()" in r.stdout
    # tagged host-materialization sites pass (and stay audit-live)
    fixed = mod.read_text() \
        .replace("a = jnp.ones(3)",
                 "a = jnp.ones(3)  # ra12-ok: pre-spawn smoke") \
        .replace("jax.device_put(a)",
                 "jax.device_put(a)  # ra12-ok: staged pre-spawn") \
        .replace("a.block_until_ready()",
                 "a.block_until_ready()  # ra12-ok: joined after stop")
    mod.write_text(fixed)
    r = run_lint(str(pkg))
    assert "RA12" not in r.stdout and "AUDIT" not in r.stdout, r.stdout
    # the same content OUTSIDE a package (no __init__.py) is not gated:
    # test harnesses and CLI tools own their whole process
    loose = tmp_path / "shard.py"
    loose.write_text(textwrap.dedent("""\
        import threading

        import jax.numpy as jnp


        class Shard:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                return jnp.ones(3)
    """))
    r = run_lint(str(loose))
    assert "RA12" not in r.stdout, r.stdout


def test_engine_and_parallel_are_ra12_clean(full_lint):
    """ISSUE 14 acceptance pin: the real worker closures (WAL shard
    encode workers, supervisors, TCP/wire reader loops) are free of
    device ops — the sharded path materializes host-side ONCE via the
    annotated `bridge` seam (`EngineDurability._host_aux`, pure d2h),
    so the PR 11 deadlock class cannot reland."""
    for mod in ("ra_tpu/engine", "ra_tpu/parallel", "ra_tpu/log",
                "ra_tpu/wire", "ra_tpu/transport"):
        out = findings_in(full_lint, mod)
        assert "RA12" not in out, (mod, out)


def test_engine_pipeline_closure_is_ra02_ra04_clean(full_lint):
    """ISSUE 14: the cross-module closure walks step/superstep through
    the annotated seams (DispatchAheadDriver staging, the durability
    bridge, the sampler).  The syncs it surfaced — _host_mask's host
    coercion, _stage's staging encodes, _dispatch's window-boundary
    readback — are documented ra02-ok points; an UNtagged sync reached
    through any of these seams now fails the gate."""
    for mod in ("ra_tpu/engine/lockstep.py", "ra_tpu/engine/durable.py",
                "ra_tpu/parallel/mesh.py"):
        out = findings_in(full_lint, mod)
        assert "RA02" not in out and "RA04" not in out, (mod, out)


def test_checker_flags_drain_inside_bench_dispatch_loop(tmp_path):
    """`.drain()` is a full pipeline barrier — the strongest sync of
    all — and the pre-ISSUE-14 gate missed it inside measured loops."""
    bad = tmp_path / "soak.py"
    bad.write_text(textwrap.dedent("""\
        def run(driver, n, p):
            for _ in range(8):
                driver.submit(n, p)
                driver.drain()
            driver.drain()
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 1, r.stdout
    assert ".drain()" in r.stdout


def test_audit_flags_stale_suppressions(tmp_path):
    """The allowlist-rot gate: a raNN-ok tag on a line its rule family
    no longer flags is itself an error; live tags, tags inside string
    literals, and tests-dir files are exempt."""
    bad = tmp_path / "lockstep.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np


        def step(x):
            host = np.asarray(x)  # ra02-ok: documented readback
            y = 1 + 1  # ra02-ok: stale - nothing flagged here
            return host, y
    """))
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("AUDIT") == 1, r.stdout
    assert "stale suppression" in r.stdout and ":6:" in r.stdout
    # a tag inside a string literal is NOT a suppression comment
    strings = tmp_path / "strings.py"
    strings.write_text(
        'S = "np.asarray(x)  # ra02-ok: not a comment"\n')
    r = run_lint(str(strings))
    assert "AUDIT" not in r.stdout, r.stdout
    # tests-dir files are exempt (their tags live inside fixtures)
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "helper.py").write_text("y = 1  # ra02-ok: fixture text\n")
    r = run_lint(str(tdir / "helper.py"))
    assert "AUDIT" not in r.stdout, r.stdout


def test_suppression_tag_families_cover_shared_closures(tmp_path):
    """RA02/RA04 police the same host-sync class from different roots;
    one line reached by both carries ONE documented tag and either
    code's tag suppresses both (and stays audit-live)."""
    bad = tmp_path / "lockstep.py"
    bad.write_text(textwrap.dedent("""\
        import numpy as np


        def step(x):
            return tick(x)


        def tick(x):
            return np.asarray(x)  # ra02-ok: one tag for both closures
    """))
    r = run_lint(str(bad))
    # tick is reached from step's RA02 closure; under telemetry.py's
    # name it would ALSO be an RA04 root — the single ra02-ok tag
    # suppresses the family either way
    assert "RA02" not in r.stdout and "RA04" not in r.stdout, r.stdout
    assert "AUDIT" not in r.stdout, r.stdout


def test_analyzer_runtime_budget():
    """Satellite (ISSUE 14, re-measured for ISSUE 15): the whole-repo
    pass stays well inside a tier-1 budget — the gate must never
    become the slow step.  With the three jit-plane rule families
    (RA13/RA14/RA15) and the migrated FILE_RULES the measured full
    pass is ~7.6s on the builder box (~4s at PR 14); 60s absorbs
    shared-CI noise with a wide margin."""
    import time as _time
    t0 = _time.monotonic()
    r = run_lint()
    elapsed = _time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert elapsed < 60.0, f"analyzer too slow for tier-1: {elapsed:.1f}s"


def test_lint_changed_mode_runs(full_lint):
    """`--changed` lints only files differing from HEAD (fast local
    loop).  Content depends on the working tree, so pin the contract:
    it runs, keeps the output format, and never scans MORE files than
    the default target set."""
    r = run_lint("--changed")
    assert r.returncode in (0, 1), r.stderr
    tail = r.stdout.strip().splitlines()[-1]
    assert tail.startswith("lint: ") and "files" in tail, r.stdout
    n_changed = int(tail.split()[1])
    assert n_changed <= full_lint["files"]


def test_lint_json_output():
    """`--json` emits the machine-readable finding pool (findings +
    suppressed + file count) for CI tooling."""
    import json as _json
    r = run_lint("--json", os.path.join(REPO, "ra_tpu", "telemetry.py"))
    data = _json.loads(r.stdout)
    assert data["files"] == 1
    assert data["findings"] == []
    assert any(s["code"] in ("RA02", "RA04") for s in data["suppressed"])


def test_lint_report_output():
    """`--report` renders the grouped human view over the same pool."""
    r = run_lint("--report", os.path.join(REPO, "ra_tpu", "telemetry.py"))
    assert "static analysis report" in r.stdout
    assert "suppressed" in r.stdout


def test_ra11_mutual_recursion_is_order_independent(tmp_path):
    """Review regression pin: mutually recursive lock-takers must
    contribute their FULL transitive lock sets regardless of traversal
    order.  The first cut memoized a cycle-truncated DFS result, so an
    early caller could poison the memo and a genuine ABBA pair went
    unreported; the analyzer now SCC-collapses the call graph."""
    pkg = tmp_path / "rec"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "m.py").write_text(textwrap.dedent("""\
        import threading


        class R:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._c = threading.Lock()

            def early(self):
                # traversal bait: computes f's set before h needs g's
                self.f(3)

            def f(self, n):
                with self._a:
                    pass
                if n:
                    self.g(n - 1)

            def g(self, n):
                with self._b:
                    pass
                if n:
                    self.f(n - 1)

            def h(self):
                with self._c:
                    self.g(1)

            def inv(self):
                with self._a:
                    with self._c:
                        pass
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1, r.stdout
    assert "RA11" in r.stdout, r.stdout
    assert "R._c" in r.stdout and "R._a" in r.stdout, r.stdout


def test_lint_missing_target_fails_loudly():
    """Review regression pin: a typo'd explicit target must not report
    green having linted nothing."""
    r = run_lint("ra_tpu/enigne_typo.py")
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "no such target" in r.stderr, r.stderr


def test_ra12_gates_positional_thread_spawns(tmp_path):
    """Review regression pin: threading.Thread's FIRST positional
    parameter is `group` — `Thread(None, self._run)` must still harvest
    `_run` as a worker root."""
    pkg = tmp_path / "pos"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "w.py").write_text(textwrap.dedent("""\
        import threading

        import jax.numpy as jnp


        class W:
            def start(self):
                self._t = threading.Thread(None, self._run)
                self._t.start()

            def _run(self):
                return jnp.ones(3)
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert "RA12" in r.stdout and "_run" in r.stdout, r.stdout


def test_ra11_ignores_locks_in_deferred_callbacks(tmp_path):
    """Review regression pin: a `with self._a:` body that merely
    DEFINES a callback taking `self._b` does not hold a while taking b
    — deferred execution must not create acquisition-order edges (the
    first cut walked nested defs and reported a bogus ABBA)."""
    pkg = tmp_path / "cb"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "m.py").write_text(textwrap.dedent("""\
        import threading


        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._cbs = []

            def register(self):
                with self._a:
                    def cb():
                        with self._b:
                            pass
                    self._cbs.append(cb)

            def other(self):
                with self._b:
                    with self._a:
                        pass
    """))
    r = run_lint(str(pkg))
    assert "RA11" not in r.stdout, r.stdout


def test_ra11_flags_plain_lock_self_deadlock(tmp_path):
    """Review regression pin: re-acquiring a held plain threading.Lock
    is a GUARANTEED self-deadlock, not a benign reentry — the first cut
    dropped every same-lock edge, so `outer()` holding `_lock` and
    calling `inner()` (which takes `_lock` again) linted clean while
    hanging the process unconditionally.  RLock (and the RLock-backed
    default Condition) stay edge-free."""
    pkg = tmp_path / "sd"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    src = textwrap.dedent("""\
        import threading


        class Eng:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
    """)
    (pkg / "eng.py").write_text(src)
    r = run_lint(str(pkg))
    assert r.returncode == 1, r.stdout
    assert "RA11" in r.stdout and "self-deadlock" in r.stdout, r.stdout
    assert "Eng._lock" in r.stdout, r.stdout
    # reentrant ctors are exempt: the same shape over an RLock is fine
    (pkg / "eng.py").write_text(src.replace("threading.Lock()",
                                            "threading.RLock()"))
    r = run_lint(str(pkg))
    assert "RA11" not in r.stdout, r.stdout
    assert r.returncode == 0, r.stdout


def test_scoped_lint_keeps_cross_module_tags_live(tmp_path):
    """Review regression pin: rule roots are harvested from every
    indexed source module, not just the lint TARGETS — the first cut
    seeded roots from targets only, so linting a tagged helper alone
    (exactly what --changed does after editing it) lost the root one
    file away, read the tag as stale, and the fast loop false-failed
    on code the full run passes."""
    pkg = tmp_path / "scoped"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helpers.py").write_text(textwrap.dedent("""\
        import numpy as np


        def pull(handle):
            return np.asarray(handle)  # ra04-ok: window boundary
    """))
    (pkg / "telemetry.py").write_text(textwrap.dedent("""\
        from .helpers import pull


        class S:
            def tick(self):
                return pull(self.handle)
    """))
    full = run_lint(str(pkg))
    assert full.returncode == 0, full.stdout
    scoped = run_lint(str(pkg / "helpers.py"))
    assert scoped.returncode == 0, scoped.stdout
    assert "AUDIT" not in scoped.stdout, scoped.stdout
    # and the gate itself still bites in the scoped run: untag the
    # helper and linting it ALONE must flag the cross-module sync
    (pkg / "helpers.py").write_text(textwrap.dedent("""\
        import numpy as np


        def pull(handle):
            return np.asarray(handle)
    """))
    scoped = run_lint(str(pkg / "helpers.py"))
    assert scoped.returncode == 1, scoped.stdout
    assert "RA04" in scoped.stdout, scoped.stdout


def test_lint_changed_rejects_explicit_paths():
    """Review regression pin: `--changed` with explicit targets used to
    silently lint the git-changed set and ignore the paths — now a loud
    usage error, like unknown flags."""
    r = run_lint("--changed", "ra_tpu")
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "no explicit targets" in r.stderr, r.stderr


def test_lint_syntax_prefix_contract(tmp_path):
    """Review regression pin: syntax findings keep the historical
    'path:N: syntax: msg' rendering (the colon after `syntax`) that CI
    greps key on."""
    bad = tmp_path / "syn.py"
    bad.write_text("def broken(:\n")
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert ": syntax: " in r.stdout, r.stdout


def test_scoped_lint_attributes_findings_to_reaching_roots(tmp_path):
    """Review regression pin (round 3): a finding carries exactly the
    root modules whose closure REACHES it — stamping the whole rule's
    root set made linting one root file report escapes only reachable
    from a different root (editing telemetry.py then `--changed` would
    false-fail on a pre-existing escape only the driver's poll()
    reaches)."""
    pkg = tmp_path / "attr"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text(textwrap.dedent("""\
        import numpy as np


        def pull(handle):
            return np.asarray(handle)
    """))
    (pkg / "lockstep.py").write_text(textwrap.dedent("""\
        from .helper import pull


        def poll(h):
            return pull(h)
    """))
    (pkg / "telemetry.py").write_text(textwrap.dedent("""\
        class S:
            def tick(self):
                return 1
    """))
    r = run_lint(str(pkg / "telemetry.py"))
    assert r.returncode == 0, r.stdout
    assert "helper.py" not in r.stdout, r.stdout
    r = run_lint(str(pkg / "lockstep.py"))
    assert r.returncode == 1, r.stdout
    assert "RA04" in r.stdout and "helper.py" in r.stdout, r.stdout


def test_ra11_annotated_locks_never_claim_unproven_self_deadlock(
        tmp_path):
    """Review regression pin (round 3): `# ra11-lock:` is the escape
    hatch for locks the resolver cannot type — forcing ctor 'Lock' on
    it false-positived a self-deadlock on annotated RLocks/Conditions.
    Unknown ctor orders ABBA edges but never claims self-deadlock; an
    explicit `# ra11-lock: Name Ctor` token or the named class's
    indexed lock attr proves one."""
    pkg = tmp_path / "ann"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    body = textwrap.dedent("""\
        class W:
            def outer(self):
                with self._shared:  # ra11-lock: Pool.biglock{tok}
                    self.inner()

            def inner(self):
                with self._shared:  # ra11-lock: Pool.biglock{tok}
                    return 1
    """)
    (pkg / "m.py").write_text(body.format(tok=""))
    r = run_lint(str(pkg))
    assert "self-deadlock" not in r.stdout, r.stdout
    assert r.returncode == 0, r.stdout
    # pinning the ctor in the annotation proves the deadlock
    (pkg / "m.py").write_text(body.format(tok=" Lock"))
    r = run_lint(str(pkg))
    assert r.returncode == 1, r.stdout
    assert "self-deadlock" in r.stdout, r.stdout
    # the named class's indexed lock attr resolves the ctor too: an
    # RLock-typed Pool.biglock stays clean without any extra token
    (pkg / "m.py").write_text(
        "import threading\n\n\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self.biglock = threading.RLock()\n\n\n"
        + body.format(tok=""))
    r = run_lint(str(pkg))
    assert "self-deadlock" not in r.stdout, r.stdout


def test_lint_changed_fails_loudly_when_git_unavailable():
    """Review regression pin (round 3): `--changed` must not silently
    widen to the full default target set when git fails — that hands
    the user findings for files they never touched."""
    env = dict(os.environ, PATH="/nonexistent")
    r = subprocess.run([sys.executable, LINT, "--changed"],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "could not read the git diff" in r.stderr, r.stderr


# ---------------------------------------------------------------------------
# ISSUE 15 — the jit-plane analyzer (tools/analyzer/jitplane.py): traced-
# closure harvest, RA13 trace hazards, RA14 donation lifetime, RA15
# pytree/sharding/checkpoint schema, and the RA05/06/07 migration onto
# the engine's declarative FILE_RULES.
# ---------------------------------------------------------------------------

def test_checker_detects_trace_hazards(tmp_path):
    """RA13: inside a traced closure (here rooted by a module-level
    jax.jit), Python control flow on tracer-typed values, host-world
    calls, and concretizing casts are flagged; keyword-only params are
    static config (the repo's partial-bound idiom) and functions the
    traced world never reaches are exempt."""
    pkg = tmp_path / "plane"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "kernels.py"
    mod.write_text(textwrap.dedent("""\
        import time

        import jax
        import numpy as np


        def _step(state, n_new, *, window):
            if window:
                n_new = n_new + 0
            if state.sum() > 0:
                n_new = n_new + 1
            assert n_new.sum() >= 0
            flag = bool(state[0])
            t0 = time.time()
            host = np.asarray(n_new)
            v = state[0].item()
            return state + n_new, (flag, t0, host, v)


        STEP = jax.jit(_step)


        def overview(state):
            if state is None:
                return 0
            return state
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    out = r.stdout
    assert out.count("RA13") == 6, out
    for frag in ("Python `if` on a traced value", "`assert` on a traced",
                 "bool() cast", "time.time()", "np.asarray() over a",
                 ".item() on a traced"):
        assert frag in out, (frag, out)
    # the static-config branch and the untraced function stay clean
    assert "overview" not in out, out
    assert ":8:" not in out, out  # `if window:` — keyword-only = static
    # tagged sites pass and stay audit-live
    fixed = mod.read_text()
    for line in ("if state.sum() > 0:", "assert n_new.sum() >= 0",
                 "flag = bool(state[0])", "t0 = time.time()",
                 "host = np.asarray(n_new)", "v = state[0].item()"):
        fixed = fixed.replace(line, line + "  # ra13-ok: fixture why")
    mod.write_text(fixed)
    r = run_lint(str(pkg))
    assert "RA13" not in r.stdout and "AUDIT" not in r.stdout, r.stdout
    # the same content OUTSIDE a package is not gated (CLI tools and
    # harnesses own their whole process, same boundary as RA12)
    loose = tmp_path / "kernels.py"
    loose.write_text(textwrap.dedent("""\
        import jax


        def _step(state):
            if state.sum() > 0:
                return state
            return state + 1


        STEP = jax.jit(_step)
    """))
    r = run_lint(str(loose))
    assert "RA13" not in r.stdout, r.stdout


def test_checker_traces_through_jit_wrapper_param(tmp_path):
    """The tentpole resolution shape: the repo jits through a wrapper
    (`_build_jit(fn, ...)` builds functools.partial(fn) and jits it),
    so the traced callable is a PARAMETER — the harvest must chase the
    wrapper's call sites and root the argument passed there."""
    pkg = tmp_path / "eng"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "lockjit.py").write_text(textwrap.dedent("""\
        import functools

        import jax


        def _step(state, n):
            while state.sum() > 0:
                state = state - n
            return state


        class Eng:
            def _build_jit(self, fn, donate):
                partial = functools.partial(fn, n=1)
                return jax.jit(partial,
                               donate_argnums=(0,) if donate else ())

            def compile(self):
                self._step = self._build_jit(_step, True)
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA13") == 1, r.stdout
    assert "Python `while` on a traced value" in r.stdout, r.stdout
    assert "_step" in r.stdout, r.stdout


def test_checker_traces_scan_and_cond_bodies(tmp_path):
    """lax.scan/cond body callables are traced roots even with no
    jax.jit in sight — scan bodies run under trace wherever the scan
    itself ends up."""
    pkg = tmp_path / "fold"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "folds.py").write_text(textwrap.dedent("""\
        from jax import lax


        def fold(xs, init):
            def body(carry, x):
                if x > 0:
                    carry = carry + x
                return carry, x
            return lax.scan(body, init, xs)


        def pick(pred, a, b):
            return lax.cond(pred,
                            lambda t: int(t[0]),
                            lambda t: 0,
                            (a, b))


        def route(i, x):
            def br0(t):
                return float(t)
            def br1(t):
                return t + 1
            return lax.switch(i, [br0, br1], x)
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA13") == 3, r.stdout
    assert "Python `if` on a traced value" in r.stdout
    assert "int() cast of a traced value" in r.stdout
    # switch branches ride ONE sequence argument — the harvest must
    # unpack the list, and operands must NOT be chased as callables
    # (review finding: positional slots 1-6 missed every real switch)
    assert "float() cast of a traced value" in r.stdout, r.stdout


def test_checker_detects_donated_buffer_read_after_call(tmp_path):
    """RA14 (lifetime half): reading the donated argument after the
    donating call is poison on backends where donation is real; the
    rebind-the-result shape (`self.state, aux = self._step(self.state,
    ...)`) is the sanctioned idiom and passes."""
    pkg = tmp_path / "don"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "eng.py").write_text(textwrap.dedent("""\
        import jax


        class Eng:
            def __init__(self, fn, state):
                self._step = jax.jit(fn, donate_argnums=(0,))
                self.state = state

            def bad(self, n):
                out, aux = self._step(self.state, n)
                return self.state.sum()

            def masked(self, n):
                out, aux = self._step(self.state, n)
                pre = self.state.sum()
                self.state = out
                return pre + self.state.sum()

            def good(self, n):
                self.state, aux = self._step(self.state, n)
                return self.state.sum()
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    # bad() reads with no rebind; masked() reads BEFORE a later rebind
    # (a post-rebind read must not mask it); good()'s rebind-at-call
    # is the sanctioned shape
    assert r.stdout.count("RA14") == 2, r.stdout
    assert "after it was DONATED" in r.stdout, r.stdout
    assert "self.state" in r.stdout, r.stdout
    assert ":15:" in r.stdout, r.stdout  # masked()'s pre-rebind read


def test_checker_detects_loop_carried_donation(tmp_path):
    """Review regression pin: a donating call inside a loop that never
    rebinds the donated key hands the invalidated buffer back in on
    the next iteration — a read the linear before/after scan cannot
    see.  A rebind in the loop body protects it, and a rebind inside a
    nested def (deferred execution) does NOT."""
    pkg = tmp_path / "loopdon"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "eng.py").write_text(textwrap.dedent("""\
        import jax


        class Eng:
            def __init__(self, fn, state):
                self._step = jax.jit(fn, donate_argnums=(0,))
                self.state = state

            def bad_loop(self, blocks):
                for b in blocks:
                    out, aux = self._step(self.state, b)
                return out

            def masked_by_nested_def(self, blocks):
                for b in blocks:
                    out, aux = self._step(self.state, b)

                    def cb():
                        self.state = out
                    self._cbs.append(cb)
                return out

            def good_loop(self, blocks):
                for b in blocks:
                    self.state, aux = self._step(self.state, b)
                return aux
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA14") == 2, r.stdout
    assert "inside a loop that never rebinds it" in r.stdout, r.stdout
    assert "good_loop" not in r.stdout


def test_checker_detects_aliased_pytree_leaves(tmp_path):
    """RA14 (aliasing half): the exact PR 6 shape as a fixture — ONE
    buffer binding passed as two NamedTuple leaves (or splatted across
    all of them) aliases one device buffer and trips the donating
    path's 'donate same buffer twice'; one constructor per leaf is the
    fix shape and passes."""
    pkg = tmp_path / "tel"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "telem.py"
    mod.write_text(textwrap.dedent("""\
        from typing import NamedTuple

        import jax.numpy as jnp


        class Telem(NamedTuple):
            a: object
            b: object


        def init_bad(n):
            z = jnp.zeros((n,), jnp.int32)
            return Telem(z, z)


        def init_splat(n):
            z = jnp.zeros((n,), jnp.int32)
            return Telem(*(z for _ in range(2)))


        def init_good(n):
            return Telem(*(jnp.zeros((n,), jnp.int32)
                           for _ in range(2)))
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA14") == 2, r.stdout
    assert "as two leaves" in r.stdout, r.stdout
    assert "splats ONE buffer binding" in r.stdout, r.stdout
    assert "init_good" not in r.stdout
    # tagged sites pass and stay audit-live
    fixed = mod.read_text() \
        .replace("return Telem(z, z)",
                 "return Telem(z, z)  # ra14-ok: fixture why") \
        .replace("return Telem(*(z for _ in range(2)))",
                 "return Telem(*(z for _ in range(2)))"
                 "  # ra14-ok: fixture why")
    mod.write_text(fixed)
    r = run_lint(str(pkg))
    assert "RA14" not in r.stdout and "AUDIT" not in r.stdout, r.stdout


def test_checker_enforces_state_shardings_coverage(tmp_path):
    """RA15(a): every schema field must be covered by the shardings
    dispatch — the fixture reproduces the PR 6 uncovered-telemetry
    shape (explicit per-field dict that forgot `telem`); generic
    `._fields` iteration is full coverage, but a by-name special case
    naming a NON-field is a stale dispatch arm."""
    pkg = tmp_path / "mesh"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "shards.py"
    mod.write_text(textwrap.dedent("""\
        from typing import NamedTuple


        class LaneState(NamedTuple):
            term: object
            ring: object
            telem: object


        def state_shardings(mesh, state: LaneState):
            return {"term": mesh, "ring": mesh}
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA15") == 1, r.stdout
    assert "does not cover" in r.stdout and "telem" in r.stdout
    # covering the field passes
    mod.write_text(mod.read_text().replace(
        'return {"term": mesh, "ring": mesh}',
        'return {"term": mesh, "ring": mesh, "telem": mesh}'))
    r = run_lint(str(pkg))
    assert "RA15" not in r.stdout, r.stdout
    # generic _fields iteration is full coverage; a special-case arm
    # naming a non-field is stale
    mod.write_text(textwrap.dedent("""\
        from typing import NamedTuple


        class LaneState(NamedTuple):
            term: object
            ring: object
            telem: object


        def state_shardings(mesh, state: LaneState):
            specs = {}
            for name in LaneState._fields:
                if name == "mac":
                    continue
                specs[name] = mesh
            return specs
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA15") == 1, r.stdout
    assert "special-cases 'mac'" in r.stdout, r.stdout
    mod.write_text(mod.read_text().replace('"mac"', '"ring"'))
    r = run_lint(str(pkg))
    assert "RA15" not in r.stdout, r.stdout


def test_checker_enforces_checkpoint_defaults_registry(tmp_path):
    """RA15(b): the schema module must declare a per-field
    CHECKPOINT_FIELD_DEFAULTS registry (parity with the schema, no
    stale keys) and restore() must consult it — the PR 6 pre-telemetry
    restore() KeyError, closed for every FUTURE field addition."""
    pkg = tmp_path / "ckpt"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "lanes.py"
    base = textwrap.dedent("""\
        from typing import NamedTuple


        class LaneState(NamedTuple):
            term: object
            telem: object


        def state_shardings(mesh, state: LaneState):
            return {"term": mesh, "telem": mesh}


        @REGISTRY@

        class Eng:
            def restore(self, path):
                @RESTORE@
    """)

    def build(registry, restore_body):
        return base.replace("@REGISTRY@", registry) \
                   .replace("@RESTORE@", restore_body)

    # no registry at all
    mod.write_text(build("", "return path"))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA15") == 1, r.stdout
    assert "no CHECKPOINT_FIELD_DEFAULTS registry" in r.stdout
    # registry missing a field + stale key + restore not consulting it
    mod.write_text(build(
        'CHECKPOINT_FIELD_DEFAULTS = {"term": "require", '
        '"mac": "zeros"}', "return path"))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    out = r.stdout
    assert out.count("RA15") == 3, out
    assert "missing" in out and "telem" in out
    assert "names ['mac']" in out, out
    assert "does not consult" in out, out
    # complete registry + consulting restore passes
    mod.write_text(build(
        'CHECKPOINT_FIELD_DEFAULTS = {"term": "require", '
        '"telem": "zeros"}',
        "return CHECKPOINT_FIELD_DEFAULTS.get(path)"))
    r = run_lint(str(pkg))
    assert "RA15" not in r.stdout, r.stdout


def test_checker_enforces_block_staging_coverage(tmp_path):
    """RA15(c): a staged superstep-block key with no entry in
    superstep_block_shardings repartitions the staged block on every
    dispatch (or rejects on a mesh) — the staging path's `.get` keys
    must all be covered."""
    pkg = tmp_path / "stage"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    mod = pkg / "driver.py"
    mod.write_text(textwrap.dedent("""\
        def superstep_block_shardings(mesh):
            return {"n_new": mesh, "payloads": mesh}


        class Driver:
            def _stage(self, blk):
                a = self.shardings.get("n_new")
                b = self.shardings.get("query")
                return a, b, blk
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("RA15") == 1, r.stdout
    assert "'query' has no entry" in r.stdout, r.stdout
    # a documented `# ra15-ok` tag suppresses and stays audit-live
    tagged = mod.read_text().replace(
        'b = self.shardings.get("query")',
        'b = self.shardings.get("query")  # ra15-ok: fixture why')
    mod.write_text(tagged)
    r = run_lint(str(pkg))
    assert "RA15" not in r.stdout and "AUDIT" not in r.stdout, r.stdout
    mod.write_text(tagged.replace("  # ra15-ok: fixture why", "")
                   .replace('{"n_new": mesh, "payloads": mesh}',
                            '{"n_new": mesh, "payloads": mesh, '
                            '"query": mesh}'))
    r = run_lint(str(pkg))
    assert "RA15" not in r.stdout, r.stdout


def test_jit_plane_modules_are_clean(full_lint):
    """ISSUE 15 acceptance pin: the engine, mesh, ingress, machine and
    ops trees carry zero untagged RA13/RA14/RA15 findings — the jitted
    arithmetic stays trace-pure, donation lifetimes hold, and the
    schema contracts (shardings coverage, checkpoint defaults, block
    staging) are satisfied on main."""
    out = findings_in(full_lint, "ra_tpu/engine", "ra_tpu/parallel",
                      "ra_tpu/ingress", "ra_tpu/models", "ra_tpu/core",
                      "ra_tpu/ops")
    for code in ("RA13", "RA14", "RA15"):
        assert code not in out, (code, out)


def test_cond_concrete_probe_is_tagged_and_audit_live():
    """The sanctioned concreteness probe (core/machine.py
    cond_concrete's bool(pred)) is a SUPPRESSED RA13 finding, not an
    absent one — the tag is live, so deleting the probe without
    removing the tag trips the audit."""
    import json as _json
    r = run_lint("--json",
                 os.path.join(REPO, "ra_tpu", "core", "machine.py"))
    data = _json.loads(r.stdout)
    assert data["findings"] == [], data["findings"]
    assert any(s["code"] == "RA13" and "bool()" in s["msg"]
               for s in data["suppressed"]), data["suppressed"]


def test_audit_covers_jitplane_tags(tmp_path):
    """The allowlist-rot audit extends to the new tag families: a
    ra13/ra14/ra15-ok tag on a line its rule no longer flags is an
    AUDIT error."""
    pkg = tmp_path / "rot"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "m.py").write_text(textwrap.dedent("""\
        X = 1  # ra13-ok: stale - nothing traced here
        Y = 2  # ra14-ok: stale
        Z = 3  # ra15-ok: stale
    """))
    r = run_lint(str(pkg))
    assert r.returncode == 1
    assert r.stdout.count("AUDIT") == 3, r.stdout


def test_file_rules_ride_the_engine(tmp_path):
    """ISSUE 15 satellite: RA05/RA06/RA07 are declarative FILE_RULES
    evaluated by the analyzer engine (one engine owns every rule).
    The behavioural contract is pinned by the per-rule tests above;
    this pins the MIGRATION — the specs live in the engine's rule
    table and the old lint-side walkers are gone."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from analyzer.rules import FILE_RULES
    finally:
        sys.path.pop(0)
    codes = {r.code for r in FILE_RULES}
    assert {"RA05", "RA06", "RA07", "RA16"} <= codes, codes
    import ast as _ast
    lint_src = open(LINT, encoding="utf-8").read()
    tree = _ast.parse(lint_src)
    defs = {n.name for n in _ast.walk(tree)
            if isinstance(n, (_ast.FunctionDef, _ast.AsyncFunctionDef))}
    for gone in ("_check_field_registry", "_check_event_registry_use",
                 "_check_autotune_contract"):
        assert gone not in defs, gone


# -- RA16: placement retry bounds (ISSUE 17) ------------------------------

_RA16_BB = 'EVENT_REGISTRY = {"placement.giveup": "doc"}\n'


def _ra16_fixture(tmp_path, body):
    """A fixture module inside a `placement/` dir (the rule's scope)
    with a local blackbox.py registering the give-up event."""
    pdir = tmp_path / "placement"
    pdir.mkdir(exist_ok=True)
    (pdir / "blackbox.py").write_text(_RA16_BB)
    mod = pdir / "sup.py"
    mod.write_text(body)
    return mod


def test_ra16_flags_unbounded_and_silent_retry_loops(tmp_path):
    """RA16: an unbounded escalation loop is flagged, and a bounded
    loop whose function never emits a registered give-up event is
    flagged too (exhaustion must be visible to the flight recorder)."""
    mod = _ra16_fixture(tmp_path, textwrap.dedent("""\
        import time
        from blackbox import record


        def unbounded(sid, cmd, router):
            while True:                     # RA16: no bound evidence
                res = process_command(sid, cmd, router)
                if res:
                    return res
                time.sleep(0.1)


        def bounded_but_silent(sid, cmd, router, clock):
            deadline = clock() + 5.0
            while clock() < deadline:       # RA16: bounded, no giveup
                res = process_command(sid, cmd, router)
                if res:
                    return res
            return None
    """))
    r = run_lint(str(mod))
    assert r.returncode == 1
    assert r.stdout.count("RA16") == 2, r.stdout
    assert "no deadline/bounded-attempt evidence" in r.stdout
    assert "never emits a registered record" in r.stdout


def test_ra16_full_shape_is_clean(tmp_path):
    """The supervisor's canonical shape passes: deadline in the loop
    test + a registered give-up record on exhaustion.  A bound-guarded
    break inside the body is accepted as bound evidence too."""
    mod = _ra16_fixture(tmp_path, textwrap.dedent("""\
        from blackbox import record


        def commit(attempt_fn, clock, timeout):
            deadline = clock() + timeout * 3
            attempts = 0
            while clock() < deadline:
                attempts += 1
                res = attempt_fn()
                if res is not None:
                    return res
            record("placement.giveup", what="commit",
                   attempts=attempts)
            raise RuntimeError("gave up")


        def poll(attempt_fn, max_tries):
            tries = 0
            while True:
                res = attempt_fn()
                if res is not None:
                    return res
                tries += 1
                if tries >= max_tries:      # bound-guarded raise
                    record("placement.giveup", what="poll",
                           attempts=tries)
                    raise RuntimeError("gave up")
    """))
    r = run_lint(str(mod))
    assert "RA16" not in r.stdout, r.stdout
    assert r.returncode == 0, r.stdout + r.stderr


def test_ra16_scope_and_suppression(tmp_path):
    """RA16 only gates files inside a `placement/` directory; inside
    the scope `# ra16-ok: <why>` allowlists a site and the audit
    flags the tag once the loop stops being a finding."""
    body = textwrap.dedent("""\
        import time


        def unbounded(sid, cmd, router):
            while True:
                res = process_command(sid, cmd, router)
                if res:
                    return res
                time.sleep(0.1)
    """)
    # same content OUTSIDE a placement/ dir: out of scope, clean
    other = tmp_path / "elsewhere.py"
    other.write_text(body)
    r = run_lint(str(other))
    assert "RA16" not in r.stdout, r.stdout
    # inside the scope, the tag suppresses (and stays audit-live)
    mod = _ra16_fixture(tmp_path, body.replace(
        "while True:",
        "while True:  # ra16-ok: fixture, externally watchdogged"))
    r = run_lint(str(mod))
    assert "RA16" not in r.stdout and "AUDIT" not in r.stdout, r.stdout
    # a tag on a line the rule no longer flags is itself an error
    stale = _ra16_fixture(tmp_path, textwrap.dedent("""\
        def fine():  # ra16-ok: stale
            return 1
    """))
    r = run_lint(str(stale))
    assert "stale suppression" in r.stdout, r.stdout


def test_placement_package_is_ra16_clean(full_lint):
    """The live pin: every retry loop the real placement package ships
    satisfies its own rule (the supervisor's _commit deadline loop and
    the soak's recovery/drain loops carry bounds + give-up events)."""
    out = findings_in(full_lint, "ra_tpu/placement")
    assert "RA16" not in out, out


# -- ISSUE 20: read-plane closure gates ------------------------------------

def test_checker_gates_read_admission_lane(tmp_path):
    """RA08 (read extension, ISSUE 20): per-session Python loops and
    dict allocation in the ingress read lane (submit_reads /
    _pop_read_block / _harvest_reads / _emit_read_replies + their
    same-module closure) are flagged; scoped to ingress/__init__.py
    only; `# ra08-ok:` allowlists survive."""
    pkg = tmp_path / "ingress"
    pkg.mkdir()
    bad = pkg / "__init__.py"
    body = textwrap.dedent("""\
        import numpy as np

        class Plane:
            def submit_reads(self, handles, seqnos, queries):
                for h in handles:                     # RA08: loop
                    self.pending[h] = 1
                return np.asarray(handles)

            def _emit_read_replies(self, blk, mask, status, wms, reps):
                out = {"rows": len(blk)}              # RA08: dict
                return out

            def read_overview(self):
                # NOT hot: overview is control-plane reporting
                return {k: 1 for k in ["a", "b"]}
    """)
    bad.write_text(body)
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA08") == 2, r.stdout
    assert "submit_reads()" in r.stdout
    assert "_emit_read_replies()" in r.stdout
    assert "read_overview()" not in r.stdout
    # allowlisted lines pass
    bad.write_text(body
                   .replace("for h in handles:",
                            "for h in handles:  # ra08-ok: tiny")
                   .replace('out = {"rows": len(blk)}',
                            'out = {"rows": len(blk)}  # ra08-ok: once'))
    r = run_lint(str(bad))
    assert "RA08" not in r.stdout, r.stdout
    # same content outside an ingress/ package: out of scope
    other = tmp_path / "plane.py"
    other.write_text(body)
    r = run_lint(str(other))
    assert "RA08" not in r.stdout, r.stdout


def test_checker_gates_read_reply_egress(tmp_path):
    """RA09 (read extension, ISSUE 20): per-read Python in the wire
    server's READ_REPLY egress (_on_reads_served /
    collect_read_replies + closure) is flagged; scoped to
    wire/server.py only."""
    pkg = tmp_path / "wire"
    pkg.mkdir()
    bad = pkg / "server.py"
    body = textwrap.dedent("""\
        import numpy as np

        class Server:
            def _on_reads_served(self, handles, seqnos, sts, wms, reps):
                frames = [bytes(r) for r in reps]     # RA09: per-read
                meta = {"n": len(handles)}            # RA09: dict
                return frames, meta

            def overview(self):
                # NOT hot
                return [i for i in range(3)]
    """)
    bad.write_text(body)
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA09") == 2, r.stdout
    assert "_on_reads_served()" in r.stdout
    assert "overview()" not in r.stdout
    # same content outside a wire/ dir: out of scope
    other = tmp_path / "server.py"
    other.write_text(body)
    r = run_lint(str(other))
    assert "RA09" not in r.stdout, r.stdout


def test_checker_gates_driver_poll(tmp_path):
    """RA04 (ISSUE 28): the driver's poll() is non-blocking by
    contract — it converts only readbacks that is_ready().  A blocking
    sync in poll() or its closure is flagged; the one documented
    conversion of a ready handle carries its reason and passes."""
    bad = tmp_path / "lockstep.py"
    body = textwrap.dedent("""\
        import numpy as np

        class Driver:
            def poll(self):
                self._handles[0][1].block_until_ready()  # RA04: waits
                while self._handles and self._handles[0][1].is_ready():
                    self._take()

            def _take(self):
                t0, h, robs = self._handles.popleft()
                self._observe(np.asarray(h))  # RA04: untagged

            def overview(self):
                # not on the poll path
                return np.asarray([1, 2]).item()
    """)
    bad.write_text(body)
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 2, r.stdout
    assert "poll" in r.stdout and "_take" in r.stdout
    good = body.replace("# RA04: untagged",
                        "# ra02-ok: a handle poll() found ready") \
               .replace("self._handles[0][1].block_until_ready()"
                        "  # RA04: waits", "pass")
    bad.write_text(good)
    r = run_lint(str(bad))
    assert "RA04" not in r.stdout and "AUDIT" not in r.stdout, r.stdout


def test_checker_gates_driver_read_observer(tmp_path):
    """RA04 (read extension, ISSUE 20): a blocking device sync inside
    the driver's read observer (_observe_reads + closure in
    lockstep.py) is flagged — the observer may only touch COMPLETED
    async read-aux copies."""
    bad = tmp_path / "lockstep.py"
    body = textwrap.dedent("""\
        import numpy as np

        class Driver:
            def _observe_reads(self, t_sub, robs):
                robs["read_done"].block_until_ready()  # RA04: sync
                return self._decode(robs)

            def _decode(self, robs):
                return np.asarray(robs["read_replies"])  # RA04: sync

            def read_overview(self):
                # not on the observer path
                return np.asarray([1, 2]).item()
    """)
    bad.write_text(body)
    r = run_lint(str(bad))
    assert r.returncode == 1
    assert r.stdout.count("RA04") == 2, r.stdout
    assert "_observe_reads" in r.stdout or "_decode" in r.stdout
    # other module names are not gated by this scope
    other = tmp_path / "driver.py"
    other.write_text(body)
    r = run_lint(str(other))
    assert "RA04" not in r.stdout, r.stdout


def test_read_plane_modules_are_read_gate_clean(full_lint):
    """Live pins: the real read lane satisfies its own gates — the
    ingress admission/reply lane (RA08), the wire READ_REPLY egress
    (RA09), and the driver read observer (RA04)."""
    out = findings_in(full_lint, "ra_tpu/ingress/__init__.py")
    assert "RA08" not in out, out
    out = findings_in(full_lint, "ra_tpu/wire/server.py")
    assert "RA09" not in out, out
    out = findings_in(full_lint, "ra_tpu/engine/lockstep.py")
    assert "RA04" not in out, out


def test_the_tree_reads_one_environment_switch():
    """``RA_TPU_*`` over the program, its tools, the smoke and the
    benchmark: one name, a path (a deployment setting).  PR 29 took the
    other 34 out with the files that read them; a new one is an option
    somebody has to test on both sides."""
    import re
    names = set()
    for root in ("ra_tpu", "tools", "benchmarks", "chip_smoke.py"):
        top = os.path.join(REPO, root)
        files = [top] if os.path.isfile(top) else [
            os.path.join(d, n) for d, _sub, ns in os.walk(top)
            for n in ns if n.endswith((".py", ".sh", ".cpp", ".json"))]
        for path in files:
            with open(path, encoding="utf-8") as f:
                names |= set(re.findall(r"RA_TPU_[A-Z0-9_]+", f.read()))
    assert names == {"RA_TPU_BLACKBOX_DIR"}, names
