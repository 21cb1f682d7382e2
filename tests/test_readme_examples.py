"""The README quickstart blocks must actually run — extracted verbatim
from README.md and executed (with only filesystem paths and sizes
patched), so the documented first-contact API can never rot."""
import os
import re

import pytest


def _patch(src, old, new):
    """Replace that REFUSES to no-op: README drift must fail the test,
    not silently run the unpatched block (full-size configs, shared
    /tmp paths, files written into the CWD)."""
    assert old in src, f"README drift: {old!r} not found"
    return src.replace(old, new)


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _fenced():
    """(language, body) of every fenced block of the README."""
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        return re.findall(r"```(\w*)\n(.*?)```", f.read(), re.DOTALL)


def _blocks():
    return [body for lang, body in _fenced() if lang == "python"]

def test_readme_has_nine_python_blocks():
    assert len(_blocks()) == 9

def test_classic_quickstart_block(tmp_path):
    src = _blocks()[0]
    assert "start_server" in src and "consistent_query" in src
    # patch only the data dir; everything else runs as documented
    src = _patch(src, 'f"/tmp/ra/{s.node}"', 'str(tmp_path / s.node)')
    ns: dict = {"tmp_path": tmp_path}
    try:
        exec(compile(src, "README.md[classic]", "exec"), ns)  # noqa: S102
        # the block printed the linearizable read; re-check it here
        import ra_tpu
        from ra_tpu.models.kv import query_get
        res = ra_tpu.consistent_query(ns["sids"][0], query_get("greeting"),
                                      router=ns["router"])
        assert res.reply == "hello"
    finally:
        for n in ns.get("nodes", {}).values():
            n.stop()
        for s in ns.get("systems", {}).values():
            s.close()

def test_engine_quickstart_block():
    src = _blocks()[1]
    assert "LockstepEngine" in src
    # shrink the documented 10k-lane config for suite runtime; the
    # structure (shapes, calls) runs exactly as written
    src = _patch(src, "10_000", "64")
    ns = {}
    exec(compile(src, "README.md[engine]", "exec"), ns)  # noqa: S102
    assert ns["eng"].committed_total() > 0

def test_trace_quickstart_block():
    src = _blocks()[3]
    lines = [ln for ln in src.splitlines()
             if not ln.strip().startswith("...")]
    src = "\n".join(lines)
    src = _patch(src, 't.dump_chrome_trace("ra_trace.json")', 'pass')
    from ra_tpu import trace
    ns = {}
    try:
        exec(compile(src, "README.md[trace]", "exec"), ns)  # noqa: S102
        assert isinstance(ns["t"].summary(), dict)
    finally:
        trace.set_tracer(None)


def test_slo_autotune_quickstart_block(tmp_path):
    """The ISSUE 9 closed-loop block: SLO verdicts + phase attribution
    + an autotuner ticking a real durable engine, as documented."""
    src = _blocks()[5]
    assert "SloEngine" in src and "AutoTuner" in src
    # patch only path + size; the loop runs exactly as documented
    src = _patch(src, '"/tmp/ra_slo_demo", 1024', "demo_dir, 64")
    ns: dict = {"demo_dir": str(tmp_path / "slo_demo")}
    try:
        exec(compile(src, "README.md[slo]", "exec"), ns)  # noqa: S102
        verdicts = ns["slo"].evaluate()["objectives"]
        assert set(verdicts) == {"commit_p99_ms", "fsync_p99_ms",
                                 "cmds_per_s", "read_p99_ms",
                                 "steady_state_recompiles"}
        ns["eng"]._dur.flush_all()  # settle async confirms -> e2e samples
        snap = ns["obs"].snapshot()
        assert snap["engine"]["phases"]["commit_e2e"]["count"] > 0
        assert "autotune" in snap and "slo" in snap
    finally:
        if "obs" in ns:
            ns["obs"].close()
        if "eng" in ns:
            ns["eng"].close()


def test_ingress_quickstart_block():
    """The ISSUE 10 session-tier block: connect a bulk fleet, submit
    with auto-minted seqnos, pump, settle — exactly once."""
    src = _blocks()[6]
    assert "IngressPlane" in src and "connect_bulk" in src
    # shrink lanes + fleet for suite runtime; structure runs as written
    src = _patch(src, "10_000", "128")
    src = _patch(src, "50_000", "2_000")
    ns: dict = {}
    try:
        exec(compile(src, "README.md[ingress]", "exec"), ns)  # noqa: S102
        plane = ns["plane"]
        assert plane.counters["accepted"] > 0
        assert plane.window.queue_rows() == 0   # settled
        assert ns["eng"].committed_total() >= plane.counters["accepted"]
    finally:
        if "eng" in ns:
            ns["eng"].close()


def test_wire_quickstart_block():
    """The ISSUE 12 wire block: real TCP listener + at-least-once
    client + machine-level dedup — exactly-once-observable through a
    reconnect."""
    import time as _time
    src = _blocks()[7]
    assert "WireListener" in src and "WireClient" in src
    assert "DedupCounterMachine" in src
    # shrink lanes for suite runtime; structure runs as written
    src = _patch(src, "256, 3", "32, 3")
    # the documented busy-wait is fine interactively; bound it for CI
    src = _patch(src, "while lst.sweep() == 0:                      "
                      "# reader ring -> numpy batch\n    pass",
                 "deadline = __import__('time').monotonic() + 30\n"
                 "while lst.sweep() == 0:\n"
                 "    assert __import__('time').monotonic() < deadline")
    ns: dict = {}
    try:
        exec(compile(src, "README.md[wire]", "exec"), ns)  # noqa: S102
        cli = ns["cli"]
        deadline = _time.monotonic() + 30
        while cli.acked_count() < 3:
            cli.flush()
            ns["lst"].sweep()
            ns["plane"].pump(force=True)
            ns["plane"].settle()
            cli.poll()
            assert _time.monotonic() < deadline
        import numpy as np
        total = int(np.asarray(
            ns["eng"].consistent_read(np.arange(32))["value"]).sum())
        assert total == 42  # 5 + 7 + 30, each exactly once
    finally:
        if "lst" in ns:
            ns["lst"].close()
        if "cli" in ns:
            ns["cli"].close()
        if "eng" in ns:
            ns["eng"].close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_failover_quickstart_block(tmp_path):
    """The ISSUE 17 failover block: one small-geometry failover soak
    runs as written and the exactly-once oracle closes (the kill-9
    dies loudly in the victim's WAL thread by design)."""
    src = _blocks()[8]
    assert "run_failover_soak" in src
    # route the soak's durable dirs into the test sandbox
    src = _patch(src, "kill_wave=2)",
                 "kill_wave=2, data_dir=str(tmp_path))")
    ns: dict = {"tmp_path": tmp_path}
    exec(compile(src, "README.md[failover]", "exec"), ns)  # noqa: S102
    row = ns["row"]
    assert row["failover_lost_acked"] == 0
    assert row["failover_double_applied"] == 0
    assert row["failover_recovery_s"] > 0
    assert row["migrations"] >= 1


def test_telemetry_quickstart_block(tmp_path):
    src = _blocks()[4]
    assert "TelemetrySampler" in src and "Observatory" in src
    ring = str(tmp_path / "obs.jsonl")
    src = _patch(src, '"obs.jsonl"', "ring")
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    ns: dict = {"engine": eng, "ring": ring}
    exec(compile(src, "README.md[telemetry]", "exec"), ns)  # noqa: S102
    for _ in range(4):
        eng.uniform_step(2)
    ns["sampler"].drain()
    snap = ns["obs"].snapshot()
    assert snap["engine"]["telemetry"]["steps"] == 4
    import os
    assert os.path.exists(ring)

def test_read_quickstart_block():
    src = _blocks()[2]
    assert "read_lanes" in src and "TtlKvMachine" in src
    # shrink the documented 1024-lane config for suite runtime; the
    # structure (shapes, calls, assertions) runs exactly as written
    src = _patch(src, "1024", "64")
    ns: dict = {}
    exec(compile(src, "README.md[reads]", "exec"), ns)  # noqa: S102
    assert ns["ok"].all() and (ns["replies"][:, 1] == 42).all()
    assert (ns["watermark"] >= 0).all()


def test_the_commands_the_readme_names_exist():
    """Every ``python <file>`` and ``tools/<file>`` of the README's
    shell blocks is a file of the tree, and the benchmark's command is
    among them: the README sends nobody to a file that is gone."""
    shell = "\n".join(body for lang, body in _fenced()
                      if lang != "python")
    named = set(re.findall(r"python3? +(?!-)([\w./]+\.py)", shell)) \
        | set(re.findall(r"(?<![\w/])(tools/[\w./]+\.(?:py|sh))", shell))
    assert "benchmarks/run.py" in named and "chip_smoke.py" in named
    for path in sorted(named):
        assert os.path.isfile(os.path.join(ROOT, path)), path
