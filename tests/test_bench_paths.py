"""Bench child-mode contract tests — the driver runs bench.py unattended
on real hardware at round end, so every measurement mode must be
exercised continuously off-hardware: a mode that crashes or prints a
malformed line would silently cost the round its benchmark evidence.

Each child runs in a subprocess exactly as the bench parent launches it,
held to the CPU (the chip belongs to one process, and not to the
suite), at tiny configs sized for a loaded single-core box.
"""
import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

BASE_ENV = {
    **os.environ,
    "RA_TPU_BENCH_CHILD": "1",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "",
    "RA_TPU_BENCH_LANES": "64",
    "RA_TPU_BENCH_MEMBERS": "3",
    "RA_TPU_BENCH_CMDS": "8",
    "RA_TPU_BENCH_SECONDS": "0.5",
}


def run_child(extra, timeout=240):
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=timeout,
                       env={**BASE_ENV, **extra}, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, r.stdout
    return json.loads(lines[-1])


def test_child_throughput_mode_contract():
    doc = run_child({})
    assert doc["value"] > 0
    assert doc["p50_commit_latency_ms"] > 0
    assert doc["machine"] == "counter" and doc["durable"] is False
    assert doc["latency_samples"] > 0


def test_child_durable_mode_contract():
    doc = run_child({"RA_TPU_BENCH_DURABLE": "1"})
    assert doc["value"] > 0
    assert doc["durable"] is True
    assert "sync_mode" in doc and "wal_strategy" in doc


def test_child_fifo_machine_contract():
    doc = run_child({"RA_TPU_BENCH_MACHINE": "fifo"})
    assert doc["value"] > 0
    assert doc["machine"] == "fifo"


def test_child_superstep_mode_contract():
    """The fused-dispatch throughput row (ISSUE 5): K engine rounds per
    XLA dispatch through the dispatch-ahead driver.  Exercised in CI so
    the superstep path can't silently rot while only the classic path
    is benchmarked — the contract pins the pipeline stamps (realized
    fusion factor, driver sync counts) and the single-step reference +
    speedup fields the acceptance criterion reads."""
    doc = run_child({"RA_TPU_BENCH_SUPERSTEP": "4",
                     "RA_TPU_BENCH_DISPATCH_AHEAD": "2"})
    assert doc["value"] > 0
    assert doc["superstep_k"] == 4 and doc["dispatch_ahead"] == 2
    pipe = doc["pipeline"]
    assert pipe["superstep_dispatches"] > 0
    # realized fusion: the fused phase adds K inner steps per dispatch
    assert pipe["inner_steps"] >= 4 * pipe["superstep_dispatches"]
    assert pipe["blocks_staged"] > 0
    # dispatch-ahead ran ahead: window syncs are a small fraction of
    # dispatches (the in-flight cap, not a per-dispatch block)
    assert pipe["window_syncs"] <= pipe["superstep_dispatches"] + 2
    ref = doc["single_step_ref"]
    assert ref["value"] > 0 and ref["steps"] > 0
    assert doc["speedup_vs_single_step"] > 0
    assert doc["latency_mode"] == "step_stamped"
    assert doc["p50_commit_latency_ms"] > 0


def test_child_superstep_durable_mode_contract():
    """Fused dispatches over the durable engine: confirms stay
    fsync-gated (the WAL stats ride along) and the mode completes with
    a sane latency distribution.  Autotune opt-in rides along (ISSUE
    9): knobs the loop cannot apply are FROZEN via bounds — the tail's
    knob stamps must describe the measured dispatches — and any K the
    controller picked is restaged live by the fused loop."""
    doc = run_child({"RA_TPU_BENCH_SUPERSTEP": "4",
                     "RA_TPU_BENCH_DURABLE": "1",
                     "RA_TPU_BENCH_AUTOTUNE": "1"})
    assert doc["value"] > 0
    assert doc["durable"] is True and doc["superstep_k"] == 4
    assert doc["pipeline"]["superstep_dispatches"] > 0
    assert "wal" in doc
    tun = doc["autotune"]
    assert tun["knobs"]["cmds_per_step"] == 8  # frozen to the env cmds
    assert tun["knobs"]["superstep_k"] >= 1
    # inner_steps must agree with whatever K sequence really ran (a
    # decision the loop did not apply would break this bookkeeping)
    assert doc["pipeline"]["inner_steps"] >= doc["steps"]


def test_child_multichip_mode_contract():
    """The sharded-mesh frontier sweep (ISSUE 11): per mesh shape x
    lane rung, the superstep+dispatch-ahead pipeline over sharded
    state vs the single-step reference, with the autotuner's chosen
    knobs and the engine_pipeline config stamped per row.  Exercised
    off-hardware at a tiny ladder on the 8 forced-host devices so the
    sweep cannot rot while only single-device modes are benchmarked."""
    doc = run_child({"RA_TPU_BENCH_MODE": "multichip",
                     "RA_TPU_BENCH_MESH_LANES": "64",
                     "RA_TPU_BENCH_SECONDS": "0.4",
                     "XLA_FLAGS":
                     "--xla_force_host_platform_device_count=8"},
                    timeout=420)
    assert doc["value"] > 0 and doc["n_devices"] == 8
    rows = doc["multichip"]
    assert {r["mesh"] for r in rows} == {"1x8", "2x4"}
    # the shared rung clamp (ladder_rungs): >= 16 lanes per lane-axis
    # device, so the 64-lane override clamps to 128 on the 1x8 shape
    expect_lanes = {"1x8": 128, "2x4": 64}
    for r in rows:
        assert r["value"] > 0 and r["lanes"] == expect_lanes[r["mesh"]]
        assert r["single_step_ref"]["value"] > 0
        assert r["speedup_vs_single_step"] > 0
        assert r["latency_mode"] == "step_stamped"
        assert r["p50_commit_latency_ms"] > 0
        # the cross-round attribution stamp (ISSUE 11 satellite)
        ep = r["engine_pipeline"]
        assert ep["mesh_shape"] == r["mesh"]
        assert ep["superstep_k"] >= 1 and ep["dispatch_ahead"] >= 1
        assert "donation" in ep and "wal_shard_layout" in ep
        # pipeline counters rode the sweep (fused dispatches happened)
        assert r["pipeline"]["superstep_dispatches"] > 0
        assert r["pipeline"]["mesh_shape"] == r["mesh"]
        # the autotuner drove the walk and its knobs are stamped
        assert r["autotune"]["knobs"]["superstep_k"] == \
            ep["superstep_k"]
        assert r["tune_k_rates"]
    assert doc["best_point"]["mesh"] in ("1x8", "2x4")


def test_bench_diff_compares_multichip_tails(tmp_path):
    """ISSUE 11 satellite: bench_diff pairs multichip rows per mesh
    shape x lane rung (cmds_per_s higher-is-better) alongside the
    existing keys, and the dryrun-format rows (cmds_per_s, no value)
    compare too."""
    import tools.bench_diff as bd
    old = {"value": 2e6, "multichip": [
        {"mesh": "1x8", "lanes": 1024, "value": 1.5e6,
         "p99_commit_latency_ms": 20.0},
        {"mesh": "2x4", "lanes": 1024, "cmds_per_s": 1.6e6},
        {"mesh": "2x4", "lanes": 8192, "value": 2.0e6}]}
    new = {"value": 2e6, "multichip": [
        {"mesh": "1x8", "lanes": 1024, "value": 1.6e6,
         "p99_commit_latency_ms": 90.0},
        {"mesh": "2x4", "lanes": 1024, "cmds_per_s": 0.5e6},
        {"mesh": "2x4", "lanes": 8192, "value": 2.1e6}]}
    res = bd.diff(old, new, noise_pct=10.0)
    rows = res["rows"]
    assert "multichip/1x8/lanes1024" in rows
    assert "multichip/2x4/lanes1024" in rows
    assert "multichip/2x4/lanes8192" in rows
    by = {(n, f["metric"]): f for n, fs in rows.items() for f in fs}
    # per-shape throughput regression flagged (higher-is-better)...
    assert by[("multichip/2x4/lanes1024", "value")]["regression"]
    # ...latency rise flagged, healthy rows clean
    assert by[("multichip/1x8/lanes1024",
               "p99_commit_latency_ms")]["regression"]
    assert not by[("multichip/2x4/lanes8192", "value")]["regression"]
    assert res["regressions"] >= 2
    assert bd.diff(old, old, noise_pct=10.0)["regressions"] == 0


def test_superstep_flag_sets_env():
    """`bench.py --superstep [K]` resolves to the child env contract
    ("auto" = the system-level superstep_k tunable)."""
    import bench
    env = {}
    try:
        os.environ.pop("RA_TPU_BENCH_SUPERSTEP", None)
        bench._parse_flags(["--superstep", "4"])
        env["explicit"] = os.environ.get("RA_TPU_BENCH_SUPERSTEP")
        os.environ.pop("RA_TPU_BENCH_SUPERSTEP", None)
        bench._parse_flags(["--superstep"])
        env["auto"] = os.environ.get("RA_TPU_BENCH_SUPERSTEP")
    finally:
        os.environ.pop("RA_TPU_BENCH_SUPERSTEP", None)
    assert env == {"explicit": "4", "auto": "auto"}


def test_child_frontier_mode_contract():
    doc = run_child({"RA_TPU_BENCH_MODE": "frontier",
                     "RA_TPU_BENCH_SIZES": "1,8",
                     "RA_TPU_BENCH_WINDOW": "2",
                     "RA_TPU_BENCH_SECONDS": "0.5"})
    assert doc["value"] > 0
    assert len(doc["points"]) == 2
    for p in doc["points"]:
        assert p["cmds_per_step"] in (1, 8)
        assert p["value"] > 0
        assert p["batches_measured"] > 0
    assert doc["sync_rtt_ms"] > 0
    assert doc["best_point"] in doc["points"]


def test_frontier_default_operating_point_holds_p99_bar():
    """The documented default operating point (32 cmds/step, window 4 —
    docs/BENCHMARKS.md) must be reported by the frontier sweep, meet
    the p99 bar, and sustain the north-star line scaled to the lane
    count (1M cmds/s at 10k lanes == 100 cmds/s/lane).

    Retries (p99 on a shared/sandboxed CPU box is scheduler-jitter
    bound; real hardware passes first try), and the p99 bar is the
    sweep's EFFECTIVE bar — lifted
    per point to the backend's own pipeline floor (window * solo step
    p99, measured unpipelined so a pipelining/readback regression
    cannot hide in it).  On real hardware steps are sub-ms and the
    effective bar equals the 25ms/RTT bar; on a shared CPU box it
    reflects what the backend can execute at all.  The p50 pin stays
    against the HARD bar — a systematic latency regression moves the
    median, not just the tail."""
    doc = None
    for _attempt in range(4):
        doc = run_child({"RA_TPU_BENCH_MODE": "frontier",
                         "RA_TPU_BENCH_SIZES": "8,32",
                         "RA_TPU_BENCH_WINDOW": "4",
                         "RA_TPU_BENCH_LANES": "256",
                         "RA_TPU_BENCH_SECONDS": "1.0"})
        dp = doc["default_point"]
        assert dp is not None and dp["cmds_per_step"] == 32
        if dp["meets_p99_bar"] and dp["value"] >= 100.0 * 256:
            break
    assert 0 < dp["p50_commit_latency_ms"] < doc["p99_bar_ms"], dp
    assert dp["meets_p99_bar"], (dp, doc["p99_bar_ms"])
    assert dp["value"] >= 100.0 * 256, dp
    assert doc["p99_bar_ms"] >= 25.0


def test_classic_bench_contract():
    """bench_classic.py (the ra_bench-parity run over the full node
    path, ra_bench.erl:84-129) must emit one JSON line with both phase
    rows, host metadata, and nonzero throughput at a tiny config."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_classic.py")],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "RA_TPU_CLASSIC_SECONDS": "1.5",
             "RA_TPU_CLASSIC_DEGREE": "2",
             "RA_TPU_CLASSIC_PIPE": "50"},
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    doc = json.loads(lines[0])
    assert doc["metric"] == "classic_node_committed_cmds_per_sec"
    assert doc["unit"] == "cmds/s"
    detail = doc["detail"]
    assert detail["errors"] == {}, detail["errors"]
    assert "cpu_count" in detail["host"]
    for phase in ("local", "tcp"):
        row = detail[phase]
        assert row["value"] > 0, (phase, row)
        assert row["durable"] is True
        assert row["p50_applied_latency_ms"] > 0
    # ISSUE 6 satellite: the local phase stamps the leader system's
    # Observatory snapshot (WAL fsync p50/p99 + queue depth)
    wal = detail["local"]["observatory"]["system"]["counters"]["wal"]
    assert "fsync_p50_ms" in wal and "queue_depth" in wal
    # ISSUE 7 satellite: the tcp phase's client-side Observatory
    # carries the reliable-RPC counters (RPC_FIELDS reach the
    # snapshot/exposition like the WAL stats do)
    rpc = detail["tcp"]["observatory"]["rpc"]
    assert "rpc_calls" in rpc and "rpc_dedup_hits" in rpc
    # ISSUE 13 satellites: the host envelope carries the fd cap next
    # to cpu_count (cross-host drift attribution) and both phases
    # stamp the CLASSIC_FIELDS batching-health shape — AER batches
    # actually multi-entry, and the local (shared-WAL) phase shows the
    # group-commit fan-in factor
    assert detail["host"]["rlimit_nofile"] > 0
    from ra_tpu.metrics import CLASSIC_FIELDS
    for phase in ("local", "tcp"):
        cb = detail[phase]["classic_batch"]
        assert cb["aer_batches_sent"] > 0, (phase, cb)
        assert cb["aer_batch_entries"] > cb["aer_batches_sent"], \
            (phase, cb)  # batching really happened (entries/batch > 1)
    local_cb = detail["local"]["classic_batch"]
    assert set(CLASSIC_FIELDS) <= set(local_cb)
    assert local_cb["records_per_fsync"] != 0
    # ...and the classic stats ride the local Observatory snapshot
    assert detail["local"]["observatory"]["classic"][
        "aer_batches_sent"] > 0
    # ISSUE 16: the classic tail stamps the device keys as ZEROS — the
    # classic plane is host-only; a nonzero compile count here means
    # jit dispatch leaked into the classic path
    assert doc["n_compiles"] == 0 and doc["n_recompiles"] == 0
    assert doc["transfer_bytes"] == 0


def test_bench_diff_compares_classic_captures(tmp_path):
    """ISSUE 13 satellite: bench_diff pairs classic captures per phase
    (classic/local + classic/tcp): throughput drops (higher-better,
    the classic_node_committed_cmds_per_sec sub-values) and
    p99_applied_latency_ms rises (lower-better) are flagged; the r05
    on-disk capture shape itself produces the rows."""
    import tools.bench_diff as bd
    r05 = bd._load(os.path.join(REPO, "BENCH_CLASSIC_r05.json"))
    rows = bd.extract_rows(r05)
    assert "classic/local" in rows and "classic/tcp" in rows
    new = {"metric": "classic_node_committed_cmds_per_sec",
           "value": 1000.0,
           "detail": {
               "local": {"value": 8000.0,
                         "p99_applied_latency_ms": 500.0},
               "tcp": {"value": 1000.0,
                       "p99_applied_latency_ms": 1100.0}}}
    res = bd.diff(r05, new, noise_pct=10.0)
    by = {(n, f["metric"]): f for n, fs in res["rows"].items()
          for f in fs}
    # local throughput halved + latency doubled: both flagged
    assert by[("classic/local", "value")]["regression"]
    assert by[("classic/local",
               "p99_applied_latency_ms")]["regression"]
    # tcp p99 improved: clean
    assert not by[("classic/tcp",
                   "p99_applied_latency_ms")]["regression"]
    assert res["regressions"] >= 3  # local value+p99, tcp value
    # self-compare is clean
    assert bd.diff(r05, r05, noise_pct=10.0)["regressions"] == 0


def test_bench_tail_carries_observatory_snapshot():
    """ISSUE 6 satellite: the throughput tail stamps the final
    Observatory snapshot — telemetry summary, sampler health, and the
    per-shard WAL fsync p50/p99 + queue depths — so cross-round
    comparisons stop hand-collecting fields."""
    doc = run_child({"RA_TPU_BENCH_DURABLE": "1",
                     "RA_TPU_BENCH_WAL_SHARDS": "2"})
    eng = doc["observatory"]["engine"]
    tel = eng["telemetry"]
    assert tel["steps"] > 0
    assert tel["committed_total"] > 0
    assert tel["stall_threshold"] > 0
    assert eng["sampler"]["samples_harvested"] >= 1
    assert eng["sampler"]["samples_started"] >= 1
    shards = eng["wal"]["shards"]
    assert len(shards) == 2
    for sh in shards:
        assert "fsync_p50_ms" in sh and "fsync_p99_ms" in sh
        assert "queue_depth" in sh and "jobs_pending" in sh
    # pipeline counters ride in the snapshot too (the SLO-autotuner
    # substrate: rate fields next to the knobs that move them)
    assert eng["pipeline"]["dispatches"] > 0


def test_bench_tail_carries_slo_and_phase_attribution():
    """ISSUE 9: the durable tail stamps the SLO verdicts (evaluated
    over the run's own ring windows) and the phase attribution rides
    the Observatory snapshot — budget decomposition + objective health
    land in the same artifact the rounds compare."""
    doc = run_child({"RA_TPU_BENCH_DURABLE": "1",
                     "RA_TPU_BENCH_WAL_SHARDS": "2",
                     "RA_TPU_BENCH_SECONDS": "1.0"})
    objs = doc["slo"]["objectives"]
    for name in ("commit_p99_ms", "fsync_p99_ms", "cmds_per_s"):
        assert name in objs
        assert objs[name]["verdict"] in ("ok", "breach", "alert",
                                         "no_data")
        assert "burn_fast" in objs[name]
    # the run produced real windows and real verdicts (a 1s durable
    # run commits plenty; commit_e2e always samples on this path)
    assert doc["slo"]["windows"] >= 2
    assert objs["commit_p99_ms"]["value"] is not None
    ph = doc["observatory"]["engine"]["phases"]
    for p in ("queue_wait", "wal_encode", "fsync_wait",
              "confirm_publish", "commit_e2e"):
        assert ph[p]["count"] > 0, p
    assert ph["dropped"] == 0
    # the tunable knobs are stamped next to the rates they move (RA07)
    pipe = doc["observatory"]["engine"]["pipeline"]
    assert pipe["cmds_per_step"] == 8
    assert pipe["wal_max_batch_interval_ms"] >= 0.0


def test_bench_diff_smoke_flags_regressions(tmp_path):
    """tools/bench_diff.py consumes the live tail format (pinned here
    so the format cannot drift out from under it): same-doc compare is
    clean/exit 0; a degraded doc flags value + p99 regressions and
    exits 1."""
    doc = run_child({})
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(doc))
    b.write_text(json.dumps(doc))
    diff_tool = os.path.join(REPO, "tools", "bench_diff.py")
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b),
                        "--json"], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.loads(r.stdout)
    assert res["rows_compared"] == 1 and res["regressions"] == 0
    worse = dict(doc)
    worse["value"] = doc["value"] * 0.5
    worse["p99_commit_latency_ms"] = \
        doc["p99_commit_latency_ms"] * 3 + 10
    b.write_text(json.dumps(worse))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert r.stdout.count("REGRESSION") == 2, r.stdout
    # history capture records (BENCH_r*.json wrappers) unwrap too
    wrapped = tmp_path / "hist.json"
    wrapped.write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "", "parsed": doc}))
    r = subprocess.run([sys.executable, diff_tool, str(wrapped),
                        str(a)], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_telemetry_opt_out():
    """RA_TPU_BENCH_TELEMETRY=0 runs the legacy tail (no sampler, no
    observatory key) — the A side of the overhead comparison."""
    doc = run_child({"RA_TPU_BENCH_TELEMETRY": "0"})
    assert doc["value"] > 0
    assert "observatory" not in doc


def test_child_wire_mode_contract(tmp_path):
    """ISSUE 12: the ``bench.py --wire`` child prints one JSON tail
    carrying the wire frontier keys (format pinned — bench_diff and
    the round captures parse this shape).  Tiny CPU-scaled config."""
    doc = run_child({
        "RA_TPU_BENCH_MODE": "wire",
        "RA_TPU_BENCH_WIRE_CONNS": "512",
        "RA_TPU_BENCH_WIRE_LANES": "64",
        "RA_TPU_BENCH_WIRE_WAVES": "4",
        "RA_TPU_BENCH_WIRE_DURABLE": "0",
    })
    assert doc["value"] > 0
    assert doc["wire_cmds_per_s"] == doc["value"]
    assert 0 <= doc["wire_shed_rate"] <= 1
    assert "wire_reconnect_recovery_s" in doc
    assert doc["conns"] == 512 and doc["metric"] == \
        "wire_committed_cmds_per_sec"
    assert doc["storm_requeued"] > 0       # the storm actually ran
    assert "host" in doc


def test_wire_flag_sets_env():
    """--wire routes the parent into the wire-mode child (the flag
    twin of --multichip)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_flags", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    old = os.environ.pop("RA_TPU_BENCH_MODE", None)
    try:
        mod._parse_flags(["--wire"])
        assert os.environ["RA_TPU_BENCH_MODE"] == "wire"
    finally:
        if old is None:
            os.environ.pop("RA_TPU_BENCH_MODE", None)
        else:
            os.environ["RA_TPU_BENCH_MODE"] = old


def test_bench_diff_compares_wire_keys(tmp_path):
    """ISSUE 12 satellite: when both tails carry the wire keys,
    bench_diff flags throughput drops, shed-rate rises AND reconnect-
    recovery regressions (0 is a healthy baseline for both; a -1
    recovery sentinel = no storm ran, skipped)."""
    diff_tool = os.path.join(REPO, "tools", "bench_diff.py")
    base = {"value": 90_000.0, "wire_cmds_per_s": 90_000.0,
            "wire_shed_rate": 0.0, "wire_reconnect_recovery_s": 0.1}
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(base))
    worse = {"value": 40_000.0, "wire_cmds_per_s": 40_000.0,
             "wire_shed_rate": 0.3, "wire_reconnect_recovery_s": 3.0}
    b.write_text(json.dumps(worse))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    # value + wire_cmds_per_s + shed rate + recovery
    assert r.stdout.count("REGRESSION") == 4, r.stdout
    b.write_text(json.dumps(base))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_tail_stamps_device_keys():
    """ISSUE 16: the throughput tail stamps the device-plane keys
    (format pinned — bench_diff compares them), and the real bench
    dispatch path itself runs recompile-free: warm-up compiles are
    counted, steady state adds none."""
    doc = run_child({})
    for k in ("n_compiles", "n_recompiles", "compile_time_s",
              "transfer_bytes", "transfer_bytes_per_cmd",
              "peak_live_bytes"):
        assert k in doc, k
    assert doc["n_compiles"] > 0          # warm-up compiles counted
    assert doc["n_recompiles"] == 0       # the zero-retrace pin, live
    assert doc["transfer_bytes"] > 0
    assert doc["transfer_bytes_per_cmd"] > 0
    assert doc["peak_live_bytes"] > 0     # watermarks rode the harvest


def test_bench_parent_promotes_device_keys():
    """The parent headline line carries the measuring CHILD's device
    stamp (counters are per-process; the parent never dispatches), so
    bench_diff can compare headline rows across rounds."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    row = {"value": 1.0, "n_compiles": 3, "n_recompiles": 0,
           "transfer_bytes": 10, "unrelated": 7}
    out = bench._promote_device_keys(row)
    assert out == {"n_compiles": 3, "n_recompiles": 0,
                   "transfer_bytes": 10}


def test_bench_diff_compares_device_keys(tmp_path):
    """ISSUE 16 satellite: n_compiles/n_recompiles compare ABSOLUTELY
    (any growth flags — a one-per-round retrace hides inside a 10%
    noise bar), the cost keys lower-is-better with 0 a healthy
    baseline (classic tails stamp zeros)."""
    diff_tool = os.path.join(REPO, "tools", "bench_diff.py")
    base = {"value": 1000.0, "n_compiles": 6, "n_recompiles": 0,
            "compile_time_s": 1.5, "transfer_bytes_per_cmd": 84.0,
            "peak_live_bytes": 50_000}
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    # +1 compile is only ~17% of 6 but must flag regardless of bar;
    # a recompile appearing from 0 must flag too
    worse = {"value": 1000.0, "n_compiles": 7, "n_recompiles": 1,
             "compile_time_s": 3.0, "transfer_bytes_per_cmd": 120.0,
             "peak_live_bytes": 50_000}
    b.write_text(json.dumps(worse))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b),
                        "--noise-pct", "25"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    # n_compiles + n_recompiles (absolute) + compile_time_s +
    # transfer_bytes_per_cmd (both past the 25% bar); peak unchanged
    assert r.stdout.count("REGRESSION") == 4, r.stdout
    # improvements are never regressions: dropping compiles is clean
    b.write_text(json.dumps(dict(base, n_compiles=3)))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_diff_compares_ingress_keys(tmp_path):
    """ISSUE 10 satellite: when both tails carry the ingress keys,
    bench_diff flags throughput drops (higher-is-better) and shed-rate
    rises — including a shed rate APPEARING from a healthy 0, which the
    latency-style o>0 guard would have skipped; tails without the keys
    keep comparing exactly as before."""
    diff_tool = os.path.join(REPO, "tools", "bench_diff.py")
    base = {"value": 1000.0, "ingress_cmds_per_s": 400_000.0,
            "ingress_shed_rate": 0.0}
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b),
                        "--json"], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.loads(r.stdout)
    metrics = [f["metric"] for f in res["rows"]["headline"]]
    assert "ingress_cmds_per_s" in metrics
    assert "ingress_shed_rate" in metrics
    worse = {"value": 1000.0, "ingress_cmds_per_s": 300_000.0,
             "ingress_shed_rate": 0.25}
    b.write_text(json.dumps(worse))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert r.stdout.count("REGRESSION") == 2, r.stdout
    # a tail without the ingress keys is compared on what it has
    b.write_text(json.dumps({"value": 1000.0}))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_diff_compares_read_keys(tmp_path):
    """ISSUE 20 satellite: when both tails carry the read-frontier keys
    (the `bench.py --reads` capture format, pinned here), bench_diff
    flags read-throughput drops, read_p99 rises, shed-rate rises AND
    stale refusals appearing from a healthy 0; the -1 "no reads ran"
    latency sentinel is skipped; tails without the keys keep comparing
    exactly as before."""
    diff_tool = os.path.join(REPO, "tools", "bench_diff.py")
    base = {"value": 25_000.0, "read_cmds_per_s": 25_000.0,
            "read_p99_ms": 4.0, "read_shed_rate": 0.0,
            "read_stale_refused": 0.0}
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b),
                        "--json"], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    res = json.loads(r.stdout)
    metrics = [f["metric"] for f in res["rows"]["headline"]]
    assert "read_cmds_per_s" in metrics
    assert "read_p99_ms" in metrics
    assert "read_shed_rate" in metrics
    assert "read_stale_refused" in metrics
    worse = {"value": 25_000.0, "read_cmds_per_s": 15_000.0,
             "read_p99_ms": 9.0, "read_shed_rate": 0.3,
             "read_stale_refused": 12.0}
    b.write_text(json.dumps(worse))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1, r.stdout
    assert r.stdout.count("REGRESSION") == 4, r.stdout
    # a write-only tail (read_p99_ms -1 sentinel, no read keys) still
    # compares on what it has
    b.write_text(json.dumps({"value": 25_000.0, "read_p99_ms": -1.0}))
    r = subprocess.run([sys.executable, diff_tool, str(a), str(b)],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
