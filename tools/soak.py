"""Fuzz soak runner — drives the interleaving fuzz families from
tests/test_props.py over fresh seed ranges (the in-suite parametrize
lists anchor known bug-finding seeds; this explores NEW schedules).

Usage:  python tools/soak.py [seeds_per_family] [offset]
        python tools/soak.py --disk-faults SEED [n]
        python tools/soak.py --superstep SEED [n]
        python tools/soak.py --obs SEED [n] [jsonl_path]
        python tools/soak.py --blackbox SEED [n]
        python tools/soak.py --ingress SEED [n] [--mesh]
        python tools/soak.py --wire SEED [--durable] [--c1m]
        python tools/soak.py --device-obs SEED [n]
        python tools/soak.py --failover SEED [SEED...]
        python tools/soak.py --geo SEED [SEED...]
        python tools/soak.py --reads SEED [n]

``--wire`` climbs the ISSUE 12 connection ladder (ra_tpu/wire/soak.py
run_wire_soak): C10k (with a real-socket side-car) → C100k loopback
connections — add ``--c1m`` for the full C1M rung — each rung the
whole wire path (fixed-stride frames → per-connection rings →
vectorized sweep → ingress → fused dispatch) under a reconnect storm,
election chaos, and a standing transport FaultPlan, closed by the
exactly-once-observable oracle (machine-level dedup).  ``--durable``
adds fsync-gated commits with a seeded DiskFaultPlan.  Prints one
JSON tail per rung carrying ``wire_cmds_per_s``/``wire_shed_rate``/
``wire_reconnect_recovery_s``.

``--ingress`` runs the ISSUE 10 acceptance scenario at FULL scale
(tests/test_ingress.run_ingress_soak): ~1M simulated sessions fanning
into 10k lanes through the session-directory → coalescer →
backpressure-ladder path, with duplicate resends, member-failure/
election chaos and a seeded DiskFaultPlan injecting real WAL faults on
the durable variant — then an exactly-once oracle check (final machine
state == the dedup'd placed set, so no resend applied twice) plus
monotone consistent-read probes.  Prints a one-line JSON tail carrying
``ingress_cmds_per_s``/``ingress_shed_rate``.

``--disk-faults`` runs the storage-plane chaos family instead
(tests/test_disk_faults.run_disk_chaos): ``n`` seeded episodes starting
at SEED, each a random DiskFaultPlan + WAL crash over a live durable
log with a cold-restart oracle check.

``--superstep`` runs the fused-dispatch parity family
(tests/test_superstep.run_superstep_fuzz): ``n`` seeded episodes of
random K/elect schedules + member failures, each exact-parity checked
against the single-step oracle every round (ISSUE 5).

``--blackbox`` runs the flight-recorder chaos family
(tests/test_blackbox.run_blackbox_chaos): ``n`` seeded episodes, each
a classic durable cluster taking traced traffic through a random
DiskFaultPlan, then a kill-9 of the WAL under the ACTIVE plan —
asserting the post-mortem bundle exists, parses, names the injected
fault, and that ``tools/ra_trace.py`` reconstructs the complete
lifecycle (ingress→submit→append→WAL write→fsync→confirm→commit→apply)
of a command the fault touched (ISSUE 7 acceptance).

``--obs`` runs the telemetry-plane chaos family
(tests/test_telemetry.run_stall_chaos): ``n`` seeded episodes that
break a random lane's quorum under traffic and assert the stall is
*detected* by the device-resident telemetry (stalled-lane count +
top-K offenders, within one sampling window), not just recovered —
while every harvested Observatory snapshot is appended to a JSONL
ring (default ``obs.jsonl``; follow it live with
``python tools/ra_top.py <path>``).

``--device-obs`` runs the device-plane observatory chaos family
(tests/test_devicewatch.run_device_obs_chaos, ISSUE 16): ``n`` seeded
episodes, each a DURABLE engine taking fixed-shape superstep traffic
through election churn and a seeded WAL DiskFaultPlan — asserting the
recompile sentinel stays QUIET (host-plane chaos is not shape drift),
then that a deliberate mixed-shape probe (K=8 -> K=4) IS detected
within one Observatory window and attributed to the drifting block
shape.  Engine configs are seed-varied so every episode compiles
fresh jit variants.

``--reads`` runs the linearizable-read oracle family
(tests/test_read_plane.run_read_oracle, ISSUE 20): ``n`` seeded
episodes, each driving BOTH read machines (TtlKvMachine, StreamMachine)
single-device AND on the sharded 8-way lane mesh — plus one durable run
under a seeded WAL DiskFaultPlan — through election churn, leader
kills and majority partitions while a host model machine folds the
same committed history.  Every read the device SERVES must equal the
model's answer over the full committed prefix (a reply matching only
an older prefix is a stale serve, pinned 0); a leader cut from quorum
must REFUSE once its lease expires; healed lanes must serve again.

Prints one line per family with pass/fail counts; exits nonzero on the
first failing seed (which should then be added to the in-suite list).
"""
from __future__ import annotations

import os

# force the CPU backend BEFORE anything imports jax: the soaks are
# correctness harnesses that need eight forced host devices (mesh
# families) or several engine processes on one host (geo), which one
# chip cannot give them (same setup as tests/conftest.py)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import tempfile
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import test_props as tp  # noqa: E402


def _disk_fault_main(argv: list) -> int:
    """--disk-faults SEED [n]: the storage-plane chaos family."""
    import test_disk_faults as tdf

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 50
    t0 = time.time()
    failed = []
    for s in range(seed, seed + n):
        with tempfile.TemporaryDirectory(prefix="soak_disk_") as d:
            try:
                tdf.run_disk_chaos(s, d)
            except Exception:  # noqa: BLE001 — report seed + continue
                failed.append(s)
                if len(failed) == 1:
                    traceback.print_exc()
    print(f"disk_faults: {n - len(failed)}/{n} ok in "
          f"{time.time() - t0:.1f}s"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def _superstep_main(argv: list) -> int:
    """--superstep SEED [n]: fresh fused-dispatch parity schedules."""
    import test_superstep as tss

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 50
    t0 = time.time()
    failed = []
    for s in range(seed, seed + n):
        try:
            tss.run_superstep_fuzz(s)
        except Exception:  # noqa: BLE001 — report seed + continue
            failed.append(s)
            if len(failed) == 1:
                traceback.print_exc()
    print(f"superstep: {n - len(failed)}/{n} ok in "
          f"{time.time() - t0:.1f}s"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def _obs_main(argv: list) -> int:
    """--obs SEED [n] [jsonl_path]: telemetry stall-detection chaos,
    Observatory snapshots streamed to a JSONL ring for ra_top."""
    import test_telemetry as tt

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 10
    path = argv[2] if len(argv) > 2 else "obs.jsonl"
    t0 = time.time()
    failed = []
    detect_windows = []
    for s in range(seed, seed + n):
        try:
            res = tt.run_stall_chaos(s, obs_path=path)
            detect_windows.append(res["detected_at"] - res["stall_from"])
        except Exception:  # noqa: BLE001 — report seed + continue
            failed.append(s)
            if len(failed) == 1:
                traceback.print_exc()
    lag = (f"  detect_lag_steps p50={sorted(detect_windows)[len(detect_windows) // 2]}"
           if detect_windows else "")
    print(f"obs_stalls: {n - len(failed)}/{n} ok in "
          f"{time.time() - t0:.1f}s{lag}  ring={path}"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def _blackbox_main(argv: list) -> int:
    """--blackbox SEED [n]: the flight-recorder chaos family."""
    import test_blackbox as tb

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 10
    t0 = time.time()
    failed = []
    traces = faults_seen = 0
    last = {}
    for s in range(seed, seed + n):
        with tempfile.TemporaryDirectory(prefix="soak_bb_") as d:
            try:
                last = tb.run_blackbox_chaos(s, d)
                traces += last["n_traces"]
                faults_seen += last["fault_events"]
            except Exception:  # noqa: BLE001 — report seed + continue
                failed.append(s)
                if len(failed) == 1:
                    traceback.print_exc()
    print(f"blackbox: {n - len(failed)}/{n} ok in "
          f"{time.time() - t0:.1f}s  traced_cmds={traces} "
          f"injected_faults={faults_seen}"
          + (f"  last_explained={last.get('trace')}" if last else "")
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def _ingress_main(argv: list) -> int:
    """--ingress SEED [n] [--mesh]: the million-session fan-in soak
    (ISSUE 10).  ``--mesh`` (ISSUE 11) runs it end-to-end on lane
    state sharded across every forced-host device — 1M sessions into
    >= 100k lanes over >= 8 devices, durable with PER-DEVICE WAL
    shards, under the same disk-fault + election chaos and
    exactly-once oracle."""
    import json

    import test_ingress as ti

    mesh = "--mesh" in argv
    argv = [a for a in argv if a != "--mesh"]
    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 1
    t0 = time.time()
    failed = []
    last = {}
    for s in range(seed, seed + n):
        with tempfile.TemporaryDirectory(prefix="soak_ing_") as d:
            try:
                last = ti.run_ingress_soak(
                    s, sessions=1_000_000,
                    lanes=102_400 if mesh else 10_000, waves=24,
                    wave_rows=200_000, durable_dir=d, disk_faults=True,
                    mesh=mesh)
            except Exception:  # noqa: BLE001 — report seed + continue
                failed.append(s)
                if len(failed) == 1:
                    traceback.print_exc()
    print(f"ingress{'-mesh' if mesh else ''}: "
          f"{n - len(failed)}/{n} ok in {time.time() - t0:.1f}s"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    if last:
        # the JSON tail (ingress throughput/shed keys)
        # with the host envelope (fd cap + core count, ISSUE 13 — the
        # drift dimensions the cross-host comparisons kept missing)
        from ra_tpu.wire.soak import _host_envelope
        last["host"] = _host_envelope()
        print(json.dumps(last), flush=True)
    return 1 if failed else 0


def _wire_main(argv: list) -> int:
    """--wire SEED [--durable] [--c1m]: the C10k→C1M ladder."""
    from ra_tpu.wire.soak import ladder_main

    durable = "--durable" in argv
    c1m = "--c1m" in argv
    argv = [a for a in argv if not a.startswith("--")]
    seed = int(argv[0]) if argv else 0
    rungs = [10_000, 100_000] + ([1_000_000] if c1m else [])
    t0 = time.time()
    try:
        ladder_main(seed, rungs, lanes=1024, waves=12,
                    durable=durable, disk_faults=durable)
    except Exception:  # noqa: BLE001 — report + nonzero exit
        traceback.print_exc()
        print(f"wire ladder: FAILED in {time.time() - t0:.1f}s",
              flush=True)
        return 1
    print(f"wire ladder: {len(rungs)}/{len(rungs)} rungs ok in "
          f"{time.time() - t0:.1f}s", flush=True)
    return 0


def _failover_main(argv: list) -> int:
    """--failover SEED [SEED...] [--disk-faults]: placement-failover
    soak — kill-9 one lane engine mid-traffic, classic control plane
    commits the re-placement, sessions re-home, exactly-once oracle
    over the union of both engines' state."""
    from ra_tpu.placement.soak import failover_main

    disk = "--disk-faults" in argv
    argv = [a for a in argv if not a.startswith("--")]
    seeds = [int(a) for a in argv] or [0]
    t0 = time.time()
    try:
        rows = failover_main(seeds, disk_faults=disk)
    except Exception:  # noqa: BLE001 — report + nonzero exit
        traceback.print_exc()
        print(f"failover: FAILED in {time.time() - t0:.1f}s",
              flush=True)
        return 1
    lost = sum(r["failover_lost_acked"] for r in rows)
    print(f"failover: {len(rows)}/{len(seeds)} seeds ok in "
          f"{time.time() - t0:.1f}s  lost_acked={lost}", flush=True)
    return 1 if lost else 0


def _geo_main(argv: list) -> int:
    """--geo SEED [SEED...]: the geo-distributed survival soak —
    control cluster + two engine hosts as separate OS processes behind
    a latency-domain matrix (control quorum 80-150 ms away), live TCP
    wire traffic, a delay-only episode that must migrate NOTHING, then
    SIGKILL of one engine host: detection over the reliable RPC tier,
    adoption + re-home over host_* control verbs, exactly-once oracle
    over both engines read back over RPC."""
    from ra_tpu.placement.geo import geo_main

    seeds = [int(a) for a in argv if not a.startswith("--")] or [0]
    t0 = time.time()
    try:
        rows = geo_main(seeds)
    except Exception:  # noqa: BLE001 — report + nonzero exit
        traceback.print_exc()
        print(f"geo: FAILED in {time.time() - t0:.1f}s", flush=True)
        return 1
    lost = sum(r["geo_lost_acked"] for r in rows)
    false_mig = sum(r["geo_false_migrations"] for r in rows)
    print(f"geo: {len(rows)}/{len(seeds)} seeds ok in "
          f"{time.time() - t0:.1f}s  lost_acked={lost} "
          f"false_migrations={false_mig}", flush=True)
    return 1 if (lost or false_mig) else 0


def _device_obs_main(argv: list) -> int:
    """--device-obs SEED [n]: the device-observatory chaos family."""
    import test_devicewatch as tdw

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 10
    t0 = time.time()
    failed = []
    injected = probes = 0
    for s in range(seed, seed + n):
        with tempfile.TemporaryDirectory(prefix="soak_dw_") as d:
            try:
                res = tdw.run_device_obs_chaos(s, d)
                injected += res["injected_faults"]
                probes += res["probe_recompiles"]
            except Exception:  # noqa: BLE001 — report seed + continue
                failed.append(s)
                if len(failed) == 1:
                    traceback.print_exc()
    print(f"device_obs: {n - len(failed)}/{n} ok in "
          f"{time.time() - t0:.1f}s  injected_faults={injected} "
          f"probe_recompiles_detected={probes}"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def _reads_main(argv: list) -> int:
    """--reads SEED [n]: the linearizable-read oracle family (ISSUE 20).

    Each seed drives every cell of {ttl_kv, stream} x {single-device,
    sharded mesh} through the read oracle — consistent reads across
    election churn, leader kills and majority partitions must reflect
    every committed write (stale serves pinned 0, refusals legal,
    healed lanes must serve) — plus one durable + disk-fault run."""
    import test_read_plane as trp

    seed = int(argv[0]) if argv else 0
    n = int(argv[1]) if len(argv) > 1 else 4
    t0 = time.time()
    failed = []
    served = refused = 0
    for s in range(seed, seed + n):
        try:
            for kind in ("ttl_kv", "stream"):
                for mesh in (False, True):
                    st = trp.run_read_oracle(s, kind, mesh=mesh,
                                             rounds=10 if mesh else 14)
                    served += st["served"]
                    refused += st["refused"]
            with tempfile.TemporaryDirectory(prefix="soak_reads_") as d:
                st = trp.run_read_oracle(s, "stream", durable_dir=d,
                                         disk_faults=True, rounds=10)
                served += st["served"]
                refused += st["refused"]
        except Exception:  # noqa: BLE001 — report seed + continue
            failed.append(s)
            if len(failed) == 1:
                traceback.print_exc()
    print(f"reads: {n - len(failed)}/{n} ok in {time.time() - t0:.1f}s  "
          f"served={served} refused={refused} stale_serves=0"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
          flush=True)
    return 1 if failed else 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--wire":
        return _wire_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--ingress":
        return _ingress_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--blackbox":
        return _blackbox_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--disk-faults":
        return _disk_fault_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--superstep":
        return _superstep_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--obs":
        return _obs_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--device-obs":
        return _device_obs_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--failover":
        return _failover_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--geo":
        return _geo_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "--reads":
        return _reads_main(sys.argv[2:])
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 400
    off = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000
    families = [
        ("elections_3", lambda s: tp.test_election_safety_and_log_matching_fuzz(s, 3)),
        ("elections_5", lambda s: tp.test_election_safety_and_log_matching_fuzz(s, 5)),
        ("snapshots_3", lambda s: tp.test_safety_fuzz_with_snapshots(
            s, 3, require_snapshot=False)),
        ("membership", tp.test_safety_fuzz_with_membership_changes),
        ("member_snap", tp.test_safety_fuzz_membership_and_snapshots),
        ("mixed_macver", tp.test_safety_fuzz_mixed_machine_versions),
        ("nonassoc", tp.test_replicated_nonassoc_arithmetic_converges),
    ]
    rc = 0
    for name, fn in families:
        t0 = time.time()
        failed = []
        for seed in range(off, off + n):
            try:
                fn(seed)
            except Exception:  # noqa: BLE001 — report seed + continue family
                failed.append(seed)
                if len(failed) == 1:
                    traceback.print_exc()
        took = time.time() - t0
        print(f"{name}: {n - len(failed)}/{n} ok in {took:.1f}s"
              + (f"  FAILED seeds: {failed[:10]}" if failed else ""),
              flush=True)
        if failed:
            rc = 1
    # durable-log family needs a tmp dir per seed
    t0 = time.time()
    failed = []
    dn = max(1, n // 8)
    for seed in range(off, off + dn):
        with tempfile.TemporaryDirectory(prefix="soak_dur_") as d:
            try:
                tp.test_safety_fuzz_over_durable_logs(d, seed, 3)
            except Exception:  # noqa: BLE001
                failed.append(seed)
                if len(failed) == 1:
                    traceback.print_exc()
    print(f"durable_logs: {dn - len(failed)}/{dn} ok in "
          f"{time.time() - t0:.1f}s"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""), flush=True)
    rc = rc or (1 if failed else 0)
    # device-path chaos (engine): slower per seed (jit warms on the
    # first), so a reduced count
    import test_engine_chaos as tec
    t0 = time.time()
    failed = []
    en = max(1, n // 16)
    for seed in range(off, off + en):
        try:
            tec.run_chaos(seed, rounds=16)
        except Exception:  # noqa: BLE001
            failed.append(seed)
            if len(failed) == 1:
                traceback.print_exc()
    print(f"engine_chaos: {en - len(failed)}/{en} ok in "
          f"{time.time() - t0:.1f}s"
          + (f"  FAILED seeds: {failed[:10]}" if failed else ""), flush=True)
    return rc or (1 if failed else 0)


if __name__ == "__main__":
    sys.exit(main())
