"""ra-tpu headline benchmark.

The ra_bench-equivalent workload at the BASELINE.md north-star config:
N concurrent M-member Raft clusters, counter machine (ra_bench's noop/'+'
machine, /root/reference/src/ra_bench.erl:43-49), sustained pipelined
commands, measuring **committed commands/sec** with quorum decisions
computed on-TPU.

Baseline (BASELINE.md): 10,000 clusters x 5 members >= 1,000,000 committed
cmds/sec on a single chip.  vs_baseline = value / 1e6.

Process contract: a chip belongs to one process at a time, so the
parent never imports jax (tests/test_chip_smoke.py pins it) — it probes
the platform in a subprocess that exits before anything else starts,
then runs each measurement in a child of its own, one at a time, under
a timeout.  A run that finds no TPU exits non-zero with a message and
no number; a child that fails makes the run exit non-zero.  Nothing
here falls back to the CPU or replays a stored row.  The child modes
print their rows on any backend (tests/test_bench_paths.py runs them on
the CPU at tiny sizes); every row names the platform it ran on.

Latency is measured honestly AND without serializing the dispatch
pipeline (ISSUE 5): per sample, the batch is enqueued at step E and the
engine's per-lane committed watermark is harvested through ASYNC
readbacks only — the first readback step O whose cumulative count
covers the batch is the observed-commit step, and p50/p99 derive from
(O - E + 1) x the sample's measured per-step time.  Host syncs happen
only at sample window boundaries (lint rule RA04 polices this).

Superstep mode (``--superstep [K]`` or RA_TPU_BENCH_SUPERSTEP): the
throughput phase fuses K engine rounds per XLA dispatch and drives them
through the dispatch-ahead staging driver (see
ra_tpu/engine/lockstep.py), reporting the single-step reference value
and the realized speedup alongside.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE = 1_000_000.0       # north-star committed cmds/sec
N_LANES = 10_000
N_MEMBERS = 5
CMDS_PER_STEP = 128          # per-lane pipelined batch per round

PROBE_TIMEOUT_S = 120
CHILD_TIMEOUT_S = 480

#: the documented latency-mode operating point (docs/BENCHMARKS.md):
#: pipelined batches of 32 cmds/lane with a 4-deep unacked window
FRONTIER_DEFAULT_CMDS = 32
FRONTIER_DEFAULT_WINDOW = 4


def _host_meta() -> dict:
    """Environment stamp for cross-round comparability: the same config
    read 112.8M cmds/s in BENCH_r02 but 33.7M in BENCH_r04 because the
    host differed — without this stamp a reader cannot tell environment
    drift from regression.  Safe in the parent: host_envelope stamps
    jax only in a process that already runs on it (the children)."""
    meta = {"unknown": True}
    try:
        import platform as _pf
        model = ""
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    if line.startswith("model name"):
                        model = line.split(":", 1)[1].strip()
                        break
        except OSError:
            pass
        meta = {
            "hostname": _pf.node(),
            "cpu_model": model,
            "cpu_count": os.cpu_count(),
            "loadavg_1m": round(os.getloadavg()[0], 2),
        }
        try:
            # host envelope (ISSUE 13 satellite): fd cap + core count,
            # the cross-host drift dimensions — one shared impl
            from ra_tpu.utils import host_envelope
            meta.update(host_envelope())
        except Exception:  # noqa: BLE001 — optional on exotic platforms
            pass
    except Exception:  # noqa: BLE001 — metadata must never kill a bench
        pass
    return meta


# ---------------------------------------------------------------------------
# child mode: one measurement in one process (safe to kill from the parent)
# ---------------------------------------------------------------------------

def _child_main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    n_lanes = int(os.environ.get("RA_TPU_BENCH_LANES", N_LANES))
    n_members = int(os.environ.get("RA_TPU_BENCH_MEMBERS", N_MEMBERS))
    cmds = int(os.environ.get("RA_TPU_BENCH_CMDS", CMDS_PER_STEP))
    measure_s = float(os.environ.get("RA_TPU_BENCH_SECONDS", "5.0"))
    quorum_impl = os.environ.get("RA_TPU_QUORUM_IMPL", "xla")
    machine_name = os.environ.get("RA_TPU_BENCH_MACHINE", "counter")
    # fused-dispatch config (ISSUE 5): K rounds per XLA dispatch + the
    # dispatch-ahead staging depth; "auto" resolves the system-level
    # tunables (ra_tpu.system.engine_pipeline_defaults)
    from ra_tpu.system import engine_pipeline_defaults
    pipe_defaults = engine_pipeline_defaults()
    ss_env = os.environ.get("RA_TPU_BENCH_SUPERSTEP", "0")
    superstep_k = pipe_defaults["superstep_k"] if ss_env == "auto" \
        else int(ss_env)
    da_env = os.environ.get("RA_TPU_BENCH_DISPATCH_AHEAD", "auto")
    dispatch_ahead = pipe_defaults["dispatch_ahead"] if da_env == "auto" \
        else int(da_env)

    # BASELINE.md rows: counter (north star), fifo (5k x 5 enqueue/
    # dequeue), kv (2k mixed put/get with jittable apply)
    if machine_name == "fifo":
        from ra_tpu.models import JitFifoMachine
        # capacity 256: a realistic queue depth for the BASELINE row
        # (the round-4 review called the former 64 dimensionally a toy)
        machine = JitFifoMachine(
            capacity=int(os.environ.get("RA_TPU_BENCH_FIFO_CAP", "256")),
            checkout_slots=8)
        import numpy as np
        host_payloads = np.zeros((n_lanes, cmds, 3), np.int32)
        host_payloads[:, 0::2] = (1, 7, 0)     # enqueue 7
        host_payloads[:, 1::2] = (2, 0, 0)     # dequeue settled
        payloads = jnp.asarray(host_payloads)
    elif machine_name == "kv":
        from ra_tpu.models import JitKvMachine
        machine = JitKvMachine(n_keys=64)
        import numpy as np
        rng = np.random.default_rng(0)
        host_payloads = np.zeros((n_lanes, cmds, 4), np.int32)
        host_payloads[..., 0] = rng.integers(1, 3, (n_lanes, cmds))  # put/get
        host_payloads[..., 1] = rng.integers(0, 64, (n_lanes, cmds))
        host_payloads[..., 2] = rng.integers(0, 1000, (n_lanes, cmds))
        payloads = jnp.asarray(host_payloads)
    else:
        machine = CounterMachine()
        payloads = jnp.ones((n_lanes, cmds, 1), jnp.int32)

    durable = os.environ.get("RA_TPU_BENCH_DURABLE") == "1"
    if durable:
        # fsync-backed mode: every step's accepted entries go through the
        # sharded fan-in WAL and commits gate on the real confirm
        # (ra_log_wal.erl:753-800 — an entry counts only after
        # write(2)+fsync).  Lane shards each own their file, writer
        # thread and fsync, group-committing independently.
        import shutil
        import tempfile

        from ra_tpu.engine import open_engine
        dur_dir = tempfile.mkdtemp(prefix="ra_tpu_bench_wal_")
        sync_mode = int(os.environ.get("RA_TPU_BENCH_SYNC_MODE", "1"))
        wal_strategy = os.environ.get("RA_TPU_BENCH_WAL_STRATEGY",
                                      "default")
        # wal_shards defaults by core budget: each shard costs a writer
        # thread + an encode worker, and concurrent fsyncs only overlap
        # when the host has cores (and a disk) to run them — on the
        # 1-2 core CI boxes the sharding win is the compacted readback,
        # not fsync parallelism, so default to a single shard there
        auto_shards = min(4, max(1, (os.cpu_count() or 1) // 2))
        wal_shards = int(os.environ.get("RA_TPU_BENCH_WAL_SHARDS",
                                        str(auto_shards)))
        eng = open_engine(machine, dur_dir, n_lanes, n_members,
                          sync_mode=sync_mode,
                          write_strategy=wal_strategy, ring_capacity=1024,
                          max_step_cmds=cmds, apply_window=cmds + 2,
                          wal_shards=wal_shards,
                          # superstep: step_seq advances K per dispatch,
                          # so the unconfirmed-step window must cover a
                          # few dispatches or backpressure serializes
                          # the fused pipeline
                          max_pending=max(8, 4 * superstep_k),
                          quorum_impl=quorum_impl)
        import atexit
        atexit.register(lambda: shutil.rmtree(dur_dir, ignore_errors=True))
    else:
        eng = LockstepEngine(machine, n_lanes, n_members,
                             ring_capacity=1024, max_step_cmds=cmds,
                             apply_window=cmds + 2, write_delay=1,
                             quorum_impl=quorum_impl)

    # device-resident telemetry plane (ISSUE 6): ON by default at the
    # standard cadence — the headline number carries the observability
    # cost real deployments pay (<3% bound is test-pinned), and the
    # final Observatory snapshot lands in the JSON tail so cross-round
    # comparisons stop hand-collecting fsync/pipeline fields
    sampler = observatory = slo = tuner = None
    if os.environ.get("RA_TPU_BENCH_TELEMETRY", "1") != "0":
        from ra_tpu.telemetry import Observatory, TelemetrySampler
        sampler = TelemetrySampler(eng)
        observatory = Observatory.for_engine(eng, sampler=sampler)
        # SLO engine over the Observatory ring (ISSUE 9): periodic
        # snapshots during the measured phases feed the ring, and the
        # verdicts land in the JSON tail next to the phase attribution
        from ra_tpu.slo import SloEngine
        slo = SloEngine(observatory)
        if os.environ.get("RA_TPU_BENCH_AUTOTUNE") == "1":
            # opt-in closed loop: the tuner ticks at snapshot cadence
            # and its decisions/knobs ride the tail.  Knobs the loop
            # cannot APPLY are frozen via bounds: cmds_per_step is
            # baked into the staged payload buffers, and superstep_k
            # is only re-stageable on the fused path — a recorded
            # decision that changes nothing measured would make the
            # tail's knob stamps a lie.  The wal batch interval always
            # applies live (set_batch_interval_ms).
            from ra_tpu.autotune import AutoTuner
            k0 = max(1, superstep_k)
            tuner = AutoTuner(slo, observatory,
                              durability=eng._dur if durable else None,
                              bounds={"cmds_per_step": (cmds, cmds),
                                      "superstep_k": (1, 64)
                                      if superstep_k else (k0, k0)},
                              knobs={"superstep_k": k0,
                                     "cmds_per_step": cmds})

    # window-cadence observation: a host-only dict merge (the sources
    # read harvested sampler data + host counters — no device sync, so
    # the measured pipeline is untouched; the <3% A/B pin covers it)
    _obs_last = [0.0]

    def maybe_observe() -> None:
        now = time.perf_counter()
        if observatory is not None and now - _obs_last[0] >= 0.2:
            _obs_last[0] = now
            observatory.snapshot()
            if tuner is not None:
                tuner.tick()

    if durable:
        # host-resident batches: the per-step H2D copy is the honest
        # ingestion path (entries arrive from the host), and the durable
        # bridge needs the host bytes for the WAL record anyway
        import numpy as np
        payloads = np.asarray(payloads)
        n_new = np.full((n_lanes,), cmds, np.int32)
        zero_n = np.zeros((n_lanes,), np.int32)
        zero_p = np.zeros_like(payloads)
    else:
        n_new = jnp.full((n_lanes,), cmds, jnp.int32)
        zero_n = jnp.zeros((n_lanes,), jnp.int32)
        zero_p = jnp.zeros_like(payloads)

    for _ in range(5):
        eng.step(n_new, payloads)
    eng.block_until_ready()

    # -- throughput phase (BOUNDED in-flight window — the headline) -------
    # Dispatch runs at most `window` steps ahead of an observed commit
    # readback: the old unbounded loop let the tail commit sit in flight
    # for seconds (the 6,395ms p99 behind the round-5 112.4M headline),
    # so the headline row is now the bounded one and the unbounded
    # number is reported separately as an explicitly-labeled ceiling.
    # Durable mode is already window-bounded by the bridge's max_pending
    # backpressure (8 steps of unconfirmed WAL), so it keeps the plain
    # loop — adding a readback bound on top would double-serialize.
    import collections as _collections
    window = int(os.environ.get("RA_TPU_BENCH_THROUGHPUT_WINDOW", "8"))

    def run_unbounded(seconds: float):
        """Back-to-back dispatch with a device barrier every 20 steps —
        the unbounded measurement protocol, shared by the durable
        throughput phase (where the bridge's max_pending backpressure
        is the bound) and the ceiling phase."""
        n = 0
        t_start = time.perf_counter()
        while True:
            eng.step(n_new, payloads)
            n += 1
            if n % 20 == 0:
                eng.block_until_ready()  # ra04-ok: 20-step window boundary
                maybe_observe()
                if time.perf_counter() - t_start >= seconds:
                    break
        eng.block_until_ready()
        return n, time.perf_counter() - t_start

    def run_single_step(seconds: float):
        """The single-step measurement protocol: window-bounded async
        readbacks (volatile) or max_pending backpressure (durable)."""
        if durable:
            return run_unbounded(seconds)
        readbacks: "_collections.deque" = _collections.deque()
        n = 0
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < seconds:
            eng.step(n_new, payloads)
            n += 1
            readbacks.append(eng.committed_lanes_async())
            while len(readbacks) > window:
                np.asarray(readbacks.popleft())  # ra04-ok: window boundary
            maybe_observe()
        eng.block_until_ready()
        return n, time.perf_counter() - t_start

    single_step_ref = None
    driver = None
    if superstep_k:
        # single-step reference at the same config first, so the fused
        # row carries its own dispatch-amortization evidence
        base_ref = eng.committed_total()
        ref_steps, ref_el = run_single_step(min(measure_s, 2.0))
        single_step_ref = {
            "value": round((eng.committed_total() - base_ref) / ref_el, 1),
            "steps": ref_steps,
            "elapsed_s": round(ref_el, 3),
        }
        # fused phase: K rounds per dispatch, host staging one block
        # ahead of device execution (the dispatch-ahead driver)
        from ra_tpu.engine import DispatchAheadDriver
        n_new_host = np.asarray(n_new)
        pay_host = np.asarray(payloads)
        n_new_blk = np.broadcast_to(n_new_host,
                                    (superstep_k,) + n_new_host.shape)
        pay_blk = np.broadcast_to(pay_host,
                                  (superstep_k,) + pay_host.shape)
        driver = DispatchAheadDriver(eng, max_in_flight=dispatch_ahead)
        for _ in range(2):
            driver.submit(n_new_blk, pay_blk)
        driver.drain()
        start_committed = eng.committed_total()
        dispatches = 0
        cur_k = superstep_k
        steps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < measure_s:
            if tuner is not None and \
                    tuner.knobs["superstep_k"] != cur_k:
                # apply the controller's decision BETWEEN dispatches:
                # restage the block at the new fusion depth (broadcast
                # views — no payload copy)
                cur_k = tuner.knobs["superstep_k"]
                n_new_blk = np.broadcast_to(
                    n_new_host, (cur_k,) + n_new_host.shape)
                pay_blk = np.broadcast_to(
                    pay_host, (cur_k,) + pay_host.shape)
            driver.submit(n_new_blk, pay_blk)
            dispatches += 1
            steps += cur_k
            maybe_observe()
        driver.drain()  # run-end window boundary
        elapsed = time.perf_counter() - t0
    else:
        start_committed = eng.committed_total()
        steps, elapsed = run_single_step(measure_s)
    committed = eng.committed_total() - start_committed
    value = committed / elapsed

    # -- unbounded ceiling (capacity measurement, NOT an operating point)
    ceiling = None
    ceiling_s = float(os.environ.get("RA_TPU_BENCH_CEILING_SECONDS",
                                     str(min(measure_s, 2.0))))
    if ceiling_s > 0 and not durable:  # durable is window-bounded anyway
        base_c = eng.committed_total()
        csteps, celapsed = run_unbounded(ceiling_s)
        ceiling = {
            "value": round((eng.committed_total() - base_c) / celapsed, 1),
            "steps": csteps,
            "note": "unbounded in-flight window: a capacity ceiling "
                    "whose tail commits sit in flight for the whole "
                    "run (p99 collapse) — quote the bounded headline "
                    "value instead (docs/BENCHMARKS.md)",
        }

    # -- latency phase: on-device step stamping (ISSUE 5) -----------------
    # The old protocol spun on committed_total() — a blocking device
    # sync per spin that serialized the very pipeline the superstep
    # path builds.  Now a sample enqueues its batch at step E, drives
    # empty rounds each starting one ASYNC per-lane committed readback,
    # and syncs only at sample window boundaries.  The observed-commit
    # step O is the first readback whose cumulative count covers the
    # batch (inner-step resolution in superstep mode via the stacked
    # [K, N] watermark), and the sample's latency derives from step
    # counts x measured step time:
    #   latency = sample_elapsed * O / steps_in_sample.
    # The enqueue->commit edge in STEPS is exact; the milliseconds come
    # from the sample's own pipelined step rate.
    expected_per_sample = n_lanes * cmds
    lats = []
    truncated = 0
    spin = 32 if durable else 8  # durable: confirm lag is real
    max_windows = 4 if durable else 2
    if superstep_k:
        zp_host = np.asarray(zero_p)
        zero_nb = np.zeros((superstep_k, n_lanes), np.int32)
        zero_pb = np.zeros((superstep_k,) + zp_host.shape, zp_host.dtype)
        batch_nb = zero_nb.copy()
        batch_nb[0] = np.asarray(n_new)
        batch_pb = zero_pb.copy()
        batch_pb[0] = np.asarray(payloads)
    for _ in range(40):
        before = eng.committed_total()  # ra04-ok: pre-sample baseline
        handles = []  # (steps covered through, watermark readback)
        obs_step = None
        steps_done = 0
        checked = 0
        elapsed_sample = 0.0
        t1 = time.perf_counter()
        if superstep_k:
            aux = eng.superstep(batch_nb, batch_pb)
            steps_done += superstep_k
            handles.append((steps_done, aux["committed_lanes"] + 0))
        else:
            eng.step(n_new, payloads)
            steps_done += 1
            handles.append((steps_done, eng.committed_lanes_async()))
        for _w in range(max_windows):
            if superstep_k:
                for _ in range(max(1, spin // superstep_k)):
                    aux = eng.superstep(zero_nb, zero_pb)
                    steps_done += superstep_k
                    handles.append((steps_done,
                                    aux["committed_lanes"] + 0))
            else:
                for _ in range(spin):
                    eng.step(zero_n, zero_p)
                    steps_done += 1
                    handles.append((steps_done,
                                    eng.committed_lanes_async()))
            eng.block_until_ready()  # ra04-ok: sample window boundary
            elapsed_sample = time.perf_counter() - t1
            while checked < len(handles) and obs_step is None:
                hi_step, h = handles[checked]
                arr = np.asarray(h).astype(np.int64)  # ra04-ok: post-boundary harvest (already synced)
                if arr.ndim == 2:  # stacked [K, N]: inner-step resolution
                    cums = arr.sum(axis=1) - before
                    for k_in in range(arr.shape[0]):
                        if cums[k_in] >= expected_per_sample:
                            obs_step = hi_step - arr.shape[0] + k_in + 1
                            break
                elif int(arr.sum()) - before >= expected_per_sample:
                    obs_step = hi_step
                checked += 1
            if obs_step is not None:
                break
        if obs_step is None:
            # a sample whose commit was never observed must not pollute
            # the distribution with a bogus-low value
            truncated += 1
        else:
            lats.append(elapsed_sample * obs_step / steps_done)
        maybe_observe()  # sample boundary: feed the SLO ring a window
    lats.sort()
    p50 = lats[len(lats) // 2] if lats else -1.0
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else -1.0

    if sampler is not None:
        sampler.drain()  # run-end barrier, after measurement
    overview = eng.overview()
    # device-plane tail (ISSUE 16): process-lifetime compile/transfer/
    # watermark totals — bench_diff flags round-over-round n_compiles
    # growth as a retrace regression
    from ra_tpu import devicewatch
    print(json.dumps({
        "value": round(value, 1),
        "committed": int(committed),
        "steps": steps,
        "elapsed_s": round(elapsed, 3),
        # durable: the max_pending WAL backpressure is the bound
        "in_flight_window_steps": "max_pending" if durable else (
            f"dispatch_ahead*{superstep_k}" if superstep_k else window),
        # fused-dispatch stamps (ISSUE 5): K=0 means the classic
        # single-step path; the pipeline dict carries the realized
        # dispatch/inner-step counters and driver sync counts
        "superstep_k": superstep_k,
        "dispatch_ahead": dispatch_ahead if superstep_k else 0,
        "pipeline": overview["pipeline"],
        **({"single_step_ref": single_step_ref,
            "speedup_vs_single_step":
                round(value / single_step_ref["value"], 3)
                if single_step_ref["value"] else -1.0}
           if single_step_ref else {}),
        **({"unbounded_ceiling": ceiling} if ceiling else {}),
        "latency_mode": "step_stamped",
        "p50_commit_latency_ms": round(1000.0 * p50, 3),
        "p99_commit_latency_ms": round(1000.0 * p99, 3),
        "latency_samples": len(lats),
        "latency_samples_dropped": truncated,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "quorum_impl": quorum_impl, "machine": machine_name,
        **({"fifo_capacity": machine.capacity,
            "fifo_checkout_slots": machine.checkout_slots,
            "fifo_consumer_slots": machine.consumer_slots}
           if machine_name == "fifo" else {}),
        "lanes": n_lanes, "members": n_members, "cmds_per_step": cmds,
        "durable": durable, "host": _host_meta(),
        **({"sync_mode": sync_mode,
            "wal_strategy": wal_strategy,
            "wal_shards": wal_shards,
            "wal": overview["wal"]} if durable else {}),
        # the unified snapshot (telemetry summary + sampler health +
        # pipeline + per-shard WAL stats + phase attribution) —
        # ISSUE 6's one-stop tail, ISSUE 9's phases ride inside it
        **({"observatory": observatory.snapshot()}
           if observatory is not None else {}),
        # SLO verdicts over the run's ring windows (ISSUE 9) + the
        # opt-in autotuner's decisions/knobs
        **({"slo": slo.evaluate()} if slo is not None else {}),
        **({"autotune": tuner.overview()} if tuner is not None else {}),
        **devicewatch.bench_tail_keys(commands=int(committed)),
    }))
    sys.stdout.flush()
    # join the WAL plane's worker/supervisor threads before interpreter
    # teardown: a daemon thread still inside an XLA readback while the
    # CPU client destructs aborts the whole child ("terminate called
    # without an active exception") — rarely, but the driver runs this
    # unattended and a dead child costs the round its measurement
    eng.close()


# ---------------------------------------------------------------------------
# multichip mode: the sharded-mesh frontier sweep (ISSUE 11)
# ---------------------------------------------------------------------------

#: the MULTICHIP_r05 2x4 throughput phase this sweep is measured
#: against (single-step mesh driver, 1024 lanes x 4 members, cmds=8,
#: 8 forced host devices on the builder box) — the acceptance bar is
#: >= 5x this at equal lanes/members on the same host
R05_2X4_CMDS_PER_S = 1_611_936.9


def _multichip_point(mesh, lanes: int, members: int, cmds: int,
                     superstep_k: int, dispatch_ahead: int,
                     seconds: float, autotune: bool) -> dict:
    """One frontier point: single-step reference, then the
    superstep+dispatch-ahead mesh pipeline (optionally autotuner-driven
    K walk), then step-stamped latency — all on state sharded over
    ``mesh`` with blocks staged pre-partitioned (zero resharding)."""
    import collections

    import numpy as np

    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine
    from ra_tpu.parallel.mesh import (drive_uniform_window,
                                      mesh_superstep_driver,
                                      shard_engine_state)

    eng = LockstepEngine(CounterMachine(), lanes, members,
                         ring_capacity=max(64, 4 * cmds),
                         max_step_cmds=cmds, apply_window=cmds + 2,
                         write_delay=1)
    shard_engine_state(eng, mesh)
    n_new = np.full((lanes,), cmds, np.int32)
    payloads = np.ones((lanes, cmds, 1), np.int32)
    for _ in range(3):
        eng.step(n_new, payloads)
    eng.block_until_ready()  # warmup boundary (outside the measured loop)

    # -- single-step reference (the MULTICHIP_r05 protocol, made
    # window-bounded): same mesh, same shardings, one round per
    # dispatch — the denominator of speedup_vs_single_step
    readbacks: "collections.deque" = collections.deque()
    ref_s = min(seconds, 1.5)
    base = eng.committed_total()  # pre-phase baseline (outside the loop)
    ref_steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ref_s:
        eng.step(n_new, payloads)
        ref_steps += 1
        readbacks.append(eng.committed_lanes_async())
        while len(readbacks) > 8:
            np.asarray(readbacks.popleft())  # ra04-ok: window boundary
    eng.block_until_ready()  # phase-end boundary (outside the loop)
    ref_el = time.perf_counter() - t0
    ref_value = (eng.committed_total() - base) / ref_el

    # -- fused pipeline: dispatch-ahead staging against the mesh
    # shardings; the autotuner walks K off the throughput floor
    driver = mesh_superstep_driver(eng, mesh,
                                   max_in_flight=dispatch_ahead)
    observatory = slo = tuner = None
    cur_k = [superstep_k]
    if autotune:
        from ra_tpu.autotune import AutoTuner
        from ra_tpu.slo import SloEngine, default_objectives
        from ra_tpu.telemetry import Observatory, TelemetrySampler
        # sampler cadence of ONE inner step: a window without a fresh
        # sample rates as no_data and stalls the walk, and at the top
        # ladder rung a single fused dispatch outlasts several snapshot
        # windows — only a per-dispatch sample keeps every window live.
        # Tune-phase only: the measured phase detaches the sampler.
        sampler = TelemetrySampler(eng, cadence_steps=1)
        observatory = Observatory.for_engine(eng, sampler=sampler)
        # the throughput floor the walk chases: past any realizable
        # mesh rate, so the tuner keeps fusing while the plant is
        # dispatch-bound and stops only at the K bound / latency wall
        slo = SloEngine(observatory,
                        default_objectives(
                            min_cmds_per_s=16.0 * max(1.0, ref_value)),
                        fast_windows=2, slow_windows=4, burn_fast=0.5)
        # K's upper bound shrinks with lane count: one fused dispatch
        # at the 64k rung already runs for most of a second per 8
        # inner steps, and a 64-deep dispatch there would swallow the
        # whole measured window (the walk is for the dispatch-bound
        # low rungs; the compute-bound top rung has nothing to fuse)
        k_hi = 32 if lanes <= 1024 else (16 if lanes <= 8192 else 8)
        tuner = AutoTuner(slo, observatory,
                          bounds={"cmds_per_step": (cmds, cmds),
                                  "superstep_k": (1, k_hi)},
                          knobs={"superstep_k": 1, "cmds_per_step": cmds},
                          cooldown_windows=1, breach_windows=1,
                          incident_freeze_s=0.0)
        cur_k = [1]

    def mk_blocks(k: int):
        return (np.broadcast_to(n_new, (k,) + n_new.shape),
                np.broadcast_to(payloads, (k,) + payloads.shape))

    _last_obs = [0.0, 0.0]  # (last tick ts, last observed committed)
    _rate_by_k: dict = {}

    def observe():
        """Window-cadence host work between dispatches: snapshot the
        ring, tick the controller, record the realized rate at the
        current K (from ``driver.last_committed`` — the EXISTING async
        watermark readbacks, no new sync), restage on a K decision."""
        now = time.perf_counter()
        if observatory is None or now - _last_obs[0] < 0.2:
            return None
        lc = driver.last_committed
        if lc is not None and _last_obs[0] > 0.0:
            done = float(lc.astype("int64").sum())
            if _last_obs[1] > 0.0:
                acc = _rate_by_k.setdefault(cur_k[0], [0.0, 0.0])
                acc[0] += done - _last_obs[1]
                acc[1] += now - _last_obs[0]
            _last_obs[1] = done
        _last_obs[0] = now
        observatory.snapshot()
        tuner.tick()
        if tuner.knobs["superstep_k"] != cur_k[0]:
            cur_k[0] = tuner.knobs["superstep_k"]
            # discard the first window at the new K: it contains the
            # new block shape's jit compile, which would poison the
            # per-K rate the argmax selection reads
            _last_obs[1] = 0.0
            return mk_blocks(cur_k[0])
        return None

    nb, pb = mk_blocks(cur_k[0])
    for _ in range(2):
        driver.submit(nb, pb)
    driver.drain()
    if tuner is not None:
        # tune phase (not measured): the controller proposes the K
        # walk; the realized per-K rates select the operating point —
        # on a dispatch-bound mesh the walk's converged K IS the
        # argmax, while on a compute-bound plant (forced-host devices
        # on a small box) the floor is unreachable, the walk pegs at
        # its bound, and the argmax keeps the sweep honest
        # budgeted to cover the jit compiles the walk triggers (each
        # new K is a fresh block shape) plus a few clean windows per K
        tune_s = float(os.environ.get("RA_TPU_BENCH_MESH_TUNE_S", "6.0"))
        drive_uniform_window(driver, nb, pb, max(tune_s, seconds),
                             observe=observe)
        driver.drain()
        measured = {k: a[0] / a[1] for k, a in _rate_by_k.items()
                    if a[1] > 0.05 and a[0] > 0}
        if measured:
            cur_k[0] = max(measured, key=lambda k: measured[k])
        # the knob stamps must describe the MEASURED dispatches (the
        # RA07 discipline): pin the controller to the selected K so
        # tail readers see one consistent operating point
        tuner.knobs["superstep_k"] = cur_k[0]
        tuner.bounds["superstep_k"] = (cur_k[0], cur_k[0])
        nb, pb = mk_blocks(cur_k[0])
        # the MEASURED phase runs exactly like the single-step ref:
        # no sampler dispatches, no snapshot/tick work — the sweep's
        # speedup_vs_single_step compares pipelines, not telemetry
        # overhead (the ref ran before the sampler was attached)
        eng._telemetry = None
        observatory_final = observatory
        observatory = None
    base = eng.committed_total()  # pre-measure baseline (outside the loop)
    t_meas = time.perf_counter()
    dispatches, inner, _loop_el = drive_uniform_window(
        driver, nb, pb, seconds, observe=observe)
    driver.drain()
    # elapsed includes the drain: up to max_in_flight+1 dispatches are
    # unobserved at loop exit, and at the 64k rung a single fused
    # dispatch is most of the window — excluding their completion
    # would overstate the rate ~2x at the top rung
    elapsed = time.perf_counter() - t_meas
    committed = eng.committed_total() - base  # post-drain (outside the loop)
    value = committed / elapsed
    k_final = cur_k[0]

    # -- solo-dispatch tail probe -> the effective p99 bar (the PR 3
    # discipline: the bar is lifted to the backend's own pipeline
    # floor, measured UNPIPELINED so a regression cannot hide in it)
    nb1, pb1 = mk_blocks(max(1, k_final))
    stimes = []
    probe_reps = 8 if lanes <= 8192 else 4
    for _ in range(probe_reps):
        ts = time.perf_counter()
        driver.submit(nb1, pb1)
        driver.drain()  # ra04-ok: solo-dispatch probe, deliberately sync
        stimes.append(time.perf_counter() - ts)
    solo_p99_ms = 1000 * sorted(stimes)[-1]
    bar = max(25.0, (dispatch_ahead + 1) * solo_p99_ms * 1.5)

    # -- step-stamped latency: a batch enters at inner step E of a
    # fused dispatch; the stacked [K, N] committed watermarks give the
    # observed-commit inner step O, and ms = sample time * O / steps
    expected = lanes * cmds
    k_lat = max(1, k_final)
    zero_nb = np.zeros((k_lat, lanes), np.int32)
    zero_pb = np.zeros((k_lat,) + payloads.shape, payloads.dtype)
    batch_nb = zero_nb.copy()
    batch_nb[0] = n_new
    batch_pb = zero_pb.copy()
    batch_pb[0] = payloads
    lats = []
    dropped = 0
    n_samples = 12 if lanes <= 8192 else 4  # top-rung steps are ~100x
    for _ in range(n_samples):
        before = eng.committed_total()  # ra04-ok: pre-sample baseline
        handles = []
        steps_done = 0
        t1 = time.perf_counter()
        aux = eng.superstep(batch_nb, batch_pb)
        steps_done += k_lat
        handles.append((steps_done, aux["committed_lanes"] + 0))
        for _w in range(max(1, 8 // k_lat)):
            aux = eng.superstep(zero_nb, zero_pb)
            steps_done += k_lat
            handles.append((steps_done, aux["committed_lanes"] + 0))
        eng.block_until_ready()  # ra04-ok: sample window boundary
        el = time.perf_counter() - t1
        obs_step = None
        for hi_step, h in handles:
            arr = np.asarray(h).astype(np.int64)  # ra04-ok: post-boundary harvest
            cums = arr.sum(axis=1) - before
            for k_in in range(arr.shape[0]):
                if cums[k_in] >= expected:
                    obs_step = hi_step - arr.shape[0] + k_in + 1
                    break
            if obs_step is not None:
                break
        if obs_step is None:
            dropped += 1
        else:
            lats.append(el * obs_step / steps_done)
    lats.sort()
    p50 = 1000 * lats[len(lats) // 2] if lats else -1.0
    p99 = 1000 * lats[min(len(lats) - 1, int(len(lats) * 0.99))] \
        if lats else -1.0

    pipeline = eng.overview()["pipeline"]
    row = {
        "mesh": eng.mesh_shape(),
        "lanes": lanes,
        "members": members,
        "cmds_per_step": cmds,
        "value": round(value, 1),
        "committed": int(committed),
        "dispatches": dispatches,
        "steps": inner,
        "elapsed_s": round(elapsed, 3),
        "single_step_ref": {"value": round(ref_value, 1),
                            "steps": ref_steps,
                            "elapsed_s": round(ref_el, 3)},
        "speedup_vs_single_step": round(value / ref_value, 3)
        if ref_value else -1.0,
        "latency_mode": "step_stamped",
        "p50_commit_latency_ms": round(p50, 3),
        "p99_commit_latency_ms": round(p99, 3),
        "latency_samples": len(lats),
        "latency_samples_dropped": dropped,
        "p99_bar_effective_ms": round(bar, 3),
        "meets_p99_bar": bool(0 < p99 < bar),
        "pipeline": pipeline,
        # cross-round attribution stamp (ISSUE 11 satellite): the
        # realized pipeline config next to the rate it produced, so
        # tools/bench_diff.py deltas are attributable to a config
        # change vs a real regression
        "engine_pipeline": {
            "superstep_k": k_final,
            "dispatch_ahead": dispatch_ahead,
            "donation": bool(eng._superstep_donate),
            "wal_shard_layout": "volatile",
            "mesh_shape": eng.mesh_shape(),
        },
    }
    if tuner is not None:
        row["autotune"] = tuner.overview()
        # the tune phase's realized per-K rates (the frontier search
        # evidence behind the chosen operating point)
        row["tune_k_rates"] = {
            str(k): round(a[0] / a[1], 1)
            for k, a in sorted(_rate_by_k.items()) if a[1] > 0.05}
        observatory_final.close()
    return row


def _multichip_main() -> None:
    """The multichip frontier sweep promoted into bench.py proper
    (ROADMAP item 1): per mesh shape x lane-ladder rung, the
    superstep+dispatch-ahead pipeline over sharded state vs the
    single-step reference, with the PR 8 autotuner walking K and the
    same p99-bar/window/step-stamped discipline as the single-device
    frontier.  One JSON line: ``multichip`` rows + the best point."""
    import jax

    devices = jax.devices()
    n_dev = len(devices)
    seconds = float(os.environ.get("RA_TPU_BENCH_SECONDS", "2.0"))
    cmds = int(os.environ.get("RA_TPU_BENCH_CMDS", "8"))
    from ra_tpu.system import engine_pipeline_defaults
    pipe_defaults = engine_pipeline_defaults()
    ss_env = os.environ.get("RA_TPU_BENCH_SUPERSTEP", "auto")
    superstep_k = pipe_defaults["superstep_k"] if ss_env == "auto" \
        else max(1, int(ss_env))
    da_env = os.environ.get("RA_TPU_BENCH_DISPATCH_AHEAD", "auto")
    dispatch_ahead = pipe_defaults["dispatch_ahead"] if da_env == "auto" \
        else int(da_env)
    # the lane ladder (ISSUE 11 satellite): RA_TPU_BENCH_MESH_LANES
    # overrides it, a malformed/empty spec degrades to the default
    from ra_tpu.parallel.mesh import (ladder_rungs, lane_ladder,
                                      lane_mesh, mesh_shapes)
    ladder = lane_ladder(os.environ.get("RA_TPU_BENCH_MESH_LANES"))
    autotune = os.environ.get("RA_TPU_BENCH_AUTOTUNE", "1") != "0"
    rows = []
    for m_ax, l_ax, members in mesh_shapes(n_dev):
        mesh = lane_mesh(devices, member_axis=m_ax)
        for lanes in ladder_rungs(ladder, l_ax):
            row = _multichip_point(mesh, lanes, members, cmds,
                                   superstep_k, dispatch_ahead,
                                   seconds, autotune)
            if row["mesh"] == "2x4" and row["lanes"] == 1024 and \
                    cmds == 8:
                # the acceptance-bar comparison at the r05 config
                # (equal lanes/members/cmds; same-host caveat rides
                # the host stamp)
                row["speedup_vs_r05"] = round(
                    row["value"] / R05_2X4_CMDS_PER_S, 3)
            rows.append(row)
            print(f"  point {row['mesh']} lanes={row['lanes']}: "
                  f"{row['value']:.0f} cmds/s "
                  f"({row['speedup_vs_single_step']}x single-step)",
                  file=sys.stderr)
    ok = [r for r in rows if r["meets_p99_bar"]]
    best = max(ok or rows, key=lambda r: r["value"])
    from ra_tpu import devicewatch
    print(json.dumps({
        "value": best["value"],
        "best_point": {"mesh": best["mesh"], "lanes": best["lanes"]},
        "multichip": rows,
        "n_devices": n_dev,
        "superstep_k": superstep_k,
        "dispatch_ahead": dispatch_ahead,
        "cmds_per_step": cmds,
        "autotune": autotune,
        "r05_2x4_cmds_per_s": R05_2X4_CMDS_PER_S,
        "platform": devices[0].platform,
        "host": _host_meta(),
        # the sweep's whole-process compile budget: every frontier point
        # reuses the jit cache, so n_compiles growing with the ladder
        # length (instead of with the distinct-config count) is the
        # retrace regression bench_diff flags
        **devicewatch.bench_tail_keys(),
    }))


# ---------------------------------------------------------------------------
# wire mode: the socket-path frontier (ISSUE 12, ROADMAP item 2)
# ---------------------------------------------------------------------------

def _wire_bench_main() -> None:
    """One rung of the wire connection ladder as a bench phase: the
    full wire path (fixed-stride frames → per-connection rings →
    vectorized sweep → ingress → fused dispatch) with a reconnect
    storm mid-run, measured end to end through a durable engine by
    default.  The tail carries ``wire_cmds_per_s`` /
    ``wire_shed_rate`` / ``wire_reconnect_recovery_s`` so
    tools/bench_diff.py tracks the wire frontier like any other."""
    import tempfile

    from ra_tpu.wire.soak import run_wire_soak

    conns = int(os.environ.get("RA_TPU_BENCH_WIRE_CONNS", "100000"))
    lanes = int(os.environ.get("RA_TPU_BENCH_WIRE_LANES", "1024"))
    waves = int(os.environ.get("RA_TPU_BENCH_WIRE_WAVES", "12"))
    durable = os.environ.get("RA_TPU_BENCH_WIRE_DURABLE", "1") == "1"
    seed = int(os.environ.get("RA_TPU_BENCH_WIRE_SEED", "0"))
    kw = dict(conns=conns, lanes=lanes, waves=waves,
              wave_ops=max(20_000, conns // 2),
              ring_records=16 if conns >= 1 << 19 else 32,
              socket_conns=32, socket_ops=16)
    if durable:
        with tempfile.TemporaryDirectory(prefix="bench_wire_") as d:
            row = run_wire_soak(seed, durable_dir=d, **kw)
    else:
        row = run_wire_soak(seed, **kw)
    row["metric"] = "wire_committed_cmds_per_sec"
    row["unit"] = "cmds/s"
    row["host"] = _host_meta()
    print(json.dumps(row))


# ---------------------------------------------------------------------------
# reads mode: the mixed read/write frontier (ISSUE 20)
# ---------------------------------------------------------------------------

def _reads_bench_main() -> None:
    """Mixed consistent-read / write workload through the ingress plane
    (ISSUE 20): per wave, ``read_share`` of the rows are lease/read-index
    reads riding the SAME fused dispatches as the writes, the rest are
    durable puts.  Three measured sections on one warm engine:

    1. per-call baseline — ``consistent_read`` one lane at a time, the
       host-path consistent_query it replaces (``percall_reads_per_s``);
    2. write-only reference — the write plane alone at the mixed run's
       write arrival rate (``write_only_p99_ms``: the frontier the mixed
       run must stay within 10% of);
    3. the mixed run — stamps ``read_cmds_per_s`` / ``read_p99_ms``
       (per-read submit→reply e2e, measured at the reply callback) /
       ``reads_per_dispatch`` / ``read_plane_speedup_vs_percall`` plus
       the write keys and BOTH SLO verdicts from the live SloEngine.

    The tail carries the devicewatch stamp and a ``steady_state_*``
    compile delta over the measured sections — reads interleaving with
    writes must not retrace the fused step."""
    import collections
    import tempfile

    import numpy as np

    import jax

    from ra_tpu import devicewatch
    from ra_tpu.engine.durable import open_engine
    from ra_tpu.ingress import IngressPlane
    from ra_tpu.models import JitKvMachine
    from ra_tpu.slo import SloEngine
    from ra_tpu.telemetry import Observatory

    lanes = int(os.environ.get("RA_TPU_BENCH_LANES", "1024"))
    members = int(os.environ.get("RA_TPU_BENCH_MEMBERS", "3"))
    seconds = float(os.environ.get("RA_TPU_BENCH_SECONDS", "3.0"))
    read_share = min(0.99, max(0.01, float(
        os.environ.get("RA_TPU_BENCH_READ_SHARE", "0.9"))))
    kr = int(os.environ.get("RA_TPU_BENCH_READ_WINDOW", "16"))
    cmds = int(os.environ.get("RA_TPU_BENCH_CMDS", "8"))
    superstep_k = int(os.environ.get("RA_TPU_BENCH_SUPERSTEP", "4")
                      if os.environ.get("RA_TPU_BENCH_SUPERSTEP", "4")
                      .isdigit() else 4)
    # rows offered per wave: ~2 rows/lane keeps a single read block
    # (<= Kr rows/lane) carrying the whole wave's read half — the
    # >=1000 reads/dispatch shape at 1024 lanes
    wave_rows = int(os.environ.get("RA_TPU_BENCH_READS_WAVE",
                                   str(2 * lanes)))
    n_w = max(1, int(round(wave_rows * (1.0 - read_share))))
    n_r = max(1, wave_rows - n_w)
    n_keys = 64
    rng = np.random.default_rng(
        int(os.environ.get("RA_TPU_BENCH_SEED", "0")))

    with tempfile.TemporaryDirectory(prefix="bench_reads_") as wal_dir:
        eng = open_engine(JitKvMachine(n_keys=n_keys), wal_dir, lanes,
                          members, wal_shards=2,
                          ring_capacity=max(64, superstep_k * cmds * 4),
                          max_step_cmds=cmds, max_step_reads=kr,
                          lease_ttl=8, donate=False)
        plane = IngressPlane(eng, superstep_k=superstep_k,
                             window_s=0.001, soft_credit=1 << 20,
                             hard_credit=1 << 20)
        obs = Observatory.for_engine(eng)
        # verdict stamping only — deliberately NOT wired into the
        # plane's credit ladder: on an oversubscribed host the write
        # p99 breaches its objective, the ladder bias would shed every
        # read at admission, and the frontier this mode exists to
        # measure would read 0.  The bias itself is test-pinned.
        slo = SloEngine(obs)
        sess = plane.directory.connect_bulk(4096, key="bench-reads")
        n_sess = len(sess)

        # write-plane wave latency: cumulative accepted-row targets
        # joined against the block-commit callback's released rows
        # (the frontier's observed-commit edge, through ingress)
        write_waves: collections.deque = collections.deque()
        write_lats: list = []
        released_rows = 0

        def _on_commit(handles) -> None:
            nonlocal released_rows
            released_rows += len(handles)
            t = time.perf_counter()
            while write_waves and write_waves[0][0] <= released_rows:
                _tgt, ts = write_waves.popleft()
                write_lats.append(t - ts)

        plane.on_block_committed = _on_commit

        # read e2e: submit wall clock per read wave (seqnos encode the
        # wave), latency measured at the reply callback for SERVED rows
        SEQ_STRIDE = 1 << 20
        wave_t = np.zeros(1 << 16, np.float64)
        read_lats: list = []

        def _on_reads(handles, seqnos, statuses, wms, payloads) -> None:
            now = time.perf_counter()
            ok = np.asarray(statuses) == 0
            if ok.any():
                w = np.asarray(seqnos)[ok] // SEQ_STRIDE
                read_lats.extend((now - wave_t[w]).tolist())

        plane.on_reads_done = _on_reads

        wave_idx = 0
        last_snap = 0.0

        def _wave(do_reads: bool) -> None:
            nonlocal wave_idx, last_snap
            wh = sess[rng.choice(n_sess, size=n_w, replace=False)]
            pay = np.zeros((n_w, 4), np.int32)
            pay[:, 0] = 1  # put
            pay[:, 1] = rng.integers(0, n_keys, n_w)
            pay[:, 2] = rng.integers(0, 1 << 20, n_w)
            plane.submit_auto(wh, pay)
            write_waves.append((plane.counters["accepted"],
                                time.perf_counter()))
            if do_reads:
                rh = sess[rng.choice(n_sess, size=n_r, replace=False)]
                q = np.zeros((n_r, 2), np.int32)
                q[:, 0] = 1  # get
                q[:, 1] = rng.integers(0, n_keys, n_r)
                seq = wave_idx * SEQ_STRIDE + np.arange(n_r)
                wave_t[wave_idx] = time.perf_counter()
                plane.submit_reads(rh, seq, q)
            wave_idx += 1
            plane.pump(force=True)
            now = time.perf_counter()
            if now - last_snap > 0.1:
                last_snap = now
                obs.snapshot()

        # -- warmup: compile the mixed-dispatch shapes ------------------
        for _ in range(3):
            _wave(do_reads=True)
        plane.settle(timeout=120.0)

        # -- per-call host-path baseline (the path reads replace) -------
        eng.consistent_read([0])  # warm the single-step path
        n_calls = 5
        t0 = time.perf_counter()
        for i in range(n_calls):
            eng.consistent_read([i % lanes])
        percall_s = (time.perf_counter() - t0) / n_calls

        # measured sections start here: fresh percentile reservoirs
        # (warmup/compile samples out of the p99 tails) and the
        # steady-state compile baseline — reads interleaved with writes
        # must not retrace past this line
        eng.phases.reset_reservoirs()
        write_lats.clear()
        read_lats.clear()
        dw0 = dict(devicewatch.WATCH.counters)

        # -- write-only reference at the mixed run's write rate ---------
        t_w0 = time.perf_counter()
        while time.perf_counter() - t_w0 < seconds * 0.5:
            _wave(do_reads=False)
        plane.settle(timeout=120.0)
        wl = sorted(write_lats)
        write_only_p99_ms = round(
            1000 * wl[min(len(wl) - 1, int(len(wl) * 0.99))], 3) \
            if wl else -1.0

        # -- the mixed run ---------------------------------------------
        eng.phases.reset_reservoirs()
        write_lats.clear()
        rc0 = dict(plane.read_counters)
        wrote0 = plane.counters["accepted"]
        t_mix = time.perf_counter()
        while time.perf_counter() - t_mix < seconds:
            _wave(do_reads=True)
        plane.settle(timeout=120.0)
        elapsed = time.perf_counter() - t_mix
        obs.snapshot()
        verdicts = {name: o["verdict"] for name, o in
                    slo.evaluate()["objectives"].items()}

        rc = plane.read_counters
        served = rc["served"] - rc0["served"]
        submitted = max(1, rc["submitted"] - rc0["submitted"])
        blocks = max(1, rc["blocks_built"] - rc0["blocks_built"])
        block_rows = rc["block_rows"] - rc0["block_rows"]
        wrote = plane.counters["accepted"] - wrote0
        read_cmds_per_s = served / max(elapsed, 1e-9)
        percall_reads_per_s = 1.0 / max(percall_s, 1e-9)
        rl = sorted(read_lats)
        wl = sorted(write_lats)
        read_p99_ms = round(
            1000 * rl[min(len(rl) - 1, int(len(rl) * 0.99))], 3) \
            if rl else -1.0
        write_p99_ms = round(
            1000 * wl[min(len(wl) - 1, int(len(wl) * 0.99))], 3) \
            if wl else -1.0
        dw = devicewatch.WATCH.counters
        ov = plane.read_overview()
        print(json.dumps({
            "metric": "read_cmds_per_sec_mixed",
            "value": round(read_cmds_per_s, 1),
            "unit": "reads/s",
            "read_cmds_per_s": round(read_cmds_per_s, 1),
            "read_p99_ms": read_p99_ms,
            "read_e2e_phase_p99_ms":
                eng.phases.overview()["read_e2e"]["p99_ms"],
            "read_share": read_share,
            "reads_per_dispatch": round(block_rows / blocks, 1),
            "read_served": int(served),
            "read_shed_rate": round(
                (rc["shed"] - rc0["shed"]) / submitted, 6),
            "read_stale_refused": int(
                rc["stale_refused"] - rc0["stale_refused"]),
            "lease_coverage_pct": ov.get("lease_coverage_pct", -1.0),
            "write_cmds_per_s": round(wrote / max(elapsed, 1e-9), 1),
            "write_p99_ms": write_p99_ms,
            "write_only_p99_ms": write_only_p99_ms,
            "write_p99_vs_write_only": round(
                write_p99_ms / write_only_p99_ms, 3)
                if write_only_p99_ms > 0 and write_p99_ms > 0 else -1.0,
            "percall_read_ms": round(1000 * percall_s, 3),
            "percall_reads_per_s": round(percall_reads_per_s, 1),
            "read_plane_speedup_vs_percall": round(
                read_cmds_per_s / percall_reads_per_s, 1),
            "slo": verdicts,
            "slo_read_verdict": verdicts.get("read_p99_ms", "no_data"),
            "slo_write_verdict": verdicts.get("commit_p99_ms", "no_data"),
            "lanes": lanes, "members": members,
            "cmds_per_step": cmds, "read_window": kr,
            "superstep_k": superstep_k, "durable": True,
            "wave_rows": wave_rows,
            "steady_state_compiles": dw["compiles"] - dw0["compiles"],
            "steady_state_recompiles":
                dw["recompiles"] - dw0["recompiles"],
            "platform": jax.devices()[0].platform,
            "host": _host_meta(),
            **devicewatch.bench_tail_keys(int(wrote + served)),
        }))


# ---------------------------------------------------------------------------
# frontier mode: the latency/throughput frontier (one child, four points)
# ---------------------------------------------------------------------------

def _frontier_main() -> None:
    """Continuous pipelined measurement at several step sizes.

    For each step size, the host dispatches batches back-to-back with a
    bounded un-acknowledged window (client-side pipelining, the credit
    window of ra_bench.erl:84-129) and harvests *asynchronous* commit
    readbacks — dispatch of step N+1 never waits for the readback of
    step N.  Per-batch commit latency is the wall clock from dispatch to
    the first harvested readback whose cumulative count covers the
    batch.  Reports cmds/s + p50/p99 per point: the frontier."""
    import collections

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    n_lanes = int(os.environ.get("RA_TPU_BENCH_LANES", N_LANES))
    n_members = int(os.environ.get("RA_TPU_BENCH_MEMBERS", N_MEMBERS))
    seconds = float(os.environ.get("RA_TPU_BENCH_SECONDS", "3.0"))
    window = int(os.environ.get("RA_TPU_BENCH_WINDOW", "4"))
    sizes = [int(s) for s in os.environ.get(
        "RA_TPU_BENCH_SIZES", "1,8,32,128").split(",")]

    # measure the backend's synchronous dispatch+readback round trip:
    # the floor under any observed-commit latency — it bounds p50/p99
    # below regardless of engine step time, so record it alongside
    x = jnp.ones((8,), jnp.int32)
    f = jax.jit(lambda a: a + 1)
    np.asarray(f(x))
    rtts = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(f(x))
        rtts.append(time.perf_counter() - t0)
    rtts.sort()
    sync_rtt_ms = round(1000 * rtts[len(rtts) // 2], 3)

    points = []
    for cmds in sizes:
        eng = LockstepEngine(CounterMachine(), n_lanes, n_members,
                             ring_capacity=1024, max_step_cmds=cmds,
                             apply_window=cmds + 2, write_delay=1)
        n_new = jnp.full((n_lanes,), cmds, jnp.int32)
        payloads = jnp.ones((n_lanes, cmds, 1), jnp.int32)
        zero_n = jnp.zeros((n_lanes,), jnp.int32)
        for _ in range(5):
            eng.step(n_new, payloads)
        for _ in range(4):
            eng.step(zero_n, payloads)  # settle: warmup entries commit
        eng.block_until_ready()  # ra04-ok: per-point warmup boundary
        # solo (unpipelined) step-time tail at this config: with a
        # window of W, the oldest in-flight batch is W rounds from its
        # readback, so W * step_p99 is the p99 floor THIS BACKEND can
        # reach regardless of the pipeline's health — the effective bar
        # takes it in alongside the RTT floor.  Probed with the REAL
        # append workload (n_new, not empty rounds — empty steps read
        # several times faster and under-state the floor), and solo, so
        # a pipelining/readback regression (what the bar guards) cannot
        # hide in it.
        stimes = []
        for _ in range(12):
            ts = time.perf_counter()
            eng.step(n_new, payloads)
            eng.block_until_ready()  # ra04-ok: solo step-time probe,
            # deliberately synchronous — it measures the UNPIPELINED
            # step tail the effective p99 bar is derived from
            stimes.append(time.perf_counter() - ts)
        step_p99_ms = round(1000 * sorted(stimes)[-1], 3)
        for _ in range(4):
            eng.step(zero_n, payloads)  # settle the probe's appends
        eng.block_until_ready()  # ra04-ok: pre-measurement boundary
        base = eng.committed_total()  # ra04-ok: pre-measurement baseline

        per_batch = n_lanes * cmds
        batches = collections.deque()    # (target_cum, t_dispatch)
        readbacks = collections.deque()  # device arrays, dispatch order
        lats = []
        dispatched = 0
        obs_cum = 0
        t_last_obs = None  # wall time the newest commit was observed

        def harvest(block: bool) -> None:
            nonlocal obs_cum, t_last_obs
            while readbacks:
                tc = readbacks[0]
                if not block and not tc.is_ready():
                    return
                readbacks.popleft()
                cum = int(np.asarray(tc).astype(np.int64).sum()) - base  # ra04-ok: ready (or window boundary)
                t_obs = time.perf_counter()
                if cum > obs_cum:
                    obs_cum = cum
                    t_last_obs = t_obs
                while batches and batches[0][0] <= obs_cum:
                    _tgt, t_disp = batches.popleft()
                    lats.append(t_obs - t_disp)
                if block:
                    return

        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            while len(batches) >= window:
                if not readbacks:
                    # commit lag >= window: drive an empty round so a
                    # readback exists to cover the oldest batch (else
                    # this wait would spin forever)
                    eng.step(zero_n, payloads)
                    readbacks.append(eng.committed_lanes_async())
                harvest(block=True)
            t = time.perf_counter()
            eng.step(n_new, payloads)
            dispatched += 1
            batches.append((dispatched * per_batch, t))
            readbacks.append(eng.committed_lanes_async())
            harvest(block=False)
        # flush: empty steps until every dispatched batch is observed
        flush_spins = 0
        while batches and flush_spins < 64:
            eng.step(zero_n, payloads)
            readbacks.append(eng.committed_lanes_async())
            harvest(block=True)
            flush_spins += 1
        elapsed = time.perf_counter() - t0
        committed = eng.committed_total() - base  # ra04-ok: post-flush readback
        # The flush loop is capped, so batches may remain unflushed:
        # their dispatch time would sit in the denominator (plus up to
        # 64 spins of flush time) with their commands missing from the
        # numerator, silently skewing the rate.  Compute the rate over
        # the observed-commit edge instead — numerator is what the
        # harvests actually saw, denominator ends at the last observed
        # commit — and report the unflushed remainder explicitly.
        rate_elapsed = (t_last_obs - t0) if t_last_obs is not None \
            else elapsed
        lats.sort()
        n = len(lats)
        points.append({
            "cmds_per_step": cmds,
            "value": round(obs_cum / rate_elapsed, 1)
                if rate_elapsed > 0 else 0.0,
            "p50_commit_latency_ms":
                round(1000 * lats[n // 2], 3) if n else -1.0,
            "p99_commit_latency_ms":
                round(1000 * lats[min(n - 1, int(n * 0.99))], 3)
                if n else -1.0,
            "batches_measured": n,
            "batches_unflushed": len(batches),
            "unflushed_cmds": len(batches) * per_batch,
            "committed_total": int(committed),
            "step_p99_ms": step_p99_ms,
            "window": window,
        })
        del eng

    # headline frontier value: best throughput among points meeting the
    # p99 < 25 ms latency bar (BASELINE.md "without p99 collapse").
    # Per point the bar is lifted to the backend's own pipeline floor:
    # the oldest in-flight batch is `window` rounds from its readback,
    # and on an oversubscribed host the pipelined tail additionally
    # stacks dispatch-queue depth on the solo step tail — hence the
    # (window+1) * solo-step-p99 * 1.5 queueing margin (measured on the
    # 2-core CI box; solo steps never queue, so a pipelining/readback
    # regression cannot hide in the probe).  On real hardware steps are
    # sub-ms and the 25ms/RTT term dominates — the bar is unchanged
    # where it matters.
    bar = max(25.0, 3 * sync_rtt_ms)
    for p in points:
        floor = (p["window"] + 1) * p["step_p99_ms"] * 1.5
        eff = max(bar, floor)
        p["p99_bar_effective_ms"] = round(eff, 3)
        p["meets_p99_bar"] = bool(0 < p["p99_commit_latency_ms"] < eff)
    ok = [p for p in points if p["meets_p99_bar"]]
    best = max(ok or points, key=lambda p: p["value"])
    # the documented DEFAULT operating point (docs/BENCHMARKS.md):
    # cmds_per_step=32 with a window of 4 — deep enough batching to
    # amortize dispatch, shallow enough that the oldest in-flight batch
    # is never more than 4 device rounds from its readback
    default_point = next(
        (p for p in points if p["cmds_per_step"] == FRONTIER_DEFAULT_CMDS),
        None)
    print(json.dumps({
        "value": best["value"],
        "best_point": best,
        "default_point": default_point,
        "p99_bar_ms": round(bar, 3),
        "points": points,
        # the frontier sweeps the BATCHING axis (cmds_per_step) on the
        # single-step path; the fused-dispatch axis (superstep_k) is
        # covered by the throughput child's --superstep row — see
        # docs/BENCHMARKS.md "choosing superstep_k vs cmds_per_step"
        "superstep_k": 0,
        "sync_rtt_ms": sync_rtt_ms,
        "note": "observed-commit latency floor ~= sync_rtt_ms; "
                "p99 bar is max(25ms, 3*rtt)",
        "platform": jax.devices()[0].platform,
        "lanes": n_lanes, "members": n_members, "host": _host_meta(),
    }))


# ---------------------------------------------------------------------------
# parent mode: orchestration that cannot hang
# ---------------------------------------------------------------------------

_CHILD_ERRORS: list = []  # (config, rc/timeout, stderr tail) of failed runs


def _run_child(env_extra: dict, timeout_s: float):
    """Run one measurement child; return its parsed JSON or None (the
    failure reason is recorded in _CHILD_ERRORS for the output detail)."""
    env = {**os.environ, **env_extra, "RA_TPU_BENCH_CHILD": "1"}
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           capture_output=True, text=True, timeout=timeout_s,
                           env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        _CHILD_ERRORS.append({"config": env_extra, "rc": "timeout"})
        return None
    if r.returncode != 0:
        _CHILD_ERRORS.append({"config": env_extra, "rc": r.returncode,
                              "stderr_tail": r.stderr[-2000:]})
        return None
    for line in reversed(r.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                if isinstance(parsed, dict) and "value" in parsed:
                    return parsed
            except json.JSONDecodeError:
                pass
            break
    _CHILD_ERRORS.append({"config": env_extra, "rc": 0,
                          "note": "no parsable result line"})
    return None


#: the device-plane bench-tail keys (ISSUE 16, devicewatch.bench_tail_keys)
_DEVICE_TAIL_KEYS = ("n_compiles", "n_recompiles", "compile_time_s",
                     "transfer_bytes", "transfer_bytes_per_cmd",
                     "peak_live_bytes")


def _promote_device_keys(child_row: dict) -> dict:
    """Copy the device-plane tail keys from the child whose ``value``
    becomes the parent headline onto the parent line itself — counters
    are per-PROCESS, so the parent (which never dispatches) must
    promote the measuring child's stamp for bench_diff to compare
    headline rows across rounds."""
    return {k: child_row[k] for k in _DEVICE_TAIL_KEYS if k in child_row}


def _probe_platform() -> str | None:
    """Return the default jax platform, or None if backend init hangs/fails.
    Runs in a subprocess: the parent must not hold the chip its children
    need, and the probe has exited before the first of them starts."""
    code = "import jax; print('PLATFORM=' + jax.devices()[0].platform)"
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=PROBE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        return None
    if r.returncode != 0:
        return None
    for line in r.stdout.splitlines():
        if line.startswith("PLATFORM="):
            return line.split("=", 1)[1].strip()
    return None


def _parse_flags(argv) -> None:
    """--superstep [K]: turn on the fused-dispatch throughput row (K
    defaults to "auto" = the system-level superstep_k tunable).  Set as
    env so measurement children inherit it.  --multichip: run the
    sharded-mesh frontier sweep instead of the headline matrix."""
    if "--superstep" in argv:
        i = argv.index("--superstep")
        k = "auto"
        if i + 1 < len(argv) and argv[i + 1].isdigit():
            k = argv[i + 1]
        os.environ["RA_TPU_BENCH_SUPERSTEP"] = k
    if "--multichip" in argv:
        os.environ["RA_TPU_BENCH_MODE"] = "multichip"
    if "--wire" in argv:
        os.environ["RA_TPU_BENCH_MODE"] = "wire"
    if "--reads" in argv:
        # the mixed read/write frontier (ISSUE 20); --read-share tunes
        # the read fraction of every wave (default 0.9 — the 90/10 mix)
        os.environ["RA_TPU_BENCH_MODE"] = "reads"
    if "--read-share" in argv:
        i = argv.index("--read-share")
        if i + 1 < len(argv):
            os.environ["RA_TPU_BENCH_READ_SHARE"] = argv[i + 1]


MULTICHIP_TIMEOUT_S = 1200

_CHILD_MODES = {"frontier": _frontier_main, "multichip": _multichip_main,
                "wire": _wire_bench_main, "reads": _reads_bench_main}


def _fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    for err in _CHILD_ERRORS:
        print(f"bench: child failed: {json.dumps(err)}", file=sys.stderr)
    return code


def main() -> int:
    _parse_flags(sys.argv[1:])
    mode = os.environ.get("RA_TPU_BENCH_MODE")
    if os.environ.get("RA_TPU_BENCH_CHILD"):
        from ra_tpu.utils import enable_compile_cache
        enable_compile_cache()
        _CHILD_MODES.get(mode, _child_main)()
        return 0

    platform = _probe_platform()
    if platform != "tpu":
        return _fail(f"no chip: JAX found platform {platform!r}; run on "
                     "the machine that holds the TPU (nothing is measured "
                     "on a CPU)", 2)

    if mode in ("wire", "reads", "multichip"):
        res = _run_child({"RA_TPU_BENCH_MODE": mode},
                         MULTICHIP_TIMEOUT_S if mode == "multichip"
                         else CHILD_TIMEOUT_S)
        if res is None:
            return _fail(f"the {mode} child failed", 1)
        print(json.dumps(res))
        return 0

    # headline matrix.  The pallas kernel is a demoted experiment: it
    # only re-enters the comparison when RA_TPU_ENABLE_PALLAS_QUORUM=1
    # opts back in
    impls = ("xla", "pallas") if os.environ.get(
        "RA_TPU_ENABLE_PALLAS_QUORUM", "") not in ("", "0") \
        else ("xla",)
    results = {impl: _run_child({"RA_TPU_QUORUM_IMPL": impl},
                                CHILD_TIMEOUT_S) for impl in impls}
    if None in results.values():
        return _fail("a headline child failed", 1)
    best_impl = max(results, key=lambda k: results[k]["value"])
    best = results[best_impl]
    detail = {"best_quorum_impl": best_impl, "host": _host_meta(),
              **results}
    # secondary BASELINE.md rows (short windows): durable, the latency
    # frontier, 5k x 5 fifo enqueue/dequeue, 2k-lane kv mixed put/get
    for row, env in (
        ("durable_10k_x5", {"RA_TPU_BENCH_DURABLE": "1",
                            "RA_TPU_BENCH_SECONDS": "4.0"}),
        ("frontier", {"RA_TPU_BENCH_MODE": "frontier",
                      "RA_TPU_BENCH_SECONDS": "3.0"}),
        ("fifo_5k_x5", {"RA_TPU_BENCH_MACHINE": "fifo",
                        "RA_TPU_BENCH_LANES": "5000",
                        "RA_TPU_BENCH_SECONDS": "2.0"}),
        ("kv_2k", {"RA_TPU_BENCH_MACHINE": "kv",
                   "RA_TPU_BENCH_LANES": "2000",
                   "RA_TPU_BENCH_SECONDS": "2.0"}),
    ):
        detail[row] = _run_child({**env, "RA_TPU_QUORUM_IMPL": best_impl},
                                 CHILD_TIMEOUT_S)
        if detail[row] is None:
            return _fail(f"the {row} child failed", 1)
    print(json.dumps({
        "metric": "committed_cmds_per_sec_10k_clusters_5_members",
        "value": best["value"],
        "unit": "cmds/s",
        "vs_baseline": round(best["value"] / BASELINE, 4),
        **_promote_device_keys(best),
        "detail": detail,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
