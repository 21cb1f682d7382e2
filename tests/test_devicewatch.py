"""Device-plane runtime observatory tests (ISSUE 16).

The jit-plane static gates (RA04/RA13/RA14/RA15) are proof-only; this
file pins their runtime mirror: every steady-state dispatch loop —
single-step, superstep K=8 through the dispatch-ahead driver, the
sharded-mesh driver, and the ingress pump — runs at ZERO new compiles
and a FIXED per-window transfer budget over a warm measured window; a
deliberate shape drift IS caught and the sentinel names the drifting
argument; the instruments' overhead on the bench dispatch path stays
under 3% (interleaved A/B, the same discipline as the telemetry
overhead pin); and the DEVICE_FIELDS round-trip Observatory ->
Prometheus -> time-series ring -> ra_top.

Deltas, not absolutes: ``WATCH`` is process-wide on purpose (compiles
and live buffers are process facts), so every pin snapshots counters
around its own measured window instead of resetting the singleton out
from under other tests.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from ra_tpu.blackbox import RECORDER
from ra_tpu.devicewatch import WATCH, bench_tail_keys
from ra_tpu.engine import DispatchAheadDriver, LockstepEngine
from ra_tpu.metrics import DEVICE_FIELDS, FIELD_REGISTRY
from ra_tpu.models import CounterMachine
from ra_tpu.telemetry import (Observatory, TelemetrySampler,
                              parse_prometheus)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, P, KC = 16, 3, 4


def mk_engine(lanes=N, cmds=KC, ring=64, **kw):
    kw.setdefault("donate", False)
    return LockstepEngine(CounterMachine(), lanes, P,
                          ring_capacity=ring, max_step_cmds=cmds, **kw)


def compile_snap():
    return (WATCH.counters["compiles"], WATCH.counters["recompiles"])


def site_snap(site):
    return dict(WATCH.sites[site])


def site_delta(site, before):
    now = WATCH.sites[site]
    return {k: now[k] - before[k] for k in before}


# ---------------------------------------------------------------------------
# registry + surface shape
# ---------------------------------------------------------------------------

def test_device_fields_registered_and_covered_by_overview():
    assert FIELD_REGISTRY["device"] is DEVICE_FIELDS
    snap = WATCH.overview()
    for f in DEVICE_FIELDS:
        assert f in snap, f
    assert "per_fn" in snap and "sites" in snap


def test_bench_tail_keys_shape():
    """The ONE definition of the soak-tail device stamp, derived
    from the live counters."""
    tail = bench_tail_keys()
    assert set(tail) == {"n_compiles", "n_recompiles", "compile_time_s",
                         "transfer_bytes", "peak_live_bytes"}
    assert tail["transfer_bytes"] == \
        WATCH.counters["h2d_bytes"] + WATCH.counters["d2h_bytes"]
    with_cmds = bench_tail_keys(commands=1000)
    assert with_cmds["transfer_bytes_per_cmd"] == \
        round(with_cmds["transfer_bytes"] / 1000, 4)


# ---------------------------------------------------------------------------
# steady-state zero-recompile pins (the acceptance loops)
# ---------------------------------------------------------------------------

def test_single_step_loop_steady_state():
    """Warm single-step dispatch: zero new compiles over the measured
    window, and the per-window transfer budget is FIXED — two equal
    windows of the bench loop body produce identical lanes_async d2h
    deltas (events and bytes)."""
    eng = mk_engine()
    n_new = np.full((N,), 2, np.int32)
    pay = np.ones((N, KC, 1), np.int32)
    for _ in range(3):                       # warm-up: compiles happen here
        eng.step(n_new, pay)
        eng.committed_lanes_async()
    eng.block_until_ready()

    def window():
        c0 = compile_snap()
        s0 = site_snap("lanes_async")
        for _ in range(20):
            eng.step(n_new, pay)
            eng.committed_lanes_async()
        eng.block_until_ready()
        assert compile_snap() == c0, "steady-state loop compiled"
        return site_delta("lanes_async", s0)

    w1, w2 = window(), window()
    assert w1["d2h_events"] == 20
    assert w1 == w2, (w1, w2)               # fixed per-window budget
    assert w1["d2h_bytes"] > 0


def test_superstep_k8_driver_loop_steady_state():
    """Warm K=8 fused dispatch through the dispatch-ahead driver: zero
    new compiles, and the budget is exactly 2 staged h2d events + 1
    watermark d2h event per submit, identical across windows."""
    eng = mk_engine()
    drv = DispatchAheadDriver(eng, max_in_flight=2)
    nb = np.full((8, N), 2, np.int32)
    pb = np.ones((8, N, KC, 1), np.int32)
    for _ in range(3):
        drv.submit(nb, pb)
    drv.drain()

    def window():
        c0 = compile_snap()
        h0 = site_snap("driver_stage")
        d0 = site_snap("driver_watermark")
        for _ in range(10):
            drv.submit(nb, pb)
        drv.drain()
        assert compile_snap() == c0, "steady-state superstep compiled"
        return (site_delta("driver_stage", h0),
                site_delta("driver_watermark", d0))

    (h1, d1), (h2, d2) = window(), window()
    assert h1["h2d_events"] == 2 * 10 and d1["d2h_events"] == 10
    assert (h1, d1) == (h2, d2)
    assert h1["h2d_bytes"] == 10 * (nb.nbytes + pb.nbytes)


def test_mesh_driver_loop_steady_state():
    """The sharded-mesh dispatch loop (back-to-back submits to a
    mesh_superstep_driver): the one-time state reshard lands in the
    mesh_shard h2d site, then the measured window adds ZERO compiles
    and only the per-dispatch staging/watermark budget."""
    import jax

    from ra_tpu.parallel.mesh import (mesh_superstep_driver,
                                      shard_engine_state)
    if len(jax.devices()) < 2:
        pytest.skip("single-device backend")
    eng = mk_engine(lanes=64)
    m0 = site_snap("mesh_shard")
    mesh = shard_engine_state(eng)
    ms = site_delta("mesh_shard", m0)
    assert ms["h2d_events"] > 0 and ms["h2d_bytes"] > 0
    drv = mesh_superstep_driver(eng, mesh, max_in_flight=2)
    nb = np.full((8, 64), 2, np.int32)
    pb = np.ones((8, 64, KC, 1), np.int32)
    for _ in range(3):
        drv.submit(nb, pb)
    drv.drain()
    c0 = compile_snap()
    m0 = site_snap("mesh_shard")
    h0 = site_snap("driver_stage")
    dispatches = 6
    for _ in range(dispatches):
        drv.submit(nb, pb)
    drv.drain()
    assert compile_snap() == c0, "mesh driver loop compiled"
    # the reshard is one-time: ZERO mesh_shard h2d inside the window
    # (a per-window delta here is the repartition bug RA15 guards)
    assert site_delta("mesh_shard", m0)["h2d_events"] == 0
    assert site_delta("driver_stage", h0)["h2d_events"] == \
        2 * dispatches


def test_ingress_pump_loop_steady_state():
    """Warm ingress pump waves (dedup -> admission -> coalesce ->
    fused dispatch): zero new compiles across the measured waves."""
    from ra_tpu.ingress import IngressPlane
    eng = mk_engine(lanes=32, cmds=4)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0,
                         soft_credit=64, hard_credit=256)
    h = plane.connect_bulk(100, tenants=4, key="dw")
    rng = np.random.default_rng(9)

    def wave():
        sess = h[rng.integers(0, len(h), 48)]
        seq = plane.directory.next_seqnos(sess)
        pay = rng.integers(1, 5, (48, 1)).astype(np.int32)
        plane.submit(sess, seq, pay)
        plane.pump(force=True)

    for _ in range(3):                      # warm-up waves
        wave()
    plane.settle()
    c0 = compile_snap()
    for _ in range(6):
        wave()
    plane.settle()
    assert compile_snap() == c0, "steady-state ingress pump compiled"


# ---------------------------------------------------------------------------
# drift attribution: the sentinel names the drifting argument
# ---------------------------------------------------------------------------

def test_shape_drift_recompile_is_detected_and_attributed():
    """A K=8 -> K=4 superstep block drift is a retrace: the sentinel
    counts a recompile, names the drifting argument (shape of the
    n_new/payload block leaves) in per_fn last_drift, and emits the
    registered device.recompile flight-recorder event."""
    # a config no other test uses, so the superstep proxy is fresh
    eng = LockstepEngine(CounterMachine(), 6, 3, ring_capacity=32,
                         max_step_cmds=3, donate=False)
    nb8 = np.full((8, 6), 2, np.int32)
    pb8 = np.ones((8, 6, 3, 1), np.int32)
    eng.superstep(nb8, pb8)                 # first compile (legit)
    eng.superstep(nb8, pb8)                 # warm: no compile
    c0 = compile_snap()
    base_events = len(RECORDER.events("device"))
    eng.superstep(nb8[:4], pb8[:4])         # K drift -> retrace
    c1 = compile_snap()
    assert c1[0] == c0[0] + 1               # one compile...
    assert c1[1] == c0[1] + 1               # ...counted as a RECOMPILE
    drift = WATCH.per_fn["superstep"]["last_drift"]
    assert "shape" in drift, drift
    assert "(8, 6" in drift and "(4, 6" in drift, drift
    evs = RECORDER.events("device")
    assert len(evs) > base_events
    ts, etype, fields = evs[-1]
    assert etype == "device.recompile"
    assert fields["fn"] == "superstep" and "shape" in fields["drift"]


def test_first_compile_of_new_config_is_not_a_recompile():
    """A different engine config compiles fresh jit variants: compiles
    grow, recompiles must NOT — warm-up is not a storm and not drift."""
    c0 = compile_snap()
    eng = LockstepEngine(CounterMachine(), 5, 3, ring_capacity=32,
                         max_step_cmds=2, donate=False)
    eng.step(np.full((5,), 1, np.int32), np.ones((5, 2, 1), np.int32))
    c1 = compile_snap()
    assert c1[0] > c0[0]
    assert c1[1] == c0[1]


# ---------------------------------------------------------------------------
# memory watermarks ride the harvest tick
# ---------------------------------------------------------------------------

def test_watermarks_sampled_on_harvest_cadence():
    """The sampler's harvest tick drives the live-buffer census: no
    sampler, no samples (zero new syncs by construction — the census
    rides the tick the loop already pays for)."""
    eng = mk_engine(lanes=8)
    w0 = WATCH.counters["watermark_samples"]
    for _ in range(4):
        eng.uniform_step(2)
    assert WATCH.counters["watermark_samples"] == w0  # no sampler yet
    s = TelemetrySampler(eng, cadence_steps=4)
    for _ in range(8):
        eng.uniform_step(2)
    s.drain()
    c = WATCH.counters
    assert c["watermark_samples"] > w0
    assert c["live_buffers"] > 0 and c["live_bytes"] > 0
    assert c["peak_live_bytes"] >= c["live_bytes"]


def test_donation_keeps_live_set_flat():
    """RA14's runtime twin: with donation ON, dispatches grow while the
    live-buffer census stays flat — the window's live_buffers delta is
    bounded (a monotonically growing live set here is the donation
    regression the watermarks exist to catch)."""
    eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=64,
                         max_step_cmds=KC, donate=False,
                         superstep_donate=True)
    nb = np.full((4, N), 2, np.int32)
    pb = np.ones((4, N, KC, 1), np.int32)
    for _ in range(3):
        eng.superstep(nb, pb)
    eng.block_until_ready()
    WATCH.sample_watermarks()
    before = WATCH.counters["live_buffers"]
    for _ in range(25):
        eng.superstep(nb, pb)
    eng.block_until_ready()
    WATCH.sample_watermarks()
    after = WATCH.counters["live_buffers"]
    # donated steady-state: no per-dispatch buffer accumulation (slack
    # covers allocator jitter, not a 25-dispatch leak)
    assert after - before < 25, (before, after)


# ---------------------------------------------------------------------------
# overhead: instruments on vs off, interleaved A/B, < 3%
# ---------------------------------------------------------------------------

def test_devicewatch_overhead_under_3pct(monkeypatch):
    """What the WATCH adds to the bench dispatch pattern, pinned as
    counts of what a warm dispatch does and not as a ratio of two wall
    windows (which 0.3 s windows on a shared box cannot resolve to 3%):
    two reads of the jit's cache size and the ledger's increments, no
    signature walk, no compile noted, no census, no wait; with the
    master switch off, nothing at all."""
    import collections

    from ra_tpu import devicewatch

    eng = LockstepEngine(CounterMachine(), 64, 3, ring_capacity=64,
                         max_step_cmds=8, donate=False)
    n_new = np.full((64,), 8, np.int32)
    pay = np.ones((64, 8, 1), np.int32)
    for _ in range(10):
        eng.step(n_new, pay)
    eng.block_until_ready()

    class Counting:
        """The jitted step, counting the sentinel's cache-size reads."""

        def __init__(self, inner):
            self.inner, self.reads = inner, 0

        def __call__(self, *a, **kw):
            return self.inner(*a, **kw)

        def _cache_size(self):
            self.reads += 1
            return self.inner._cache_size()

    # the proxy is shared through the step cache: put its jit back
    proxy = eng._step
    counting = Counting(proxy._inner)
    walks = []
    sig = devicewatch._abstract_sig
    monkeypatch.setattr(devicewatch, "_abstract_sig",
                        lambda *a: walks.append(1) or sig(*a))

    def loop(n):
        rb: collections.deque = collections.deque()
        for _ in range(n):         # the bench dispatch pattern
            eng.step(n_new, pay)
            rb.append(eng.committed_lanes_async())
            while len(rb) > 8:
                np.asarray(rb.popleft())
        eng.block_until_ready()

    assert WATCH.enabled
    loop(10)                       # the readback's own programs, warm
    proxy._inner = counting
    try:
        c0 = dict(WATCH.counters)
        loop(200)
        c1 = dict(WATCH.counters)
        assert counting.reads == 2 * 200
        assert walks == []
        for k in ("compiles", "recompiles", "xla_compiles",
                  "watermark_samples"):
            assert c1[k] == c0[k], k
        # the ledger: one async watermark readback a dispatch, and the
        # copy of the step's apply flags (4 bytes a round, ISSUE 34)
        assert c1["d2h_events"] - c0["d2h_events"] == 2 * 200
        assert WATCH.sites["apply_flags"]["d2h_bytes"] >= 4 * 200
        WATCH.enabled = False
        counting.reads = 0
        loop(50)
        assert counting.reads == 0
        assert dict(WATCH.counters) == c1
    finally:
        WATCH.enabled = True
        proxy._inner = counting.inner


# ---------------------------------------------------------------------------
# round trip: Observatory -> Prometheus -> ring -> ra_top
# ---------------------------------------------------------------------------

def test_device_source_round_trips_observatory_prometheus_ring(tmp_path):
    eng = mk_engine(lanes=8)
    s = TelemetrySampler(eng, cadence_steps=4)
    for _ in range(8):
        eng.uniform_step(2)
    s.drain()
    obs = Observatory.for_engine(eng, sampler=s)
    try:
        snap = obs.snapshot()
        dev = snap["device"]
        for f in DEVICE_FIELDS:
            assert f in dev, f
        assert dev["compiles"] == WATCH.counters["compiles"]
        # Prometheus exposition
        flat = parse_prometheus(obs.prometheus())
        assert ("ra_tpu_device_compiles", "") in flat
        assert ("ra_tpu_device_peak_live_bytes", "") in flat
        assert flat[("ra_tpu_device_recompiles", "")] == \
            WATCH.counters["recompiles"]
        # time-series ring: flattened device_* keys, nested per-site
        obs.snapshot()
        _ts, flat_ring = obs.ring()[-1]
        dev_keys = [k for k in flat_ring if k.startswith("device_")]
        for f in DEVICE_FIELDS:
            assert f"device_{f}" in dev_keys
        assert any(k.startswith("device_sites_") for k in dev_keys)
        # ra_top renders the device panel from the JSONL ring
        path = str(tmp_path / "obs.jsonl")
        obs.to_jsonl(path)
        obs.to_jsonl(path)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ra_top.py"),
             path, "--once"], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "device  compiles=" in r.stdout, r.stdout
        assert "peak=" in r.stdout and "h2d=" in r.stdout
    finally:
        obs.close()


def test_slo_steady_state_recompiles_objective():
    """The default SLO set carries steady_state_recompiles <= 0 as a
    rate objective: device-plane rings evaluate it (ok at zero), and a
    classic-plane deployment without the device key stays no_data —
    never a false breach."""
    from ra_tpu.slo import SloEngine, default_objectives
    objs = default_objectives()
    assert any(o.name == "steady_state_recompiles" for o in objs)
    eng = mk_engine(lanes=8)
    s = TelemetrySampler(eng, cadence_steps=4)
    obs = Observatory.for_engine(eng, sampler=s)
    try:
        slo = SloEngine(obs, objs)
        for _ in range(8):
            eng.uniform_step(2)
        s.drain()
        obs.snapshot()
        obs.snapshot()
        res = slo.evaluate()["objectives"]["steady_state_recompiles"]
        assert res["verdict"] == "ok", res
        assert res["value"] == 0.0
        # a ring without device keys -> no_data, not a breach
        bare = Observatory()
        try:
            bare.add_source("engine", lambda: {"telemetry": {}})
            slo2 = SloEngine(bare, objs)
            bare.snapshot()
            bare.snapshot()
            res2 = slo2.evaluate()["objectives"][
                "steady_state_recompiles"]
            assert res2["verdict"] == "no_data"
        finally:
            bare.close()
    finally:
        obs.close()


# ---------------------------------------------------------------------------
# soak family: tools/soak.py --device-obs
# ---------------------------------------------------------------------------

def run_device_obs_chaos(seed, data_dir):
    """One seeded episode of the device-observatory chaos family
    (driven over fresh seed ranges by ``tools/soak.py --device-obs``):
    a DURABLE engine takes fixed-shape superstep traffic through
    election churn and a seeded DiskFaultPlan on its WAL — the
    recompile sentinel must stay QUIET over the measured window (zero
    compiles of any kind once every code path is warm; host-plane
    chaos is not shape drift) — then a deliberate mixed-shape probe
    (K=8 -> K=4 block) MUST be detected within ONE Observatory window
    and attributed to the drifting block shape.  Raises on any
    violation; returns a summary dict for the soak tail.

    The engine config is seed-varied (lanes/cmds) so every episode in
    a multi-seed soak run gets FRESH jit variants — otherwise the
    process-global jit cache would hide the probe from episode 2 on.
    """
    import random as _random

    from ra_tpu.engine import open_engine
    from ra_tpu.log import faults

    rng = _random.Random(seed)
    lanes = 6 + seed % 5
    cmds = 2 + seed % 3
    plan = faults.DiskFaultPlan(seed=seed, by_class={
        "wal": faults.DiskFaultSpec(
            fsync_eio=rng.uniform(0.0, 0.15),
            limit=rng.randint(1, 4))})
    # default sync_mode=1: commits are fsync-gated, so the WAL
    # fault plan has real fsyncs to hit
    eng = open_engine(CounterMachine(), data_dir, lanes, P,
                      ring_capacity=48, max_step_cmds=cmds, donate=False)
    obs = Observatory.for_engine(eng)
    nb = np.full((8, lanes), 1, np.int32)
    pb = np.ones((8, lanes, cmds, 1), np.int32)
    faults.install_plan(plan)
    try:
        # warm every code path the chaos rounds exercise BEFORE the
        # measured window: fused dispatch, election, async readback
        eng.superstep(nb, pb)
        eng.trigger_election(list(range(lanes)))
        eng.superstep(nb, pb)
        np.asarray(eng.committed_lanes_async())
        eng.block_until_ready()
        c0 = compile_snap()
        rounds = 24
        for _ in range(rounds):
            roll = rng.random()
            if roll < 0.6:
                eng.superstep(nb, pb)
            elif roll < 0.8:
                eng.trigger_election(list(range(lanes)))
            else:
                np.asarray(eng.committed_lanes_async())
        eng.block_until_ready()
        c1 = compile_snap()
        assert c1 == c0, \
            f"sentinel fired under election/disk chaos: {c0} -> {c1}"
        # the deliberate mixed-shape probe: detected within ONE window
        obs.snapshot()
        pre = obs.ring()[-1][1]["device_recompiles"]
        eng.superstep(nb[:4], pb[:4])       # K=8 -> K=4 drift
        obs.snapshot()
        post = obs.ring()[-1][1]["device_recompiles"]
        assert post >= pre + 1, \
            f"mixed-shape probe NOT detected: {pre} -> {post}"
        drift = WATCH.per_fn["superstep"]["last_drift"]
        assert "shape" in drift, drift
        return {"rounds": rounds,
                "injected_faults": sum(plan.counters.values()),
                "probe_recompiles": int(post - pre), "drift": drift}
    finally:
        faults.clear_plan()
        obs.close()
        eng.close()


def test_device_obs_chaos_pinned_seed(tmp_path):
    run_device_obs_chaos(0, str(tmp_path / "s0"))


def test_autotuner_freezes_on_compile_storm():
    """A compile observed between autotuner ticks freezes tuning
    (reason compile_storm) for compile_freeze_s; quiet ticks thaw."""
    import time as _time

    from ra_tpu.autotune import AutoTuner
    eng = mk_engine(lanes=8)
    obs = Observatory.for_engine(eng)
    try:
        from ra_tpu.slo import SloEngine, default_objectives
        slo = SloEngine(obs, default_objectives())
        tun = AutoTuner(slo, compile_freeze_s=0.2)
        assert tun._compile_storm_reason() is None  # baseline tick
        WATCH.counters["compiles"] += 1             # a storm arrives
        assert tun._compile_storm_reason() == "compile_storm"
        assert tun._compile_storm_reason() == "compile_storm"  # quiet win
        _time.sleep(0.25)
        assert tun._compile_storm_reason() is None  # thawed
    finally:
        obs.close()
