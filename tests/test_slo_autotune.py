"""Phase attribution + SLO engine + closed-loop autotuner (ISSUE 9).

The acceptance pins: on a dispatch-bound workload the controller
raises ``superstep_k`` and CONVERGES within a bounded number of
windows; on an fsync-bound one it backs off the WAL batch interval and
then K instead; decisions freeze under an active DiskFaultPlan (and a
transport FaultPlan, and a fresh incident); every decision is a
registered flight-recorder event; phase attribution and SLO verdicts
reach the Prometheus exposition; and the whole plane's interleaved A/B
overhead on the bench dispatch path stays under 3%.

The closed-loop tests drive a SYNTHETIC workload: an Observatory whose
engine source is a controllable dict in the exact layout the real
engine source emits (same flat ring keys), with a plant model mapping
knob values to the next window's latencies — deterministic, seedless,
and it exercises the controller's real input path (ring -> flat keys
-> window_rates -> verdicts), not a mock of it.
"""
import gc
import time

import numpy as np
import pytest

from ra_tpu.autotune import AutoTuner, TUNABLE_KNOBS
from ra_tpu.blackbox import EVENT_REGISTRY, RECORDER
from ra_tpu.metrics import FIELD_REGISTRY, PHASE_FIELDS
from ra_tpu.slo import Objective, SloEngine, default_objectives
from ra_tpu.telemetry import Observatory, PhaseStats, parse_prometheus


# ---------------------------------------------------------------------------
# PhaseStats: the attribution substrate
# ---------------------------------------------------------------------------

def test_phase_fields_registered():
    assert FIELD_REGISTRY["phase"] is PHASE_FIELDS


def test_phase_stats_accumulates_and_buckets():
    ph = PhaseStats(reservoir=8)
    for ms in (0.5, 1.5, 3.0, 100.0):
        ph.note("fsync_wait", ms / 1000.0)
    ov = ph.overview()
    f = ov["fsync_wait"]
    assert f["count"] == 4
    assert f["total_ms"] == pytest.approx(105.0, rel=1e-3)
    assert f["p50_ms"] > 0 and f["max_ms"] == pytest.approx(100.0, rel=1e-3)
    # log2-ms buckets: 0.5ms -> b0, 1.5 -> b1, 3 -> b2, 100 -> b7
    assert f["hist"][0] == 1 and f["hist"][1] == 1
    assert f["hist"][2] == 1 and f["hist"][7] == 1
    # unknown phases are counted, never silently eaten
    ph.note("zz_bogus", 0.001)
    assert ph.overview()["dropped"] == 1
    # untouched phases report the -1 "never measured" sentinel
    assert ov["queue_wait"]["p50_ms"] == -1.0


# ---------------------------------------------------------------------------
# ring edge cases the SLO engine depends on (satellite)
# ---------------------------------------------------------------------------

def test_percentile_over_empty_and_missing_keys():
    obs = Observatory()
    assert obs.percentile("anything", 0.5) is None   # empty ring
    obs.add_source("s", lambda: {"x": 1})
    obs.snapshot()
    assert obs.percentile("s_x", 0.5) == 1.0
    assert obs.percentile("s_missing", 0.99) is None  # key never seen
    assert obs.window_rates() == {}                   # single entry


def test_window_rates_n_window_span():
    vals = iter(range(0, 500, 10))
    obs = Observatory()
    obs.add_source("s", lambda: {"ctr_count": next(vals)})
    t0 = time.time()
    for _ in range(5):
        obs.snapshot()
    # span=4 rates ring[-5] -> ring[-1]: delta 40 over the elapsed dt
    r = obs.window_rates(span=4)
    (ta, a), (tb, b) = obs.ring()[-5], obs.ring()[-1]
    assert r["s_ctr_count"] == pytest.approx(
        (b["s_ctr_count"] - a["s_ctr_count"]) / max(tb - ta, 1e-9),
        rel=1e-3)
    # an `end` in the past rates an interior pair
    r_mid = obs.window_rates(span=1, end=2)
    assert r_mid["s_ctr_count"] > 0
    # out-of-range spans yield {} rather than indexing garbage
    assert obs.window_rates(span=10) == {}
    del t0


def test_window_rates_keeps_depth_gauge_negative_drift():
    """dispatches_in_flight is a DEPTH gauge, not a counter: its
    negative drift (the pipeline draining) must stay visible — a
    substring monotone hint ('dispatches') must not swallow it."""
    depth = iter([4.0, 1.0])
    disp = iter([100.0, 50.0])  # the true counter resets -> omitted
    obs = Observatory()
    obs.add_source("engine", lambda: {"pipeline": {
        "dispatches_in_flight": next(depth),
        "dispatches": next(disp)}})
    obs.snapshot()
    obs.snapshot()
    rates = obs.window_rates()
    assert rates["engine_pipeline_dispatches_in_flight"] < 0
    assert "engine_pipeline_dispatches" not in rates


def test_window_rates_omits_counter_reset():
    """An engine restart zeroes monotone counters mid-ring: the rate
    must be OMITTED, never negative — a burn-rate evaluator fed a huge
    negative 'rate' across the restart window would mis-verdict."""
    seq = iter([1000.0, 2000.0, 5.0])  # restart before the 3rd snap
    gauge = iter([10.0, 4.0, 2.0])     # gauges may drift down freely
    obs = Observatory()
    obs.add_source("s", lambda: {"committed_total": next(seq),
                                 "lag_depth": next(gauge)})
    obs.snapshot()
    obs.snapshot()
    assert obs.window_rates()["s_committed_total"] > 0
    obs.snapshot()  # 2000 -> 5: backwards-moving monotone counter
    rates = obs.window_rates()
    assert "s_committed_total" not in rates
    assert rates["s_lag_depth"] < 0  # gauge drift still reported


# ---------------------------------------------------------------------------
# SLO engine: per-window verdicts + burn rates
# ---------------------------------------------------------------------------

def mk_obs(state):
    """An Observatory whose engine source mirrors the real layout —
    same flat ring keys the production SloEngine objectives read."""
    obs = Observatory(ring_capacity=64)

    def engine_src():
        return {
            "phases": {
                "device_dispatch": {
                    "total_ms": state["disp_total"]},
                "fsync_wait": {"total_ms": state["fsync_total"]},
                "commit_e2e": {"total_ms": state["e2e_total"],
                               "p99_ms": state["commit_p99"]},
            },
            "wal": {"shards": [{"fsync_p99_ms": state["fsync_p99"]}]},
            "telemetry": {"ts": time.time(),
                          "committed_total": state["committed"]},
            # a plant-controlled throughput GAUGE (value-kind floor
            # objectives): deterministic under scheduler jitter, unlike
            # differentiating committed_total against wall time
            "gauge_cmds_per_s": state["gauge_rate"],
        }

    obs.add_source("engine", engine_src)
    return obs


def base_state():
    return {"disp_total": 0.0, "fsync_total": 0.0, "e2e_total": 0.0,
            "commit_p99": 5.0, "fsync_p99": 5.0, "committed": 0.0,
            "gauge_rate": -1.0}


def test_slo_verdicts_ok_breach_alert_no_data():
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=100.0),
                    fast_windows=2, slow_windows=4,
                    burn_fast=0.5, burn_slow=0.5)
    v = slo.evaluate()
    assert v["objectives"]["commit_p99_ms"]["verdict"] == "no_data"
    for _ in range(3):
        state["committed"] += 1000.0
        time.sleep(0.002)
        obs.snapshot()
    v = slo.evaluate()["objectives"]
    assert v["commit_p99_ms"]["verdict"] == "ok"
    assert v["cmds_per_s"]["verdict"] == "ok"
    assert v["cmds_per_s"]["value"] > 100.0
    # sustained breach: fast then slow windows burn -> breach -> alert
    state["commit_p99"] = 90.0
    seen = []
    for _ in range(4):
        state["committed"] += 1000.0
        time.sleep(0.002)
        obs.snapshot()
        seen.append(slo.evaluate()["objectives"]["commit_p99_ms"])
    assert seen[0]["verdict"] in ("breach", "alert")
    assert seen[-1]["verdict"] == "alert"
    assert seen[-1]["burn_fast"] == 1.0
    assert not seen[-1]["ok"]
    # the verdicts ride the snapshot + exposition via the slo source
    snap = obs.snapshot()
    assert snap["slo"]["objectives"]["commit_p99_ms"]["ok"] is False
    text = obs.prometheus(snap)
    parsed = parse_prometheus(text)
    assert parsed[("ra_tpu_slo_objectives_commit_p99_ms_ok", "")] == 0.0


def test_slo_wildcard_aggregates_shards_and_skips_sentinels():
    shards = [{"fsync_p99_ms": -1.0}, {"fsync_p99_ms": 70.0}]
    obs = Observatory()
    obs.add_source("engine", lambda: {"wal": {"shards": shards}})
    slo = SloEngine(
        obs, (Objective("fsync_p99_ms",
                        "engine_wal_shards_*_fsync_p99_ms", "<=", 50.0),),
        fast_windows=1, slow_windows=2, burn_fast=0.5)
    obs.snapshot()
    v = slo.evaluate()["objectives"]["fsync_p99_ms"]
    # max over shards, -1 "never synced" sentinel excluded (with a
    # 1-window fast AND slow burn both saturate -> alert immediately)
    assert v["value"] == 70.0 and v["verdict"] in ("breach", "alert")
    assert not v["ok"]
    shards[1]["fsync_p99_ms"] = -1.0
    obs.snapshot()
    assert slo.evaluate()["objectives"]["fsync_p99_ms"]["verdict"] \
        == "no_data"


def test_slo_duplicate_objective_names_rejected():
    obs = Observatory()
    objs = (Objective("a", "x", "<=", 1.0), Objective("a", "y", ">=", 1.0))
    with pytest.raises(ValueError):
        SloEngine(obs, objs)


# ---------------------------------------------------------------------------
# the closed loop (acceptance demo, synthetic plants)
# ---------------------------------------------------------------------------

def mk_tuner(slo, obs, **kw):
    kw.setdefault("freeze_guard", lambda: None)  # plants, not chaos
    kw.setdefault("incident_freeze_s", 0.0)  # other tests dump bundles
    kw.setdefault("cooldown_windows", 0)
    kw.setdefault("breach_windows", 2)
    return AutoTuner(slo, obs, **kw)


def drive(obs, tuner, state, plant, windows):
    """Run the loop: plant(knobs) -> next window's metrics -> snapshot
    -> tick.  Returns the decisions made."""
    decisions = []
    for _ in range(windows):
        plant(tuner.knobs, state)
        time.sleep(0.002)
        obs.snapshot()
        d = tuner.tick()
        if d is not None:
            decisions.append(d)
    return decisions


def dispatch_bound_plant(knobs, state):
    """Fixed per-dispatch overhead amortized by K: commit p99 and the
    dispatch phase's budget share fall as superstep_k rises."""
    k = knobs["superstep_k"]
    state["disp_total"] += 100.0 / k
    state["fsync_total"] += 4.0
    state["e2e_total"] += 110.0 / k
    state["commit_p99"] = 100.0 / k + 5.0
    state["committed"] += 10000.0


def test_closed_loop_raises_superstep_k_when_dispatch_bound():
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = mk_tuner(slo, obs, knobs={"superstep_k": 1})
    base_events = len(RECORDER.events("tune"))
    decisions = drive(obs, tuner, state, dispatch_bound_plant,
                      windows=16)
    # k=1: p99 105 -> 2: 55 -> 4: 30 -> 8: 17.5 (under the 25ms SLO):
    # three doublings, all attributed to the dispatch phase, then quiet
    assert [d["knob"] for d in decisions] == ["superstep_k"] * 3
    assert [d["new"] for d in decisions] == [2, 4, 8]
    assert all(d["phase"] == "device_dispatch" for d in decisions)
    assert all(d["objective"] == "commit_p99_ms" for d in decisions)
    assert tuner.knobs["superstep_k"] == 8
    assert tuner.decisions.maxlen == 256  # bounded, like every record
    # CONVERGED: green windows keep the knobs still
    more = drive(obs, tuner, state, dispatch_bound_plant, windows=6)
    assert more == []
    # every decision is a registered flight-recorder event
    evs = RECORDER.events("tune")[base_events:]
    decided = [e for e in evs if e[1] == "tune.decision"]
    assert len(decided) == 3
    assert all(e[1] in EVENT_REGISTRY for e in evs)
    assert RECORDER.counters["unregistered_events"] == 0
    # and the snapshot carries the controller state for ra_top
    snap = obs.snapshot()
    assert snap["autotune"]["knobs"]["superstep_k"] == 8
    assert snap["autotune"]["last_decision"]["new"] == 8


def fsync_bound_plant(knobs, state):
    """A slow disk: the fsync phase owns the budget, and the group
    -commit wait plus the per-dispatch burst (K) both add to the
    syscall tail."""
    k = knobs["superstep_k"]
    interval = knobs["wal_max_batch_interval_ms"]
    state["fsync_total"] += 100.0
    state["disp_total"] += 5.0
    state["e2e_total"] += 120.0
    state["fsync_p99"] = 30.0 + 2.0 * interval + 4.0 * k
    # commit p99 tracks the fsync tail (the path is fsync-gated):
    # both objectives go green together once the disk is relieved
    state["commit_p99"] = state["fsync_p99"] / 2.0
    state["committed"] += 1000.0


def test_closed_loop_backs_off_interval_then_k_when_fsync_bound():
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = mk_tuner(slo, obs,
                     knobs={"superstep_k": 8,
                            "wal_max_batch_interval_ms": 2.0})
    decisions = drive(obs, tuner, state, fsync_bound_plant, windows=16)
    # fsync p99: iv=2,k=8 -> 66; back off iv 2->1 (64), 1->0 (62),
    # THEN halve K 8->4 (46 < 50: green) — never raise K into a slow
    # disk
    assert [(d["knob"], d["new"]) for d in decisions] == [
        ("wal_max_batch_interval_ms", 1.0),
        ("wal_max_batch_interval_ms", 0.0),
        ("superstep_k", 4)]
    assert all(d["objective"] == "fsync_p99_ms" for d in decisions)
    assert all(d["phase"] == "fsync_wait" for d in decisions)
    # converged
    assert drive(obs, tuner, state, fsync_bound_plant, windows=6) == []


def throughput_bound_plant(knobs, state):
    """Latency green, throughput below the floor until fusion/batching
    deepen: the achieved rate scales with k * cmds."""
    k = knobs["superstep_k"]
    c = knobs["cmds_per_step"]
    state["disp_total"] += 10.0
    state["commit_p99"] = 5.0
    state["gauge_rate"] = 100.0 * k * c
    state["e2e_total"] += 10.0


def test_closed_loop_deepens_batching_on_throughput_floor():
    state = base_state()
    obs = mk_obs(state)
    # floor requires k*c >= 512 * 100: k caps at 4 -> cmds must double
    slo = SloEngine(
        obs,
        (Objective("commit_p99_ms", "engine_phases_commit_e2e_p99_ms",
                   "<=", 25.0),
         Objective("cmds_per_s", "engine_gauge_cmds_per_s",
                   ">=", 25_000.0)),
        fast_windows=3, slow_windows=6, burn_fast=0.5, burn_slow=0.25)
    tuner = mk_tuner(slo, obs, bounds={"superstep_k": (1, 4)},
                     knobs={"superstep_k": 1, "cmds_per_step": 32})
    decisions = drive(obs, tuner, state, throughput_bound_plant,
                      windows=20)
    knobs = [(d["knob"], d["new"]) for d in decisions]
    # fusion deepens to its bound first, then the per-lane batch grows
    # (4 * 64 * 100 = 25.6k >= the floor: converged)
    assert knobs == [("superstep_k", 2), ("superstep_k", 4),
                     ("cmds_per_step", 64)]
    assert all(d["objective"] == "cmds_per_s" for d in decisions)
    assert drive(obs, tuner, state, throughput_bound_plant,
                 windows=6) == []


def mk_mesh_obs(state):
    """An Observatory whose engine source mirrors a SHARDED-MESH
    durable engine: mesh_shape stamped in the pipeline overview and
    PER-DEVICE WAL shards (8, one per lane-axis device) feeding the
    wildcard fsync objective — the layout the multichip sweep's tuner
    reads (ISSUE 11)."""
    obs = Observatory(ring_capacity=64)

    def engine_src():
        fp = state["fsync_p99"]
        return {
            "pipeline": {"mesh_shape": "1x8"},
            "phases": {
                "device_dispatch": {"total_ms": state["disp_total"]},
                "fsync_wait": {"total_ms": state["fsync_total"]},
                "commit_e2e": {"total_ms": state["e2e_total"],
                               "p99_ms": state["commit_p99"]},
            },
            # 8 per-device shards; the objective's max-aggregation
            # must read the laggiest device's fsync tail
            "wal": {"shards": [
                {"shard": i, "lanes": [i * 8, (i + 1) * 8],
                 "fsync_p99_ms": fp if fp < 0 else fp + 0.01 * i}
                for i in range(8)]},
            "telemetry": {"ts": time.time(),
                          "committed_total": state["committed"]},
            "gauge_cmds_per_s": state["gauge_rate"],
        }

    obs.add_source("engine", engine_src)
    return obs


def mesh_plant(knobs, state):
    """Synthetic sharded-mesh plant: dispatch-bound while the fixed
    per-dispatch cost dominates (fusion amortizes it across the mesh);
    once the per-device WAL shards saturate (``regime`` flips), the
    fsync tail grows with the group wait AND the per-dispatch burst K
    — fusing deeper into the saturated shards makes it worse."""
    k = knobs["superstep_k"]
    interval = knobs["wal_max_batch_interval_ms"]
    if state["regime"] == "dispatch":
        state["disp_total"] += 100.0 / k
        state["fsync_total"] += 4.0
        state["e2e_total"] += 110.0 / k
        state["commit_p99"] = 100.0 / k + 5.0
        state["fsync_p99"] = 5.0
    else:
        state["fsync_total"] += 100.0
        state["disp_total"] += 5.0
        state["e2e_total"] += 120.0
        state["fsync_p99"] = 30.0 + 2.0 * interval + 4.0 * k
        state["commit_p99"] = state["fsync_p99"] / 2.0
    state["committed"] += 10000.0


def test_closed_loop_converges_on_mesh_plant():
    """ISSUE 11 acceptance: pointing the PR 8 controller at a mesh
    plant is the cheapest frontier search we own — on the
    dispatch-bound mesh K walks up (1->2->4->8, attributed to
    device_dispatch) and converges; when the per-device WAL shards
    go fsync-bound it backs the group wait off 2->1->0 and then
    halves K, never fusing deeper into saturated shards."""
    state = {**base_state(), "regime": "dispatch"}
    obs = mk_mesh_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = mk_tuner(slo, obs,
                     knobs={"superstep_k": 1,
                            "wal_max_batch_interval_ms": 2.0})
    up = drive(obs, tuner, state, mesh_plant, windows=16)
    assert [(d["knob"], d["new"]) for d in up] == [
        ("superstep_k", 2), ("superstep_k", 4), ("superstep_k", 8)]
    assert all(d["phase"] == "device_dispatch" for d in up)
    # converged on the dispatch-bound mesh: green windows stay quiet
    assert drive(obs, tuner, state, mesh_plant, windows=4) == []
    # the per-device shards saturate: fsync owns the budget
    state["regime"] = "fsync"
    down = drive(obs, tuner, state, mesh_plant, windows=18)
    assert [(d["knob"], d["new"]) for d in down] == [
        ("wal_max_batch_interval_ms", 1.0),
        ("wal_max_batch_interval_ms", 0.0),
        ("superstep_k", 4)]
    assert all(d["objective"] == "fsync_p99_ms" for d in down)
    assert all(d["phase"] == "fsync_wait" for d in down)
    assert drive(obs, tuner, state, mesh_plant, windows=6) == []
    # the chosen knobs ride the snapshot the multichip tail stamps
    snap = obs.snapshot()
    assert snap["autotune"]["knobs"]["superstep_k"] == 4
    assert snap["engine"]["pipeline"]["mesh_shape"] == "1x8"


def test_hysteresis_one_noisy_window_never_turns_a_knob():
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=2, slow_windows=4, burn_fast=0.5)
    tuner = mk_tuner(slo, obs, breach_windows=2,
                     knobs={"superstep_k": 1})

    def noisy_plant(knobs, st):
        dispatch_bound_plant(knobs, st)
        # alternate: one breaching window, then a green one
        st["commit_p99"] = 90.0 if st["committed"] % 20000 else 5.0

    decisions = drive(obs, tuner, state, noisy_plant, windows=10)
    assert decisions == []


def test_cooldown_spaces_decisions():
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = mk_tuner(slo, obs, cooldown_windows=3,
                     knobs={"superstep_k": 1})

    def always_slow(knobs, st):
        dispatch_bound_plant(knobs, st)
        st["commit_p99"] = 90.0  # never improves: worst case walk

    ticks = []
    for w in range(12):
        always_slow(tuner.knobs, state)
        time.sleep(0.002)
        obs.snapshot()
        if tuner.tick() is not None:
            ticks.append(w)
    # >= cooldown+1 windows between consecutive decisions
    assert len(ticks) >= 2
    assert all(b - a >= 4 for a, b in zip(ticks, ticks[1:])), ticks


# ---------------------------------------------------------------------------
# freeze guards (acceptance: frozen under an active DiskFaultPlan)
# ---------------------------------------------------------------------------

def breach_forever(knobs, state):
    dispatch_bound_plant(knobs, state)
    state["commit_p99"] = 90.0


def isolated_guard():
    """``default_freeze_guard`` minus plans that PREDATE this test:
    the plan registries are process-global and weakly held, so earlier
    suite tests can leave plans alive (a router pinned by a leaked
    node); the guard logic under test is identical, filtered to plans
    this test creates."""
    from ra_tpu.log import faults
    from ra_tpu.transport.rpc import live_fault_plans
    gc.collect()
    pre_net = {id(p) for p in live_fault_plans()}
    pre_disk = faults.current_plan()

    def guard():
        cur = faults.current_plan()
        if cur is not None and cur is not pre_disk:
            return "disk_fault_plan_active"
        if any(id(p) not in pre_net and not p.quiet()
               for p in live_fault_plans()):
            return "transport_fault_plan_active"
        return None

    return guard


def test_frozen_under_active_disk_fault_plan():
    from ra_tpu.autotune import default_freeze_guard
    from ra_tpu.log import faults
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = AutoTuner(slo, obs, cooldown_windows=0, breach_windows=2,
                      incident_freeze_s=0.0,
                      freeze_guard=isolated_guard(),
                      knobs={"superstep_k": 1})
    # a QUIET plan (no fault probabilities): installed-ness is what
    # freezes; injecting real fsync EIO here would hit OTHER tests'
    # lingering WAL threads through the process-global IO shim
    plan = faults.DiskFaultPlan(seed=7)
    faults.install_plan(plan)
    try:
        # the REAL default guard names it (disk is checked first, so
        # this is deterministic whatever plans earlier tests leaked)
        assert default_freeze_guard() == "disk_fault_plan_active"
        base_f = len([e for e in RECORDER.events("tune")
                      if e[1] == "tune.freeze"])
        decisions = drive(obs, tuner, state, breach_forever, windows=6)
        assert decisions == []  # hard freeze: sustained breach ignored
        ov = tuner.overview()
        assert ov["frozen"] and \
            ov["freeze_reason"] == "disk_fault_plan_active"
        # freeze recorded ON THE TRANSITION, not per frozen tick
        freezes = [e for e in RECORDER.events("tune")
                   if e[1] == "tune.freeze"]
        assert len(freezes) == base_f + 1
    finally:
        faults.clear_plan()
    # thaw: breach streaks were reset, so it takes breach_windows
    # fresh windows of evidence before the first post-fault decision
    decisions = drive(obs, tuner, state, breach_forever, windows=4)
    assert decisions and decisions[0]["knob"] == "superstep_k"
    assert not tuner.overview()["frozen"]


def test_quiet_or_healed_transport_plan_does_not_freeze():
    """Liveness is not activity: routers pin their FaultPlan object
    after a chaos exercise ends, so the default guard must ignore
    plans that can no longer inject (all-zero specs, partitions
    healed) — otherwise one healed plan freezes every tuner in the
    process forever."""
    from ra_tpu.autotune import default_freeze_guard
    from ra_tpu.log import faults
    from ra_tpu.transport.rpc import FaultPlan, FaultSpec
    # plan registration is test-scoped (the conftest autouse fixture
    # unregisters plans leaked by earlier tests and restores the disk
    # slot), so this probe runs UNCONDITIONALLY — tier-1 carries no
    # skips; a failure here means the scoping fixture regressed
    assert faults.current_plan() is None, \
        "conftest plan scoping failed to restore the disk-plan slot"
    quiet = FaultPlan(seed=1)  # all-default specs: nothing to inject
    assert quiet.quiet()
    partitioned = FaultPlan(seed=2)
    partitioned.partition("nodeB")
    assert not partitioned.quiet()
    lossy = FaultPlan(seed=3, default=FaultSpec(drop=0.5))
    assert not lossy.quiet()
    partitioned.heal()
    assert partitioned.quiet()  # healed partition-only plan: quiet
    del lossy
    gc.collect()
    # only quiet plans remain alive: the scoped registry holds nothing
    # non-quiet from earlier tests, and this test's lossy plan is gone
    from ra_tpu.transport.rpc import live_fault_plans
    assert all(p.quiet() for p in live_fault_plans()), \
        "conftest plan scoping failed to unregister a leaked plan"
    assert default_freeze_guard() is None


def test_frozen_under_live_transport_fault_plan():
    from ra_tpu.transport.rpc import (FaultPlan, FaultSpec,
                                      live_fault_plans)
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = AutoTuner(slo, obs, cooldown_windows=0, breach_windows=2,
                      incident_freeze_s=0.0,
                      freeze_guard=isolated_guard(),
                      knobs={"superstep_k": 1})
    # an ACTIVE (non-quiet) plan: a lossy spec, wired to no transport
    plan = FaultPlan(seed=3, default=FaultSpec(drop=0.25))
    try:
        assert plan in live_fault_plans()  # the registry the guard reads
        assert not plan.quiet()
        assert drive(obs, tuner, state, breach_forever, windows=5) == []
        assert tuner.overview()["freeze_reason"] == \
            "transport_fault_plan_active"
    finally:
        del plan
        gc.collect()
    assert drive(obs, tuner, state, breach_forever, windows=4)


def test_frozen_after_fresh_incident(tmp_path):
    state = base_state()
    obs = mk_obs(state)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0),
                    fast_windows=3, slow_windows=6,
                    burn_fast=0.5, burn_slow=0.25)
    tuner = AutoTuner(slo, obs, cooldown_windows=0, breach_windows=2,
                      freeze_guard=lambda: None,  # isolate the incident leg
                      incident_freeze_s=3600.0,
                      knobs={"superstep_k": 1})
    RECORDER.dump("tuner_unit_incident", what="w",
                  data_dir=str(tmp_path))
    try:
        assert drive(obs, tuner, state, breach_forever, windows=5) == []
        assert tuner.overview()["freeze_reason"] == "recent_incident"
    finally:
        RECORDER.incidents.clear()  # do not freeze later tests' tuners
    assert drive(obs, tuner, state, breach_forever, windows=4)


# ---------------------------------------------------------------------------
# real-engine integration: phases flow end to end
# ---------------------------------------------------------------------------

def test_phase_attribution_on_real_durable_engine(tmp_path):
    from ra_tpu.engine import DispatchAheadDriver, open_engine
    from ra_tpu.models import CounterMachine

    eng = open_engine(CounterMachine(), str(tmp_path / "d"), 16, 3,
                      wal_shards=2, max_step_cmds=4, ring_capacity=64)
    try:
        obs = Observatory.for_engine(eng)
        slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0))
        drv = DispatchAheadDriver(eng, max_in_flight=2)
        nb = np.full((4, 16), 4, np.int32)
        pb = np.ones((4, 16, 4, 1), np.int32)
        for i in range(10):
            drv.submit(nb, pb)
            if i % 3 == 0:
                time.sleep(0.01)
                obs.snapshot()
        drv.drain()
        eng._dur.flush_all()
        snap = obs.snapshot()
        ph = snap["engine"]["phases"]
        # every phase of the durable dispatch path collected samples
        for p in ("host_staging", "device_dispatch", "queue_wait",
                  "wal_encode", "fsync_wait", "confirm_publish",
                  "commit_e2e"):
            assert ph[p]["count"] > 0, p
            assert ph[p]["total_ms"] >= 0
        assert ph["dropped"] == 0
        # knob stamps ride the pipeline overview (RA07's runtime half)
        pipe = snap["engine"]["pipeline"]
        for knob in TUNABLE_KNOBS:
            if knob != "cmds_per_step":
                assert knob in pipe
        assert pipe["cmds_per_step"] == 4
        # exposition: flattened phase scalars + the labelled histogram
        text = obs.prometheus(snap)
        parse_prometheus(text)
        assert "ra_tpu_engine_phases_commit_e2e_p99_ms" in text
        assert 'ra_tpu_engine_phase_ms_bucket{phase="fsync_wait"' in text
        assert "ra_tpu_slo_objectives_fsync_p99_ms_ok" in text
        # live batch-interval retarget lands on every shard
        eng._dur.set_batch_interval_ms(3.5)
        assert all(sh.wal.max_batch_interval_ms == 3.5
                   for sh in eng._dur._shards)
        assert eng._dur.batch_interval_ms() == 3.5
        obs.close()
        del slo
    finally:
        eng.close()


def test_volatile_engine_has_phase_plane_too():
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4)
    for _ in range(4):
        eng.uniform_step(2)
    ov = eng.phases.overview()
    # no driver, no WAL: the plane exists (zero-filled), never crashes
    assert ov["commit_e2e"]["count"] == 0
    assert eng.overview()["pipeline"]["wal_max_batch_interval_ms"] == -1.0


# ---------------------------------------------------------------------------
# ra_top: SLO verdict panel + autotuner footer
# ---------------------------------------------------------------------------

def test_ra_top_renders_slo_panel_and_tuner_footer(tmp_path):
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    snap = {
        "seq": 1, "ts": time.time(),
        "engine": {"lanes": 4, "members": 3},
        "slo": {"objectives": {
            "commit_p99_ms": {"verdict": "ok", "value": 8.2,
                              "op": "<=", "threshold": 25.0,
                              "burn_fast": 0.0, "burn_slow": 0.0},
            "fsync_p99_ms": {"verdict": "breach", "value": 61.0,
                             "op": "<=", "threshold": 50.0,
                             "burn_fast": 0.8, "burn_slow": 0.2}}},
        "autotune": {
            "knobs": {"superstep_k": 16, "cmds_per_step": 32,
                      "wal_max_batch_interval_ms": 0.0},
            "frozen": True, "freeze_reason": "disk_fault_plan_active",
            "decisions": 3, "cooldown_left": 2,
            "last_decision": {"ts": time.time() - 12,
                              "knob": "superstep_k", "old": 8,
                              "new": 16, "phase": "device_dispatch",
                              "objective": "commit_p99_ms"}},
    }
    path = str(tmp_path / "obs.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(snap) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ra_top.py"),
         path, "--once"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "commit_p99_ms OK" in out
    assert "fsync_p99_ms BREACH" in out and "burn=0.8/0.2" in out
    assert "superstep_k 8->16 via device_dispatch/commit_p99_ms" in out
    assert "FROZEN(disk_fault_plan_active)" in out
    assert "superstep_k=16" in out and "decisions=3" in out


# ---------------------------------------------------------------------------
# overhead: the whole plane (phases + SLO + tuner) on the bench path
# ---------------------------------------------------------------------------

def test_plane_overhead_under_3pct_on_bench_path(monkeypatch):
    """What the ISSUE 9 plane (phase stamps + Observatory snapshots +
    SLO evaluation + tuner ticks at a window cadence) adds to the bench
    dispatch pattern, pinned as counts and not as a ratio of two wall
    windows (which 0.3 s windows on a shared box cannot resolve to 3%).
    Both loops run with the PR 6 sampler attached; the plane's loop adds
    one snapshot, one SLO evaluation and one tick a window, under 3% of
    its dispatches; a window boundary reads nothing back from the
    device, compiles nothing and never waits; between boundaries the
    plane does nothing at all."""
    import collections

    from ra_tpu import devicewatch
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine
    from ra_tpu.telemetry import PhaseStats, TelemetrySampler

    eng = LockstepEngine(CounterMachine(), 64, 3, ring_capacity=64,
                         max_step_cmds=8, donate=False)
    n_new = np.full((64,), 8, np.int32)
    pay = np.ones((64, 8, 1), np.int32)
    for _ in range(10):
        eng.step(n_new, pay)
    eng.block_until_ready()
    sampler = TelemetrySampler(eng, cadence_steps=64)
    obs = Observatory.for_engine(eng, sampler=sampler)
    slo = SloEngine(obs, default_objectives(min_cmds_per_s=1.0))
    tuner = mk_tuner(slo, obs)
    sampler.drain()  # compile the jitted summary OUTSIDE the loops
    waits0 = sampler.counters["blocking_waits"]  # a drain may wait

    window = 64                    # dispatches a window
    notes, evals = [], []
    note = PhaseStats.note
    monkeypatch.setattr(
        PhaseStats, "note",
        lambda self, phase, dt: notes.append(phase) or note(self, phase, dt))
    window_value = slo._window_value
    monkeypatch.setattr(
        slo, "_window_value",
        lambda *a: evals.append(1) or window_value(*a))

    def loop(n, plane_on):
        """(d2h events off the sampler's own site, compiles) of n
        dispatches of the bench pattern."""
        c = devicewatch.WATCH.counters
        sites = devicewatch.WATCH.sites
        d2h0 = c["d2h_events"] - sites["sampler_harvest"]["d2h_events"]
        comp0 = c["xla_compiles"] + c["compiles"]
        rb: collections.deque = collections.deque()
        for i in range(1, n + 1):
            eng.step(n_new, pay)
            rb.append(eng.committed_lanes_async())
            while len(rb) > 8:
                np.asarray(rb.popleft())
            # a serving loop's window cadence, on a count of
            # dispatches here so that the pins are exact
            if plane_on and i % window == 0:
                obs.snapshot()
                tuner.tick()
        eng.block_until_ready()
        return (c["d2h_events"] - sites["sampler_harvest"]["d2h_events"]
                - d2h0, c["xla_compiles"] + c["compiles"] - comp0)

    steps = 10 * window
    try:
        loop(window, True)         # first snapshot: its own warm-up
        del notes[:], evals[:]
        off = loop(steps, False)
        assert notes == [] and evals == []
        ticks0, seq0, ring0 = tuner.ticks, obs._seq, len(obs.ring())
        on = loop(steps, True)
        # bounded work: one snapshot, one evaluation, one tick a window
        assert tuner.ticks - ticks0 == obs._seq - seq0 \
            == steps // window
        assert len(obs.ring()) <= ring0 + steps // window
        assert (steps // window) / steps < 0.03
        # an evaluation walks the ring's windows, never the dispatches
        per_eval = len(evals) / (steps // window)
        assert 0 < per_eval <= len(slo.objectives) * slo.slow_windows
        # a boundary reads nothing back and compiles nothing (a dispatch
        # reads back its watermark and its apply flags, ISSUE 34); the
        # volatile bench path stamps no phase with the plane on or off
        assert on == off == (2 * steps, 0)
        assert notes == []
        assert sampler.counters["blocking_waits"] == waits0
    finally:
        obs.close()
