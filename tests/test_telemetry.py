"""Device-resident telemetry plane + unified Observatory (ISSUE 6).

Covers: the TELEMETRY/TELEMETRY_SUMMARY field-registry parity (rule
RA05's runtime half), the Counters telemetry_dropped self-metric, the
async sampler's no-blocking-tick contract and snapshot correctness,
stall DETECTION under chaos (single-device and sharded-mesh — the
acceptance scenario), Prometheus exposition round-trip, the
time-series ring's rate consistency, the JSONL ring + ra_top renderer,
and the telemetry-on overhead bound on the bench dispatch path.
"""
import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

import ra_tpu
from ra_tpu.core.types import ServerId
from ra_tpu.engine import LockstepEngine
from ra_tpu.engine.lockstep import LaneTelemetry
from ra_tpu.metrics import (Counters, FIELD_REGISTRY, TELEMETRY_FIELDS,
                            TELEMETRY_SUMMARY_FIELDS)
from ra_tpu.models import CounterMachine
from ra_tpu.telemetry import (Observatory, TelemetrySampler,
                              append_jsonl_ring, parse_prometheus,
                              read_jsonl_tail)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mk_engine(n_lanes=16, n_members=3):
    return LockstepEngine(CounterMachine(), n_lanes, n_members,
                          ring_capacity=64, max_step_cmds=4,
                          donate=False)


# ---------------------------------------------------------------------------
# registry parity (rule RA05's runtime half)
# ---------------------------------------------------------------------------

def test_lane_telemetry_matches_registry():
    assert LaneTelemetry._fields == TELEMETRY_FIELDS
    assert FIELD_REGISTRY["telemetry"] is TELEMETRY_FIELDS
    assert FIELD_REGISTRY["telemetry_summary"] is TELEMETRY_SUMMARY_FIELDS


def test_summary_snapshot_covers_registry_fields():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=4)
    for _ in range(4):
        eng.uniform_step(2)
    snap = s.drain()
    for field in TELEMETRY_SUMMARY_FIELDS:
        assert field in snap, field
    # host stamps ride alongside, never shadowing registry fields
    assert snap["stall_threshold"] == s.stall_threshold
    assert snap["inner_steps_at_sample"] == 4


def test_every_registry_group_documented():
    """Every field of every FIELD_REGISTRY group is named (backticked)
    in docs/OBSERVABILITY.md — the doc half of lint rule RA05, pinned
    at runtime too so the lint and the live registry cannot drift."""
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    for group, fields in FIELD_REGISTRY.items():
        for field in fields:
            assert f"`{field}`" in doc, (group, field)


# ---------------------------------------------------------------------------
# Counters telemetry_dropped self-metric (satellite 1)
# ---------------------------------------------------------------------------

def test_counters_count_dropped_increments():
    c = Counters()
    c.new("srv", ("a", "b"))
    c.incr("srv", "a")
    c.incr("srv", "b", 3)
    assert c.self_metrics() == {"telemetry_dropped": 0}
    c.incr("srv", "typo_field")       # unknown field
    c.incr("no_such_group", "a")      # unknown group
    assert c.self_metrics() == {"telemetry_dropped": 2}
    assert c.fetch("srv") == {"a": 1, "b": 3}


def test_node_workload_drops_nothing():
    """A real cluster workload must leave telemetry_dropped at 0: a
    nonzero value means an instrumentation site addresses a field the
    registry does not know (the silent-loss class this metric ends)."""
    from nemesis import await_leader
    from ra_tpu.core.machine import SimpleMachine
    from ra_tpu.node import LocalRouter, RaNode

    router = LocalRouter()
    nodes = [RaNode(f"tn{i}", router=router) for i in (1, 2, 3)]
    try:
        sids = [ServerId(f"tm{i}", f"tn{i}") for i in (1, 2, 3)]
        ra_tpu.start_cluster("tel_drop",
                             lambda: SimpleMachine(
                                 lambda cmd, st: st + cmd, 0),
                             sids, router=router)
        leader = await_leader(router, sids)
        for v in (1, 2, 3, 4):
            ra_tpu.process_command(leader, v, router=router)
        for n in nodes:
            assert n.counters.self_metrics()["telemetry_dropped"] == 0
    finally:
        for n in nodes:
            n.stop()


# ---------------------------------------------------------------------------
# sampler: async drain, no blocking ticks, correct values
# ---------------------------------------------------------------------------

def test_sampler_tick_path_never_blocks():
    eng = mk_engine()
    s = TelemetrySampler(eng, cadence_steps=4)
    for _ in range(16):
        eng.uniform_step(2)  # engine ticks the attached sampler itself
    assert s.counters["samples_started"] == 4
    assert s.counters["blocking_waits"] == 0
    s.drain()
    assert s.counters["samples_harvested"] == \
        s.counters["samples_started"] - s.counters["samples_dropped"]


def test_sampler_snapshot_matches_engine():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=8)
    for _ in range(10):
        eng.uniform_step(3)
    snap = s.drain()
    assert snap["steps"] == 10
    assert snap["committed_total"] == eng.committed_total()
    # healthy steady-state: no stalls, no churn, stable leaders
    assert snap["stalled_lanes"] == 0
    assert snap["leader_changes"] == 0
    assert snap["commit_lag_hist"][0] == 8  # all lanes at lag 0
    assert sum(snap["commit_lag_hist"]) == 8


def test_sampler_counts_elections():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=64)
    eng.uniform_step(1)
    eng.trigger_election([0, 3])
    snap = s.drain()
    assert snap["elections_requested"] == 2
    assert snap["elections_won"] == 2
    # the incumbent (longest log) wins the re-election: the leader
    # never MOVED, so stability age keeps counting — leader_age agrees
    # with leader_changes (0), not with elections_won
    assert snap["leader_changes"] == 0
    assert snap["leader_age_min"] == snap["steps"]


def test_sampler_superstep_cadence():
    """The fused path ticks the sampler K rounds per dispatch."""
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=8)
    for _ in range(4):
        eng.uniform_superstep(4, 2)
    assert s.counters["samples_started"] == 2
    snap = s.drain()
    assert snap["steps"] == 16
    assert snap["committed_total"] == eng.committed_total()


def test_sampler_cadence_carries_superstep_overshoot():
    """A superstep K that does not divide the cadence must not stretch
    the effective window: the overshoot carries into the next window
    (48 rounds in ticks of 3 at cadence 8 -> exactly 48//8 samples,
    not the 5 a reset-to-zero cadence would give)."""
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=8)
    for _ in range(16):
        eng.uniform_superstep(3, 1)
    assert s.counters["samples_started"] == 6


def test_sampler_overflow_evicts_oldest_without_blocking():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=1, max_pending=2)
    for _ in range(8):
        eng.uniform_step(1)
    assert s.counters["samples_started"] == 8
    assert s.counters["blocking_waits"] == 0
    assert len(s._pending) <= 2


def test_sampler_observer_fault_isolation():
    """A raising observer (a full JSONL ring's ENOSPC, say) must never
    crash the dispatch loop the harvest path rides: the error is
    counted in ``observer_errors``, later observers still run, and
    harvesting continues."""
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=2)
    seen = []
    s.add_observer(lambda _snap: (_ for _ in ()).throw(OSError("disk full")))
    s.add_observer(seen.append)
    for _ in range(8):
        eng.uniform_step(1)
    s.drain()
    assert s.counters["observer_errors"] >= 1
    assert s.counters["samples_harvested"] >= 2
    assert len(seen) == s.counters["samples_harvested"]


# ---------------------------------------------------------------------------
# stall detection under chaos (the acceptance scenario)
# ---------------------------------------------------------------------------

def run_stall_chaos(seed, obs_path=None, shard=False):
    """One chaos episode: break a random lane's quorum under traffic,
    assert the stall is DETECTED (stalled-lane count + top-K offender
    membership) within one sampling window of crossing the stall
    threshold, then heal and assert the flag clears.  Shared with
    ``tools/soak.py --obs``; ``shard=True`` runs the identical episode
    over a lanes-sharded mesh (virtual CPU devices)."""
    rng = random.Random(seed)
    N, P, cadence, threshold = 16, 3, 8, 4
    eng = mk_engine(N, P)
    if shard:
        from ra_tpu.parallel.mesh import shard_engine_state
        shard_engine_state(eng)
    s = TelemetrySampler(eng, cadence_steps=cadence, top_k=4,
                         stall_threshold=threshold)
    obs = Observatory.for_engine(eng, sampler=s)
    harvested: list = []
    s.add_observer(harvested.append)
    if obs_path:
        s.add_observer(lambda _snap: obs.to_jsonl(obs_path))

    # warmup traffic, everyone healthy
    for _ in range(4):
        eng.uniform_step(2)

    # break the victim's quorum: both non-leader members fail, so its
    # leader keeps accepting commands it can never commit
    victim = rng.randrange(N)
    lead = int(np.asarray(eng.state.leader_slot)[victim])
    for slot in range(P):
        if slot != lead:
            eng.fail_member(victim, slot)
    stall_from = eng.pipeline_counters["inner_steps"]
    for _ in range(2 * cadence):
        eng.uniform_step(2)
    assert s.counters["blocking_waits"] == 0, "tick path blocked"
    snap = s.drain()
    assert snap["stalled_lanes"] >= 1, snap
    assert victim in snap["top_lanes"], (victim, snap)
    rank = snap["top_lanes"].index(victim)
    assert snap["top_stall_steps"][rank] >= threshold
    assert snap["top_commit_lag"][rank] > 0
    assert snap["commit_lag_max"] > 0
    # detection latency: the first flagged PERIODIC sample landed within
    # one sampling window of the lane crossing the stall threshold
    flagged = [h["inner_steps_at_sample"] for h in harvested
               if h["stalled_lanes"] >= 1]
    assert flagged, "no periodic sample flagged the stall"
    assert min(flagged) <= stall_from + threshold + cadence

    # heal: recover the failed members, let the backlog commit
    for slot in range(P):
        if slot != lead:
            eng.recover_member(victim, slot)
    for _ in range(2 * cadence):
        eng.uniform_step(0)
    snap2 = s.drain()
    assert snap2["stalled_lanes"] == 0, snap2
    assert snap2["commit_lag_max"] == 0
    return {"victim": victim, "detected_at": min(flagged),
            "stall_from": stall_from, "snapshots": len(harvested)}


@pytest.mark.parametrize("seed", [0, 7])
def test_stalled_lane_detected_single_device(seed):
    run_stall_chaos(seed)


def test_stalled_lane_detected_sharded_mesh():
    """The same episode over a lanes-sharded mesh: the jitted summary's
    reductions + top_k lower to cross-device collectives, so the
    offender ids stay global lane ids."""
    run_stall_chaos(11, shard=True)


# ---------------------------------------------------------------------------
# Observatory: merge, ring, rates, exposition
# ---------------------------------------------------------------------------

def test_shard_stats_reach_exposition_and_ring():
    """Per-shard WAL stats are a LIST of dicts in wal_overview(): the
    numeric flattening indexes into them so fsync p50/p99 and queue
    depths reach the Prometheus exposition and the time-series ring
    (the SLO-autotuner substrate), not just the raw JSONL view."""
    obs = Observatory()
    obs.add_source("engine", lambda: {
        "wal": {"shards": [{"fsync_p50_ms": 3.0, "queue_depth": 2},
                           {"fsync_p50_ms": 5.5, "queue_depth": 0}]}})
    snap = obs.snapshot()
    parsed = parse_prometheus(obs.prometheus(snap))
    assert parsed[("ra_tpu_engine_wal_shards_0_fsync_p50_ms", "")] == 3.0
    assert parsed[("ra_tpu_engine_wal_shards_1_fsync_p50_ms", "")] == 5.5
    obs.snapshot()
    assert obs.percentile("engine_wal_shards_0_queue_depth", 0.5) == 2.0
    assert obs.window_rates().get("engine_wal_shards_1_queue_depth") == 0.0


def test_per_device_shard_stats_round_trip_under_mesh(tmp_path):
    """ISSUE 11 satellite: a REAL durable engine sharded over the 8
    forced-host devices with PER-DEVICE WAL shards (8, one per
    lane-axis device) — every shard's fsync/queue/confirm stats must
    round-trip through the Prometheus exposition and land in the
    time-series ring as rateable keys (>4 shards: nothing may silently
    truncate), and ra_top must render the per-shard rows."""
    import subprocess
    import sys

    import jax

    from ra_tpu.engine.durable import open_engine
    from ra_tpu.parallel.mesh import (lane_mesh, per_device_wal_shards,
                                      shard_engine_state)
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 forced host devices")
    mesh = lane_mesh(jax.devices(), member_axis=1)
    n_shards = per_device_wal_shards(mesh)
    assert n_shards == 8
    eng = open_engine(CounterMachine(), str(tmp_path / "d"), 64,
                      wal_shards=n_shards, ring_capacity=256,
                      max_step_cmds=8, donate=False)
    try:
        shard_engine_state(eng, mesh)
        obs = Observatory.for_engine(eng)
        n_new = np.full((64,), 8, np.int32)
        pay = np.ones((64, 8, 1), np.int32)
        for _ in range(4):
            eng.step(n_new, pay)
        eng._dur.flush_all()
        obs.snapshot()
        for _ in range(4):
            eng.step(n_new, pay)
        eng._dur.flush_all()
        snap = obs.snapshot()
        assert len(snap["engine"]["wal"]["shards"]) == 8
        parsed = parse_prometheus(obs.prometheus(snap))
        for i in range(8):
            # every per-device shard's latency + depth gauges exposed
            assert ("ra_tpu_engine_wal_shards_%d_fsync_p50_ms" % i,
                    "") in parsed, i
            assert ("ra_tpu_engine_wal_shards_%d_queue_depth" % i,
                    "") in parsed, i
        # monotone per-shard counters rate over the ring (writes
        # happened between the two snapshots on every shard)
        rates = obs.window_rates()
        for i in range(8):
            assert rates.get("engine_wal_shards_%d_writes" % i, 0) > 0, i
        # the mesh stamp rides the pipeline overview
        assert snap["engine"]["pipeline"]["mesh_shape"] == "1x8"
        obs.close()
        # ra_top renders one row per shard with its lane slice
        import json as _json
        path = str(tmp_path / "obs.jsonl")
        with open(path, "w") as f:
            f.write(_json.dumps(snap, default=repr) + "\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "ra_top.py"),
             path, "--once"], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        for i in range(8):
            assert f"wal[{i}]" in r.stdout, r.stdout
        assert "lanes=56..64" in r.stdout  # the last device's slice
    finally:
        eng.close()


def test_prometheus_round_trip():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=4)
    for _ in range(8):
        eng.uniform_step(2)
    s.drain()
    obs = Observatory.for_engine(eng, sampler=s)
    text = obs.prometheus()
    parsed = parse_prometheus(text)
    assert parsed  # every non-comment line parsed or ValueError raised
    names = {n for n, _lbl in parsed}
    assert "ra_tpu_engine_telemetry_committed_total" in names
    assert "ra_tpu_engine_sampler_samples_started" in names
    # histogram family: cumulative, +Inf bucket == lane count == count
    buckets = sorted((lbl, v) for (n, lbl), v in parsed.items()
                     if n == "ra_tpu_engine_commit_lag_bucket")
    assert buckets, text
    inf = [v for lbl, v in buckets if "+Inf" in lbl]
    assert inf == [8.0]
    assert parsed[("ra_tpu_engine_commit_lag_count", "")] == 8.0
    # top-K offender gauges carry lane + rank labels
    assert any(n == "ra_tpu_engine_top_commit_lag" and "lane=" in lbl
               for n, lbl in parsed)


def test_prometheus_parser_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("ra_tpu_ok 1\nnot a metric line at all\n")
    with pytest.raises(ValueError):
        parse_prometheus("ra_tpu_ok notanumber\n")
    # every value form the exposition format allows must parse —
    # including negative exponents, which _fmt_num emits for tiny
    # floats (review catch: a char-class regex rejected '5e-05')
    got = parse_prometheus(
        "ra_tpu_tiny 5e-05\nra_tpu_neg -1\nra_tpu_inf +Inf\n")
    assert got[("ra_tpu_tiny", "")] == 5e-05
    assert got[("ra_tpu_neg", "")] == -1.0
    assert got[("ra_tpu_inf", "")] == float("inf")


def test_window_rates_consistent_with_counters():
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=4)
    obs = Observatory.for_engine(eng, sampler=s)
    for _ in range(4):
        eng.uniform_step(2)
    s.drain()
    obs.snapshot()
    c0 = eng.committed_total()
    time.sleep(0.05)
    for _ in range(6):
        eng.uniform_step(2)
    s.drain()
    obs.snapshot()
    c1 = eng.committed_total()
    rates = obs.window_rates()
    key = "engine_telemetry_committed_total"
    (t0, a), (t1, b) = obs.ring()[-2:]
    # telemetry keys rate over the SAMPLE's own window, not snapshot ts
    tdt = b["engine_telemetry_ts"] - a["engine_telemetry_ts"]
    assert rates[key] == pytest.approx((c1 - c0) / tdt, rel=1e-4)
    assert rates[key] > 0
    # monotone counters never read negative; seq ticks exactly 1/snap
    # (window_rates rounds to 4 decimals, hence the loose tolerance)
    assert rates["seq"] * (t1 - t0) == pytest.approx(1.0, rel=1e-2)
    assert obs.percentile(key, 0.5) is not None


def test_window_rates_omit_stale_telemetry_sample():
    """Snapshots faster than the harvest cadence re-embed the same
    sample: telemetry keys must be OMITTED (absent beats a misleading
    0 cmds/s on a running engine); other sources still rate."""
    same_sample = {"ts": 1000.0, "committed_total": 512.0}
    obs = Observatory()
    obs.add_source("engine", lambda: {"telemetry": dict(same_sample),
                                      "pipeline": {"dispatches": 7}})
    obs.snapshot()
    obs.snapshot()
    rates = obs.window_rates()
    assert "engine_telemetry_committed_total" not in rates
    assert rates.get("engine_pipeline_dispatches") == 0.0


def test_failing_source_degrades_not_dies():
    obs = Observatory()
    obs.add_source("ok", lambda: {"x": 1})
    obs.add_source("boom", lambda: 1 / 0)
    snap = obs.snapshot()
    assert snap["ok"] == {"x": 1}
    assert "error" in snap["boom"]
    parse_prometheus(obs.prometheus(snap))  # still exports


def test_system_observatory_merges_wal_counters(tmp_path):
    from ra_tpu.system import RaSystem

    sysm = RaSystem(str(tmp_path), wal_supervise=False)
    try:
        obs = sysm.observatory()
        snap = obs.snapshot()
        wal = snap["system"]["counters"]["wal"]
        assert "fsync_p50_ms" in wal and "queue_depth" in wal
        assert "disk_faults" in snap["system"]["counters"]
        parse_prometheus(obs.prometheus(snap))
    finally:
        sysm.close()


# ---------------------------------------------------------------------------
# JSONL ring + ra_top
# ---------------------------------------------------------------------------

def test_jsonl_ring_bounds_and_tail(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    for i in range(70):
        append_jsonl_ring(path, {"seq": i}, max_lines=16)
    with open(path) as f:
        lines = f.readlines()
    assert len(lines) <= 32  # compacts once past 2*max_lines
    tail = read_jsonl_tail(path, 3)
    assert [t["seq"] for t in tail] == [67, 68, 69]


def test_ra_top_renders_observatory_snapshot(tmp_path):
    eng = mk_engine(8)
    s = TelemetrySampler(eng, cadence_steps=4)
    # stall a lane so the offender row renders
    lead = int(np.asarray(eng.state.leader_slot)[2])
    for slot in range(3):
        if slot != lead:
            eng.fail_member(2, slot)
    for _ in range(12):
        eng.uniform_step(2)
    s.drain()
    obs = Observatory.for_engine(eng, sampler=s)
    path = str(tmp_path / "obs.jsonl")
    obs.to_jsonl(path)
    obs.to_jsonl(path)  # two snapshots -> the rate line renders too
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ra_top.py"),
         path, "--once"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "ra_top" in out and "stalled=1" in out
    assert "STALLED" in out and "#2" in out
    assert "cmds/s" in out and "pipe" in out


# ---------------------------------------------------------------------------
# overhead: telemetry on at default cadence stays under 3% (bench path)
# ---------------------------------------------------------------------------

def test_telemetry_overhead_under_3pct(monkeypatch):
    """What the sampler adds to the bench dispatch pattern at its
    default cadence, pinned as counts of what a tick does and not as a
    ratio of two wall windows (which two 0.3 s windows on a shared box
    cannot resolve to 3%): one extra dispatch and one fixed set of
    async copies a cadence window, under 3% of the loop's dispatches;
    no tick ever waits on the device; the live-buffer census is
    throttled after the first harvest."""
    import collections

    from ra_tpu import devicewatch, telemetry

    eng = LockstepEngine(CounterMachine(), 64, 3, ring_capacity=64,
                         max_step_cmds=8, donate=False)
    s = TelemetrySampler(eng)      # attaches at the default cadence
    summaries = []
    fn = s._fn
    s._fn = lambda *a: summaries.append(1) or fn(*a)
    census = []
    sample = devicewatch.sample_watermarks
    monkeypatch.setattr(
        devicewatch, "sample_watermarks",
        lambda min_interval_s=0.0: census.append(min_interval_s)
        or sample(min_interval_s))
    d2h0 = dict(devicewatch.WATCH.sites["sampler_harvest"])

    n_new = np.full((64,), 8, np.int32)
    pay = np.ones((64, 8, 1), np.int32)
    rb: collections.deque = collections.deque()
    steps = 20 * s.cadence_steps
    for _ in range(steps):         # the bench dispatch pattern
        eng.step(n_new, pay)
        rb.append(eng.committed_lanes_async())
        while len(rb) > 8:
            np.asarray(rb.popleft())
    eng.block_until_ready()

    # bounded work: one summary dispatch a cadence window, nothing else
    assert len(summaries) == s.counters["samples_started"] \
        == steps // s.cadence_steps
    assert len(summaries) / steps < 0.03
    # a fixed transfer budget: the same few async copies a sample
    site = devicewatch.WATCH.sites["sampler_harvest"]
    per_sample = (site["d2h_events"] - d2h0["d2h_events"]) \
        / len(summaries)
    assert per_sample == len(s.last) - 3      # ts, steps, threshold
    assert (site["d2h_bytes"] - d2h0["d2h_bytes"]) / len(summaries) < 4096
    # no device sync on the tick: a sample not ready is left for a
    # later tick, never waited for
    assert s.counters["blocking_waits"] == 0
    assert s.counters["samples_harvested"] >= 1
    assert s.counters["samples_harvested"] + len(s._pending) \
        + s.counters["samples_dropped"] == len(summaries)
    # the census: eager on the first harvest, throttled ever after
    assert len(census) == s.counters["samples_harvested"]
    assert census[0] == 0.0
    assert set(census[1:]) <= {telemetry.CENSUS_MIN_INTERVAL_S}


def test_node_incr_sites_address_server_fields():
    """Every counter increment the node shell issues by field literal
    must name a SERVER_FIELDS member — its groups are created with
    that field set, so anything else is silently dropped (pre-PR) or
    flags telemetry_dropped (now).  Review catch: a snapshot_installed
    incr here targeted a LOG_FIELDS name and was lost for five PRs;
    the log facade owns that field."""
    import ast
    import inspect

    from ra_tpu import node as node_mod
    from ra_tpu.metrics import SERVER_FIELDS

    tree = ast.parse(inspect.getsource(node_mod))
    sites = [(n.lineno, n.args[1].value) for n in ast.walk(tree)
             if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "incr" and len(n.args) >= 2
             and isinstance(n.args[1], ast.Constant)
             and isinstance(n.args[1].value, str)]
    assert sites, "expected incr sites in node.py"
    bad = [s for s in sites if s[1] not in SERVER_FIELDS]
    assert not bad, f"incr sites addressing non-SERVER_FIELDS names: {bad}"
