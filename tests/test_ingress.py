"""Ingress plane tests (ISSUE 10): session directory placement +
reconnect epochs, vectorized seqno dedup (at-most-once end-to-end),
dense superstep coalescing, the graduated backpressure ladder, and the
ROADMAP item 2 acceptance scenario — sessions fanning into lanes under
chaos with an exactly-once oracle.

The oracle: every submission the plane answered OK/SLOW (placed) must
be applied EXACTLY once — the final per-lane CounterMachine state
equals the host-side sum of placed increments per lane, despite
duplicate resends (dedup'd), rejected/deferred/shed rows (not marked,
so their resends stay fresh), member failures and elections (noops add
0 to a counter).  Linearizability of reads is checked by monotone
consistent-read probes bounded by the host-side placed watermark —
for a grow-only counter register, a read that is monotone, never ahead
of what was placed at its completion, and exact at the end, is
linearizable.

``run_ingress_soak`` is the soak entry point (tools/soak.py --ingress
runs it at 1M sessions x 10k lanes); the tier-1 variants here are
CPU-scaled, the full-scale one rides ``-m slow``.
"""
import time
from collections import deque

import numpy as np
import pytest

from harness import Dispatched, ReadbackGate
from ra_tpu.blackbox import RECORDER
from ra_tpu.engine import LockstepEngine
from ra_tpu.ingress import (DEFER, DUP, OK, REJECT, SLOW, CoalesceWindow,
                            CreditLadder, IngressPlane, SessionDirectory,
                            batch_rank)
from ra_tpu.models import CounterMachine

#: the classic-TCP 3-member cluster baseline (BENCH_CLASSIC_r05) the
#: ISSUE 10 acceptance bar is phrased against
CLASSIC_TCP_BASELINE = 2934.0


def mk_engine(lanes=64, cmds=8, ring=128, **kw):
    kw.setdefault("donate", False)
    return LockstepEngine(CounterMachine(), lanes, 3,
                          ring_capacity=ring, max_step_cmds=cmds, **kw)


# ---------------------------------------------------------------------------
# directory: placement, reconnect epochs, dedup
# ---------------------------------------------------------------------------

def test_batch_rank_counts_within_batch_occurrences():
    assert batch_rank(np.array([7, 3, 7, 7, 3])).tolist() == \
        [0, 0, 1, 2, 1]
    assert batch_rank(np.array([], np.int64)).tolist() == []
    assert batch_rank(np.array([5])).tolist() == [0]


def test_placement_is_deterministic_and_reconnect_stable():
    d1 = SessionDirectory(256, n_shards=4, seed=5)
    d2 = SessionDirectory(256, n_shards=4, seed=5)
    for eid in ("acme/alice", "acme/bob", "solo-client"):
        assert d1.place(eid) == d2.place(eid)
    tenant, lane, shard = d1.place("acme/alice")
    assert tenant == "acme" and 0 <= lane < 256
    assert shard == lane * 4 // 256
    assert d1.place("solo-client")[0] == "default"
    h, reconnected = d1.connect("acme/alice")
    assert not reconnected and d1.epoch[h] == 1
    h2, reconnected = d1.connect("acme/alice")
    assert h2 == h and reconnected and d1.epoch[h] == 2
    assert int(d1.lane[h]) == lane  # placement survives the reconnect


def test_bulk_connect_spreads_lanes_and_bumps_epochs():
    d = SessionDirectory(128, seed=1)
    h = d.connect_bulk(10_000, tenants=4, key="fleet")
    counts = np.bincount(d.lane[h], minlength=128)
    assert counts.min() > 0  # 78x the mean leaves no lane empty
    assert set(np.unique(d.tenant[h])) == {0, 1, 2, 3}
    same = d.connect_bulk(10_000, tenants=4, key="fleet")
    np.testing.assert_array_equal(h, same)   # same fleet, same handles
    assert (d.epoch[h] == 2).all()           # fleet-wide reconnect


def test_seqno_dedup_is_at_most_once():
    d = SessionDirectory(16)
    a = d.connect("c/a")[0]
    b = d.connect("c/b")[0]
    handles = np.array([a, a, b, a], np.int64)
    seqnos = np.array([1, 1, 1, 2], np.int64)
    fresh = d.fresh(handles, seqnos)
    # within-batch duplicate (a,1) passes once; (b,1) and (a,2) pass
    assert fresh.tolist() == [True, False, True, True]
    d.mark(handles[fresh], seqnos[fresh])
    # cross-batch resend of the whole wave: everything is a duplicate
    assert not d.fresh(handles, seqnos).any()
    # a row that was NOT marked (rejected/shed) stays fresh on resend
    fresh2 = d.fresh(np.array([a]), np.array([3]))
    assert fresh2.all()
    assert d.fresh(np.array([a]), np.array([3])).all()  # still unmarked
    # distinct pairs 2^32 apart must NOT collide in the batch dedup (a
    # packed 32-bit key would silently DUP the second — rows lost)
    far = d.fresh(np.array([a, a], np.int64),
                  np.array([10, 10 + 2 ** 32], np.int64))
    assert far.tolist() == [True, True]


def test_bulk_tenants_do_not_alias_named_tenants():
    """connect_bulk's round-robin must land on the REGISTERED bulk
    tenant ids: with a named tenant already in the table, raw modulo
    values would charge half the fleet to the named tenant's quota."""
    d = SessionDirectory(16)
    a = d.connect("acme/alice")[0]
    h = d.connect_bulk(4, tenants=2, key="fleet")
    bulk_tenants = set(d.tenant[h].tolist())
    assert int(d.tenant[a]) not in bulk_tenants
    assert len(bulk_tenants) == 2


# ---------------------------------------------------------------------------
# coalescer: dense blocks, overflow shed
# ---------------------------------------------------------------------------

def test_coalescer_builds_dense_superstep_blocks():
    w = CoalesceWindow(4, 2, 1, superstep_k=2, capacity=8, window_s=0.0)
    lanes = np.array([0, 0, 0, 1, 2])
    pay = np.arange(1, 6, dtype=np.int32)[:, None]
    placed = w.offer(lanes, pay, np.arange(5))
    assert placed.all() and w.queue_rows() == 5
    n_new, payloads, handles, take = w.pop_block()
    assert n_new.shape == (2, 4) and payloads.shape == (2, 4, 2, 1)
    assert take.tolist() == [3, 1, 1, 0]
    # lane 0: 3 rows split [2, 1] over the two inner steps, in order
    assert n_new[:, 0].tolist() == [2, 1]
    assert payloads[0, 0, :, 0].tolist() == [1, 2]
    assert payloads[1, 0, 0, 0] == 3
    assert n_new[:, 1].tolist() == [1, 0] and payloads[0, 1, 0, 0] == 4
    assert n_new[:, 3].tolist() == [0, 0]
    assert handles[0, :3].tolist() == [0, 1, 2]
    assert w.queue_rows() == 0
    # overflow: the bounded ring places capacity rows, sheds the rest
    lanes = np.zeros(10, np.int64)
    placed = w.offer(lanes, np.ones((10, 1), np.int32), np.arange(10))
    assert placed.sum() == 8 and (~placed).sum() == 2
    # the ring wraps correctly across pops (head moved by the take)
    n_new, payloads, handles, take = w.pop_block()
    assert take[0] == 4 and int(n_new[:, 0].sum()) == 4
    assert w.fill[0] == 4


# -- the flat write block (ISSUE 26): pop_rows + ra_densify == pop_block ----

_FLAT_K, _FLAT_KC, _FLAT_C, _FLAT_N = 2, 4, 3, 6
_FLAT_W = _FLAT_K * _FLAT_KC


def _flat_case(case: str):
    """(ring heads, lane of each offered row) for one layout of the
    coalescer, on 6 lanes with blocks of 2 x 4 rows a lane and rings of
    3 blocks."""
    n, w = _FLAT_N, _FLAT_W
    heads = np.zeros(n, np.int64)
    if case == "wrapped_heads":
        # heads near the ring's end: every lane's window wraps
        heads[:] = 3 * w - np.arange(1, n + 1)
        lanes = np.repeat(np.arange(n), [5, 3, 8, 1, 2, 4])
    elif case == "empty_lanes":
        heads[:] = [0, 7, 3, 23, 11, 5]
        lanes = np.repeat([1, 4], [3, 6])
    elif case == "overfull_lane":
        # lane 2 holds more than K*Kc rows: a block takes K*Kc of them
        heads[:] = [4, 0, 20, 9, 0, 1]
        lanes = np.repeat([0, 2, 5], [2, 2 * w + 3, 1])
    elif case == "full_block":
        heads[:] = [0, 5, 10, 15, 20, 23]
        lanes = np.repeat(np.arange(n), w)
    elif case == "one_row":
        heads[:] = 23
        lanes = np.array([3])
    else:
        assert case == "interleaved"
        heads[:] = [21, 2, 19, 0, 13, 22]
        lanes = np.random.default_rng(26).integers(0, n, 31)
    return heads, np.asarray(lanes, np.int64)


_FLAT_CASES = ["wrapped_heads", "empty_lanes", "overfull_lane",
               "full_block", "one_row", "interleaved"]


def _flat_pair(case: str):
    """Two coalescers in the same state, one to pop each way."""
    heads, lanes = _flat_case(case)
    pay = np.random.default_rng(7).integers(
        1, 2 ** 31, (len(lanes), _FLAT_C)).astype(np.int32)
    out = []
    for _ in range(2):
        w = CoalesceWindow(_FLAT_N, _FLAT_KC, _FLAT_C,
                           superstep_k=_FLAT_K, capacity=3 * _FLAT_W,
                           window_s=0.0)
        w.head[:] = heads
        # stale bytes wherever nothing was offered: a dense block
        # carries them past n_new, a densified one carries zeros
        w.buf[:] = -7
        assert w.offer(lanes, pay, 100 + np.arange(len(lanes))).all()
        out.append(w)
    return out


@pytest.mark.parametrize("case", _FLAT_CASES)
def test_pop_rows_densified_equals_pop_block(case):
    from ra_tpu.engine.lockstep import densify_fn
    dense_w, flat_w = _flat_pair(case)
    n_new, payloads, _h, take = dense_w.pop_block()
    n_new_f, rows, _hf, take_f, row_base = flat_w.pop_rows()
    assert n_new_f.dtype == np.int32 and row_base.dtype == np.int32
    assert (n_new_f == n_new).all() and (take_f == take).all()
    assert row_base.tolist() == (np.cumsum(take) - take).tolist()
    assert rows.shape == (int(take.sum()), _FLAT_C)
    # padded as the driver pads it; none where the block is full and
    # the last lane's window ends with the table
    table = np.zeros((len(rows) + (3 if case != "full_block" else 0),
                      _FLAT_C), np.int32)
    table[:len(rows)] = rows
    block = np.asarray(densify_fn(_FLAT_K, _FLAT_KC)(
        table, row_base, take.astype(np.int32)))
    assert block.shape == payloads.shape
    live = np.arange(_FLAT_KC)[None, None, :] < n_new[:, :, None]
    assert (block[live] == payloads[live]).all()
    assert (block[~live] == 0).all()
    if case != "full_block":
        assert (payloads[~live] == -7).any()    # the control: stale


@pytest.mark.parametrize("case", _FLAT_CASES)
def test_pop_rows_leaves_the_ring_as_pop_block_does(case):
    dense_w, flat_w = _flat_pair(case)
    for _ in range(3):      # the overfull lane drains over three pops
        dense_w.pop_block()
        flat_w.pop_rows()
        assert (flat_w.head == dense_w.head).all()
        assert (flat_w.fill == dense_w.fill).all()
        assert flat_w.queue_rows() == dense_w.queue_rows()
        assert flat_w.block_rows() == dense_w.block_rows()
    assert flat_w.queue_rows() == 0


@pytest.mark.parametrize("case", _FLAT_CASES)
def test_pop_rows_handles_are_the_valid_dense_handles_in_order(case):
    dense_w, flat_w = _flat_pair(case)
    _n, _p, handles, take = dense_w.pop_block()
    valid = np.arange(_FLAT_W)[None, :] < take[:, None]
    flat = flat_w.pop_rows()[2]
    assert flat.dtype == np.int64
    assert flat.tolist() == handles[valid].tolist()


def test_flat_buckets_follow_the_block_geometry():
    from ra_tpu.engine.lockstep import flat_buckets
    assert flat_buckets(4, 10_000, 16) == (10_000, 40_000, 160_000)
    assert flat_buckets(4, 1_000, 16) == (1_000, 4_000, 16_000)
    # rounded up to a multiple of 8, and never the whole block
    assert flat_buckets(2, 6, 4) == (8, 16)
    assert flat_buckets(1, 1, 4) == ()


def test_coalescer_ready_on_fill_or_cadence():
    w = CoalesceWindow(2, 2, 1, superstep_k=1, capacity=8,
                       window_s=10.0, fill_frac=0.5)
    assert not w.ready()  # empty: never ready
    w.offer(np.array([0]), np.ones((1, 1), np.int32), np.array([1]))
    assert not w.ready()          # below fill trigger, cadence far off
    assert w.ready(now=time.monotonic() + 20.0)   # cadence trigger
    w.offer(np.array([0, 1]), np.ones((2, 1), np.int32),
            np.array([2, 3]))
    assert w.ready()              # fill trigger (>= half a full block)


# ---------------------------------------------------------------------------
# backpressure ladder
# ---------------------------------------------------------------------------

def test_credit_ladder_graduates_and_enforces_tenant_fairness():
    d = SessionDirectory(8)
    a = d.connect("t0/a")[0]
    b = d.connect("t0/b")[0]
    c = d.connect("t1/c")[0]
    lad = CreditLadder(d, soft_credit=8, hard_credit=16, tenant_quota=2)
    st = lad.admit(np.full(20, a, np.int64))
    # within-batch multiplicity: ok x8, slow x8, reject past the hard
    # window (the StopSending analogue)
    assert st.tolist() == [OK] * 8 + [SLOW] * 8 + [REJECT] * 4
    assert lad.used[a] == 16
    lad.release(np.full(16, a, np.int64))
    assert lad.used[a] == 0
    # a commit_p99 breach tightens credits BEFORE queues grow
    base = len([e for e in RECORDER.events("ingress")
                if e[1] == "ingress.level"])
    lvl = lad.on_slo({"objectives": {"commit_p99_ms":
                                     {"verdict": "breach"}}})
    assert lvl == 1 and lad.effective_limits() == (4, 8)
    st = lad.admit(np.full(10, a, np.int64))
    assert st.tolist() == [OK] * 4 + [SLOW] * 4 + [REJECT] * 2
    lad.release(np.full(8, a, np.int64))
    # alert escalates to tenant fairness: the over-quota tenant defers,
    # the light tenant stays admitted
    assert lad.on_slo({"objectives": {"commit_p99_ms":
                                      {"verdict": "alert"}}}) == 2
    assert lad.effective_limits() == (2, 4)
    st = lad.admit(np.array([a, b, b, c], np.int64))
    # tenant t0's third row crosses quota=2 -> DEFER; tenant t1 is fine
    assert st.tolist() == [OK, OK, DEFER, OK]
    # recovery decays one level per two clean windows (hysteresis)
    assert lad.on_slo({"objectives": {"commit_p99_ms":
                                      {"verdict": "ok"}}}) == 2
    assert lad.on_slo({"objectives": {"commit_p99_ms":
                                      {"verdict": "ok"}}}) == 1
    # every transition is a registered flight-recorder event
    levels = [e for e in RECORDER.events("ingress")
              if e[1] == "ingress.level"]
    assert len(levels) >= base + 3


def test_within_wave_twin_of_unplaced_row_is_not_dup():
    """DUP means 'already placed — stop resending'.  A within-wave
    duplicate of a row that was REJECTED (never placed) must inherit
    the refusal, not read as DUP — a client trusting status 4 would
    otherwise drop a command the engine never saw."""
    eng = mk_engine(lanes=8, cmds=4, ring=64)
    plane = IngressPlane(eng, superstep_k=1, window_s=0.0,
                         soft_credit=1, hard_credit=1)
    h = plane.connect("t/x")
    # exhaust the hard credit (1): the first row places, rest refuse
    st = plane.submit(np.array([h], np.int64), np.array([1]),
                      np.ones((1, 1), np.int32))
    assert st.tolist() == [OK]
    # one wave with (h,2) twice: both rows hit the exhausted window —
    # first is REJECT, and its twin must be REJECT too, not DUP
    st = plane.submit(np.array([h, h], np.int64), np.array([2, 2]),
                      np.ones((2, 1), np.int32))
    assert st.tolist() == [REJECT, REJECT]
    # twin of a PLACED row is a genuine DUP: release credit, resend
    plane.pump(force=True)
    plane.settle()
    st = plane.submit(np.array([h, h], np.int64), np.array([2, 2]),
                      np.ones((2, 1), np.int32))
    assert st.tolist() == [OK, DUP]
    # and a pure watermark resend stays DUP
    st = plane.submit(np.array([h], np.int64), np.array([2]),
                      np.ones((1, 1), np.int32))
    assert st.tolist() == [DUP]


def test_slo_verdict_accessor_drives_the_ladder():
    """The pump path polls ``SloEngine.verdict("commit_p99_ms")`` (one
    memoized dict hit) and feeds ``on_verdict`` — the same transitions
    as the dict-shaped ``on_slo`` form."""
    from ra_tpu.slo import SloEngine, default_objectives
    from ra_tpu.telemetry import Observatory
    obs = Observatory()
    try:
        slo = SloEngine(obs, default_objectives())
        assert slo.verdict("commit_p99_ms") == "no_data"  # empty ring
        assert slo.verdict("no-such-objective") == "no_data"
        d = SessionDirectory(4)
        lad = CreditLadder(d)
        assert lad.on_verdict(slo.verdict("commit_p99_ms")) == 0  # hold
        assert lad.on_verdict("breach") == 1
        assert lad.on_verdict("alert") == 2
    finally:
        obs.close()


# ---------------------------------------------------------------------------
# end to end: dedup + coalesce + engine, Observatory wiring
# ---------------------------------------------------------------------------

def test_ingress_end_to_end_oracle_and_observatory():
    eng = mk_engine(lanes=32, cmds=4, ring=64)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0,
                         soft_credit=64, hard_credit=256)
    h = plane.connect_bulk(200, tenants=4, key="e2e")
    rng = np.random.default_rng(3)
    expected = np.zeros(32, np.int64)
    for _wave in range(6):
        sess = h[rng.integers(0, len(h), 64)]
        seq = plane.directory.next_seqnos(sess)
        pay = rng.integers(1, 5, (64, 1)).astype(np.int32)
        st = plane.submit(sess, seq, pay)
        ok = st <= SLOW
        np.add.at(expected, plane.directory.lane[sess[ok]],
                  pay[ok, 0].astype(np.int64))
        # immediate resend of the SAME wave: placed rows all dedup
        st2 = plane.submit(sess, seq, pay)
        assert (st2[ok] == DUP).all()
        ok2 = st2 <= SLOW   # rows admitted only on the retry
        np.add.at(expected, plane.directory.lane[sess[ok2]],
                  pay[ok2, 0].astype(np.int64))
        plane.pump(force=True)
    plane.settle()
    mac = np.asarray(eng.consistent_read(np.arange(32)))
    np.testing.assert_array_equal(mac.astype(np.int64), expected)
    assert plane.counters["accepted"] > 0
    assert plane.counters["dup_dropped"] > 0
    # Observatory.for_engine picks the attached plane up automatically;
    # INGRESS_FIELDS reach the exposition + time-series ring
    from ra_tpu.telemetry import Observatory, parse_prometheus
    obs = Observatory.for_engine(eng)
    try:
        snap = obs.snapshot()
        assert snap["ingress"]["accepted"] == plane.counters["accepted"]
        assert snap["ingress"]["queue_rows"] == 0
        flat = parse_prometheus(obs.prometheus())
        assert flat[("ra_tpu_ingress_accepted", "")] == \
            plane.counters["accepted"]
        assert ("ra_tpu_ingress_shed_rows", "") in flat
        # counters rate as monotone keys over the ring; queue gauge
        # keeps its drift
        obs.snapshot()
        rates = obs.window_rates()
        assert "ingress_accepted" in rates
    finally:
        obs.close()
    # the engine overview stamps the session tier next to its pipeline
    ov = eng.overview()
    assert ov["ingress"]["sessions"] == 200
    assert ov["ingress"]["inflight_blocks"] == 0


def _reconnect_scenario(shard_mesh: bool) -> None:
    """Kill a client mid-flight, reconnect under the SAME external id,
    resend the unacked window: seqno dedup yields no duplicate apply
    (settle-based, fixed seed — the ISSUE 10 reconnect satellite)."""
    eng = mk_engine(lanes=16, cmds=4, ring=64)
    if shard_mesh:
        import jax

        from ra_tpu.parallel.mesh import shard_engine_state
        if len(jax.devices()) < 2:
            pytest.skip("single-device backend")
        shard_engine_state(eng)
    # one session -> one lane: the staging ring must hold the whole
    # 60-command burst (default capacity is sized for spread fan-in)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0, capacity=64)
    h = plane.connect("acme/alice")
    lane = int(plane.directory.lane[h])
    # 40 in-flight commands; only part of them dispatched before the
    # client dies (the rest staged in the window)
    st = plane.submit(np.full(40, h, np.int64), np.arange(1, 41),
                      np.ones((40, 1), np.int32))
    assert (st <= SLOW).all()
    plane.pump(force=True)
    # reconnect: same id -> same handle, same lane, bumped epoch, and
    # the dedup watermark SURVIVES the reconnect
    h2 = plane.connect("acme/alice")
    assert h2 == h and plane.directory.epoch[h] == 2
    assert int(plane.directory.lane[h2]) == lane
    # client resends its unacked tail 20..40 plus new traffic 41..60
    resend = np.arange(20, 61)
    st2 = plane.submit(np.full(len(resend), h2, np.int64), resend,
                       np.ones((len(resend), 1), np.int32))
    assert (st2[:21] == DUP).all()      # already placed: at-most-once
    assert (st2[21:] <= SLOW).all()     # fresh tail admitted
    plane.settle()
    val = int(np.asarray(eng.consistent_read([lane]))[0])
    assert val == 60                    # 1..60 exactly once
    assert plane.counters["dup_dropped"] == 21
    assert plane.counters["reconnects"] == 1


def test_session_reconnect_no_duplicate_apply_single_device():
    _reconnect_scenario(shard_mesh=False)


def test_session_reconnect_no_duplicate_apply_sharded_mesh():
    _reconnect_scenario(shard_mesh=True)


# ---------------------------------------------------------------------------
# the flat write block end to end (ISSUE 26)
# ---------------------------------------------------------------------------

def _wal_records(data_dir: str) -> dict:
    """Every WAL record under ``data_dir`` as bytes: {shard dir:
    {step: (term, payload)}}."""
    import os

    from ra_tpu.engine.durable import UID
    from ra_tpu.log.wal import scan_wal_file
    out = {}
    for root, _dirs, files in sorted(os.walk(data_dir)):
        tables: dict = {}
        for f in sorted(files):
            if f.endswith(".wal"):
                scan_wal_file(os.path.join(root, f), tables)
        if tables:
            out[os.path.relpath(root, data_dir)] = tables[UID]
    return out


def _served_run(data_dir: str, mesh: bool, flat: bool) -> dict:
    """Seeded waves through a durable plane, one dispatch at a time
    behind a durability barrier (so both forms see the same confirm
    horizon at every dispatch); what the run left behind."""
    import jax

    from ra_tpu.engine.durable import open_engine
    from ra_tpu.parallel.mesh import (per_device_wal_shards,
                                      shard_engine_state,
                                      superstep_block_shardings)
    lanes, k, cmds = 64, 2, 8
    shards, device_mesh = 2, None
    if mesh:
        from ra_tpu.parallel.mesh import lane_mesh
        device_mesh = lane_mesh(jax.devices(), member_axis=1)
        shards = per_device_wal_shards(device_mesh)
    eng = open_engine(CounterMachine(), data_dir, lanes, wal_shards=shards,
                      ring_capacity=128, max_step_cmds=cmds)
    if mesh:
        shard_engine_state(eng, device_mesh)
    plane = IngressPlane(eng, superstep_k=k, window_s=0.0,
                         capacity=4 * k * cmds, hard_credit=4096,
                         soft_credit=4096)
    assert plane.driver._flat_buckets == (16, 64, 256)
    if not flat:
        plane.driver._flat_buckets = ()     # every block goes dense
    seen = Dispatched(eng)
    handles = plane.connect_bulk(2_000, key="fleet")
    rng = np.random.default_rng(2626)
    watermarks, sent_sharding = [], None
    # rows a wave: one for each bucket, then more than the top bucket
    for rows in (9, 50, 200, 600, 3, 130):
        pick = rng.choice(handles, rows, replace=False)
        st = plane.submit(pick, plane.directory.next_seqnos(pick),
                          rng.integers(1, 100, (rows, 1)).astype(np.int32))
        assert (st <= SLOW).all()
        while plane.window.queue_rows():
            plane.pump(force=True)
            sent_sharding = seen.blocks[-1][1].sharding
            plane.driver.drain()
            eng._dur.flush_all()
            watermarks.append(plane.driver.last_committed.copy())
    plane.settle()
    eng._dur.flush_all()
    state = jax.tree.map(np.asarray, eng.state)
    counters = dict(plane.counters)
    if mesh:
        assert sent_sharding.is_equivalent_to(
            superstep_block_shardings(device_mesh)["payloads"], 4)
    eng.close()
    return {"state": state, "watermarks": watermarks,
            "counters": counters, "wal": _wal_records(data_dir)}


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh8"])
def test_flat_and_dense_write_blocks_serve_identically(tmp_path, mesh):
    """The same seeded submissions through the flat path (pop_rows ->
    ra_densify) and through the dense one: identical LaneState,
    identical committed watermarks a dispatch, byte-identical WAL."""
    import jax
    if mesh:
        _require_multidevice()
    flat = _served_run(str(tmp_path / "flat"), mesh, flat=True)
    dense = _served_run(str(tmp_path / "dense"), mesh, flat=False)
    # the flat plane took each bucket, and went dense above the top one
    fc, dc = flat["counters"], dense["counters"]
    assert dc["flat_blocks"] == 0 and dc["flat_rows_padded"] == 0
    assert 0 < fc["flat_blocks"] < fc["blocks_built"] == dc["blocks_built"]
    assert fc["block_rows"] == dc["block_rows"] == 9 + 50 + 200 + 600 + 133
    assert fc["credits_released"] == dc["credits_released"] \
        == fc["block_rows"]
    assert len(flat["watermarks"]) == len(dense["watermarks"])
    for a, b in zip(flat["watermarks"], dense["watermarks"]):
        assert (a == b).all()
    assert int(flat["watermarks"][-1].sum()) > 0
    for (path, a), (_p, b) in zip(
            jax.tree_util.tree_leaves_with_path(flat["state"]),
            jax.tree_util.tree_leaves_with_path(dense["state"])):
        assert (a == b).all(), jax.tree_util.keystr(path)
    assert flat["wal"].keys() == dense["wal"].keys() and flat["wal"]
    for shard, table in flat["wal"].items():
        assert table == dense["wal"][shard], shard
        assert len(table) >= fc["blocks_built"]


def _pumped_plane(data_dir, arrived: bool):
    """A durable plane behind a ``ReadbackGate`` (with ``arrived`` every
    readback is there at once: the CPU's immediate readiness, made
    certain), a wave of fresh traffic, a pump followed by the WAL's
    fsync of what it dispatched, and the pump number of every ACK."""
    from ra_tpu.engine.durable import open_engine
    eng = open_engine(CounterMachine(), data_dir, 64, wal_shards=2,
                      ring_capacity=128, max_step_cmds=8)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0)
    gate = ReadbackGate(eng, arrived)
    handles = deque(plane.connect_bulk(512, key="fleet").tolist())
    acked_at, pumps = {}, [0]
    plane.on_block_committed = lambda hs: acked_at.update(
        {int(h): pumps[0] for h in hs})

    def wave(rows=24):
        pick = np.array([handles.popleft() for _ in range(rows)])
        st = plane.submit(pick, plane.directory.next_seqnos(pick),
                          np.ones((rows, 1), np.int32))
        assert (st == OK).all()
        return pick

    def pump(**kw):
        pumps[0] += 1
        out = plane.pump(**kw)
        eng._dur.flush_all()
        return out

    return eng, plane, gate, wave, pump, acked_at


@pytest.mark.parametrize("rule,pumps_to_ack", [("poll", 2), ("cap", 4)])
def test_pumps_from_a_blocks_pop_to_its_ack(tmp_path, rule, pumps_to_ack):
    """What a commit costs in loop cycles (ISSUE 28, ISSUE 36).  With
    traffic in every cycle a block is popped and dispatched in pump 1
    and fsynced behind it, and committed on the device by pump 2's
    dispatch, which samples that confirm.  The driver polls that
    dispatch's watermark as soon as it is there, so pump 2's second
    harvest releases the block: two pumps, the pop's included.  Under
    the rule before ISSUE 28 (a watermark read only when the
    in-flight cap pushes it out, two dispatches later) the same block
    takes four."""
    eng, plane, _gate, wave, pump, acked_at = _pumped_plane(
        str(tmp_path / "wal"), arrived=True)
    if rule == "cap":
        plane.driver.poll = lambda: 0
    first = wave()
    for _ in range(8):
        assert pump(force=True)
        if acked_at:
            break
        wave()
    assert sorted(acked_at) == sorted(first.tolist())
    assert set(acked_at.values()) == {pumps_to_ack}
    pc = eng.pipeline_counters
    assert pc["window_syncs"] == 0
    assert (pc["early_observes"] > 0) == (rule == "poll")
    plane.settle()
    eng.close()


def test_a_block_popped_in_a_pump_retires_in_the_next(tmp_path):
    """ISSUE 36: a write block goes out in the pump that pops it.  On a
    durable engine whose WAL confirm lands behind every pump, the
    block popped in pump c is committed by pump c+1's dispatch and its
    credit released by that pump's harvest: ``block_e2e`` (pop to
    retire) has one sample more after every pump from the second on,
    and none after the first.  Counted in pumps, not milliseconds."""
    eng, plane, _gate, wave, pump, acked_at = _pumped_plane(
        str(tmp_path / "wal"), arrived=True)
    seen = Dispatched(eng)
    retired, firsts = [], []
    for _ in range(6):
        firsts.append(wave())
        assert pump(force=True)
        # this pump's block went out in this pump
        assert int(np.asarray(seen.blocks[-1][0]).sum()) == 24
        retired.append(eng.phases.overview()["block_e2e"]["count"])
    assert retired == [0, 1, 2, 3, 4, 5]
    assert len(seen.blocks) == plane.driver.staged == 6
    for c, pick in enumerate(firsts[:-1]):
        assert {acked_at[int(h)] for h in pick} == {c + 2}
    assert plane.ladder.used.sum() == 24        # the last block's rows
    plane.settle()
    assert plane.ladder.used.sum() == 0
    eng.close()


def test_a_pump_with_nothing_to_dispatch_releases_what_has_arrived(
        tmp_path):
    """The serve loop's idle tick: a block whose commit the device has
    finished is released by the next pump() even when that pump has
    nothing to dispatch, because every harvest polls the driver.  While
    the readback has not arrived nothing is released and nothing
    waits."""
    eng, plane, gate, wave, pump, acked_at = _pumped_plane(
        str(tmp_path / "wal"), arrived=False)
    first = wave()
    assert pump(force=True)
    wave()
    assert pump(force=True)
    # two pumps: the block is committed on the device by the second
    # one's dispatch, both dispatches are inside the in-flight window,
    # and no readback has arrived
    assert not acked_at and plane.driver.in_flight() == 2
    staged = plane.driver.staged
    assert not pump() and not acked_at           # idle, still nothing
    gate.release()
    assert not pump()                            # dispatches nothing
    assert plane.driver.staged == staged
    assert sorted(acked_at) == sorted(first.tolist())
    pc = eng.pipeline_counters
    assert pc["early_observes"] == 2 and pc["window_syncs"] == 0
    assert plane.driver.in_flight() == 0
    plane.settle()
    eng.close()


def test_flat_path_compiles_once_a_bucket_and_counts_what_went_out():
    """Every ra_densify program is compiled when the plane is built:
    pumps whose occupancy crosses every bucket (and the dense path
    above the top one) compile nothing, neither a densify nor a
    superstep program; flat_blocks / flat_rows_padded count them."""
    from ra_tpu import devicewatch
    from ra_tpu.engine.lockstep import flat_buckets
    watch = devicewatch.WATCH
    lanes, k, cmds = 40, 3, 5       # a geometry no other test compiles
    buckets = flat_buckets(k, lanes, cmds)
    assert buckets == (16, 40, 152)
    before = watch.per_fn["densify"]["compiles"]
    recompiles = watch.counters["recompiles"]
    eng = mk_engine(lanes=lanes, cmds=cmds, ring=64)
    plane = IngressPlane(eng, superstep_k=k, window_s=0.0,
                         capacity=2 * k * cmds, hard_credit=4096,
                         soft_credit=4096)
    assert watch.per_fn["densify"]["compiles"] - before == len(buckets)
    assert [plane.driver.flat_rows(m) for m in (0, 16, 17, 152, 153)] \
        == [16, 16, 40, 152, None]
    handles = plane.connect_bulk(1_000, key="fleet")
    by_lane = handles[np.argsort(plane.directory.lane[handles],
                                 kind="stable")]

    def wave(rows):
        # spread evenly over the lanes, so one pop takes the wave whole
        pick = by_lane[np.arange(rows) * len(by_lane) // rows]
        st = plane.submit_auto(pick, np.ones((rows, 1), np.int32))
        assert (st <= SLOW).all()
        built = plane.counters["blocks_built"]
        plane.pump(force=True)
        assert plane.counters["blocks_built"] == built + 1
        assert plane.window.queue_rows() == 0

    # warm: one flat and one dense dispatch, and the empty one of settle
    wave(4)
    wave(300)
    plane.settle()
    densify0 = watch.per_fn["densify"]["compiles"]
    sstep0 = watch.per_fn["superstep"]["compiles"]
    xla0 = watch.counters["xla_compiles"]
    c0 = dict(plane.counters)
    h2d0 = watch.sites["driver_stage"]["h2d_bytes"]
    for rows in (1, 16, 17, 40, 41, 152):       # both edges of each bucket
        wave(rows)
    flat_h2d = watch.sites["driver_stage"]["h2d_bytes"] - h2d0
    wave(153)                                   # one row too many: dense
    wave(400)
    plane.settle()
    assert watch.per_fn["densify"]["compiles"] == densify0
    assert watch.per_fn["superstep"]["compiles"] == sstep0
    assert watch.counters["xla_compiles"] == xla0
    # a bucket's program is no retrace of another's
    assert watch.counters["recompiles"] == recompiles
    c = plane.counters
    assert c["blocks_built"] - c0["blocks_built"] == 8
    assert c["flat_blocks"] - c0["flat_blocks"] == 6
    assert c["flat_rows_padded"] - c0["flat_rows_padded"] \
        == 2 * (16 + 40 + 152)
    assert c["block_rows"] - c0["block_rows"] \
        == 1 + 16 + 17 + 40 + 41 + 152 + 153 + 400
    # the ledger counts the bytes really put: the padded rows, the two
    # per-lane index vectors and n_new, not the dense block
    assert flat_h2d == 4 * (c["flat_rows_padded"] - c0["flat_rows_padded"]) \
        + 6 * 4 * (2 * lanes + k * lanes)
    lane_sums = np.asarray(eng.consistent_read(np.arange(lanes)))
    assert int(lane_sums.sum()) == c["block_rows"] == c["accepted"]


# ---------------------------------------------------------------------------
# overload: the ladder sheds, the queue stays bounded
# ---------------------------------------------------------------------------

def test_overload_sheds_and_queue_depth_stays_bounded():
    eng = mk_engine(lanes=64, cmds=8, ring=256)
    plane = IngressPlane(eng, superstep_k=4, window_s=0.0, capacity=64,
                         soft_credit=1 << 20, hard_credit=1 << 20)
    h = plane.connect_bulk(1000, tenants=2, key="overload")
    rng = np.random.default_rng(9)
    cap_total = 64 * 64
    block_rows = 4 * 8 * 64
    expected = np.zeros(64, np.int64)
    for _ in range(20):
        # 2x overload: twice a full block offered per drain opportunity
        sess = h[rng.integers(0, len(h), 2 * block_rows)]
        pay = np.ones((len(sess), 1), np.int32)
        st = plane.submit(sess, plane.directory.next_seqnos(sess), pay)
        ok = st <= SLOW
        np.add.at(expected, plane.directory.lane[sess[ok]], 1)
        plane.pump(force=True)
        # bounded: the ring sheds instead of growing
        assert plane.window.queue_rows() <= cap_total
    assert plane.counters["shed_rows"] > 0
    shed_ev = [e for e in RECORDER.events("ingress")
               if e[1] == "ingress.shed"]
    assert shed_ev, "shed episode must be a recorded incident"
    plane.settle()
    # exactly-once holds THROUGH the shed episodes: every placed row
    # applied once, every shed row never
    mac = np.asarray(eng.consistent_read(np.arange(64)))
    np.testing.assert_array_equal(mac.astype(np.int64), expected)


# ---------------------------------------------------------------------------
# throughput: the ISSUE 10 acceptance bar
# ---------------------------------------------------------------------------

def _throughput_run(seconds: float = 1.2) -> float:
    eng = mk_engine(lanes=512, cmds=32, ring=2048)
    plane = IngressPlane(eng, superstep_k=8, max_in_flight=2,
                         window_s=0.0, soft_credit=1 << 20,
                         hard_credit=1 << 20)
    h = plane.connect_bulk(4096, tenants=8, key="tput")
    rng = np.random.default_rng(0)
    # 75% of one full block per pump: lane-level Poisson variance must
    # never outrun the per-pump drain, or the bounded ring (correctly)
    # sheds and the clean-throughput measurement stops being clean
    rows = 512 * 32 * 6
    pay = np.ones((rows, 1), np.int32)
    # warm the fused executable + settle path OUTSIDE the measured
    # window (compile time is a one-off, not ingress throughput)
    plane.submit_auto(h[rng.integers(0, len(h), rows)], pay)
    plane.pump(force=True)
    plane.settle()
    base = plane.counters["accepted"]
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sess = h[rng.integers(0, len(h), rows)]
        plane.submit_auto(sess, pay)
        plane.pump(force=True)
    plane.settle()
    elapsed = time.perf_counter() - t0
    # hashed placement leaves some lanes structurally hot (2x the mean
    # session count), and their bounded rings shed the excess — that is
    # the design working; the throughput claim counts ACCEPTED rows
    c = plane.counters
    assert c["shed_rows"] < 0.2 * c["submitted"]
    return (c["accepted"] - base) / elapsed


def test_ingress_throughput_beats_classic_tcp_100x():
    """The acceptance bar: the batched ingress path moves >= 100x the
    classic-TCP per-command baseline (2,934 cmds/s, BENCH_CLASSIC_r05)
    END TO END — session dedup + admission + coalescing + fused
    dispatch + settle all inside the measured window.  One retry
    absorbs shared-CI weather (the bench tests' pattern)."""
    rate = _throughput_run()
    if rate < 100 * CLASSIC_TCP_BASELINE:  # pragma: no cover — CI load
        rate = _throughput_run(2.0)
    assert rate >= 100 * CLASSIC_TCP_BASELINE, f"{rate:.0f} cmds/s"


# ---------------------------------------------------------------------------
# the soak scenario (tools/soak.py --ingress; CPU-scaled in tier-1)
# ---------------------------------------------------------------------------

def run_ingress_soak(seed, *, sessions=50_000, lanes=512, waves=12,
                     wave_rows=20_000, durable_dir=None,
                     disk_faults=False, superstep_k=4, cmds=16,
                     wal_shards=2, mesh=False,
                     throughput_bar=None) -> dict:
    """ROADMAP item 2 acceptance: ``sessions`` simulated sessions fan
    into ``lanes`` lanes through the full ingress path with duplicate
    resends, member-failure/election chaos (the lane plane's transport
    events), a live lossy transport FaultPlan standing in the process
    registry, and — on the durable variant — a seeded DiskFaultPlan
    injecting real WAL faults.  Exactly-once oracle + monotone
    consistent-read probes; returns the run's tail row.

    ``mesh=True`` (ISSUE 11) runs the SAME scenario end-to-end on
    lane state sharded over every available device: per-device WAL
    shards on the durable variant (fsync parallelism follows the lane
    sharding), blocks staged pre-partitioned via the plane's auto
    shardings, and submission waves pumped through the mesh-side
    ``ingress_submit_wave`` path."""
    from ra_tpu.transport.rpc import FaultPlan, FaultSpec
    rng = np.random.default_rng(seed)
    ring = max(512, superstep_k * cmds * 4)
    device_mesh = None
    _mesh_wave = None
    if mesh:
        import jax

        from ra_tpu.parallel.mesh import (
            ingress_submit_wave as _mesh_wave, lane_mesh,
            per_device_wal_shards)
        if len(jax.devices()) < 2:
            # a plain error, NOT pytest.skip: this is a library entry
            # (tools/soak.py --mesh) and Skipped derives from
            # BaseException, which would blow through soak's per-seed
            # except Exception reporting
            raise RuntimeError(
                "mesh soak needs >=2 devices; run with JAX_PLATFORMS="
                "cpu XLA_FLAGS=--xla_force_host_platform_device_count=8")
        device_mesh = lane_mesh(jax.devices(), member_axis=1)
        if durable_dir is not None:
            # per-device WAL shard layout: one shard per lane-axis
            # device, slice boundaries matching the lane sharding
            wal_shards = per_device_wal_shards(device_mesh)
    if durable_dir is not None:
        from ra_tpu.engine.durable import open_engine
        eng = open_engine(CounterMachine(), durable_dir, lanes,
                          wal_shards=wal_shards, ring_capacity=ring,
                          max_step_cmds=cmds, donate=False)
    else:
        eng = mk_engine(lanes=lanes, cmds=cmds, ring=ring)
    if device_mesh is not None:
        from ra_tpu.parallel.mesh import shard_engine_state
        shard_engine_state(eng, device_mesh)
    disk_plan = None
    net_plan = FaultPlan(seed=seed, default=FaultSpec(drop=0.1))
    if disk_faults:
        from ra_tpu.log import faults
        disk_plan = faults.DiskFaultPlan(
            seed=seed, by_class={"wal": faults.DiskFaultSpec(
                fsync_eio=0.05, short_write=0.02, limit=4)})
        faults.install_plan(disk_plan)
    plane = IngressPlane(eng, superstep_k=superstep_k, window_s=0.001,
                         soft_credit=1 << 20, hard_credit=1 << 20)
    try:
        h = plane.connect_bulk(sessions, tenants=16, key="soak")
        # warm the fused/settle/read executables outside the measured
        # window: zero-increment payloads leave the oracle untouched
        plane.submit_auto(h[:min(1024, sessions)],
                          np.zeros((min(1024, sessions), 1), np.int32))
        plane.pump(force=True)
        plane.settle()
        eng.consistent_read([0])
        expected = np.zeros(lanes, np.int64)
        placed_waves: deque = deque(maxlen=4)
        failed_member = None
        probe_lane = int(rng.integers(lanes))
        probe_floor = 0
        placed_total = 0
        resent_rows = 0
        # work_s times the INGRESS PATH (submission, dedup, admission,
        # coalescing, dispatch, final drain); chaos barriers, probe
        # reads and fault-recovery stalls are scenario scaffolding, not
        # path cost — the acceptance bar is about the path
        work_s = 0.0
        t0 = time.perf_counter()
        for w in range(waves):
            tw = time.perf_counter()
            sess = h[rng.integers(0, sessions, wave_rows)]
            seq = plane.directory.next_seqnos(sess)
            pay = rng.integers(1, 8, (wave_rows, 1)).astype(np.int32)
            if device_mesh is not None:
                # the mesh-side pump path (vectorized end to end;
                # lint RA08 gates its module closure)
                st = _mesh_wave(plane, sess, seq, pay)
            else:
                st = plane.submit(sess, seq, pay)
            ok = st <= SLOW
            np.add.at(expected, plane.directory.lane[sess[ok]],
                      pay[ok, 0].astype(np.int64))
            placed_total += int(ok.sum())
            placed_waves.append((sess[ok], seq[ok], pay[ok]))
            if device_mesh is None:
                plane.pump(force=True)
            work_s += time.perf_counter() - tw
            # duplicate resends of an earlier placed wave: the dedup
            # gate must answer DUP for every row (at-most-once)
            if w >= 1 and rng.random() < 0.8:
                ps, pq, pp = placed_waves[int(rng.integers(
                    len(placed_waves)))]
                cut = int(rng.integers(1, len(ps) + 1))
                st2 = plane.submit(ps[:cut], pq[:cut], pp[:cut])
                assert (st2 == DUP).all(), "resend applied twice"
                resent_rows += cut
            # chaos: recover last wave's victim, fail a fresh leader
            # and elect around it (the in-process lane plane's
            # transport-fault analogue)
            if w % 4 == 2:
                if durable_dir is not None:
                    # durability barrier before the leader kill: a
                    # dispatched-but-unfsynced tail is Raft-legally
                    # truncated by the election (it was never acked
                    # committed — docs/INGRESS.md "Delivery
                    # guarantees"); the soak's oracle demands zero
                    # loss, so chaos strikes on a settled plane
                    plane.settle(timeout=60.0)
                if failed_member is not None:
                    lane_c, slot = failed_member
                    if int(np.asarray(
                            eng.state.leader_slot)[lane_c]) != slot:
                        eng.recover_member(lane_c, slot)
                    failed_member = None
                lane_c = int(rng.integers(lanes))
                slot = int(np.asarray(eng.state.leader_slot)[lane_c])
                eng.fail_member(lane_c, slot)
                eng.trigger_election([lane_c])
                failed_member = (lane_c, slot)
            # monotone linearizable-read probe: never below the last
            # read, never above what was placed by its completion
            if w % 5 == 4:
                val = int(np.asarray(
                    eng.consistent_read([probe_lane]))[0])
                assert probe_floor <= val <= expected[probe_lane], \
                    (probe_floor, val, int(expected[probe_lane]))
                probe_floor = val
        if disk_plan is not None:
            from ra_tpu.log import faults
            faults.clear_plan()  # heal so the durable tail converges
        ts = time.perf_counter()
        plane.settle(timeout=120.0)
        work_s += time.perf_counter() - ts  # the final drain is path
        elapsed = time.perf_counter() - t0
        gauges = plane.gauges()
        if durable_dir is not None:
            # the durability half of the backlog gauge is wired
            assert gauges["wal_pending_steps"] >= 0
        assert gauges["queue_rows"] == 0 and \
            gauges["inflight_blocks"] == 0
        mac = np.asarray(eng.consistent_read(np.arange(lanes)))
        np.testing.assert_array_equal(mac.astype(np.int64), expected)
        assert plane.counters["dup_dropped"] >= resent_rows
        throughput = placed_total / work_s
        if throughput_bar is not None:
            assert throughput >= throughput_bar, \
                f"{throughput:.0f} < bar {throughput_bar:.0f} cmds/s"
        c = plane.counters
        return {
            "value": throughput,
            "ingress_cmds_per_s": throughput,
            "ingress_shed_rate": c["shed_rows"] / max(1, c["submitted"]),
            "sessions": sessions, "lanes": lanes,
            "placed": placed_total, "dup_dropped": c["dup_dropped"],
            "blocks_built": c["blocks_built"], "elapsed_s": elapsed,
            "work_s": work_s,
            "durable": durable_dir is not None,
            # mesh stamps (ISSUE 11): the sharding + WAL layout the
            # oracle ran against
            "mesh": eng.mesh_shape(),
            "wal_shards": wal_shards if durable_dir is not None else 0,
            "wal_shard_layout": eng._dur.shard_layout()
            if durable_dir is not None else [],
            "disk_faults_injected":
                dict(disk_plan.counters) if disk_plan else {},
        }
    finally:
        net_plan.unregister()
        if disk_faults:
            from ra_tpu.log import faults
            faults.clear_plan()
        eng.close()


def test_ingress_soak_cpu_scaled_volatile():
    """Tier-1 CPU-scaled acceptance run: 50k sessions -> 512 lanes,
    resends + election chaos, exactly-once oracle."""
    res = run_ingress_soak(0)
    assert res["placed"] > 100_000
    assert res["dup_dropped"] > 0


def test_ingress_soak_cpu_scaled_durable_with_disk_faults(tmp_path):
    """Tier-1 durable variant: commits gate on real fsyncs while a
    seeded DiskFaultPlan injects EIO/torn writes into the WAL shards —
    the exactly-once oracle must hold through poison/rollover/resend."""
    res = run_ingress_soak(1, sessions=5_000, lanes=64, waves=8,
                           wave_rows=4_000, superstep_k=2, cmds=8,
                           durable_dir=str(tmp_path / "ing"),
                           disk_faults=True, wal_shards=2)
    assert res["durable"] and res["placed"] > 10_000


def _require_multidevice():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("single-device backend")


def test_ingress_soak_cpu_scaled_mesh_durable(tmp_path):
    """Tier-1 mesh variant (ISSUE 11): the same exactly-once scenario
    end-to-end on lane state SHARDED over the 8 forced-host devices —
    per-device WAL shards (one per lane-axis device, fsync parallelism
    following the lane sharding), blocks staged pre-partitioned via
    the plane's auto shardings, disk-fault + election chaos."""
    _require_multidevice()
    res = run_ingress_soak(3, sessions=4_000, lanes=64, waves=6,
                           wave_rows=2_500, superstep_k=2, cmds=8,
                           durable_dir=str(tmp_path / "ing"),
                           disk_faults=True, mesh=True)
    assert res["durable"] and res["mesh"] == "1x8"
    assert res["wal_shards"] == 8
    # per-device layout: 8 equal contiguous lane slices
    assert res["wal_shard_layout"] == [[i * 8, (i + 1) * 8]
                                       for i in range(8)]
    assert res["placed"] > 5_000
    assert res["dup_dropped"] > 0


@pytest.mark.slow
def test_ingress_soak_full_scale_mesh(tmp_path):
    """The ISSUE 11 acceptance scenario at full scale: 1M sessions
    into >= 100k lanes sharded across the 8 forced-host devices,
    durable with per-device WAL shards, under disk-fault + election
    chaos, exactly-once oracle exact (tools/soak.py --ingress --mesh
    runs the same entry)."""
    _require_multidevice()
    res = run_ingress_soak(0, sessions=1_000_000, lanes=102_400,
                           waves=24, wave_rows=200_000,
                           durable_dir=str(tmp_path / "ing"),
                           disk_faults=True, mesh=True)
    assert res["sessions"] == 1_000_000 and res["lanes"] >= 100_000
    assert res["mesh"] == "1x8" and res["wal_shards"] == 8


@pytest.mark.slow
def test_ingress_soak_full_scale(tmp_path):
    """The full ISSUE 10 acceptance scenario: ~1M sessions into 10k
    lanes, durable, under disk faults, with the >=100x classic-TCP
    throughput bar.  Behind ``-m slow`` (tools/soak.py --ingress runs
    the same entry)."""
    res = run_ingress_soak(0, sessions=1_000_000, lanes=10_000,
                           waves=24, wave_rows=200_000,
                           durable_dir=str(tmp_path / "ing"),
                           disk_faults=True,
                           throughput_bar=100 * CLASSIC_TCP_BASELINE)
    assert res["sessions"] == 1_000_000


def test_ra_top_renders_ingress_panel(tmp_path):
    """ra_top shows the session tier: accept rate over the snapshot
    window, queue depth, ladder level, dup/shed counters, and the
    SHEDDING flag when shed_rows grew between frames."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_ing = {"sessions": 1_000_000, "queue_rows": 512,
                "accepted": 10_000, "dup_dropped": 37, "shed_rows": 0,
                "rejected": 5, "wal_pending_steps": 3,
                "ladder": {"level_name": "tight", "level": 1}}
    t0 = time.time()
    snap0 = {"seq": 1, "ts": t0 - 1.0,
             "engine": {"lanes": 16, "members": 3},
             "ingress": base_ing}
    snap1 = {"seq": 2, "ts": t0,
             "engine": {"lanes": 16, "members": 3},
             "ingress": {**base_ing, "accepted": 60_000,
                         "shed_rows": 40}}
    path = str(tmp_path / "obs.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(snap0) + "\n")
        f.write(json.dumps(snap1) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ra_top.py"),
         path, "--once"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "ingress" in out and "sessions=1000000" in out
    assert "q=512" in out and "level=tight" in out
    assert "dup=37" in out and "shed=40" in out
    # the durability half of the backlog renders under durable/mesh
    # runs (ISSUE 11 satellite)
    assert "wal_pending=3" in out
    assert "SHEDDING" in out
    assert "50.0K acc/s" in out or "acc/s" in out
