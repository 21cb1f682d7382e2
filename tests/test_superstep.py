"""Superstep parity tests (ISSUE 5): a K-round fused dispatch
(`LockstepEngine.superstep`, lax.scan over the step body) must be
ORACLE-EXACT against K single steps — same LaneState bit for bit — for
every machine flavour (batch-apply counter/kv AND the sequential-window
fifo), including mid-superstep election masks, member failures and ring
backpressure.  Durable-mode behaviour (confirm hold-back, kill-9
recovery of a superstep-driven run) lives in test_engine_durable.py /
test_wal_shards.py; this file pins the pure state-transition algebra.

Also the soak entry point: ``run_superstep_fuzz`` explores fresh random
schedules (tools/soak.py --superstep).
"""
import numpy as np
import pytest

from harness import Dispatched, ReadbackGate
from ra_tpu.engine import DispatchAheadDriver, LockstepEngine
from ra_tpu.models import CounterMachine, JitFifoMachine, JitKvMachine

N, P, KC = 8, 3, 4  # lanes, members, max cmds/step


def _machine(name):
    if name == "jit_kv":
        return JitKvMachine(n_keys=16)
    if name == "jit_fifo":
        return JitFifoMachine(capacity=16, checkout_slots=4)
    return CounterMachine()


def _payloads(name, rng, k):
    """Random valid [k, N, KC, C] command blocks for the machine."""
    if name == "jit_kv":
        p = np.zeros((k, N, KC, 4), np.int32)
        p[..., 0] = rng.integers(1, 5, (k, N, KC))     # put/get/del/cas
        p[..., 1] = rng.integers(0, 16, (k, N, KC))    # key
        p[..., 2] = rng.integers(0, 100, (k, N, KC))   # value
        p[..., 3] = rng.integers(-1, 5, (k, N, KC))    # cas expected
        return p
    if name == "jit_fifo":
        p = np.zeros((k, N, KC, 3), np.int32)
        p[..., 0] = rng.integers(1, 3, (k, N, KC))     # enqueue/dequeue
        p[..., 1] = rng.integers(1, 9, (k, N, KC))
        return p
    return rng.integers(1, 9, (k, N, KC, 1)).astype(np.int32)


def _mk(name, **kw):
    kw.setdefault("ring_capacity", 64)
    kw.setdefault("max_step_cmds", KC)
    kw.setdefault("write_delay", 1)
    return LockstepEngine(_machine(name), N, P, **kw)


def _assert_state_equal(a, b, ctx=""):
    for f in a.state._fields:
        if f == "mac":
            continue
        xa, xb = np.asarray(getattr(a.state, f)), \
            np.asarray(getattr(b.state, f))
        np.testing.assert_array_equal(xa, xb, err_msg=f"{ctx}: {f}")
    import jax
    for pa, pb in zip(jax.tree.leaves(a.state.mac),
                      jax.tree.leaves(b.state.mac)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=f"{ctx}: mac")


@pytest.mark.parametrize("machine_name", ["counter", "jit_kv", "jit_fifo"])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_superstep_oracle_exact(machine_name, k):
    """K fused rounds == K single rounds, bit for bit, through normal
    traffic, a member failure and a mid-superstep election (the elect
    schedule fires at an INNER step, so candidate selection, the
    term-opening noop and the same-round follower clamp all run inside
    the scan)."""
    a = _mk(machine_name)
    b = _mk(machine_name)
    rng = np.random.default_rng(100 + k)
    for rnd in range(3):
        n_new = rng.integers(0, KC + 1, (k, N)).astype(np.int32)
        pay = _payloads(machine_name, rng, k)
        elect = np.zeros((k, N), bool)
        if rnd == 1:
            # fail lane 2's leader, then request the election at a
            # mid-superstep inner index
            leader = int(np.asarray(a.state.leader_slot)[2])
            a.fail_member(2, leader)
            b.fail_member(2, leader)
            elect[min(1, k - 1), 2] = True
        for j in range(k):
            a.step(n_new[j], pay[j], elect_mask=elect[j])
        b.superstep(n_new, pay, elect_blk=elect)
        _assert_state_equal(a, b, f"{machine_name} k={k} round={rnd}")


def test_superstep_aux_watermarks_are_per_inner_step():
    """The stacked aux carries the cumulative committed and applied
    watermarks after EACH inner step — monotone, ending exactly at the
    engine's final state (what the dispatch-ahead driver and the bench
    latency stamping read)."""
    eng = _mk("counter")
    rng = np.random.default_rng(0)
    eng.superstep(np.full((4, N), 2, np.int32),
                  _payloads("counter", rng, 4))
    aux = eng.uniform_superstep(4, 2)
    com = np.asarray(aux["committed_lanes"]).astype(np.int64)
    app = np.asarray(aux["applied_lanes"]).astype(np.int64)
    assert com.shape == (4, N) and app.shape == (4, N)
    assert (np.diff(com, axis=0) >= 0).all()
    assert (np.diff(app, axis=0) >= 0).all()
    np.testing.assert_array_equal(
        com[-1], np.asarray(eng.state.total_committed))


def test_superstep_ring_backpressure_parity():
    """Bursts beyond ring headroom inside the fused loop clip exactly
    like the single-step path (n_acc per inner step)."""
    a = _mk("counter", ring_capacity=16, max_step_cmds=8,
            apply_window=4)
    b = _mk("counter", ring_capacity=16, max_step_cmds=8,
            apply_window=4)
    rng = np.random.default_rng(7)
    for _ in range(4):
        n_new = np.full((4, N), 8, np.int32)
        pay = rng.integers(1, 5, (4, N, 8, 1)).astype(np.int32)
        for j in range(4):
            a.step(n_new[j], pay[j])
        b.superstep(n_new, pay)
        _assert_state_equal(a, b, "backpressure")


def test_dispatch_ahead_driver_matches_plain_supersteps():
    """The staging driver is a pure pipelining layer: the final engine
    state equals driving the same blocks through superstep() directly,
    and its in-flight cap is honoured."""
    a = _mk("counter")
    b = _mk("counter")
    rng = np.random.default_rng(3)
    blocks = [(np.full((4, N), 2, np.int32), _payloads("counter", rng, 4))
              for _ in range(6)]
    for nb, pb in blocks:
        a.superstep(nb, pb)
    drv = DispatchAheadDriver(b, max_in_flight=2)
    for nb, pb in blocks:
        drv.submit(nb, pb)
        assert drv.in_flight() <= 2
    final = drv.drain()
    _assert_state_equal(a, b, "driver")
    np.testing.assert_array_equal(final,
                                  np.asarray(b.state.total_committed))
    assert b.pipeline_counters["superstep_dispatches"] == 6
    assert b.pipeline_counters["inner_steps"] == 24
    assert b.overview(0)["pipeline"]["dispatch_ahead"] == 2


@pytest.mark.parametrize("entry", ["dense", "flat"])
def test_each_submit_dispatches_the_block_it_was_given(entry):
    """ISSUE 36: ``submit`` / ``submit_rows`` stage the block they are
    given and dispatch that block in the same call; nothing is held
    over to the next call.  Dispatches rise by one a call, the call
    returns that dispatch's own watermark handle, and ``drain()`` after
    a single call observes the rows of the block it was given (the
    same watermark as the block run through ``superstep`` directly)."""
    a, b = _mk("counter"), _mk("counter")
    drv = DispatchAheadDriver(b, max_in_flight=2)
    k = 4
    if entry == "flat":
        drv.prepare_flat(k)
    pc = b.pipeline_counters
    rng = np.random.default_rng(36)
    for i in range(3):
        take = rng.integers(1, KC + 1, N)
        row_base = (np.cumsum(take) - take).astype(np.int32)
        rows = rng.integers(1, 9, (int(take.sum()), 1)).astype(np.int32)
        n_new = np.clip(take[None, :] - (np.arange(k) * KC)[:, None],
                        0, KC).astype(np.int32)
        dense = np.zeros((k, N, KC, 1), np.int32)
        for lane in range(N):
            for j in range(take[lane]):
                dense[j // KC, lane, j % KC] = rows[row_base[lane] + j]
        a.superstep(n_new, dense)
        before = pc["superstep_dispatches"]
        if entry == "flat":
            h = drv.submit_rows(n_new, rows, row_base, take)
        else:
            h = drv.submit(n_new, dense)
        assert pc["superstep_dispatches"] == before + 1 == i + 1
        assert drv.staged == i + 1 and h is not None
        assert pc["blocks_staged"] == i + 1
        got = drv.drain()
        assert drv.observed == i + 1 and drv.in_flight() == 0
        want = np.asarray(a.state.total_committed)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(np.asarray(h)[0], want)
    assert (got > 0).all()
    _assert_state_equal(a, b, f"{entry} submit")


def test_driver_stages_blocks_under_mesh_shardings():
    """A sharded engine + a driver built with
    superstep_block_shardings: the n_new/payloads each dispatch is
    given land lane-sharded over the mesh (no resharding copy at
    dispatch) and the fused run
    stays parity-exact with an unsharded engine.  conftest forces 8
    host devices, so the mesh is real."""
    import jax
    from ra_tpu.parallel.mesh import (shard_engine_state,
                                      superstep_block_shardings)
    if len(jax.devices()) < 2:
        pytest.skip("single-device backend")
    a = _mk("counter")
    b = _mk("counter")
    mesh = shard_engine_state(b)
    sh = superstep_block_shardings(mesh)
    # elect is host data; the read block shards with the write block
    # (ISSUE 20), and the flat write block's table and per-lane index
    # have their entries (ISSUE 26)
    assert set(sh) == {"n_new", "payloads", "query", "n_read", "read_q",
                       "rows", "row_base", "take"}
    drv = DispatchAheadDriver(b, max_in_flight=2, shardings=sh)
    seen = Dispatched(b)
    rng = np.random.default_rng(23)
    blocks = [(np.full((4, N), 2, np.int32),
               _payloads("counter", rng, 4)) for _ in range(4)]
    for nb, pb in blocks:
        a.superstep(nb, pb)
        drv.submit(nb, pb)
    assert len(seen.blocks) == 4
    for n_new, payloads, _r in seen.blocks:
        for arr, key in ((n_new, "n_new"), (payloads, "payloads")):
            assert arr.sharding.is_equivalent_to(sh[key], arr.ndim), key
    drv.drain()
    _assert_state_equal(a, b, "mesh driver")
    assert b.pipeline_counters["blocks_staged"] == 4


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "mesh8"])
def test_driver_submit_rows_matches_submit(mesh):
    """The driver's flat entry (ISSUE 26): a block given as the rows
    it carries is dispatched, in the same call, as the same dense
    device array ``submit`` would put (under the mesh: with the
    payloads' sharding), and the fused run stays parity-exact with the
    dense form."""
    import jax
    from ra_tpu.parallel.mesh import (shard_engine_state,
                                      superstep_block_shardings)
    if mesh and len(jax.devices()) < 2:
        pytest.skip("single-device backend")
    a, b = _mk("counter"), _mk("counter")
    sh = superstep_block_shardings(shard_engine_state(b)) if mesh else None
    drv = DispatchAheadDriver(b, max_in_flight=2, shardings=sh)
    seen = Dispatched(b)
    assert drv.flat_rows(1) is None        # the entry is not open yet
    k = 4
    drv.prepare_flat(k)
    assert drv._flat_buckets == (8, 32)
    rng = np.random.default_rng(26)
    for most in (1, 4, 2, 4):
        take = rng.integers(0, most + 1, N)
        take[rng.integers(0, N)] = most
        m = int(take.sum())
        row_base = (np.cumsum(take) - take).astype(np.int32)
        rows = rng.integers(1, 9, (m, 1)).astype(np.int32)
        n_new = np.clip(take[None, :] - (np.arange(k) * KC)[:, None],
                        0, KC).astype(np.int32)
        dense = np.zeros((k, N, KC, 1), np.int32)
        for lane in range(N):
            for j in range(take[lane]):
                dense[j // KC, lane, j % KC] = rows[row_base[lane] + j]
        assert drv.flat_rows(m) == (8 if m <= 8 else 32)
        a.superstep(n_new, dense)
        drv.submit_rows(n_new, rows, row_base, take)
        sent = seen.blocks[-1][1]
        np.testing.assert_array_equal(np.asarray(sent), dense)
        if mesh:
            assert sent.sharding.is_equivalent_to(sh["payloads"], 4)
    assert drv.flat_rows(33) is None       # over the top bucket: dense
    with pytest.raises(ValueError, match="fit no bucket"):
        drv.submit_rows(n_new, np.zeros((33, 1), np.int32), row_base, take)
    drv.drain()
    _assert_state_equal(a, b, "flat driver")
    assert b.pipeline_counters["blocks_staged"] == 4


def test_window_syncs_count_only_real_waits():
    """window_syncs backs the 'window_syncs << dispatches' health rule,
    so a readback that was already ready when harvested must NOT count:
    on this backend the tiny dispatches complete long before the host
    loops back, so a healthy dispatch-ahead run reports (near-)zero
    syncs while dispatches climb."""
    eng = _mk("counter")
    drv = DispatchAheadDriver(eng, max_in_flight=2)
    nb = np.full((4, N), 2, np.int32)
    pb = np.ones((4, N, KC, 1), np.int32)
    import time
    for _ in range(20):
        drv.submit(nb, pb)
        time.sleep(0.002)  # device finishes: harvests find ready handles
    drv.drain()
    pc = eng.pipeline_counters
    assert pc["superstep_dispatches"] == 20
    assert pc["window_syncs"] <= 2, pc


def _arrive(eng, drv):
    """Let the device finish: the step and every read-aux copy in
    flight (the gated watermarks arrive when the test says)."""
    import jax
    eng.block_until_ready()
    jax.block_until_ready([e[2] for e in drv._handles])


def test_poll_observes_a_ready_dispatch_with_no_further_dispatch():
    """ISSUE 28: a dispatch's watermark is observed when it has
    arrived, by poll() alone: no later dispatch has to push it out of
    the in-flight window.  Before it has arrived poll() takes nothing
    and does not wait."""
    eng = _mk("counter")
    drv = DispatchAheadDriver(eng, max_in_flight=2)
    gate = ReadbackGate(eng)
    made, log = gate.made, gate.log
    nb = np.full((4, N), 2, np.int32)
    pb = np.ones((4, N, KC, 1), np.int32)
    drv.submit(nb, pb)                  # dispatch 0
    _arrive(eng, drv)
    assert len(made) == 1 and drv.in_flight() == 1
    assert drv.poll() == 0 and drv.observed == 0 and log == []
    assert drv.last_committed is None
    made[0].ready = True
    assert drv.poll() == 1
    assert drv.observed == 1 and drv.in_flight() == 0 and log == [0]
    np.testing.assert_array_equal(drv.last_committed,
                                  np.asarray(eng.state.total_committed))
    assert drv.last_ring_used is not None
    assert drv.poll() == 0              # each dispatch once
    pc = eng.pipeline_counters
    assert pc["early_observes"] == 1 and pc["window_syncs"] == 0
    assert pc["superstep_dispatches"] == 1
    assert eng.overview(0)["pipeline"]["early_observes"] == 1
    assert eng.phases.overview()["device_dispatch"]["count"] == 1


@pytest.mark.parametrize("reads", [False, True], ids=["writes", "reads"])
@pytest.mark.parametrize("max_in_flight", [1, 2, 3])
def test_every_dispatch_is_observed_once_in_staging_order(max_in_flight,
                                                          reads):
    """Whether poll() or the in-flight cap's pop takes it, every
    dispatch is observed exactly once and in the order it was staged
    (``observed`` is an ordinal the ingress plane compares a block's
    staging number with); poll() stops at the first readback that has
    not arrived even when a later one has; ``max_in_flight`` still
    bounds the dispatches not observed; ``window_syncs`` counts the
    cap's pops that had to wait and ``early_observes`` poll()'s."""
    name = "counter" if reads else "jit_fifo"
    eng = _mk(name)
    assert eng.reads_enabled == reads
    drv = DispatchAheadDriver(eng, max_in_flight=max_in_flight)
    gate = ReadbackGate(eng)
    made, log = gate.made, gate.log
    rng = np.random.default_rng(28 + max_in_flight)
    n_blocks, polled = 12, 0
    for i in range(n_blocks):
        read_blk = eng.uniform_read_block(2, 1) if reads and i % 2 else None
        before = drv.observed
        drv.submit(np.full((2, N), 1, np.int32), _payloads(name, rng, 2),
                   read_blk=read_blk)
        assert drv.in_flight() <= max_in_flight
        # nothing was ready at this launch: only the cap observed
        assert drv.observed - before <= 1
        _arrive(eng, drv)
        # arrivals out of order: one launch in three nothing arrives,
        # one the newest readback alone (poll() may not pass the head
        # for it), one everything
        live = [h for h in made if h.seq >= drv.observed]
        if live and i % 3 == 1:
            live[-1].ready = True
            if len(live) > 1:
                assert drv.poll() == 0, "took a dispatch past its head"
        elif i % 3 == 2:
            for h in live:
                h.ready = True
        polled += drv.poll()
        assert log == list(range(drv.observed))
    pc = dict(eng.pipeline_counters)
    assert pc["superstep_dispatches"] == n_blocks == len(made)
    assert pc["early_observes"] == polled > 0
    # the cap's pops: every one found its readback not arrived
    assert pc["window_syncs"] == drv.observed - polled
    if max_in_flight == 1:
        assert pc["window_syncs"] > 0
    drv.drain()
    assert log == list(range(n_blocks)) and drv.observed == n_blocks
    assert eng.pipeline_counters["window_syncs"] == pc["window_syncs"]
    assert eng.pipeline_counters["early_observes"] == polled
    np.testing.assert_array_equal(drv.last_committed,
                                  np.asarray(eng.state.total_committed))
    if reads:
        # one read observation a dispatch, beside its watermark
        assert len(drv.read_obs) == n_blocks
        assert drv.last_read_served is not None


def test_poll_waits_for_the_read_copies_of_a_reads_enabled_engine():
    """On a reads-enabled engine a dispatch is taken only when its
    read-aux copies have arrived too: poll() must not turn
    _observe_reads into a wait."""
    eng = _mk("counter")
    drv = DispatchAheadDriver(eng, max_in_flight=2)
    made = ReadbackGate(eng).made

    class _Late:
        nbytes = 4

        def is_ready(self):
            return False

    nb = np.full((2, N), 1, np.int32)
    pb = np.ones((2, N, KC, 1), np.int32)
    drv.submit(nb, pb, read_blk=eng.uniform_read_block(2, 1))
    _arrive(eng, drv)
    t0, h, robs = drv._handles[0]
    assert set(robs) >= {"read_served_lanes", "read_done"}
    made[0].ready = True
    drv._handles[0] = (t0, h, {**robs, "read_done": _Late()})
    assert drv.poll() == 0 and drv.observed == 0
    drv._handles[0] = (t0, h, robs)
    assert drv.poll() == 1 and drv.observed == 1
    assert len(drv.read_obs) == 1


@pytest.mark.parametrize("machine_name", ["counter", "jit_kv"])
@pytest.mark.parametrize("k", [1, 8])
def test_mesh_superstep_parity(machine_name, k):
    """ISSUE 11: the fused superstep over state SHARDED on the 8
    forced-host devices is bit-exact vs the single-device engine on
    identical schedules — including a mid-superstep election (the vote
    round runs inside the scan over sharded state, with the quorum
    math lowering to collectives) and donation ON (the superstep
    default), driven through the mesh dispatch-ahead driver with
    pre-partitioned staged blocks."""
    import jax

    from ra_tpu.parallel.mesh import (mesh_superstep_driver,
                                      shard_engine_state)
    if len(jax.devices()) < 2:
        pytest.skip("single-device backend")
    a = _mk(machine_name)                       # single-device oracle
    b = _mk(machine_name, superstep_donate=True)
    mesh = shard_engine_state(b)
    drv = mesh_superstep_driver(b, mesh, max_in_flight=2)
    rng = np.random.default_rng(300 + k)
    for rnd in range(3):
        n_new = rng.integers(0, KC + 1, (k, N)).astype(np.int32)
        pay = _payloads(machine_name, rng, k)
        elect = np.zeros((k, N), bool)
        if rnd == 1:
            # fail lane 1's leader, request the election at a
            # mid-superstep inner index: candidate selection, the
            # term-opening noop and the same-round follower clamp all
            # run inside the scan on SHARDED state
            leader = int(np.asarray(a.state.leader_slot)[1])
            a.fail_member(1, leader)
            b.fail_member(1, leader)
            elect[min(1, k - 1), 1] = True
        for j in range(k):
            a.step(n_new[j], pay[j], elect_mask=elect[j])
        b.superstep(n_new, pay, elect_blk=elect)
        _assert_state_equal(a, b, f"mesh {machine_name} k={k} r={rnd}")
    # the driver path too: staged blocks land pre-partitioned and the
    # final state still matches the oracle
    for _ in range(3):
        nb = rng.integers(0, KC + 1, (k, N)).astype(np.int32)
        pb = _payloads(machine_name, rng, k)
        for j in range(k):
            a.step(nb[j], pb[j])
        drv.submit(nb, pb)
    drv.drain()
    _assert_state_equal(a, b, f"mesh driver {machine_name} k={k}")


def test_superstep_donation_parity():
    """Donating the state buffer into the fused dispatch (the superstep
    default) changes nothing observable vs donate-off."""
    a = _mk("counter", superstep_donate=False)
    b = _mk("counter", superstep_donate=True)
    rng = np.random.default_rng(11)
    for _ in range(3):
        nb = rng.integers(0, KC + 1, (8, N)).astype(np.int32)
        pb = _payloads("counter", rng, 8)
        a.superstep(nb, pb)
        b.superstep(nb, pb)
        _assert_state_equal(a, b, "donation")


def test_superstep_consistent_read_still_linearizable():
    """consistent_read interleaves with superstep driving: the
    certified state reflects every committed fused round."""
    eng = _mk("counter")
    eng.uniform_superstep(4, 2)
    eng.uniform_superstep(4, 0)  # settle the write-delay confirms
    mac = eng.consistent_read(range(N))
    per_lane = np.asarray(eng.state.total_committed)
    np.testing.assert_array_equal(np.asarray(mac) >= 2 * 4, True)
    assert (np.asarray(mac) <= per_lane * 2).all()


def run_superstep_fuzz(seed, rounds=4):
    """Soak entry (tools/soak.py --superstep): random K/schedules with
    failures + elections, exact-parity checked every round."""
    rng = np.random.default_rng(seed)
    name = ["counter", "jit_kv", "jit_fifo"][seed % 3]
    a = _mk(name)
    b = _mk(name)
    failed: set = set()
    for rnd in range(rounds):
        k = int(rng.choice([1, 2, 4, 8]))
        n_new = rng.integers(0, KC + 1, (k, N)).astype(np.int32)
        pay = _payloads(name, rng, k)
        elect = np.zeros((k, N), bool)
        if rng.random() < 0.5:
            lane = int(rng.integers(0, N))
            leader = int(np.asarray(a.state.leader_slot)[lane])
            if (lane, leader) not in failed and \
                    sum(1 for (ln, _s) in failed if ln == lane) < P // 2:
                a.fail_member(lane, leader)
                b.fail_member(lane, leader)
                failed.add((lane, leader))
                elect[int(rng.integers(0, k)), lane] = True
        for j in range(k):
            a.step(n_new[j], pay[j], elect_mask=elect[j])
        b.superstep(n_new, pay, elect_blk=elect)
        _assert_state_equal(a, b, f"fuzz seed={seed} round={rnd} k={k}")


@pytest.mark.parametrize("seed", [0, 1, 2, 5])
def test_superstep_fuzz_anchor_seeds(seed):
    run_superstep_fuzz(seed)
