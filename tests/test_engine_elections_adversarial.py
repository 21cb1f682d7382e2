"""Adversarial election scenarios on the lane engine — elections under
inflight traffic, repeated failovers, concurrent failures in the same
round, and a fuzzed multi-step failure schedule under the 2-D device
mesh (VERDICT r3 weak items 4-5).

The properties asserted are the reference's: committed entries survive
any sequence of leader failures (ra_server.erl §5.4 safety via
increment_commit_index, :2955-2964), an uncommitted suffix of a deposed
leader is truncated and never resurrects (AER consistency repair,
ra_server.erl:1032-1156), and a minority can never commit or elect
(:986-1002).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu.engine import LockstepEngine
from ra_tpu.models import CounterMachine, RegisterMachine

from test_register_machine import host_fold

N, P, K = 4, 5, 4


def zeros_step(eng):
    eng.step(jnp.zeros((eng.n_lanes,), jnp.int32),
             jnp.zeros((eng.n_lanes, eng.max_step_cmds,
                        eng.payload_width), eng.payload_dtype))


def drain_committed(eng, limit=32):
    """Drive empty rounds until every lane's leader log is fully
    committed and applied on every active member."""
    lane = np.arange(eng.n_lanes)
    for _ in range(limit):
        st = eng.state
        leads = np.asarray(st.leader_slot)
        tail = np.asarray(st.last_index)[lane, leads]
        com = np.asarray(st.commit)[lane, leads]
        act = np.asarray(st.active)
        app = np.where(act, np.asarray(st.applied),
                       np.iinfo(np.int32).max).min(axis=1)
        if (com >= tail).all() and (app >= com).all():
            return
        zeros_step(eng)
    raise AssertionError("drain_committed did not converge")


def reg_payload(cmds):
    pay = np.zeros((N, K, 4), np.int32)
    for k, c in enumerate(cmds[:K]):
        pay[:, k] = c
    return pay


def test_committed_state_survives_repeated_failovers():
    """Six successive leader kills + elections; every command committed
    in any term survives to the end on every member."""
    rng = np.random.default_rng(7)
    eng = LockstepEngine(RegisterMachine(n_slots=8), N, P,
                         ring_capacity=256, max_step_cmds=K,
                         write_delay=1, donate=False)
    committed = []
    dead = {lane: set() for lane in range(N)}
    for _round in range(6):
        cmds = [(1, int(rng.integers(0, 8)), int(rng.integers(1, 100)), 0)
                for _ in range(K)]
        committed += cmds
        eng.step(jnp.full((N,), K, jnp.int32),
                 jnp.asarray(reg_payload(cmds)))
        drain_committed(eng)
        # revive previously-dead members so the next kill still leaves a
        # 3/5 quorum, then kill each lane's current leader
        leads = np.asarray(eng.state.leader_slot)
        for lane in range(N):
            for slot in list(dead[lane]):
                eng.recover_member(lane, slot)
                dead[lane].discard(slot)
            eng.fail_member(lane, int(leads[lane]))
            dead[lane].add(int(leads[lane]))
        term0 = np.asarray(eng.state.term).copy()
        eng.trigger_election(list(range(N)))
        term1 = np.asarray(eng.state.term)
        assert (term1 == term0 + 1).all(), (term0, term1)
        leads1 = np.asarray(eng.state.leader_slot)
        for lane in range(N):
            assert int(leads1[lane]) not in dead[lane]
    for lane in range(N):
        for slot in list(dead[lane]):
            eng.recover_member(lane, slot)
    drain_committed(eng)
    want = host_fold(committed)
    mac = np.asarray(eng.state.mac)
    for lane in range(N):
        for member in range(P):
            assert mac[lane, member].tolist() == want, \
                (lane, member, mac[lane, member].tolist(), want)


def test_uncommitted_suffix_never_resurrects():
    """A deposed leader's unreplicated suffix (accepted while cut off
    from its majority) must never reach any machine, even after the old
    leader rejoins — while every previously committed write survives."""
    rng = np.random.default_rng(11)
    eng = LockstepEngine(RegisterMachine(n_slots=8), N, P,
                         ring_capacity=256, max_step_cmds=K,
                         write_delay=1, donate=False)
    committed = [(1, int(rng.integers(0, 4)), int(rng.integers(1, 100)), 0)
                 for _ in range(K)]
    eng.step(jnp.full((N,), K, jnp.int32),
             jnp.asarray(reg_payload(committed)))
    drain_committed(eng)

    # cut the leader (slot with current leadership) off from everyone:
    # fail all four followers, then push a doomed write to slot 7
    leads = np.asarray(eng.state.leader_slot)
    for lane in range(N):
        for slot in range(P):
            if slot != int(leads[lane]):
                eng.fail_member(lane, slot)
    doomed = [(1, 7, 777, 0)] * K
    for _ in range(2):
        eng.step(jnp.full((N,), K, jnp.int32),
                 jnp.asarray(reg_payload(doomed)))
    base = eng.committed_total()
    zeros_step(eng)
    assert eng.committed_total() == base, "minority leader committed"

    # majority side comes back without the old leader and elects
    for lane in range(N):
        eng.fail_member(lane, int(leads[lane]))
        for slot in range(P):
            if slot != int(leads[lane]):
                eng.recover_member(lane, slot)
    eng.trigger_election(list(range(N)))
    more = [(1, int(rng.integers(0, 4)), int(rng.integers(1, 100)), 0)
            for _ in range(K)]
    committed += more
    eng.step(jnp.full((N,), K, jnp.int32), jnp.asarray(reg_payload(more)))
    drain_committed(eng)

    # deposed leader rejoins; its slot-7 write must be gone everywhere
    for lane in range(N):
        eng.recover_member(lane, int(leads[lane]))
    drain_committed(eng)
    want = host_fold(committed)
    assert want[7] == 0
    mac = np.asarray(eng.state.mac)
    for lane in range(N):
        for member in range(P):
            got = mac[lane, member].tolist()
            assert got[7] == 0, (lane, member, got)
            assert got == want, (lane, member, got, want)


def test_election_with_concurrent_follower_failure_and_traffic():
    """One round carrying everything at once: the leader AND a follower
    fail, an election is requested, and fresh commands arrive.  The new
    leader must seat (3/5 still up), accept the batch in the same round,
    and commit it."""
    eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=128,
                         max_step_cmds=K, donate=False)
    eng.step(jnp.full((N,), K, jnp.int32), jnp.ones((N, K, 1), jnp.int32))
    drain_committed(eng)
    before = eng.committed_total()
    term0 = np.asarray(eng.state.term).copy()
    for lane in range(N):
        eng.fail_member(lane, 0)   # the leader (fresh engine: slot 0)
        eng.fail_member(lane, 1)   # plus one follower
    elect = np.ones((N,), bool)
    eng.step(jnp.full((N,), K, jnp.int32), jnp.ones((N, K, 1), jnp.int32),
             elect_mask=jnp.asarray(elect))
    st = eng.state
    assert (np.asarray(st.term) == term0 + 1).all()
    assert (np.asarray(st.leader_slot) >= 2).all()
    drain_committed(eng)
    # the same-round batch landed on the new leader and committed
    # (+N: each lane's term-opening noop commits too)
    assert eng.committed_total() - before == N * K + N


def test_mesh_sharded_election_fuzz():
    """Fuzzed failure/election schedule under the 2-D (members, lanes)
    mesh: per-step invariants (terms and commits never regress, commit
    bounded by the leader log) and final convergence of all replicas.
    This is the sharded, multi-step version of the dryrun's election
    phase — elections race fresh traffic and follower failures across
    many rounds with the member axis laid out over devices."""
    from ra_tpu.parallel import lane_mesh, state_shardings
    from ra_tpu.engine.lockstep import _step

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = lane_mesh(devices[:8], member_axis=2)
    n_lanes, n_members, k = 16, 4, 4

    machine = CounterMachine()
    eng = LockstepEngine(machine, n_lanes, n_members, ring_capacity=128,
                         max_step_cmds=k, donate=False)
    shardings = state_shardings(mesh, eng.state)
    state = jax.device_put(eng.state, shardings)
    from jax.sharding import NamedSharding, PartitionSpec as Pspec
    lane_sh = NamedSharding(mesh, Pspec("lanes"))
    step = jax.jit(
        functools.partial(_step, machine=machine,
                          ring_capacity=128, apply_window=k + 2,
                          pipeline_window=4096, max_append_batch=128,
                          write_delay=1, ring_io="gather"),
        in_shardings=(shardings, lane_sh, lane_sh,
                      NamedSharding(mesh, Pspec("lanes", "members")),
                      lane_sh, lane_sh, lane_sh, lane_sh, lane_sh),
        out_shardings=(shardings,
                       {"appended_hi": lane_sh, "n_acc": lane_sh,
                        "n_app": lane_sh,
                        # one flag a round, the same on every device
                        "apply_member": NamedSharding(mesh, Pspec()),
                        # the read-plane aux block (ISSUE 20) is
                        # lane-major like everything else
                        "read_done": lane_sh, "read_shed": lane_sh,
                        "read_stale": lane_sh, "read_replies": lane_sh,
                        "read_watermark": lane_sh,
                        "read_served_lanes": lane_sh,
                        "read_shed_lanes": lane_sh,
                        "read_stale_lanes": lane_sh}))

    rng = np.random.default_rng(3)
    n_new = jnp.full((n_lanes,), k, jnp.int32)
    payloads = jnp.ones((n_lanes, k, 1), jnp.int32)
    confirm = jnp.zeros((n_lanes,), jnp.int32)
    query = jnp.zeros((n_lanes,), bool)
    n_read = jnp.zeros((n_lanes,), jnp.int32)
    read_q = jnp.zeros((n_lanes, eng.read_window, eng.query_width),
                       eng.query_dtype)
    fail_host = np.zeros((n_lanes, n_members), bool)

    prev = jax.device_get(
        {"term": state.term, "commit": state.commit,
         "total": state.total_committed})
    for step_i in range(15):
        # fail at most one member per lane (always a 3/4 quorum left);
        # heal with probability 1/2; elect lanes whose leader is down,
        # plus an occasional gratuitous leadership transfer
        leads = np.asarray(state.leader_slot)
        for lane in range(n_lanes):
            if fail_host[lane].any() and rng.random() < 0.5:
                fail_host[lane] = False
            elif not fail_host[lane].any() and rng.random() < 0.4:
                fail_host[lane, rng.integers(0, n_members)] = True
        elect = fail_host[np.arange(n_lanes), leads].copy()
        elect |= rng.random(n_lanes) < 0.1
        # revived members must be re-seeded before stepping (the host
        # snapshot-install contract of recover_member) — here members
        # only fail transiently within the mask, so active stays
        # governed by the mask itself
        state, _aux = step(state, n_new, payloads,
                           jnp.asarray(fail_host), jnp.asarray(elect),
                           confirm, query, n_read, read_q)
        cur = jax.device_get(
            {"term": state.term, "commit": state.commit,
             "total": state.total_committed})
        assert (cur["term"] >= prev["term"]).all(), step_i
        assert (cur["commit"] >= prev["commit"]).all(), step_i
        assert (cur["total"] >= prev["total"]).all(), step_i
        tails = np.asarray(state.last_index)
        leads = np.asarray(state.leader_slot)
        lane_idx = np.arange(n_lanes)
        assert (cur["commit"][lane_idx, leads] <=
                tails[lane_idx, leads]).all(), step_i
        prev = cur

    # heal in the only loss-free order (the recover_member contract):
    # 1) revive dead NON-leader members (snapshot install from the
    #    leader replica, live or frozen), 2) elect lanes whose leader is
    #    still down — the longest durable log wins, exactly what a
    #    restarting reference leader's log comparison gives — and only
    #    then 3) revive the deposed ex-leader slots from the new leader.
    eng.state = jax.device_get(state)
    eng.state = jax.tree.map(jnp.asarray, eng.state)
    was_down = np.asarray(~eng.state.active)
    leads = np.asarray(eng.state.leader_slot)
    for lane in range(n_lanes):
        for slot in range(n_members):
            if was_down[lane, slot] and slot != leads[lane]:
                eng.recover_member(lane, slot)
    act = np.asarray(eng.state.active)
    stalled = [lane for lane in range(n_lanes)
               if not act[lane, leads[lane]]]
    if stalled:
        eng.trigger_election(stalled)
    leads2 = np.asarray(eng.state.leader_slot)
    act2 = np.asarray(eng.state.active)
    for lane in stalled:
        assert act2[lane, leads2[lane]], (lane, "election failed")
        if not act2[lane, leads[lane]]:
            eng.recover_member(lane, int(leads[lane]))
    drain_committed(eng)
    st = eng.state
    mac = np.asarray(st.mac)
    app = np.asarray(st.applied)
    assert (np.asarray(st.total_committed) > 0).all()
    for lane in range(n_lanes):
        assert (mac[lane] == mac[lane, 0]).all(), (lane, mac[lane])
        assert (app[lane] == app[lane, 0]).all(), (lane, app[lane])
