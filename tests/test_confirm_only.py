"""A confirm that misses the next dispatch is carried at that pump's
tail by ``ra_confirm``, stages 3 and 4 of the step alone: the program
against the step's own stages, a confirm-only run followed by a
superstep against the superstep alone, and the plane's tail rule on
one device, on the eight forced devices and under a read.

A late confirm is made certain without a clock: the dispatch after a
block's own is fed the horizon sampled before the block was submitted
(a sample may lag the WAL, never lead it), and its hand-off to the WAL
waits until every shard has fsynced everything submitted.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu import devicewatch, trace
from ra_tpu.engine import LockstepEngine, lockstep, open_engine
from ra_tpu.ingress import IngressPlane
from ra_tpu.models import CounterMachine, JitKvMachine

SPLIT = ("durable_wait", "confirm_carry", "commit_observe")
CONFIRMED = ("last_written", "match", "next_index", "commit",
             "total_committed")


# -- the program against the step's stages ----------------------------------

def _random_lanes(seed, n, p):
    """A state as a round leaves it (every active follower's log no
    longer than its leader's, ``last_written`` no longer than the log),
    with members down, followers truncated far behind, ``term_start``
    above the commit and lanes whose leader is down."""
    rng = np.random.default_rng(seed)
    leader = rng.integers(0, p, n)
    active = rng.random((n, p)) < 0.75
    voter = rng.random((n, p)) < 0.9
    lead_last = rng.integers(0, 60, n)
    last = rng.integers(0, 80, (n, p))
    follower = np.arange(p)[None, :] != leader[:, None]
    trunc = rng.random((n, p)) < 0.3
    last = np.where(active & follower, np.minimum(
        np.where(trunc, rng.integers(0, 5, (n, p)), last),
        lead_last[:, None]), last)
    last[np.arange(n), leader] = lead_last
    written = np.minimum(last, rng.integers(0, 80, (n, p)))
    commit = np.minimum(last, rng.integers(0, 40, (n, p)))
    match = np.minimum(last, rng.integers(0, 70, (n, p)))
    # no credit to ship with: the send cursor past the match
    nxt = match + 1 + rng.integers(0, 20, (n, p))
    term_start = rng.integers(0, 70, n)
    total = rng.integers(0, 1000, n)
    confirm = rng.integers(0, 80, n)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return dict(leader_slot=i32(leader), active=jnp.asarray(active),
                voter=jnp.asarray(voter), last_index=i32(last),
                last_written=i32(written), commit=i32(commit),
                match=i32(match), next_index=i32(nxt),
                term_start=i32(term_start), total_committed=i32(total)), \
        i32(confirm)


@pytest.mark.parametrize("seed, p", [(1, 3), (2, 5), (3, 5), (4, 3)])
def test_ra_confirm_is_stages_3_and_4_of_the_step(seed, p):
    """With no append, no election and no credit to replicate, stages
    0 to 2 leave the log as it is, so the step's stages 3 and 4 are all
    that moves what ``ra_confirm`` returns."""
    n = 64
    eng = LockstepEngine(CounterMachine(), n, p, ring_capacity=32,
                         max_step_cmds=4, pipeline_window=0)
    lanes, confirm = _random_lanes(seed, n, p)
    st = eng.state._replace(**lanes)
    step = jax.jit(functools.partial(lockstep._step, durable=True,
                                     **eng._step_kwargs))
    z = jnp.zeros((n,), jnp.int32)
    want, _aux = step(st, z, jnp.zeros((n, 4, 1), jnp.int32),
                      jnp.zeros((n, p), bool), jnp.zeros((n,), bool),
                      confirm, jnp.zeros((n,), bool), z,
                      jnp.zeros((n, 1, 1), jnp.int32))
    got = jax.jit(lockstep.ra_confirm)(
        st.last_index, st.active, st.voter, st.leader_slot, st.term_start,
        st.last_written, st.match, st.next_index, st.commit,
        st.total_committed, st.telem.stall_steps, confirm)
    for name, g in zip(CONFIRMED, got):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(getattr(want, name)), name)
    # the random states exercise what they claim to
    assert int(jnp.sum(got[3] != st.commit)) > 0
    assert bool(jnp.any(~st.active)) and bool(jnp.any(
        st.term_start > jnp.max(st.commit, axis=-1)))
    # the stall count resets where the commit moved, as a round's does
    np.testing.assert_array_equal(
        np.asarray(got[5]),
        np.where(np.asarray(got[4]) > np.asarray(st.total_committed), 0,
                 np.asarray(st.telem.stall_steps)))


def _appended(eng, rounds):
    """A durable engine's state after ``rounds`` rounds of appends that
    no confirm has reached, with one member of lane 1 down."""
    n, c = eng.n_lanes, eng.max_step_cmds
    sstep = jax.jit(functools.partial(lockstep._superstep, durable=True,
                                      **eng._step_kwargs))
    rng = np.random.default_rng(38)
    st = eng.state._replace(active=eng.state.active.at[1, 2].set(False))
    for _ in range(rounds):
        n_new = jnp.asarray(rng.integers(0, c + 1, (2, n)), jnp.int32)
        pay = jnp.asarray(rng.integers(1, 9, (2, n, c, 1)), jnp.int32)
        st, _aux = sstep(st, n_new, pay, jnp.zeros((n, 3), bool),
                         jnp.zeros((2, n), bool),
                         jnp.zeros((n,), jnp.int32),
                         jnp.zeros((2, n), bool),
                         jnp.zeros((2, n), jnp.int32),
                         jnp.zeros((2, n, 1, 1), jnp.int32))
    return st, sstep


@pytest.mark.parametrize("frac", [0.0, 0.5, 1.0])
def test_a_confirm_only_run_then_a_superstep_is_the_superstep_alone(frac):
    """The superstep after ``ra_confirm`` samples a horizon no lower,
    recomputes the same commit and applies the same entries: every leaf
    of the state equal, but the stall counter, which the confirm-only
    run resets and the round after it counts again."""
    eng = LockstepEngine(CounterMachine(), 32, 3, ring_capacity=64,
                         max_step_cmds=4)
    st, sstep = _appended(eng, 3)
    lead_last = np.asarray(jnp.take_along_axis(
        st.last_index, st.leader_slot[:, None], axis=-1)[:, 0])
    confirm = jnp.asarray((lead_last * frac).astype(np.int32))
    n = eng.n_lanes
    block = (jnp.full((2, n), 2, jnp.int32),
             jnp.full((2, n, 4, 1), 5, jnp.int32),
             jnp.zeros((n, 3), bool), jnp.zeros((2, n), bool), confirm,
             jnp.zeros((2, n), bool), jnp.zeros((2, n), jnp.int32),
             jnp.zeros((2, n, 1, 1), jnp.int32))
    alone, _a = sstep(st, *block)
    out = jax.jit(lockstep.ra_confirm)(
        st.last_index, st.active, st.voter, st.leader_slot, st.term_start,
        st.last_written, st.match, st.next_index, st.commit,
        st.total_committed, st.telem.stall_steps, confirm)
    mid = st._replace(**dict(zip(CONFIRMED, out[:5])),
                      telem=st.telem._replace(stall_steps=out[5]))
    if frac:
        assert int(jnp.sum(mid.total_committed - st.total_committed)) > 0
    after, _b = sstep(mid, *block)
    flat_a = jax.tree_util.tree_leaves_with_path(alone)
    flat_b = jax.tree_util.tree_leaves_with_path(after)
    for (path, a), (_p, b) in zip(flat_a, flat_b):
        if "stall_steps" in jax.tree_util.keystr(path):
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      jax.tree_util.keystr(path))


# -- the plane's tail ------------------------------------------------------

class _Late:
    """A durable plane whose next dispatch misses a block's confirm:
    ``hold()`` makes the dispatch after the next pump's block sample
    the horizon of before it, and that dispatch's hand-off to the WAL
    wait until everything submitted is fsynced."""

    def __init__(self, d, machine, lanes=16, mesh=False):
        shards, device_mesh = 2, None
        if mesh:
            from ra_tpu.parallel.mesh import lane_mesh, per_device_wal_shards
            device_mesh = lane_mesh(jax.devices(), member_axis=1)
            shards = per_device_wal_shards(device_mesh)
        self.eng = eng = open_engine(machine, str(d), lanes,
                                     wal_shards=shards, ring_capacity=64,
                                     max_step_cmds=4)
        if mesh:
            from ra_tpu.parallel.mesh import shard_engine_state
            shard_engine_state(eng, device_mesh)
        self.plane = IngressPlane(eng, superstep_k=2, window_s=0.0)
        # a readback is there when the device is done: on the chip the
        # tail finds it so, the CPU's copy may still be under way
        self.plane.driver._ready = \
            lambda e: bool(jax.block_until_ready(e[1:])) or True
        self.handles = self.plane.connect_bulk(4 * lanes, key="late")
        self._used = 0
        self.released = []
        self.plane.on_block_committed = self.released.append
        self.samples = {p: [] for p in SPLIT + ("block_e2e",)}
        real = eng.phases.note

        def note(phase, dt_s):
            if phase in self.samples:
                self.samples[phase].append(dt_s)
            real(phase, dt_s)

        eng.phases.note = note

    def wave(self, payloads):
        """Submit a row a session not used before; their handles."""
        h = self.handles[self._used:self._used + len(payloads)]
        self._used += len(h)
        st = self.plane.submit(h, self.plane.directory.next_seqnos(h),
                               np.asarray(payloads, np.int32))
        assert (st <= 1).all()
        return h

    def hold(self):
        """The next dispatch but one samples the horizon as it stands
        now; its WAL hand-off returns once all is durable."""
        dur, counters = self.eng._dur, self.eng.pipeline_counters
        stale = dur.confirm_sample()
        real_sample, real_submit = dur.confirm_sample, dur.submit_block
        target = counters["dispatches"] + 2

        def sample():
            if counters["dispatches"] == target:
                return stale[0], stale[1], time.monotonic()
            return real_sample()

        def submit_block(aux, k):
            real_submit(aux, k)
            if counters["dispatches"] == target:
                dur.confirm_sample = real_sample
                dur.submit_block = real_submit
                dur.flush_all()

        dur.confirm_sample = sample
        dur.submit_block = submit_block

    def acked(self):
        return set(np.concatenate(self.released).tolist()) \
            if self.released else set()

    def close(self):
        self.plane.settle()
        self.eng._dur.flush_all()
        self.eng.close()


def _late_block_is_carried_at_the_next_pumps_tail(s):
    eng, plane = s.eng, s.plane
    s.wave(np.arange(1, 17)[:, None])
    plane.settle()
    counters = eng.pipeline_counters
    runs0, late0, blocks0 = (counters[k] for k in (
        "confirm_only_runs", "confirm_late_blocks", "confirm_only_blocks"))
    by_fun = devicewatch.WATCH.xla_compiles_by_fun
    programs = ("jit(ra_confirm)", "jit(ra_superstep)", "jit(ra_watermarks)")
    compiles = [by_fun[p] for p in programs]
    assert compiles[0] >= 1      # at the plane's open, in this process
    recompiles = devicewatch.WATCH.counters["recompiles"]
    s.hold()
    first = set(s.wave(np.full((16, 1), 3)).tolist())
    assert plane.pump(force=True)
    assert not first & s.acked()
    second = set(s.wave(np.full((8, 1), 5)).tolist())
    with_trace = trace.Tracer()
    trace.set_tracer(with_trace)
    try:
        assert plane.pump(force=True)
    finally:
        trace.set_tracer(None)
    # the dispatch of this pump missed the first block's confirm: the
    # pump's tail carried it, and everything durable with it
    assert counters["confirm_only_runs"] == runs0 + 1
    assert first | second <= s.acked()
    assert counters["confirm_only_blocks"] == blocks0 + 2
    assert counters["confirm_late_blocks"] == late0 + 1
    assert eng.overview()["pipeline"]["confirm_only_blocks"] == blocks0 + 2
    assert eng.pump_split["confirm_only"] > 0
    names = [e["name"] for e in with_trace.events()]
    assert names.count("ra.pump.confirm_only") == 1
    # its commit is the WAL's: nothing above the fsynced horizon
    lead_commit = np.asarray(eng.state.commit).max(axis=1)
    assert (lead_commit <= eng._dur.confirm_upto).all()
    assert (plane.driver.last_committed ==
            np.asarray(eng.state.total_committed)).all()
    # the next dispatch finds the signature the last one left (the
    # program's outputs are placed as the leaves they replace), and
    # nothing compiled the confirm-only program inside the window
    s.wave(np.full((16, 1), 7))
    assert plane.pump(force=True)
    assert [by_fun[p] for p in programs] == compiles
    assert devicewatch.WATCH.counters["recompiles"] == recompiles
    s.close()
    b = s.samples["block_e2e"]
    parts = [s.samples[p] for p in SPLIT]
    assert b and all(len(p) == len(b) for p in parts)
    for i, whole in enumerate(b):
        assert all(p[i] >= 0 for p in parts), i
        assert sum(p[i] for p in parts) == pytest.approx(whole, abs=1e-9)
    assert int(np.asarray(eng.state.total_committed).sum()) == 56


def test_a_late_confirm_is_carried_at_the_next_pumps_tail(tmp_path):
    _late_block_is_carried_at_the_next_pumps_tail(
        _Late(tmp_path, CounterMachine()))


def test_a_late_confirm_is_carried_at_the_next_pumps_tail_mesh8(tmp_path):
    if len(jax.devices()) < 8:
        pytest.skip("needs the eight forced host devices")
    _late_block_is_carried_at_the_next_pumps_tail(
        _Late(tmp_path, CounterMachine(), mesh=True))


def test_a_confirm_in_time_runs_no_program(tmp_path):
    """Each pump waits for the WAL: the next dispatch carries every
    block and the tail runs nothing."""
    s = _Late(tmp_path, CounterMachine())
    for v in range(1, 6):
        s.wave(np.full((8, 1), v))
        assert s.plane.pump(force=True)
        s.eng._dur.flush_all()
    s.close()
    c = s.eng.pipeline_counters
    assert c["confirm_only_runs"] == c["confirm_only_blocks"] == 0
    assert len(s.samples["block_e2e"]) == 5


def test_no_program_while_a_failure_waits_for_its_dispatch(tmp_path):
    """Only a full step changes ``active``: a member failed since the
    last dispatch holds the tail back, the next dispatch carries."""
    s = _Late(tmp_path, CounterMachine())
    s.wave(np.arange(1, 17)[:, None])
    s.plane.settle()
    s.hold()
    s.wave(np.full((16, 1), 3))
    assert s.plane.pump(force=True)
    s.wave(np.full((8, 1), 5))
    real = s.eng.superstep

    def superstep(*a, **kw):
        out = real(*a, **kw)
        s.eng.fail_member(3, 1)
        return out

    s.eng.superstep = superstep
    assert s.plane.pump(force=True)
    s.eng.superstep = real
    assert s.eng.pipeline_counters["confirm_only_runs"] == 0
    assert not s.eng.confirm_ready()
    s.close()


def test_a_read_after_an_ack_the_program_released_sees_the_write(tmp_path):
    """On the KV machine: a put released by the confirm-only program,
    then a get of its key sent after that ACK, returns the put's
    value (the read registers at a commit that covers the put and
    waits for it to be applied)."""
    s = _Late(tmp_path, JitKvMachine(n_keys=8), lanes=8)
    plane, eng = s.plane, s.eng
    replies = []
    plane.on_reads_done = lambda h, q, st, wm, pay: replies.append(
        (h.copy(), st.copy(), pay.copy()))
    lanes = plane.directory.lane[s.handles]
    s.wave([[1, 2, 10, 0]] * 8)
    plane.settle()
    s.hold()
    first = s.wave([[1, 2, 100 + i, 0] for i in range(8)])
    assert plane.pump(force=True)
    s.wave([[1, 5, 7, 0]] * 4)
    assert plane.pump(force=True)
    assert eng.pipeline_counters["confirm_only_runs"] == 1
    assert set(first.tolist()) <= s.acked()
    # the value each lane's last acknowledged put of key 2 left
    want = {}
    for i, h in enumerate(first):
        want[int(lanes[h])] = 100 + i
    readers = np.array([s.handles[np.flatnonzero(lanes[s.handles] == ln)[0]]
                        for ln in sorted(want)])
    st = plane.submit_reads(readers, np.arange(len(readers)),
                            np.tile([[1, 2]], (len(readers), 1)))
    assert (st <= 1).all()
    for _ in range(50):
        plane.pump(force=True)
        if sum(len(r[0]) for r in replies) >= len(readers):
            break
    got = {int(lanes[h]): (int(code), list(p))
           for hs, sts, ps in replies for h, code, p in zip(hs, sts, ps)}
    assert sorted(got) == sorted(want)
    for ln, (code, pay) in got.items():
        assert code == 0 and pay == [1, want[ln]], (ln, code, pay)
    s.close()


def test_the_new_counters_and_span_are_registered_and_documented():
    import os

    from ra_tpu import metrics
    from ra_tpu.blackbox import EVENT_REGISTRY
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")).read()
    for name in ("confirm_only_runs", "confirm_only_blocks"):
        assert name in metrics.ENGINE_PIPELINE_FIELDS
        assert f"| `{name}` |" in doc
    assert "ra.pump.confirm_only" in EVENT_REGISTRY
    assert "`ra.pump.confirm_only`" in doc
    assert "confirm_only" in lockstep.PUMP_SPLIT
    assert "`confirm_only`" in doc
