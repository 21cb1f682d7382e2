"""Stage 5's lane path (ISSUE 34): a batch machine's fold runs once a
lane, on the lane's representative, and its result is handed to the
members that share the representative's interval.  It has to give, bit
for bit, what the per-member fold gives; a round in which some member
does not share takes the per-member fold and is counted; and a machine
whose state outweighs its ring never gets the lane path at all."""
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.machine import BodyCounterMachine
from harness import step_args
from ra_tpu.engine import LockstepEngine, open_engine
from ra_tpu.engine import lockstep
from ra_tpu.engine.lockstep import _step, _superstep
from ra_tpu.metrics import ENGINE_PIPELINE_FIELDS
from ra_tpu.models.counter import CounterMachine
from ra_tpu.models.jit_fifo import JitFifoMachine
from ra_tpu.models.jit_kv import JitKvMachine, JitRecordKvMachine
from ra_tpu.models.registers import RegisterMachine
from ra_tpu.models.stream import StreamMachine
from ra_tpu.telemetry import PhaseStats
from ra_tpu.wire.dedup import DedupCounterMachine

N, P, K = 6, 3, 4
A = K + 2
#: a ring of 128 entries outweighs every machine's state below (the
#: largest, the queue's, is 3 x 81 words a lane against 128 x 3)
R = 128
#: replication ships two entries a member a round: fewer than a round
#: can append (K) or apply (A), which is what lets one follower's log,
#: and so its commit, lag another's
MAX_APPEND = 2
BIG = 1 << 20


def _ids(rng, r):
    """[N, K] op ids that rise round by round, with resends of old ones."""
    fresh = np.broadcast_to(r * K + 1 + np.arange(K), (N, K))
    return np.where(rng.random((N, K)) < 0.2,
                    rng.integers(0, r * K + 2, (N, K)), fresh)


def _cols(*cols):
    return np.stack(cols, axis=-1).astype(np.int32)


def _body(rng, r):
    cmd = rng.integers(0, 1 << 20, (N, K, 64))
    cmd[..., 0] = rng.integers(-1, 9, (N, K))           # slot, some bad
    cmd[..., 1] = _ids(rng, r)
    cmd[..., 2] = rng.integers(-5, 6, (N, K))
    return cmd.astype(np.int32)


def _counter(rng, r):
    return rng.integers(-3, 8, (N, K, 1)).astype(np.int32)


def _kv(rng, r):        # noop, put, get, delete, cas; keys 0..7 and bad
    op = rng.choice(5, (N, K), p=[.1, .5, .1, .1, .2] if r % 3 else
                    [.2, .6, .1, .1, 0.])
    return _cols(op, rng.integers(-1, 9, (N, K)),
                 rng.integers(0, 6, (N, K)), rng.integers(-1, 6, (N, K)))


def _register(rng, r):  # noop, put, add, cas (some windows cas-free)
    op = rng.choice(4, (N, K), p=[.1, .4, .3, .2] if r % 3 else
                    [.1, .5, .4, 0.])
    return _cols(op, rng.integers(0, 8, (N, K)),
                 rng.integers(-4, 9, (N, K)), rng.integers(-4, 9, (N, K)))


def _stream(rng, r):    # noop, append, commit_cursor, truncate
    op = rng.choice(4, (N, K), p=[.1, .6, .2, .1] if r % 3 else
                    [.2, .8, 0., 0.])
    return _cols(op, rng.integers(-1, 9, (N, K)),
                 rng.integers(0, 4 * (r + 1), (N, K)))


def _fifo(rng, r):      # the whole vocabulary, enqueue and dequeue mostly
    p = np.array([1, 8, 4, 2, 1, 1, .3, 1, .3, .3, 2, .5])
    if r % 3 == 0:
        p[3:] = 0       # a window the vectorized fold takes
    op = rng.choice(12, (N, K), p=p / p.sum())
    return _cols(op, rng.integers(0, 4, (N, K)), rng.integers(0, 4, (N, K)))


def _dedup(rng, r):
    return _cols(rng.integers(-1, 9, (N, K)), _ids(rng, r),
                 rng.integers(-5, 6, (N, K)))


MACHINES = {
    "BodyCounterMachine": (lambda: BodyCounterMachine(slots=8), _body),
    "CounterMachine": (CounterMachine, _counter),
    "JitKvMachine": (lambda: JitKvMachine(n_keys=8), _kv),
    "RegisterMachine": (lambda: RegisterMachine(n_slots=8), _register),
    "StreamMachine": (lambda: StreamMachine(capacity=8, groups=4), _stream),
    "JitFifoMachine": (lambda: JitFifoMachine(
        capacity=16, checkout_slots=4, consumer_slots=2), _fifo),
    "DedupCounterMachine": (lambda: DedupCounterMachine(slots=8), _dedup),
}

#: (lane, slot) of the members a scenario fails; slot 0 leads every lane
VICTIMS = (np.array([1, 4]), np.array([2, 1]))
FAIL_AT, HOLD_TO, RECOVER_AT, ROUNDS = 3, 9, 9, 30


def _schedule(scenario, r):
    """(most commands a lane appends, the WAL's confirm horizon, fail
    the victims now, recover them before this round) of round ``r``."""
    fail = scenario != "level" and r == FAIL_AT
    if scenario != "lagging":
        # a member comes back in a round that appends nothing: its send
        # cursor is a round stale, and it draws level in the next
        back = scenario == "recovered" and r == RECOVER_AT
        return (0 if back else MAX_APPEND), BIG, fail, back
    # nothing is confirmed while the leaders append: the commit then
    # jumps by more than a round applies, and the victims come back, at
    # the leader's applied index, into lanes with entries still to
    # apply, which reach them two a round
    if r < FAIL_AT:
        return 2, BIG, False, False
    if r < HOLD_TO:
        return K, 0, fail, False
    return (0 if r < 24 else 2), BIG, False, r == RECOVER_AT


@functools.lru_cache(maxsize=None)
def _programs(name):
    """(engine, its first state, the step as the tree lowers it, the
    step with the per-member fold alone), both durable so that a test
    sets the confirm horizon a round."""
    eng = LockstepEngine(MACHINES[name][0](), N, P, ring_capacity=R,
                         max_step_cmds=K, max_append_batch=MAX_APPEND)
    assert lockstep._lane_fold_fits(eng.state.mac, eng.state.ring)
    args = step_args(eng)

    def compiled():     # a partial of its own each: jit keys traces on it
        return jax.jit(functools.partial(
            _step, durable=True, **eng._step_kwargs)).lower(*args).compile()

    lane = compiled()
    with mock.patch.object(lockstep, "_lane_fold_fits",
                           lambda mac, ring: False):
        member = compiled()
    return eng, eng.state, lane, member


def _uniform(pre, post):
    """Stage 5's predicate, from a round's states, in numpy."""
    active, applied0 = np.asarray(post.active), np.asarray(pre.applied)
    apply_to = np.minimum(np.asarray(post.commit), applied0 + A)
    base = np.where(active, applied0, 1 << 30).min(axis=-1)
    at_base = active & (applied0 == base[:, None])
    top = apply_to[np.arange(N), at_base.argmax(axis=-1)]
    return bool((~active | (at_base & (apply_to == top[:, None]))).all())


def _run(name, scenario, seed=0):
    """Drive both programs through a scenario on the same traffic;
    every round's state and aux are compared here.  Returns (the lane
    path's flags, what the predicate says of each round)."""
    eng, first, lane, member = _programs(name)
    rng = np.random.default_rng(seed)
    traffic = MACHINES[name][1]
    states = [first, first]
    fail = np.zeros((N, P), bool)
    flags, expect = [], []
    for r in range(ROUNDS):
        cap, confirm, fail_now, recover_now = _schedule(scenario, r)
        if fail_now:
            fail[VICTIMS] = True
        if recover_now:
            fail[VICTIMS] = False
            for i, st in enumerate(states):
                eng.state = st
                eng.recover_members(*VICTIMS)
                states[i] = eng.state
        rest = (jnp.asarray(rng.integers(0, cap + 1, N), jnp.int32),
                jnp.asarray(traffic(rng, r)), jnp.asarray(fail),
                jnp.zeros(N, bool), jnp.full(N, confirm, jnp.int32),
                jnp.zeros(N, bool), eng._zero_nread, eng._zero_readq)
        pre = states[1]
        (states[0], aux), (states[1], ref) = \
            lane(states[0], *rest), member(states[1], *rest)
        flags.append(int(aux.pop("apply_member")))
        assert int(ref.pop("apply_member")) == 1
        expect.append(0 if _uniform(pre, states[1]) else 1)
        got, want = jax.tree.leaves_with_path((states[0], aux)), \
            jax.tree.leaves((states[1], ref))
        for (path, a), b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (r, jax.tree_util.keystr(path))
    assert int(np.asarray(states[0].applied).max()) > 20   # it did apply
    return flags, expect


@pytest.mark.parametrize("scenario",
                         ["level", "frozen", "lagging", "recovered"])
@pytest.mark.parametrize("name", list(MACHINES))
def test_the_lane_fold_is_the_member_fold_bit_for_bit(name, scenario):
    flags, expect = _run(name, scenario)
    assert flags == expect
    if scenario == "lagging":
        # the victims catch up two entries a round: those rounds, and
        # no round before they came back or after they drew level
        assert flags[:RECOVER_AT] == [0] * RECOVER_AT
        assert sum(flags) >= 5 and flags[-3:] == [0, 0, 0]
    else:
        assert flags == [0] * ROUNDS


@pytest.mark.parametrize("name", list(MACHINES))
def test_a_fused_dispatch_is_its_rounds_one_by_one(name):
    """`_superstep` scans the same `_step`: its state and stacked aux
    are those of the rounds taken singly, flags included."""
    eng, first, lane, _member = _programs(name)
    rng = np.random.default_rng(7)
    k = 3
    n_new = rng.integers(0, K + 1, (k, N)).astype(np.int32)
    pay = np.stack([MACHINES[name][1](rng, r) for r in range(k)])
    fail = np.zeros((N, P), bool)
    fail[VICTIMS] = True
    zeros = jnp.zeros((k, N), bool)
    confirm = jnp.full(N, BIG, jnp.int32)
    fused = jax.jit(functools.partial(_superstep, durable=True,
                                      **eng._step_kwargs))
    st_f, aux_f = fused(first, n_new, pay, fail, zeros, confirm, zeros,
                        jnp.zeros((k, N), jnp.int32),
                        jnp.broadcast_to(eng._zero_readq,
                                         (k,) + eng._zero_readq.shape))
    st = first
    for j in range(k):
        st, aux = lane(st, jnp.asarray(n_new[j]), jnp.asarray(pay[j]),
                       jnp.asarray(fail), zeros[j], confirm, zeros[j],
                       eng._zero_nread, eng._zero_readq)
        for key, val in aux.items():
            assert np.array_equal(np.asarray(aux_f[key][j]),
                                  np.asarray(val)), (j, key)
    assert aux_f["apply_member"].shape == (k,)
    for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- the counter -------------------------------------------------------------

class _ScriptedWal:
    """The durability bridge's side of the engine, with the confirm
    horizon a test's to set: what makes a real engine's rounds lag
    deterministically."""

    def __init__(self):
        self.phases = PhaseStats()
        self.confirm_upto = np.zeros(N, np.int32)
        self.blocks = 0
        self.step_seq = 0

    def backpressure(self):
        pass

    def confirm_sample(self):
        return self.confirm_upto, (0,), 0.0

    def submit(self, aux):
        self.blocks += 1

    def submit_block(self, aux, k):
        self.blocks += k

    def drain_all(self):
        pass

    def batch_interval_ms(self):
        return 0.0

    def wal_overview(self):
        return {}


def _table_machine():
    """A machine whose state (3 x 65 words a lane) outweighs a ring of
    8 entries of 3 words: every round folds once a member."""
    return DedupCounterMachine(slots=64)


@pytest.mark.parametrize("fused", [False, True], ids=["step", "superstep"])
@pytest.mark.parametrize("scenario", ["level", "lagging"])
def test_the_counter_counts_the_rounds_that_lag(scenario, fused):
    eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=R,
                         max_step_cmds=K, max_append_batch=MAX_APPEND)
    assert eng.overview()["pipeline"]["apply_member_rounds"] == 0
    wal = _ScriptedWal()
    eng.attach_durability(wal)
    rng = np.random.default_rng(1)
    expect = 0
    for r in range(ROUNDS):
        cap, confirm, fail_now, recover_now = _schedule(scenario, r)
        if fail_now:
            for lane_, slot in zip(*VICTIMS):
                eng.fail_member(int(lane_), int(slot))
        if recover_now:
            eng.recover_members(*VICTIMS)
        wal.confirm_upto = np.full(N, confirm, np.int32)
        n_new = rng.integers(0, cap + 1, N).astype(np.int32)
        pay = _counter(rng, r)
        pre = jax.tree.map(np.asarray, eng.state)   # the step donates it
        if fused:
            eng.superstep(n_new[None], pay[None])
        else:
            eng.step(n_new, pay)
        expect += 0 if _uniform(pre, eng.state) else 1
    assert wal.blocks == ROUNDS
    pipe = eng.overview()["pipeline"]
    assert pipe["inner_steps"] == ROUNDS
    assert pipe["apply_member_rounds"] == expect
    assert (expect == 0) == (scenario == "level")
    assert not eng._apply_flags             # overview() counted them all


def test_the_counter_trails_by_no_more_than_the_dispatches_in_flight():
    eng = LockstepEngine(_table_machine(), N, P, ring_capacity=8, max_step_cmds=K)
    assert not lockstep._lane_fold_fits(eng.state.mac, eng.state.ring)
    for _ in range(5):
        eng.uniform_step(1)
    eng.block_until_ready()
    eng.uniform_step(1)
    # the sixth dispatch counted the five before it: nothing waited
    assert eng.pipeline_counters["apply_member_rounds"] >= 5
    assert eng.overview()["pipeline"]["apply_member_rounds"] == 6


def test_the_counter_is_a_pipeline_field_from_construction():
    assert "apply_member_rounds" in ENGINE_PIPELINE_FIELDS
    eng = LockstepEngine(CounterMachine(), 4, 3)
    assert eng.pipeline_counters["apply_member_rounds"] == 0
    assert eng.overview()["pipeline"]["apply_member_rounds"] == 0


@pytest.mark.parametrize("machine,ring,every_round",
                         [(CounterMachine, R, False), (_table_machine, 8, True)],
                         ids=["lane_path", "state_outweighs_ring"])
def test_the_counter_survives_the_replay_of_open_engine(tmp_path, machine,
                                                        ring, every_round):
    kw = dict(ring_capacity=ring, max_step_cmds=K)
    eng = open_engine(machine(), str(tmp_path), N, P, **kw)
    for _ in range(6):
        eng.uniform_step(2)
    eng._dur.flush_all()
    eng.close()
    eng = open_engine(machine(), str(tmp_path), N, P, **kw)
    try:
        pipe = eng.overview()["pipeline"]
        assert pipe["inner_steps"] > 0          # the replay's rounds
        assert pipe["apply_member_rounds"] == \
            (pipe["inner_steps"] if every_round else 0)
        eng.uniform_step(1)
        assert eng.overview()["pipeline"]["apply_member_rounds"] == \
            (pipe["inner_steps"] + 1 if every_round else 0)
    finally:
        eng.close()


# -- the shape rule ----------------------------------------------------------

def _eqns(jaxpr, under=""):
    """(primitive, name stack, eqn) of every equation, sub-jaxprs
    included; a sub-jaxpr's stack is relative, so its parent's leads."""
    for eqn in jaxpr.eqns:
        stack = under + "/" + str(eqn.source_info.name_stack)
        yield eqn.primitive.name, stack, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, stack)


def _shapes(jaxpr):
    return [tuple(v.aval.shape) for _p, _s, eqn in _eqns(jaxpr)
            for v in eqn.outvars if hasattr(v.aval, "shape")]


def _step_jaxpr(machine, **kw):
    eng = LockstepEngine(machine, N, P, max_step_cmds=K, **kw)
    closed = jax.make_jaxpr(functools.partial(
        _step, durable=True, **eng._step_kwargs))(*step_args(eng))
    return eng, closed.jaxpr


def test_a_table_larger_than_its_ring_keeps_the_member_fold_alone():
    machine = JitRecordKvMachine(records=64, fields=2, field_words=3)
    eng, jaxpr = _step_jaxpr(machine, ring_capacity=32, max_step_reads=2)
    table = eng.state.mac["rec"].shape
    assert table == (N, P, 64, 6)
    assert not lockstep._lane_fold_fits(eng.state.mac, eng.state.ring)
    assert not [s for prim, s, _e in _eqns(jaxpr)
                if prim == "cond" and "ra.s5_apply" in s]
    # the step holds the table as often as the machine's own fold and
    # query do, called as the step calls them, and no more often
    C, Kr = eng.payload_width, eng.read_window
    fold = jax.make_jaxpr(lambda c, m, s: machine.jit_apply_batch(
        {"index": jnp.zeros((N, P, A), jnp.int32),
         "term": jnp.zeros((N, 1, 1), jnp.int32)}, c, m, s))(
        jnp.zeros((N, P, A, C), jnp.int32), jnp.zeros((N, P, A), bool),
        eng.state.mac).jaxpr
    query = jax.make_jaxpr(machine.jit_query)(
        jnp.zeros((N, P, Kr, eng.query_width), jnp.int32),
        eng.state.mac).jaxpr
    assert _shapes(jaxpr).count(table) == \
        _shapes(fold).count(table) + _shapes(query).count(table)


def test_the_lane_branch_holds_no_operand_with_a_member_axis_and_a_window():
    eng, jaxpr = _step_jaxpr(BodyCounterMachine(slots=8), ring_capacity=R)
    C = eng.payload_width
    conds = [e for prim, s, e in _eqns(jaxpr)
             if prim == "cond" and s.endswith("ra.s5_apply")]
    assert len(conds) == 1
    member, lane = (b.jaxpr for b in conds[0].params["branches"])
    wide = {(N, P, A, C), (N, P, A), (N, P, A, A)}
    assert (N, P, A, C) in _shapes(member)      # the fold P times over
    assert not wide & set(_shapes(lane))
    assert (N, A, A) in _shapes(lane)           # the fold, once a lane
    # and nothing of those shapes is handed to the cond from outside it
    assert not wide & {tuple(v.aval.shape) for v in conds[0].invars
                       if hasattr(v.aval, "shape")} - {(N, P, A)}
