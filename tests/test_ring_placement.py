"""A fused dispatch writes the ring once: its rounds leave the
ring alone, each round's apply window reads this dispatch's own rows
beside it (`lockstep._dispatch_window`), and `lockstep._ring_write`
places what survives after the last round.  Held here against a plain
reference, rounds that each write the ring with a generic scatter before
their window reads it, over seeded schedules: state (the ring included)
and every aux leaf, bit for bit.  And the counter of the window rows a
dispatch took from its own rows, ``window_dispatch_rows``."""
import functools
import zlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu.core.machine import JitMachine
from ra_tpu.engine import LockstepEngine, lockstep, open_engine
from ra_tpu.engine.lockstep import _step, _superstep
from ra_tpu.models import CounterMachine

N, P, KC = 8, 3, 4
#: a ring a few rounds long: the schedules wrap it and run out of room
R = 2 * KC + 1
BIG = 1 << 20
#: rounds of a seeded run, whatever its dispatches' K
ROUNDS = 24


class _HashMachine(JitMachine):
    """Six-word commands folded in order into a wrapping polynomial hash
    of every word and the entry's index: a window row read from the
    wrong slot, or a row of the wrong round, changes the state.  Six
    words make the chip's placement take passes that cut across rounds
    (4 rounds x 4 rows = 16 columns, 3 passes of 6)."""

    command_spec = ("int32", (6,))
    reply_spec = ("int32", ())
    version = 0

    def jit_init(self, n_lanes: int):
        return jnp.zeros((n_lanes,), jnp.int32)

    def jit_apply(self, meta, command, state):
        w = jnp.asarray([3, 5, 7, 11, 13, 17], jnp.int32)
        h = state * 31 + jnp.sum(command * w, axis=-1,
                                 dtype=jnp.int32) + meta["index"]
        return h, h


MACHINES = {"counter": CounterMachine, "hash": _HashMachine}
#: (durable, write_delay) of each mode
MODES = {"durable": (True, 0), "write_delay_0": (False, 0),
         "write_delay_1": (False, 1)}


def _plain_write(ring, rows, tail, n_acc, elect_ok, *, impl):
    """One round's append (``rows`` [N, Kc, C], marks [1, N]) with a
    generic scatter: entry tail+1+p at slot (tail+p) % R, rows p <
    n_acc, zeros at p == n_acc on a won election."""
    n, r, c = ring.shape
    tail, n_acc, elect_ok = tail[0], n_acc[0], elect_ok[0]
    p = jnp.arange(rows.shape[1] + 1)[None, :]
    noop = p == n_acc[:, None]
    vals = jnp.concatenate([rows, jnp.zeros((n, 1, c), ring.dtype)], 1)
    vals = jnp.where(noop[..., None], 0, vals)
    keep = (p < n_acc[:, None]) | (noop & elect_ok[:, None])
    slot = jnp.where(keep, (tail[:, None] + p) % r, r)      # r: dropped
    return ring.at[jnp.arange(n)[:, None], slot].set(vals, mode="drop")


def _plain_window(window, idx_lane, d, *, impl):
    """The window as a round that wrote the ring first reads it."""
    ring = _plain_write(d.ring, d.rows, d.tail, d.n_acc, d.elect_ok,
                        impl=impl)
    slot = (idx_lane - 1) % ring.shape[1]
    got = jnp.take_along_axis(
        ring, jnp.broadcast_to(slot[..., None], slot.shape +
                               ring.shape[-1:]), axis=1)
    return got, jnp.zeros(idx_lane.shape, bool)


def _engine(machine, mode, impl, **kw):
    durable, delay = MODES[mode]
    return LockstepEngine(MACHINES[machine](), N, P, ring_capacity=R,
                          max_step_cmds=KC, max_append_batch=2,
                          write_delay=delay, ring_io=impl, **kw)


@functools.lru_cache(maxsize=None)
def _programs(machine, mode, impl, k):
    """(engine, the fused dispatch of k rounds, the plain reference
    round), both compiled for the mode."""
    eng = _engine(machine, mode, impl)
    durable = MODES[mode][0]
    fused = jax.jit(functools.partial(_superstep, durable=durable,
                                      **eng._step_kwargs))
    args = (eng.state, jnp.zeros(N, jnp.int32),
            jnp.zeros((N, KC, eng.payload_width), jnp.int32),
            eng._zero_fail, eng._zero_elect, eng._zero_confirm,
            eng._zero_elect, eng._zero_nread, eng._zero_readq)
    with mock.patch.object(lockstep, "_ring_write", _plain_write), \
            mock.patch.object(lockstep, "_dispatch_window", _plain_window):
        plain = jax.jit(functools.partial(
            _step, durable=durable, **eng._step_kwargs)).lower(
                *args).compile()
    return eng, fused, plain


def _payloads(rng, machine, k):
    if machine == "counter":        # every bit of a word reaches the state
        return rng.integers(-2**31, 2**31, (k, N, KC, 1),
                            dtype=np.int64).astype(np.int32)
    return rng.integers(-1000, 1000, (k, N, KC, 6)).astype(np.int32)


def _schedule(rng, machine, mode, k, state, fail):
    """One dispatch's inputs: commands, elections in any round, now and
    then a failed member, and in durable mode a confirm horizon that
    covers everything or stops short of the leaders' tails."""
    if rng.random() < 0.3:
        fail[rng.integers(0, N), rng.integers(0, P)] = True
    tail = np.asarray(state.last_index)[np.arange(N),
                                        np.asarray(state.leader_slot)]
    confirm = np.full(N, BIG, np.int32)
    if mode == "durable":
        short = rng.random(N) < 0.5
        confirm[short] = np.maximum(tail[short] - rng.integers(0, 4, N)[
            short], 0)
    return (rng.integers(0, KC + 1, (k, N)).astype(np.int32),
            _payloads(rng, machine, k), fail.copy(),
            rng.random((k, N)) < 0.2, confirm)


def _zeros(eng, k):
    return (jnp.zeros((k, N), bool), jnp.zeros((k, N), jnp.int32),
            jnp.broadcast_to(eng._zero_readq, (k,) + eng._zero_readq.shape))


@pytest.mark.parametrize("impl", ["gather", "onehot"])
@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("machine", list(MACHINES))
def test_a_dispatch_places_its_rows_as_rounds_that_wrote_them(
        machine, mode, k, impl):
    """Seeded dispatches with elections mid-dispatch (a new leader
    truncates and overwrites what an earlier round of the same dispatch
    appended), failed members, a ring that wraps and runs out of room,
    and windows that apply rows of their own dispatch: the fused
    dispatch's state and stacked aux are the plain rounds', bit for
    bit."""
    eng, fused, plain = _programs(machine, mode, impl, k)
    rng = np.random.default_rng(zlib.crc32(f"{machine}{mode}{k}{impl}"
                                           .encode()))
    st_f = st_r = eng.state
    fail = np.zeros((N, P), bool)
    query, n_read, read_q = _zeros(eng, k)
    seen = {"taken": 0, "clipped": 0, "appended": 0, "truncated": 0}
    for d in range(ROUNDS // k):
        n_new, pay, fail_d, elect, confirm = _schedule(
            rng, machine, mode, k, st_r, fail)
        st_f, aux_f = fused(st_f, n_new, pay, fail_d, elect, confirm,
                            query, n_read, read_q)
        hi_before = None
        for j in range(k):
            st_r, aux = plain(st_r, n_new[j], pay[j], fail_d, elect[j],
                              confirm, query[j], n_read[j], read_q[j])
            for key, val in aux.items():
                assert np.array_equal(np.asarray(aux_f[key][j]),
                                      np.asarray(val)), (d, j, key)
            n_acc, n_app = np.asarray(aux["n_acc"]), np.asarray(aux["n_app"])
            hi = np.asarray(aux["appended_hi"])
            # some rows taken, not all: the ring's headroom
            seen["clipped"] += int(((n_acc > 0) & (n_acc < n_new[j])).sum())
            seen["appended"] += int(n_app.sum())
            if hi_before is not None:
                # an election that cut below what this dispatch appended
                seen["truncated"] += int(((n_app > n_acc) &
                                          (hi - n_app < hi_before)).sum())
            hi_before = hi
        seen["taken"] += int(np.asarray(aux_f["window_dispatch_rows"]).sum())
        for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st_r)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), d
    assert seen["appended"] > 2 * R and seen["clipped"] > 0, seen
    if mode != "write_delay_1" or k > 1:
        assert seen["taken"] > 0, seen
    if mode != "write_delay_0" and k > 1:
        assert seen["truncated"] > 0, seen


@pytest.mark.parametrize("impl", ["gather", "onehot"])
def test_a_sharded_dispatch_places_its_rows_as_rounds_that_wrote_them(impl):
    """The same on state sharded over four forced CPU devices, through
    the engine's own superstep (donating), against the plain rounds on
    one device."""
    from ra_tpu.parallel.mesh import lane_mesh, shard_engine_state
    if len(jax.devices()) < 4:
        pytest.skip("needs four forced CPU devices")
    k = 4
    _eng, _fused, plain = _programs("hash", "write_delay_1", impl, k)
    eng = _engine("hash", "write_delay_1", impl)
    shard_engine_state(eng, lane_mesh(jax.devices()[:4], member_axis=1))
    st_r = _eng.state
    rng = np.random.default_rng(44)
    fail = np.zeros((N, P), bool)
    query, n_read, read_q = _zeros(eng, k)
    for d in range(ROUNDS // k):
        n_new, pay, fail_d, elect, confirm = _schedule(
            rng, "hash", "write_delay_1", k, st_r, fail)
        for lane, slot in zip(*np.nonzero(fail_d & ~eng._fail_host)):
            eng.fail_member(int(lane), int(slot))
        eng.superstep(n_new, pay, elect_blk=elect)
        for j in range(k):
            st_r, _aux = plain(st_r, n_new[j], pay[j], fail_d, elect[j],
                               confirm, query[j], n_read[j], read_q[j])
        for a, b in zip(jax.tree.leaves(eng.state), jax.tree.leaves(st_r)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), d
    assert len(eng.state.ring.sharding.device_set) == 4


# -- the counter -------------------------------------------------------------

def test_window_dispatch_rows_counts_rows_a_dispatch_applied_of_its_own():
    """A non-durable engine with no write delay commits and applies rows
    in the dispatch that appended them: its windows take them from the
    dispatch's rows, and the counter sums the aux leaf."""
    eng = _engine("counter", "write_delay_0", "gather")
    rng = np.random.default_rng(3)
    total = 0
    for _ in range(3):
        aux = eng.superstep(np.full((4, N), 2, np.int32),
                            _payloads(rng, "counter", 4))
        assert aux["window_dispatch_rows"].shape == (4,)
        total += int(np.asarray(aux["window_dispatch_rows"]).sum())
    assert total > 0
    assert eng.overview()["pipeline"]["window_dispatch_rows"] == total
    # a single step has no such leaf and counts nothing
    eng.step(np.full(N, 2, np.int32), np.ones((N, KC, 1), np.int32))
    assert eng.overview()["pipeline"]["window_dispatch_rows"] == total


def test_window_dispatch_rows_reads_0_on_the_durable_served_path(tmp_path):
    """Served traffic on a durable engine: every confirm horizon is
    sampled from WAL rows of earlier dispatches, so no window applies a
    row of its own dispatch, while commits and applies go on."""
    from ra_tpu.ingress import IngressPlane
    from ra_tpu.wire import DedupCounterMachine, LoopbackFleet, WireListener

    eng = open_engine(DedupCounterMachine(slots=64), str(tmp_path / "wal"),
                      16, wal_shards=2, ring_capacity=256, max_step_cmds=8)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0,
                         soft_credit=1 << 20, hard_credit=1 << 20)
    lst = WireListener(plane, port=None, max_conns=64, ring_bytes=4096)
    fleet = LoopbackFleet(lst, 32, key="placement", seed=0)
    try:
        for _ in range(12):
            fleet.new_ops(np.arange(32), np.full(32, 3, np.int32))
            fleet.send_queued()
            lst.sweep()
            fleet.collect()
            assert plane.pump(force=True)
            fleet.collect()
        plane.settle()
        pipe = eng.overview()["pipeline"]
        assert pipe["superstep_dispatches"] >= 12
        assert int(np.asarray(eng.state.applied).max()) > 0
        assert pipe["window_dispatch_rows"] == 0
    finally:
        lst.close()
        eng.close()
