"""Lockstep lane-engine tests: batched commit/apply correctness, failure +
election behavior, ring backpressure, write-delay (async WAL) mode."""
import numpy as np
import pytest

from ra_tpu.engine import LockstepEngine
from ra_tpu.models import CounterMachine


def mk(n_lanes=8, n_members=3, **kw):
    return LockstepEngine(CounterMachine(), n_lanes, n_members, **kw)


def test_commands_commit_and_apply_all_members():
    e = mk()
    for _ in range(5):
        e.uniform_step(4, payload_value=2)
    e.uniform_step(0)  # let the last confirms settle
    mac = e.machine_states()
    # every lane committed 20 commands of +2 on every member
    assert mac.shape == (8, 3)
    assert (mac == 40).all()
    assert e.committed_per_lane().min() >= 20


def test_commit_requires_majority():
    e = mk(n_lanes=4, n_members=3)
    e.uniform_step(1)
    # kill both followers of lane 0: no quorum beyond what's committed
    e.fail_member(0, 1)
    e.fail_member(0, 2)
    before = e.committed_per_lane()[0]
    for _ in range(3):
        e.uniform_step(1)
    after = e.committed_per_lane()
    assert after[0] == before  # no quorum -> commit index frozen
    assert (after[1:] >= before + 3).all()  # healthy lanes keep committing


def test_one_follower_down_still_commits():
    e = mk(n_lanes=4, n_members=3)
    e.fail_member(2, 1)
    for _ in range(4):
        e.uniform_step(2, payload_value=3)
    e.uniform_step(0)
    mac = e.machine_states()
    # lane 2 still commits via leader+follower2 (majority of 3)
    assert mac[2, 0] == 8 * 3
    assert mac[2, 2] == 8 * 3
    # the dead member applied nothing new
    assert mac[2, 1] < 8 * 3


def test_election_rotates_leader_and_term():
    e = mk(n_lanes=4, n_members=3)
    e.uniform_step(3)
    assert e.overview(1)["leader_slot"] == 0
    e.fail_member(1, 0)  # kill lane 1's leader
    e.trigger_election([1])
    o = e.overview(1)
    assert o["term"] == 2
    assert o["leader_slot"] in (1, 2)
    # lane 1 keeps committing under the new leader
    before = e.committed_per_lane()[1]
    for _ in range(3):
        e.uniform_step(2)
    e.uniform_step(0)
    assert e.committed_per_lane()[1] > before
    # untouched lane is unaffected
    assert e.overview(0)["term"] == 1


def test_write_delay_models_async_wal():
    e = mk(n_lanes=2, write_delay=1)
    e.uniform_step(5)
    # step 1: appended but nothing confirmed -> no commit
    assert e.committed_per_lane().max() == 0
    e.uniform_step(0)
    # step 2: previous tail confirmed -> committed
    assert e.committed_per_lane().min() == 5


def test_ring_backpressure_drops_excess_cleanly():
    # tiny ring: with apply keeping up the ring never overflows, but a
    # burst beyond headroom must be truncated, not corrupt state
    e = mk(n_lanes=2, ring_capacity=32, max_step_cmds=16)
    for _ in range(10):
        e.uniform_step(16)
    e.uniform_step(0)
    mac = e.machine_states()
    commits = e.committed_per_lane()
    # applied value == committed count (each +1): no loss, no duplication
    assert (mac[:, 0] == commits).all()


def test_recovery_past_ring_horizon_installs_snapshot():
    """A member that was down while the ring recycled its unapplied range
    must come back via snapshot-install (copy from leader), not by applying
    recycled slots — distinct payloads catch silent divergence."""
    import jax.numpy as jnp
    e = mk(n_lanes=1, n_members=3, ring_capacity=32, max_step_cmds=8)
    e.fail_member(0, 1)
    for i in range(20):  # 160 entries >> ring 32, varying payloads
        e.step(jnp.full((1,), 8, jnp.int32),
               jnp.full((1, 8, 1), i + 1, jnp.int32))
    e.recover_member(0, 1)
    for _ in range(3):
        e.uniform_step(0)
    mac = e.machine_states()
    assert mac[0, 1] == mac[0, 0] == mac[0, 2], mac


def test_large_lane_count_smoke():
    e = mk(n_lanes=512, n_members=5)
    for _ in range(3):
        e.uniform_step(8)
    e.uniform_step(0)
    assert e.committed_per_lane().min() >= 24
    assert (e.machine_states()[:, 0] == 24).all()


def test_membership_add_promote_remove_quorum():
    """Per-lane membership: a removed voter leaves the quorum
    denominator, a joined nonvoter does not count until promoted, and a
    promoted member does (ra_server.erl:3218-3293 on the lane engine)."""
    import jax.numpy as jnp
    import numpy as np
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    N, P, K = 4, 5, 4
    eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=128,
                         max_step_cmds=K, donate=False)
    n_new = jnp.full((N,), K, jnp.int32)
    payloads = jnp.ones((N, K, 1), jnp.int32)
    zero = jnp.zeros((N,), jnp.int32)
    zpay = jnp.zeros((N, K, 1), jnp.int32)

    def drain():
        for _ in range(3):
            eng.step(zero, zpay)
        eng.block_until_ready()

    eng.step(n_new, payloads)
    drain()
    base = eng.committed_per_lane()[0]
    assert base > 0

    # remove two voters from lane 0: 3 voters remain -> quorum 2 holds
    eng.remove_member(0, 3)
    eng.remove_member(0, 4)
    eng.step(n_new, payloads)
    drain()
    after_remove = eng.committed_per_lane()[0]
    assert after_remove > base

    # fail one of the remaining three: 2 of 3 active -> still commits
    eng.fail_member(0, 2)
    eng.step(n_new, payloads)
    drain()
    after_fail = eng.committed_per_lane()[0]
    assert after_fail > after_remove

    # fail another: 1 of 3 voters active -> lane 0 stalls, others advance
    eng.fail_member(0, 1)
    before_stall = eng.committed_per_lane().copy()
    eng.step(n_new, payloads)
    drain()
    now = eng.committed_per_lane()
    assert now[0] == before_stall[0], "minority lane must not commit"
    assert now[1] > before_stall[1]

    # dead members stay in the quorum denominator until REMOVED (a
    # leader that lost its majority must not commit); removing one dead
    # voter leaves voters {0,1} with only slot 0 alive -> still stalled
    eng.remove_member(0, 2)
    eng.step(n_new, payloads)
    drain()
    assert eng.committed_per_lane()[0] == before_stall[0]
    # a joining NONVOTER must not restore quorum...
    eng.add_member(0, 3, voter=False)
    eng.step(n_new, payloads)
    drain()
    assert eng.committed_per_lane()[0] == before_stall[0]
    # ...but promoting it does: voters {0,1,3}, alive {0,3} = quorum 2
    eng.promote_member(0, 3)
    eng.step(n_new, payloads)
    drain()
    assert eng.committed_per_lane()[0] > before_stall[0]
    # machine state on the joined member matches the leader's replica
    mac = np.asarray(eng.state.mac)
    leader = int(np.asarray(eng.state.leader_slot)[0])
    assert mac[0, 3] == mac[0, leader]


def test_engine_save_restore_roundtrip(tmp_path):
    """Checkpoint/resume for the lane engine: a fresh engine restored
    from a saved snapshot continues committing from the same state."""
    import jax.numpy as jnp
    import numpy as np
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    N, K = 8, 4
    eng = LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                         max_step_cmds=K, donate=False)
    n_new = jnp.full((N,), K, jnp.int32)
    pay = jnp.ones((N, K, 1), jnp.int32)
    for _ in range(5):
        eng.step(n_new, pay)
    eng.block_until_ready()
    committed = eng.committed_total()
    mac_before = np.asarray(eng.state.mac).copy()
    path = str(tmp_path / "lanes.npz")
    eng.save(path)

    eng2 = LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                          max_step_cmds=K, donate=False)
    eng2.restore(path)
    assert eng2.committed_total() == committed
    assert (np.asarray(eng2.state.mac) == mac_before).all()
    # resumed engine keeps committing
    for _ in range(3):
        eng2.step(n_new, pay)
    eng2.block_until_ready()
    assert eng2.committed_total() > committed
    # geometry mismatch is refused
    import pytest
    bad = LockstepEngine(CounterMachine(), N + 1, 3, ring_capacity=64,
                         max_step_cmds=K, donate=False)
    with pytest.raises(ValueError):
        bad.restore(path)


def test_engine_restore_pre_telemetry_checkpoint(tmp_path):
    """An archive written before LaneState grew the telem pytree (the
    PR5-era index-flattened format) restores with zero-filled
    telemetry: a durable dir must never be stranded behind a health-
    counter format bump."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.engine.lockstep import LaneState, LaneTelemetry
    from ra_tpu.models import CounterMachine

    N, K = 8, 4
    eng = LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                         max_step_cmds=K, donate=False)
    n_new = jnp.full((N,), K, jnp.int32)
    pay = jnp.ones((N, K, 1), jnp.int32)
    for _ in range(5):
        eng.step(n_new, pay)
    eng.block_until_ready()
    path = str(tmp_path / "lanes.npz")
    eng.save(path)

    # rewrite the archive exactly as the pre-telemetry save wrote it:
    # index-flattened a{i} keys (the pre-ISSUE-15 positional format),
    # with the telem leaves dropped and the index gap closed
    n_tel = len(LaneTelemetry._fields)
    tel_at = len(jax.tree.flatten(
        tuple(eng.state[:LaneState._fields.index("telem")]))[0])
    with np.load(path) as z:
        meta = z["__meta__"]
        arrays = []
        for name in LaneState._fields:
            n_leaves = len(jax.tree.flatten(
                getattr(eng.state, name))[0])
            arrays += [z[f"{name}:{j}"] for j in range(n_leaves)]
    legacy = arrays[:tel_at] + arrays[tel_at + n_tel:]
    np.savez(path, __meta__=meta,
             **{f"a{i}": a for i, a in enumerate(legacy)})

    eng2 = LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                          max_step_cmds=K, donate=False)
    eng2.restore(path)
    assert eng2.committed_total() == eng.committed_total()
    assert (np.asarray(eng2.state.mac) == np.asarray(eng.state.mac)).all()
    # telemetry restarts from zero and keeps accumulating
    assert int(np.asarray(eng2.state.telem.steps).sum()) == 0
    eng2.step(n_new, pay)
    eng2.block_until_ready()
    assert int(np.asarray(eng2.state.telem.steps).sum()) == N


def test_engine_restore_schema_defaults_cover_missing_fields(tmp_path):
    """ISSUE 15: the schema-named checkpoint format restores a field
    the archive predates through its CHECKPOINT_FIELD_DEFAULTS entry —
    the PR 6 pre-telemetry special case generalized, so the NEXT
    pytree field addition is covered automatically (rule RA15 pins
    registry parity with LaneState._fields).  A missing REQUIRED field
    and an unknown (newer-schema) field both refuse: consensus state
    is never silently dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.engine.lockstep import (CHECKPOINT_FIELD_DEFAULTS,
                                        LaneState)
    from ra_tpu.models import CounterMachine

    # the static half of the contract, pinned at runtime too: every
    # field has a declared default mode
    assert set(CHECKPOINT_FIELD_DEFAULTS) == set(LaneState._fields)

    N, K = 8, 4
    eng = LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                         max_step_cmds=K, donate=False)
    n_new = jnp.full((N,), K, jnp.int32)
    pay = jnp.ones((N, K, 1), jnp.int32)
    for _ in range(5):
        eng.step(n_new, pay)
    eng.block_until_ready()
    path = str(tmp_path / "lanes.npz")
    eng.save(path)

    def rewrite(drop_prefix=None, add=None):
        with np.load(path) as z:
            arrays = {k: z[k] for k in z.files}
        if drop_prefix is not None:
            arrays = {k: v for k, v in arrays.items()
                      if not k.startswith(drop_prefix + ":")}
        if add is not None:
            arrays.update(add)
        out = str(tmp_path / "rewritten.npz")
        np.savez(out, **arrays)
        return out

    def fresh():
        return LockstepEngine(CounterMachine(), N, 3, ring_capacity=64,
                              max_step_cmds=K, donate=False)

    # a "zeros"-defaulted field missing from the archive zero-fills,
    # everything else restores exactly (the old-format-checkpoint
    # shape for ANY future defaultable field, not just telem)
    assert CHECKPOINT_FIELD_DEFAULTS["telem"] == "zeros"
    e2 = fresh()
    e2.restore(rewrite(drop_prefix="telem"))
    assert e2.committed_total() == eng.committed_total()
    assert int(np.asarray(e2.state.telem.steps).sum()) == 0
    e2.step(n_new, pay)
    e2.block_until_ready()
    assert int(np.asarray(e2.state.telem.steps).sum()) == N

    # a required field missing is a corrupt archive: refuse loudly
    with pytest.raises(ValueError, match="required field"):
        fresh().restore(rewrite(drop_prefix="commit"))

    # an archive from a NEWER schema (unknown field) refuses too —
    # silently dropping state is not this layer's call
    with pytest.raises(ValueError, match="unknown schema field"):
        fresh().restore(rewrite(
            add={"lease_ms:0": np.zeros((N,), np.int32)}))


def test_checkpoint_roundtrip_with_zero_leaf_field(tmp_path):
    """Review regression pin (ISSUE 15): a LaneState field whose
    pytree flattens to ZERO leaves (a stateless machine's empty mac)
    writes no archive keys — restore() must treat it as trivially
    satisfied, not as a missing 'require' field refusing a checkpoint
    the very same engine just wrote."""
    import jax.numpy as jnp
    from ra_tpu.core.machine import JitMachine
    from ra_tpu.engine import LockstepEngine

    class StatelessMachine(JitMachine):
        command_spec = ("int32", ())
        reply_spec = ("int32", ())

        def jit_init(self, n_lanes):
            return {}

        def jit_apply(self, meta, command, state):
            return state, jnp.int32(0)

    eng = LockstepEngine(StatelessMachine(), 4, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    path = str(tmp_path / "stateless.npz")
    eng.save(path)
    eng2 = LockstepEngine(StatelessMachine(), 4, 3, ring_capacity=64,
                          max_step_cmds=4, donate=False)
    eng2.restore(path)  # must not raise "missing required field 'mac'"
    assert eng2.committed_total() == 0


def test_committed_lanes_async_readback():
    """Non-blocking readback path used by the bench frontier: the async
    copy must survive buffer donation by subsequent steps and match the
    blocking readback."""
    import numpy as np
    from ra_tpu.models import CounterMachine
    from ra_tpu.engine import LockstepEngine

    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4)
    n_new = np.full((8,), 2, np.int32)
    payloads = np.ones((8, 4, 1), np.int32)
    handles = []
    for _ in range(6):
        eng.step(n_new, payloads)
        handles.append(eng.committed_lanes_async())
    eng.block_until_ready()
    assert all(h.is_ready() for h in handles)
    vals = [int(np.asarray(h).astype(np.int64).sum()) for h in handles]
    assert vals == sorted(vals)  # cumulative, monotone
    assert vals[-1] == eng.committed_total()


def _ring_case(R, K, C, n_lanes=12, seed=0):
    """Seeded inputs of one ring geometry: values over the whole int32
    range, tails anywhere in five laps of the ring."""
    rng = np.random.default_rng(seed)

    def i32(shape):
        return rng.integers(-2**31, 2**31, shape,
                            dtype=np.int64).astype(np.int32)
    case = {"ring": i32((n_lanes, R, C)), "pay": i32((n_lanes, K, C)),
            "last": rng.integers(0, 5 * R, n_lanes).astype(np.int32),
            "n_acc": rng.integers(0, K + 1, n_lanes).astype(np.int32),
            "elect": rng.integers(0, 2, n_lanes).astype(bool)}
    # the extremes themselves, in a row that is written
    case["pay"][:, 0, 0] = np.int32(-2**31)
    case["pay"][:, 0, -1] = np.int32(2**31 - 1)
    return case


def _ring_write_loop(ring, pay, last, n_acc, elect):
    """The append as a plain loop over lanes: entry i at slot (i-1) % R."""
    out = ring.copy()
    R = ring.shape[1]
    for n in range(ring.shape[0]):
        for k in range(n_acc[n]):
            out[n, (last[n] + k) % R] = pay[n, k]
        if elect[n]:
            out[n, (last[n] + n_acc[n]) % R] = 0
    return out


def _set(case, **cols):
    for name, val in cols.items():
        case[name][:] = val
    return case


_K = 16
RING_WRITE_CASES = {
    # name: (R, K, C, what the case pins on every lane)
    "nothing_to_write": (64, _K, 3, dict(n_acc=0, elect=False)),
    "one_row": (64, _K, 3, dict(n_acc=1, elect=False)),
    "full_batch": (64, _K, 3, dict(n_acc=_K, elect=False)),
    "election_alone": (64, _K, 3, dict(n_acc=0, elect=True)),
    "election_behind_rows": (64, _K, 3, dict(n_acc=5, elect=True)),
    "election_behind_full_batch": (64, _K, 3, dict(n_acc=_K, elect=True)),
    "wraps_the_rings_end": (64, _K, 3, dict(last=64 - 3, n_acc=_K,
                                             elect=True)),
    "ends_on_the_last_slot": (64, _K, 3, dict(last=2 * 64 - _K,
                                               n_acc=_K, elect=False)),
    "smallest_ring_mixed": (_K + 2, _K, 3, {}),
    "smallest_ring_full": (_K + 2, _K, 3, dict(n_acc=_K, elect=True)),
    "served_geometry_mixed": (1024, _K, 64, {}),
    "served_geometry_wrap": (1024, _K, 64, dict(last=3 * 1024 - 7,
                                                n_acc=_K, elect=True)),
    "scalar_commands": (32, 4, 1, {}),
}


@pytest.mark.parametrize("impl", ["gather", "onehot"])
@pytest.mark.parametrize("name", sorted(RING_WRITE_CASES))
def test_ring_write_matches_a_loop_over_lanes(name, impl):
    """Each lowering of `_ring_write` (``ring_io``: the CPU's and the
    chip's) against the plain loop: payload rows at slots (idx-1) % R,
    the zero noop at column n_acc on a won election, the wrap across
    the ring's end, and every slot it does not write (whole lanes with
    nothing to append among them) unchanged bit for bit."""
    import jax.numpy as jnp
    from ra_tpu.engine.lockstep import _ring_write
    R, K, C, pins = RING_WRITE_CASES[name]
    case = _set(_ring_case(R, K, C, seed=len(name)), **pins)
    if not pins:
        # mixed lanes: a few with nothing at all to write
        case["n_acc"][::4] = 0
        case["elect"][::4] = False
    # one round: a dispatch of K = 1, whose rows are the round's
    got = _ring_write(jnp.asarray(case["ring"]), jnp.asarray(case["pay"]),
                      *(jnp.asarray(case[k])[None] for k in
                        ("last", "n_acc", "elect")), impl=impl)
    want = _ring_write_loop(case["ring"], case["pay"], case["last"],
                            case["n_acc"], case["elect"])
    assert got.dtype == jnp.int32 and got.shape == case["ring"].shape
    np.testing.assert_array_equal(np.asarray(got), want)
    idle = (case["n_acc"] == 0) & ~case["elect"]
    np.testing.assert_array_equal(np.asarray(got)[idle],
                                  case["ring"][idle])


RING_READ_CASES = {
    # name: (R, C, A, first entry index of the window on every lane, or
    # None for seeded ones anywhere in five laps)
    "inside_the_ring": (64, 3, _K + 2, 5),
    "first_entry": (64, 3, _K + 2, 1),
    "wraps_the_rings_end": (64, 3, _K + 2, 64 - 4),
    "wraps_on_a_later_lap": (64, 3, _K + 2, 3 * 64 - 1),
    "smallest_ring_whole": (_K + 2, 3, _K + 2, 7),
    "served_geometry": (1024, 64, _K + 2, None),
    "served_geometry_wrap": (1024, 64, _K + 2, 2 * 1024 - 9),
    "recovery_window": (1024, 64, 66, None),
    "one_entry_window": (32, 1, 1, None),
}


@pytest.mark.parametrize("impl", ["gather", "onehot"])
@pytest.mark.parametrize("name", sorted(RING_READ_CASES))
def test_ring_read_window_matches_a_loop_over_lanes(name, impl):
    """Each lowering of `_ring_read_window` against the plain loop:
    entry i read from slot (i-1) % R, whatever lap of the ring the
    window is on."""
    import jax.numpy as jnp
    from ra_tpu.engine.lockstep import _ring_read_window
    R, C, A, first = RING_READ_CASES[name]
    case = _ring_case(R, _K, C, seed=len(name))
    ring, n_lanes = case["ring"], case["ring"].shape[0]
    base = np.full(n_lanes, first, np.int32) if first is not None \
        else case["last"] + 1
    idx = (base[:, None] + np.arange(A)[None, :]).astype(np.int32)
    got = _ring_read_window(jnp.asarray(ring), jnp.asarray(idx),
                            impl=impl)
    want = np.stack([ring[n, (idx[n] - 1) % R] for n in range(n_lanes)])
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_ring_io_onehot_matches_gather():
    """The MXU one-hot ring IO (split16 exact matmul) must be bit-exact
    vs the along-axis gather path, including negative payloads, noop
    columns, and ring wraparound."""
    import numpy as np
    import jax.numpy as jnp
    from ra_tpu.engine.lockstep import _ring_write, _ring_read_window

    rng = np.random.default_rng(7)
    N, R, K, C = 16, 12, 4, 3
    ring0 = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (N, R, C),
                                     dtype=np.int64).astype(np.int32))
    pay = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (N, K, C),
                                   dtype=np.int64).astype(np.int32))
    leader_last = jnp.asarray(rng.integers(0, 50, N).astype(np.int32))
    n_acc = jnp.asarray(rng.integers(0, K + 1, N).astype(np.int32))
    elect = jnp.asarray(rng.integers(0, 2, N).astype(bool))
    marks = (leader_last[None], n_acc[None], elect[None])
    a = _ring_write(ring0, pay, *marks, impl="gather")
    b = _ring_write(ring0, pay, *marks, impl="onehot")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    idx = jnp.asarray(rng.integers(1, 100, (N, 6)).astype(np.int32))
    ra = _ring_read_window(a, idx, impl="gather")
    rb = _ring_read_window(a, idx, impl="onehot")
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))


def test_engine_runs_with_onehot_ring_io():
    """Full engine correctness under the MXU ring-IO path (forced on
    CPU): commits and replica convergence match the gather path."""
    import numpy as np
    from ra_tpu.models import CounterMachine
    from ra_tpu.engine import LockstepEngine

    res = {}
    for impl in ("gather", "onehot"):
        eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                             max_step_cmds=4, write_delay=1, ring_io=impl)
        n_new = np.full((8,), 3, np.int32)
        pay = np.ones((8, 4, 1), np.int32)
        for _ in range(10):
            eng.step(n_new, pay)
        eng.fail_member(2, 0)
        eng.trigger_election([2])
        for _ in range(6):
            eng.step(n_new, pay)
        res[impl] = (eng.committed_total(),
                     np.asarray(eng.state.mac).copy())
    assert res["gather"][0] == res["onehot"][0]
    np.testing.assert_array_equal(res["gather"][1], res["onehot"][1])


def test_scan_machine_float_state_exact():
    """The lane-scan trajectory select must be exact for float machine
    state (gather path — a matmul select would 0*Inf-poison)."""
    import numpy as np
    import jax.numpy as jnp
    from ra_tpu.core.machine import JitMachine
    from ra_tpu.engine import LockstepEngine

    class FloatAcc(JitMachine):
        command_spec = ("int32", (1,))
        supports_batch_apply = False

        def jit_init(self, n_lanes):
            return jnp.zeros((n_lanes,), jnp.float32)

        def jit_apply(self, meta, command, state):
            new = state + command[..., 0].astype(jnp.float32) * 0.5
            return new, new

    eng = LockstepEngine(FloatAcc(), 4, 3, ring_capacity=64,
                         max_step_cmds=4, write_delay=1)
    n_new = np.full((4,), 3, np.int32)
    pay = np.ones((4, 4, 1), np.int32)
    for _ in range(8):
        eng.step(n_new, pay)
    st = eng.state
    lane = np.arange(4)
    applied = np.asarray(st.applied)
    mac = np.asarray(st.mac)
    act = np.asarray(st.active)
    for i in range(4):
        for p in range(3):
            if act[i, p]:
                # counter noop entries contribute 0; commands 0.5 each
                assert abs(mac[i, p] - 0.5 * applied[i, p]) < 1e-5, \
                    (i, p, mac[i, p], applied[i, p])
