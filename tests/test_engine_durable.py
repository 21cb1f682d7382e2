"""Durable lane-engine tests: fsync-gated commits, WAL crash/restart
survival, checkpoint pruning, election truncation across the WAL
boundary, and a kill -9 recovery test.

Reference behaviour being matched: an entry counts toward the commit
median only after write(2)+fsync (/root/reference/src/ra_log_wal.erl:
753-800), WAL crash -> resend above the durable horizon
(/root/reference/src/ra_log.erl:778-793), and recovery = snapshot + WAL
re-read with overwrite dedup (/root/reference/src/ra_log_wal.erl:871-955).
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from ra_tpu.engine import LockstepEngine, open_engine
from ra_tpu.engine.durable import (decode_block, encode_block,
                                   _final_logs)
from ra_tpu.models import CounterMachine



N, P, K = 16, 3, 8


def make_engine(tmp_path, **kw):
    kw.setdefault("sync_mode", 0)  # tests: no fsync, same protocol
    kw.setdefault("ring_capacity", 256)
    kw.setdefault("max_step_cmds", K)
    return open_engine(CounterMachine(), str(tmp_path), N, P, **kw)


def drive(eng, n_steps, cmds=4, value=1):
    n_new = np.full((N,), cmds, np.int32)
    payloads = np.full((N, K, 1), value, np.int32)
    for _ in range(n_steps):
        eng.step(n_new, payloads)


def settle(eng, max_steps=50):
    zero_n = np.zeros((N,), np.int32)
    zero_p = np.zeros((N, K, 1), np.int32)
    for _ in range(max_steps):
        eng.step(zero_n, zero_p)
        eng._dur.drain_all()
        eng._dur.wal.flush()
    return eng


# -- block codec ------------------------------------------------------------

def test_block_roundtrip():
    rng = np.random.default_rng(0)
    hi = rng.integers(1, 100, N).astype(np.int32)
    n_acc = rng.integers(0, K, N).astype(np.int32)
    n_app = n_acc + rng.integers(0, 2, N).astype(np.int32)
    ph = rng.integers(0, 1000, (N, K, 1)).astype(np.int32)
    blk = encode_block(hi, n_app, n_acc, ph)
    lane_lo, hi2, n_app2, n_acc2, rows = decode_block(blk)
    assert lane_lo == 0
    np.testing.assert_array_equal(hi, hi2)
    np.testing.assert_array_equal(n_app, n_app2)
    np.testing.assert_array_equal(n_acc, n_acc2)
    for i in range(N):
        np.testing.assert_array_equal(rows[i, :n_acc[i]], ph[i, :n_acc[i]])
        assert (rows[i, n_acc[i]:] == 0).all()  # noop rows zero-filled


def test_flat_encode_matches_legacy_bytes():
    """The device-compaction encode path (flat accepted rows in) must be
    byte-identical to the legacy host-mask path — the wal_shards=1
    format-compat guarantee."""
    from ra_tpu.engine.durable import encode_block_flat
    rng = np.random.default_rng(1)
    hi = rng.integers(1, 100, N).astype(np.int32)
    n_acc = rng.integers(0, K, N).astype(np.int32)
    n_app = n_acc + rng.integers(0, 2, N).astype(np.int32)
    ph = rng.integers(0, 1000, (N, K, 1)).astype(np.int32)
    mask = np.arange(K)[None, :] < n_acc[:, None]
    flat = ph[mask]
    assert encode_block_flat(hi, n_app, n_acc, flat) == \
        encode_block(hi, n_app, n_acc, ph)


def test_sharded_block_carries_lane_offset():
    from ra_tpu.engine.durable import encode_block_flat
    hi = np.array([7, 9], np.int32)
    n_app = np.array([2, 1], np.int32)
    n_acc = np.array([2, 1], np.int32)
    flat = np.array([[1], [2], [3]], np.int32)
    blk = encode_block_flat(hi, n_app, n_acc, flat, lane_lo=8)
    lane_lo, hi2, n_app2, n_acc2, rows = decode_block(blk)
    assert lane_lo == 8
    np.testing.assert_array_equal(hi2, hi)
    np.testing.assert_array_equal(rows[0, :2, 0], [1, 2])
    np.testing.assert_array_equal(rows[1, :1, 0], [3])


def test_final_logs_truncation():
    # two blocks then an election block that truncates below block 2
    tail = np.zeros((2,), np.int32)
    b1 = (1, np.array([4, 4], np.int32), np.array([4, 4], np.int32),
          np.array([4, 4], np.int32), np.ones((2, 4, 1), np.int32))
    b2 = (2, np.array([8, 8], np.int32), np.array([4, 4], np.int32),
          np.array([4, 4], np.int32), np.ones((2, 4, 1), np.int32))
    # election on lane 0: truncate to 5, append noop -> hi 6
    b3 = (3, np.array([6, 12], np.int32), np.array([1, 4], np.int32),
          np.array([0, 4], np.int32), np.ones((2, 4, 1), np.int32))
    surv, trimmed, final = _final_logs([b1, b2, b3], tail)
    np.testing.assert_array_equal(surv[0], [4, 4])
    np.testing.assert_array_equal(surv[1], [1, 4])  # entries 6..8 die
    np.testing.assert_array_equal(surv[2], [1, 4])
    np.testing.assert_array_equal(final, [6, 12])


# -- commit gating ----------------------------------------------------------

def test_commits_gate_on_wal_confirm(tmp_path):
    eng = make_engine(tmp_path)
    drive(eng, 10)
    # confirm path is asynchronous; drain + flush then step to fold
    settle(eng, 5)
    total = eng.committed_total()
    assert total > 0
    # every committed entry is <= the WAL-confirmed horizon
    st = eng.state
    lane = np.arange(N)
    leader = np.asarray(st.leader_slot)
    com = np.asarray(st.commit)[lane, leader]
    assert (com <= eng._dur.confirm_upto).all()
    eng.close()


# Wal.kill() below makes the batch thread die by an uncaught
# exception on purpose — that IS the scenario under test
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_commits_freeze_when_wal_dies(tmp_path):
    # wal_supervise=False: this test asserts the RAW frozen state and
    # restarts by hand — the default supervisor would race the asserts
    eng = make_engine(tmp_path, wal_supervise=False)
    drive(eng, 6)
    settle(eng, 5)
    before = eng.committed_total()
    eng._dur.wal.kill()
    # steps keep running (appends continue) but commits freeze at the
    # confirmed horizon; submits hit WalDown and blocks stay pending
    from ra_tpu.log.wal import WalDown
    n_new = np.full((N,), 4, np.int32)
    payloads = np.ones((N, K, 1), np.int32)
    frozen = None
    for _ in range(6):
        try:
            eng.step(n_new, payloads)
        except WalDown:
            pass
        frozen = eng.committed_total()
    # nothing beyond the last confirm may commit
    assert frozen is not None
    confirmed_hi = int(eng._dur.confirm_upto.sum())
    lane = np.arange(N)
    st = eng.state
    com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
    assert int(com.sum()) <= confirmed_hi
    # supervised restart: resend above the durable horizon, commits resume
    eng._dur.wal.restart()
    for _ in range(10):
        try:
            eng.step(n_new, payloads)
        except WalDown:
            time.sleep(0.05)
    settle(eng, 10)
    assert eng.committed_total() > before
    eng.close()


def test_checkpoint_prunes_wal_files(tmp_path):
    eng = make_engine(tmp_path)
    drive(eng, 8)
    eng.checkpoint()
    wal_dir = os.path.join(str(tmp_path), "wal")
    files = [f for f in os.listdir(wal_dir) if f.endswith(".wal")]
    # only the fresh post-rollover file remains
    assert len(files) == 1
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt.npz"))
    eng.close()


# -- recovery ---------------------------------------------------------------

def test_recover_from_wal_only(tmp_path):
    eng = make_engine(tmp_path)
    drive(eng, 10, cmds=4)
    settle(eng, 5)
    st = eng.state
    lane = np.arange(N)
    leader = np.asarray(st.leader_slot)
    commits = np.asarray(st.commit)[lane, leader].copy()
    counters = np.asarray(st.mac)[lane, leader].copy()
    eng.close()

    eng2 = make_engine(tmp_path)
    st2 = eng2.state
    leader2 = np.asarray(st2.leader_slot)
    com2 = np.asarray(st2.commit)[lane, leader2]
    mac2 = np.asarray(st2.mac)[lane, leader2]
    assert (com2 >= commits).all()
    assert (mac2 >= counters).all()
    # replicas converge: every active member has the leader's state
    mac_all = np.asarray(st2.mac)
    act = np.asarray(st2.active)
    for i in range(N):
        vals = mac_all[i][act[i]]
        assert (vals == vals[0]).all()
    eng2.close()


def test_replay_donates_and_the_engine_steps_as_it_was_built(tmp_path):
    """Recovery's replay donates the state each step replaces (a fleet
    that fills its device cannot hold two of them); the reopened engine
    steps as it was built to: with the default ``donate=False`` a state
    taken before a step is still readable after it, and
    ``step(donate=True)`` gives it up."""
    eng = make_engine(tmp_path)
    drive(eng, 6, cmds=4)
    settle(eng, 5)
    eng.close()

    replayed = []
    step = LockstepEngine.step

    def spy(self, *args, **kw):
        replayed.append((self._dur is None, kw.get("donate", False)))
        return step(self, *args, **kw)
    LockstepEngine.step = spy
    try:
        eng2 = make_engine(tmp_path)
    finally:
        LockstepEngine.step = step
    assert replayed and all(r == (True, True) for r in replayed)
    before = eng2.state
    drive(eng2, 1, cmds=2)
    assert not before.ring.is_deleted()
    total = int(np.asarray(before.commit).sum())
    before = eng2.state
    eng2.step(np.zeros((N,), np.int32), np.zeros((N, K, 1), np.int32),
              donate=True)
    assert before.ring.is_deleted()
    assert int(np.asarray(eng2.state.commit).sum()) >= total
    eng2.close()


def test_recover_from_checkpoint_plus_wal(tmp_path):
    eng = make_engine(tmp_path)
    drive(eng, 6, cmds=4)
    eng.checkpoint()
    drive(eng, 6, cmds=4)  # post-checkpoint tail lives only in the WAL
    settle(eng, 5)
    lane = np.arange(N)
    st = eng.state
    commits = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)].copy()
    eng.close()

    eng2 = make_engine(tmp_path)
    st2 = eng2.state
    com2 = np.asarray(st2.commit)[lane, np.asarray(st2.leader_slot)]
    assert (com2 >= commits).all()
    eng2.close()


def test_durable_dir_with_old_format_checkpoint_reopens(tmp_path):
    """ISSUE 15 forward-compat at the DURABLE-DIR level (the PR 6
    verify probe promoted into tier-1 and generalized): a dir whose
    ckpt.npz was written by an OLD engine — positional a<i> keys,
    telemetry plane absent — reopens through restore()'s legacy branch
    + the RA15 schema defaults, recovers every committed command, and
    keeps committing.  A checkpoint format bump never strands a
    durable dir."""
    import jax

    from ra_tpu.engine.lockstep import LaneState, LaneTelemetry

    eng = make_engine(tmp_path)
    drive(eng, 6, cmds=4)
    eng.checkpoint()
    drive(eng, 3, cmds=4)
    settle(eng, 5)
    committed = eng.committed_total()
    state = eng.state
    eng.close()

    # rewrite ckpt.npz exactly as the pre-telemetry positional save
    # wrote it: index-flattened keys, telem leaves dropped
    ckpt = tmp_path / "ckpt.npz"
    n_tel = len(LaneTelemetry._fields)
    tel_at = len(jax.tree.flatten(
        tuple(state[:LaneState._fields.index("telem")]))[0])
    with np.load(str(ckpt)) as z:
        meta = z["__meta__"]
        arrays = []
        for name in LaneState._fields:
            n_leaves = len(jax.tree.flatten(getattr(state, name))[0])
            arrays += [z[f"{name}:{j}"] for j in range(n_leaves)]
    legacy = arrays[:tel_at] + arrays[tel_at + n_tel:]
    np.savez(str(ckpt), __meta__=meta,
             **{f"a{i}": a for i, a in enumerate(legacy)})

    eng2 = make_engine(tmp_path)
    settle(eng2, 5)
    assert eng2.committed_total() >= committed
    # telemetry zero-fills and accumulates from the reopen
    drive(eng2, 2, cmds=4)
    eng2.block_until_ready()
    assert int(np.asarray(eng2.state.telem.steps).max()) > 0
    eng2.close()


def test_recover_with_election_truncation(tmp_path):
    eng = make_engine(tmp_path)
    drive(eng, 6)
    settle(eng, 5)
    # fail the leader of lane 0 and elect a replacement: the dead
    # leader's unreplicated tail (if any) is truncated, indexes reused
    st = eng.state
    leader0 = int(np.asarray(st.leader_slot)[0])
    eng.fail_member(0, leader0)
    eng.trigger_election([0])
    drive(eng, 6)
    settle(eng, 8)
    lane = np.arange(N)
    st = eng.state
    commits = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)].copy()
    eng.close()

    eng2 = make_engine(tmp_path)
    st2 = eng2.state
    com2 = np.asarray(st2.commit)[lane, np.asarray(st2.leader_slot)]
    assert (com2 >= commits).all()
    # converged replicas on the failed lane too
    mac = np.asarray(st2.mac)[0]
    act = np.asarray(st2.active)[0]
    vals = mac[act]
    assert (vals == vals[0]).all()
    eng2.close()


# -- superstep durable contracts (ISSUE 5) ----------------------------------

def test_superstep_durable_parity(tmp_path):
    """A durable run driven in K-fused supersteps converges to the SAME
    state as a single-step durable run over the same schedule: identical
    WAL records per inner step, identical commits/applies/machine state
    once both settle (the stacked-aux submit_block path feeds the shard
    workers exactly what K step() calls would)."""
    a = make_engine(tmp_path / "a", wal_shards=2, max_pending=32)
    b = make_engine(tmp_path / "b", wal_shards=2, max_pending=32)
    rng = np.random.default_rng(42)
    SK = 4
    for _ in range(3):
        n_new = rng.integers(0, K + 1, (SK, N)).astype(np.int32)
        pay = rng.integers(1, 5, (SK, N, K, 1)).astype(np.int32)
        for j in range(SK):
            a.step(n_new[j], pay[j])
        b.superstep(n_new, pay)
    settle(a, 20)
    settle(b, 20)
    for f in ("commit", "applied", "total_committed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, f)),
            np.asarray(getattr(b.state, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.state.mac),
                                  np.asarray(b.state.mac))
    # both runs recover to equal durable state too
    a.close()
    b.close()
    a2 = make_engine(tmp_path / "a", wal_shards=2)
    b2 = make_engine(tmp_path / "b", wal_shards=2)
    np.testing.assert_array_equal(np.asarray(a2.state.mac),
                                  np.asarray(b2.state.mac))
    a2.close()
    b2.close()


def test_superstep_confirms_only_lag_fsync(tmp_path):
    """The confirm horizon is sampled ONCE per fused dispatch: no entry
    may commit inside a superstep beyond what was already WAL-confirmed
    when the dispatch launched (write_delay semantics — confirms lag,
    never lead).  Checked against the horizon the dispatch itself
    sampled (the WAL threads may move ``confirm_upto`` between a copy
    taken here and that sample), which is strictly stronger than the
    settled-state gate."""
    eng = make_engine(tmp_path, max_pending=64)
    lane = np.arange(N)
    rng = np.random.default_rng(7)
    real, sampled = eng._sstep, []

    def spy(*args):
        sampled.append(np.asarray(args[5]))
        return real(*args)
    eng._sstep = spy
    for i in range(6):
        n_new = rng.integers(0, K + 1, (4, N)).astype(np.int32)
        pay = rng.integers(1, 5, (4, N, K, 1)).astype(np.int32)
        eng.superstep(n_new, pay)
        st = eng.state
        com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
        assert len(sampled) == i + 1
        assert (com <= sampled[-1]).all(), (com, sampled[-1])
        assert (sampled[-1] <= eng._dur.confirm_upto).all()
    # ...and the horizon does advance once the WAL drains, so the gate
    # above is hold-back, not a frozen pipeline
    settle(eng, 20)
    assert eng.committed_total() > 0
    eng.close()


_CHILD = r"""
import os, sys, json
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
from ra_tpu.engine import open_engine
from ra_tpu.models import CounterMachine

N, P, K = 16, 3, 8
mode = sys.argv[4] if len(sys.argv) > 4 else "step"
eng = open_engine(CounterMachine(), sys.argv[1], N, P,
                  sync_mode=1, ring_capacity=256, max_step_cmds=K,
                  wal_shards=int(sys.argv[3]),
                  # superstep: step_seq advances SK per dispatch, so the
                  # unconfirmed window must cover a few fused dispatches
                  max_pending=32 if mode == "superstep" else 8)
report = sys.argv[2]
n_new = np.full((N,), 4, np.int32)
payloads = np.ones((N, K, 1), np.int32)
SK = 4
n_new_blk = np.broadcast_to(n_new, (SK, N)).copy()
pay_blk = np.broadcast_to(payloads, (SK, N, K, 1)).copy()
lane = np.arange(N)
for i in range(10_000):
    if mode == "superstep":
        eng.superstep(n_new_blk, pay_blk)
    else:
        eng.step(n_new, payloads)
    if i % 5 == 4:
        # report the fsync-confirmed commit frontier crash-safely
        st = eng.state
        com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
        com = np.minimum(com, eng._dur.confirm_upto)
        tmp = report + ".tmp"
        with open(tmp, "w") as f:
            json.dump([int(x) for x in com], f)
            f.flush(); os.fsync(f.fileno())
        os.replace(tmp, report)
        print("REPORTED", i, flush=True)
"""


@pytest.mark.parametrize("shards,mode", [(1, "step"), (4, "step"),
                                         (4, "superstep")])
def test_kill9_recovers_all_reported_commits(tmp_path, shards, mode):
    """SIGKILL mid-bench: every entry ever reported committed (which the
    engine only does after its WAL block is fsynced) survives recovery —
    for the single-shard compat layout AND the sharded WAL plane (a
    crash can tear one shard mid-write; recovery merges the ragged
    per-shard coverage), and for a run driven in FUSED SUPERSTEP mode
    (ISSUE 5: the kill lands mid-block — some of a dispatch's K
    per-inner-step WAL records written, some not — and recovery still
    honours every fsync-gated report).  The recovered machine state must
    equal the never-crashed oracle at the recovered apply frontier: with
    no elections every applied entry is a +1 command, so the oracle
    counter at applied index a is exactly a."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    data = str(tmp_path / "data")
    report = str(tmp_path / "report.json")
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(repo=repo), data, report,
         str(shards), mode],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    # wait for a few reports, then SIGKILL with no warning (generous
    # deadline: the child pays a fresh jax import + jit compile, minutes
    # on a loaded single-core box; success path exits long before).
    # Read the RAW fd: readline alone would block past the deadline, and
    # select() on the buffered stream misses lines the BufferedReader
    # already slurped.
    import select
    deadline = time.time() + 360
    reports = 0
    fd = child.stdout.fileno()
    buf = b""
    while time.time() < deadline and reports < 4:
        ready, _, _ = select.select([fd], [], [],
                                    max(0.0, deadline - time.time()))
        if not ready:
            break
        chunk = os.read(fd, 65536)
        if not chunk:  # EOF: child died early — stderr tells why
            break
        buf += chunk
        reports = sum(1 for line in buf.split(b"\n")[:-1]
                      if line.startswith(b"REPORTED"))
    child.send_signal(signal.SIGKILL)
    child.wait(timeout=30)
    assert reports >= 4, child.stderr.read()

    import json
    with open(report) as f:
        reported = np.array(json.load(f), np.int32)
    assert reported.sum() > 0

    eng = make_engine(tmp_path / "data", sync_mode=1, wal_shards=shards)
    lane = np.arange(N)
    st = eng.state
    com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
    assert (com >= reported).all(), (com, reported)
    # oracle equivalence: the replayed lane state equals what a
    # never-crashed run holds at the recovered apply frontier — the
    # workload is pure +1 commands (no elections, no noops), so the
    # oracle counter at applied index a is exactly a, on every member
    mac = np.asarray(st.mac)
    app = np.asarray(st.applied)
    act = np.asarray(st.active)
    assert (mac[act] == app[act]).all(), (mac, app)
    assert (mac[lane, np.asarray(st.leader_slot)] >= reported).all()
    eng.close()


def test_volatile_mode_unchanged(tmp_path):
    """The volatile engine (no durable_dir) still works as before."""
    eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=256,
                        max_step_cmds=K)
    n_new = np.full((N,), 4, np.int32)
    payloads = np.ones((N, K, 1), np.int32)
    for _ in range(6):
        eng.step(n_new, payloads)
    assert eng.committed_total() > 0


def test_recover_revives_failed_member_by_snapshot(tmp_path):
    """Regression (r04 review): recovery must revive a failed member via
    snapshot install from its lane leader — a bare active-flag flip
    leaves a frozen applied cursor that would drag the lane-uniform
    apply window onto recycled ring slots and silently diverge."""
    eng = make_engine(tmp_path, ring_capacity=64)
    drive(eng, 4)
    settle(eng, 5)
    eng.fail_member(0, 1)
    # push far more entries than ring_capacity so the failed member's
    # frozen cursor falls behind the reclaim horizon
    drive(eng, 40)
    settle(eng, 5)
    eng.checkpoint()
    lane = np.arange(N)
    st = eng.state
    leader_mac = np.asarray(st.mac)[lane, np.asarray(st.leader_slot)]
    eng.close()

    eng2 = make_engine(tmp_path, ring_capacity=64)
    st2 = eng2.state
    assert bool(np.asarray(st2.active)[0, 1])  # revived
    # the revived member's state equals its leader's (snapshot), and
    # further traffic keeps every replica converged
    drive(eng2, 4)
    settle(eng2, 10)
    st2 = eng2.state
    mac = np.asarray(st2.mac)
    act = np.asarray(st2.active)
    for i in range(N):
        vals = mac[i][act[i]]
        assert (vals == vals[0]).all(), (i, mac[i], act[i])
    led2 = np.asarray(st2.leader_slot)
    assert (mac[lane, led2] >= leader_mac).all()
    eng2.close()


@pytest.mark.parametrize("total", [1, 7, 64, 100])
def test_rows_window_reads_any_slice_through_power_of_two_lengths(total):
    """The WAL readback's window (a start that is data, a length that
    is a power of two, trimmed on the host) gives every ``[r0, r1)`` of
    the compacted buffer, at the buffer's ends too, through at most
    log2 lengths' programs."""
    import jax.numpy as jnp
    from ra_tpu.engine import durable
    rows = jnp.arange(total * 3, dtype=jnp.int32).reshape(total, 3)
    host = np.asarray(rows)
    durable._pull_rows(rows, 0, total)      # the jit exists from here on
    before = durable._ROWS_WINDOW._cache_size()
    for r0 in range(total + 1):
        for r1 in range(r0, total + 1):
            np.testing.assert_array_equal(
                durable._pull_rows(rows, r0, r1), host[r0:r1])
    assert durable._ROWS_WINDOW._cache_size() - before \
        <= total.bit_length() + 1
