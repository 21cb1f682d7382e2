"""Sharded WAL plane tests: per-shard group commit, compacted device->
host readback accounting, ragged crash coverage across shards, and
shard-count migration.

Reference behaviour being extended: the single fan-in WAL writer of
ra_log_wal.erl (one batch, one fdatasync for every co-hosted server)
multiplied across lane shards — each shard keeps the same confirm-
before-commit contract over its lane slice, and the merged per-lane
confirm vector feeds the engine's quorum gate exactly as before.
"""
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ra_tpu.engine import open_engine
from ra_tpu.log import faults
from ra_tpu.log.faults import DiskFaultPlan, DiskFaultSpec
from ra_tpu.log.wal import Wal
from ra_tpu.models import CounterMachine

N, P, K = 16, 3, 8

# the poison->escalate ladder may legitimately kill a shard's batch
# thread under injected faults; the shard supervisor restarts it
pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")


def make(tmp_path, shards, **kw):
    kw.setdefault("sync_mode", 0)
    kw.setdefault("ring_capacity", 256)
    kw.setdefault("max_step_cmds", K)
    return open_engine(CounterMachine(), str(tmp_path), N, P,
                       wal_shards=shards, **kw)


def drive(eng, n_steps, cmds=4):
    n_new = np.full((N,), cmds, np.int32)
    payloads = np.ones((N, eng.max_step_cmds, 1), np.int32)
    for _ in range(n_steps):
        eng.step(n_new, payloads)


def settle(eng, max_steps=30):
    zero_n = np.zeros((N,), np.int32)
    zero_p = np.zeros((N, eng.max_step_cmds, 1), np.int32)
    for _ in range(max_steps):
        eng.step(zero_n, zero_p)
        eng._dur.drain_all()
        eng._dur.flush_all()


def leader_view(eng, field):
    st = eng.state
    lane = np.arange(N)
    return np.asarray(getattr(st, field))[lane,
                                          np.asarray(st.leader_slot)]


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_commit_and_recover(tmp_path, shards):
    """Commits gate on the merged per-shard confirms, and recovery from
    the sharded layout restores everything ever reported committed with
    oracle-exact machine state (pure +1 workload: counter == applied)."""
    eng = make(tmp_path, shards)
    assert len(eng._dur.wals) == shards
    drive(eng, 10)
    settle(eng)
    com = leader_view(eng, "commit").copy()
    assert com.sum() > 0
    assert (com <= eng._dur.confirm_upto).all()
    # every shard wrote into its own file sequence
    for i, sh in enumerate(eng._dur._shards):
        assert sh.wal.counters["writes"] > 0, i
    eng.close()

    eng2 = make(tmp_path, shards)
    com2 = leader_view(eng2, "commit")
    assert (com2 >= com).all()
    mac = np.asarray(eng2.state.mac)
    app = np.asarray(eng2.state.applied)
    act = np.asarray(eng2.state.active)
    assert (mac[act] == app[act]).all()
    eng2.close()


def test_shard_count_change_recovers(tmp_path):
    """Blocks self-describe their lane slice (RTB1/RTB2), so reopening
    with a different wal_shards needs no migration: 1 -> 4 -> 1."""
    eng = make(tmp_path, 1)
    drive(eng, 6)
    settle(eng)
    com = leader_view(eng, "commit").copy()
    eng.close()

    eng2 = make(tmp_path, 4)
    com2 = leader_view(eng2, "commit")
    assert (com2 >= com).all()
    drive(eng2, 6)
    settle(eng2)
    com2 = leader_view(eng2, "commit").copy()
    eng2.close()

    eng3 = make(tmp_path, 1)
    com3 = leader_view(eng3, "commit")
    assert (com3 >= com2).all()
    mac = np.asarray(eng3.state.mac)
    app = np.asarray(eng3.state.applied)
    act = np.asarray(eng3.state.active)
    assert (mac[act] == app[act]).all()
    eng3.close()
    # the legacy single-shard layout is pruned at the first checkpoint
    eng4 = make(tmp_path, 4)
    drive(eng4, 2)
    eng4.checkpoint()
    assert not os.path.isdir(os.path.join(str(tmp_path), "wal")) or \
        not os.listdir(os.path.join(str(tmp_path), "wal"))
    eng4.close()


def test_torn_shard_tail_recovery(tmp_path):
    """Crash mid-write on ONE shard (torn tail): recovery merges the
    ragged per-shard coverage — the torn shard's lanes replay their
    surviving prefix and carry forward, every other lane keeps its full
    log, and the merged state stays oracle-consistent."""
    eng = make(tmp_path, 4)
    drive(eng, 8)
    settle(eng)
    com = leader_view(eng, "commit").copy()
    torn = eng._dur._shards[2]
    lo, hi = torn.lo, torn.hi
    wal_dir = torn.wal.dir
    eng.close()

    # tear the newest wal file of shard 2 mid-record
    files = sorted(f for f in os.listdir(wal_dir) if f.endswith(".wal"))
    assert files
    path = os.path.join(wal_dir, files[-1])
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(4, size - 11))

    eng2 = make(tmp_path, 4)
    com2 = leader_view(eng2, "commit")
    outside = np.ones((N,), bool)
    outside[lo:hi] = False
    # untouched shards lose nothing
    assert (com2[outside] >= com[outside]).all()
    # the torn shard's lanes recover a (possibly shorter) prefix, and
    # the whole merged state is still the oracle at its apply frontier
    mac = np.asarray(eng2.state.mac)
    app = np.asarray(eng2.state.applied)
    act = np.asarray(eng2.state.active)
    assert (mac[act] == app[act]).all()
    # the lane engine keeps working after the ragged recovery
    drive(eng2, 4)
    settle(eng2)
    com3 = leader_view(eng2, "commit")
    assert (com3 > com2).all()
    eng2.close()


def test_group_commit_amortizes_fsyncs(tmp_path):
    """With a nonzero batch interval the writer holds the group open and
    one fdatasync covers the burst (flush on max_batch_bytes OR
    max_batch_interval_ms — ra_log_wal.erl:193-214 extended with an
    explicit wait budget)."""
    confirmed = []
    done = threading.Event()

    def notify(uid, lo, hi, term):
        confirmed.append((lo, hi))
        if hi >= 20:
            done.set()

    wal = Wal(str(tmp_path), sync_mode=1, max_batch_interval_ms=150.0)
    try:
        wal.register("u", notify)
        for i in range(1, 21):
            wal.write("u", i, 1, b"x" * 64)
        assert done.wait(5.0)
        wal.flush()
        assert wal.counters["writes"] == 20
        # the burst lands in very few groups => few durability syscalls
        assert wal.counters["syncs"] <= 3, wal.counters
        st = wal.stats()
        assert st["records_per_fsync"] >= 5
        assert st["fsync_p50_ms"] >= 0
    finally:
        wal.close()


def test_group_commit_byte_cap_closes_group(tmp_path):
    """max_batch_bytes closes a group early even inside the interval."""
    wal = Wal(str(tmp_path), sync_mode=0, max_batch_interval_ms=500.0,
              max_batch_bytes=256)
    try:
        wal.register("u", lambda *a: None)
        t0 = time.monotonic()
        for i in range(1, 9):
            wal.write("u", i, 1, b"y" * 128)
        wal.flush()
        # 8 * 128B at a 256B cap: the writer must not sit out the full
        # 500ms interval per group
        assert time.monotonic() - t0 < 2.0
        assert wal.counters["writes"] == 8
        assert wal.counters["batches"] >= 2
    finally:
        wal.close()


def test_compacted_readback_counters(tmp_path):
    """The device-side payload compaction shrinks the per-step host
    readback by the occupancy factor: at 2 accepted commands of a
    16-wide batch the compacted bytes must be >= 2x below what the
    full-ring readback would have moved (the ISSUE 3 CI criterion)."""
    eng = make(tmp_path, 1, max_step_cmds=16)
    n_new = np.full((N,), 2, np.int32)   # 2 of 16 slots occupied
    payloads = np.ones((N, 16, 1), np.int32)
    for _ in range(8):
        eng.step(n_new, payloads)
    eng._dur.drain_all()
    ctr = eng._dur.counters
    assert ctr["encoded_blocks"] >= 8
    assert ctr["readback_bytes"] * 2 <= ctr["readback_bytes_full"], ctr
    eng.close()


def test_superstep_block_submit_feeds_every_shard(tmp_path):
    """A K-fused dispatch's stacked aux lands on the sharded WAL plane
    as K consecutive per-inner-step jobs on EVERY shard (ISSUE 5:
    submit_block slices the [K, ...] leaves; record format, per-shard
    file sequences and the merged confirm vector are unchanged), and
    recovery from a superstep-driven sharded layout is oracle-exact."""
    eng = make(tmp_path, 4, max_pending=32)
    SK = 4
    seq0 = eng._dur.step_seq
    n_new = np.full((SK, N), 4, np.int32)
    pay = np.ones((SK, N, eng.max_step_cmds, 1), np.int32)
    for _ in range(5):
        eng.superstep(n_new, pay)
    # step_seq advances one per INNER step — K per fused dispatch
    assert eng._dur.step_seq - seq0 == 5 * SK
    settle(eng)
    com = leader_view(eng, "commit").copy()
    assert com.sum() > 0
    assert (com <= eng._dur.confirm_upto).all()
    for i, sh in enumerate(eng._dur._shards):
        assert sh.wal.counters["writes"] > 0, i
    eng.close()

    eng2 = make(tmp_path, 4)
    com2 = leader_view(eng2, "commit")
    assert (com2 >= com).all()
    mac = np.asarray(eng2.state.mac)
    app = np.asarray(eng2.state.applied)
    act = np.asarray(eng2.state.active)
    assert (mac[act] == app[act]).all()
    eng2.close()


def test_wal_overview_reports_shard_health(tmp_path):
    """engine.overview() merges ENGINE_WAL_FIELDS and per-shard WAL
    stats (batch bytes, records/fsync, fsync p50/p99, confirm lag) —
    the RPC_FIELDS observability pattern on the durability plane."""
    eng = make(tmp_path, 2, sync_mode=1)
    drive(eng, 4)
    settle(eng, 6)
    ov = eng.overview()
    w = ov["wal"]
    for f in ("readback_bytes", "readback_bytes_full", "encoded_blocks",
              "encoded_bytes", "confirm_lag_steps"):
        assert f in w["engine"], f
    assert len(w["shards"]) == 2
    for st in w["shards"]:
        for f in ("bytes_written", "records_per_fsync", "fsync_p50_ms",
                  "fsync_p99_ms", "confirm_lag_steps", "lanes"):
            assert f in st, st
        assert st["bytes_written"] > 0
        assert st["syncs"] > 0
    assert w["engine"]["confirm_lag_steps"] == 0  # settled
    # which of the two WAL I/O paths ran is part of every durable row
    from ra_tpu.native import IO
    assert w["io_path"] == ("native" if IO.native else "python")
    eng.close()


def test_poisoned_shard_holds_back_confirms(tmp_path):
    """fsync-EIO on ONE shard (shard03): its confirm slice freezes at
    the durable horizon, so the merged confirm vector — and therefore
    the fsync-gated commit — provably never advances past unfsynced
    entries; once the fault clears, the poison/rollover resend path
    catches the shard back up and recovery is oracle-exact (the
    per-shard confirm hold-back of ISSUE 4)."""
    faults.reset_disk_fault_counters()
    eng = make(tmp_path, 4, sync_mode=1)
    try:
        drive(eng, 4)
        settle(eng, 6)
        torn = eng._dur._shards[3]
        faults.install_plan(DiskFaultPlan(seed=31, rules=[
            ("wal", DiskFaultSpec(fsync_eio=1.0, limit=3,
                                  path_match="shard03"))]))
        n_new = np.full((N,), 2, np.int32)
        payloads = np.ones((N, K, 1), np.int32)
        from ra_tpu.log.wal import WalDown
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                eng.step(n_new, payloads)
            except WalDown:
                pass  # supervisor races the ladder's rung 3
            # the acceptance invariant, sampled every step: commit is
            # gated on the MERGED confirm vector
            lane = np.arange(N)
            st = eng.state
            com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
            assert (com <= eng._dur.confirm_upto).all(), \
                (com, eng._dur.confirm_upto)
            time.sleep(0.05)  # let the batch thread reach its fsync
            if faults.disk_fault_counters()["poisoned_files"] >= 1:
                break
        faults.clear_plan()
        ctr = faults.disk_fault_counters()
        assert ctr["faults_injected"] >= 1, ctr
        assert ctr["poisoned_files"] >= 1, ctr
        assert ctr["fsync_retries_after_failure"] == 0, ctr
        # fault cleared: the shard catches up and commits resume
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                (not torn.wal.alive or
                 torn.confirmed_step < eng._dur.step_seq):
            try:
                settle(eng, 2)
            except (WalDown, TimeoutError):
                time.sleep(0.05)
        assert torn.wal.alive
        com = leader_view(eng, "commit").copy()
        assert (com > 0).all()
        assert (com <= eng._dur.confirm_upto).all()
    finally:
        faults.clear_plan()
        eng.close()
    # cold reopen: oracle-exact at the apply frontier
    eng2 = make(tmp_path, 4, sync_mode=1)
    com2 = leader_view(eng2, "commit")
    assert (com2 >= com).all()
    mac = np.asarray(eng2.state.mac)
    app = np.asarray(eng2.state.applied)
    act = np.asarray(eng2.state.active)
    assert (mac[act] == app[act]).all()
    eng2.close()


_FAULT_CHILD = r"""
import os, sys, json
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
from ra_tpu.engine import open_engine
from ra_tpu.log import faults
from ra_tpu.log.faults import DiskFaultPlan, DiskFaultSpec
from ra_tpu.models import CounterMachine

# the ISSUE 4 kill-9 matrix plan: torn writes on shard 0, fsync-EIO on
# shard 3 — active for the child's WHOLE life, including its recovery
faults.install_plan(DiskFaultPlan(seed=97, rules=[
    ("wal", DiskFaultSpec(short_write=0.10, limit=6,
                          path_match="shard00")),
    ("wal", DiskFaultSpec(fsync_eio=0.15, limit=6,
                          path_match="shard03")),
]))

N, P, K = 16, 3, 8
eng = open_engine(CounterMachine(), sys.argv[1], N, P,
                  sync_mode=1, ring_capacity=256, max_step_cmds=K,
                  wal_shards=4)
report = sys.argv[2]
n_new = np.full((N,), 4, np.int32)
payloads = np.ones((N, K, 1), np.int32)
lane = np.arange(N)
from ra_tpu.log.wal import WalDown
import time as _time
for i in range(10_000):
    try:
        eng.step(n_new, payloads)
    except WalDown:
        _time.sleep(0.05)  # shard supervisor races the escalation rung
        continue
    if i % 5 == 4:
        # report the fsync-confirmed commit frontier crash-safely; the
        # min() with confirm_upto is the fsynced-watermark clamp
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


def test_kill9_with_active_disk_faults_recovers_reported(tmp_path):
    """The kill-9 matrix under an ACTIVE DiskFaultPlan (torn write on
    shard 0, fsync-EIO on shard 3): SIGKILL mid-bench while the
    degradation ladder is live, then recover with NO faults — every
    commit the child ever reported (clamped to the fsynced watermark)
    survives, and the replayed state is oracle-exact."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    data = str(tmp_path / "data")
    report = str(tmp_path / "report.json")
    child = subprocess.Popen(
        [sys.executable, "-c", _FAULT_CHILD.format(repo=repo), data,
         report],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
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
        if not chunk:
            break
        buf += chunk
        reports = sum(1 for line in buf.split(b"\n")[:-1]
                      if line.startswith(b"REPORTED"))
    child.send_signal(signal.SIGKILL)
    child.wait(timeout=30)
    assert reports >= 4, child.stderr.read()

    with open(report) as f:
        reported = np.array(json.load(f), np.int32)
    assert reported.sum() > 0

    eng = make(tmp_path / "data", 4, sync_mode=1)
    lane = np.arange(N)
    st = eng.state
    com = np.asarray(st.commit)[lane, np.asarray(st.leader_slot)]
    assert (com >= reported).all(), (com, reported)
    # oracle equivalence at the recovered apply frontier (+1 workload)
    mac = np.asarray(st.mac)
    app = np.asarray(st.applied)
    act = np.asarray(st.active)
    assert (mac[act] == app[act]).all(), (mac, app)
    assert (mac[lane, np.asarray(st.leader_slot)] >= reported).all()
    eng.close()


def test_checkpoint_prunes_every_shard(tmp_path):
    eng = make(tmp_path, 4)
    drive(eng, 6)
    eng.checkpoint()
    for sh in eng._dur._shards:
        files = [f for f in os.listdir(sh.wal.dir)
                 if f.endswith(".wal")]
        assert len(files) == 1, (sh.idx, files)  # only the fresh file
    eng.close()
