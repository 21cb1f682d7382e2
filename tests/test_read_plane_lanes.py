"""The read lane settles lane by lane (ISSUE 35): a read block rides
every dispatch, over the lanes whose last batch has settled; a lane
whose batch is still out keeps its rows staged, in order; an
observation older than the dispatch a batch rode cannot settle it; the
block is popped at the head of the pump whose dispatch it rides
(ISSUE 36: handed to the same submit as the pump's write block), so a
read rides the first dispatch after it was staged and a served lane
gives its next batch to the next one.

Small engines on the CPU.  A lane is made slow by cutting its leader
from both followers and burning the lease: its batch registers on the
device, cannot be certified, and stays out until ``read_timeout``
refuses it (REJECT), while every other lane serves under its lease in
the dispatch that carries its batch.
"""
import argparse

import jax
import numpy as np
import pytest

from harness import Dispatched
from ra_tpu.engine import LockstepEngine
from ra_tpu.ingress import OK, REJECT, SHED, IngressPlane
from ra_tpu.models import CounterMachine

N, P = 6, 3
SLOW = 0            # the lane whose leader is cut from its followers


def _zeros_step(eng):
    eng.step(np.zeros((eng.n_lanes,), np.int32),
             np.zeros((eng.n_lanes, eng.max_step_cmds, eng.payload_width),
                      np.dtype(eng.payload_dtype)))


class _Fleet:
    """A plane over a small counter fleet, one session a lane, and a
    log of every settlement the plane fans out."""

    def __init__(self, *, max_step_reads=2, read_timeout=6, cut=True):
        eng = LockstepEngine(CounterMachine(), N, P, ring_capacity=32,
                             max_step_cmds=4, max_step_reads=max_step_reads,
                             lease_ttl=4, read_timeout=read_timeout)
        if cut:
            lead = int(np.asarray(eng.state.leader_slot)[SLOW])
            for s in range(P):
                if s != lead:
                    eng.fail_member(SLOW, s)
            for _ in range(3 * eng.lease_ttl):
                _zeros_step(eng)
        self.eng = eng
        self.plane = IngressPlane(eng, superstep_k=1, window_s=0.0)
        # as on the chip, a dispatch is observed in the pump that made it
        # (there the driver's conversion of the reply block waits for
        # the copy; the CPU's is often not ready at the pump's end)
        self.plane.driver._ready = \
            lambda entry: bool(jax.block_until_ready(entry[1:])) or True
        handles = self.plane.connect_bulk(16 * N, key="fleet")
        lanes = self.plane.directory.lane[handles]
        #: one session of each lane
        self.on = np.asarray([handles[lanes == lane][0]
                              for lane in range(N)])
        #: dispatches made with a batch out and no read half: the
        #: batch's serve would ride no reply tensor and never be seen
        self.blind = 0
        dispatch = self.plane.driver._dispatch

        def watched(blk):
            self.blind += bool(self.plane._read_pend.any()
                               and blk[3] is None)
            return dispatch(blk)
        self.plane.driver._dispatch = watched
        self.settled = []           # (lane, seqno, status, watermark)
        self.plane.on_reads_done = self._done
        self.next_seqno = 0

    def _done(self, handles, seqnos, statuses, wms, _pay):
        lanes = self.plane.directory.lane[handles]
        self.settled += list(zip(lanes.tolist(), seqnos.tolist(),
                                 statuses.tolist(), wms.tolist()))

    def read(self, lane, n=1):
        """Offer ``n`` reads to ``lane``; returns (seqnos, statuses)."""
        seq = np.arange(self.next_seqno, self.next_seqno + n)
        self.next_seqno += n
        st = self.plane.submit_reads(
            np.full(n, self.on[lane]), seq,
            np.zeros((n, self.eng.query_width), np.int32))
        return seq, st

    def pump(self, times=1):
        for _ in range(times):
            self.plane.pump(force=True)

    def of(self, lane):
        return [(s, st) for ln, s, st, _wm in self.settled if ln == lane]


def test_a_dispatch_pops_the_free_lane_and_only_it():
    """(a) Reads staged for lanes A and B with A's batch still out: a
    dispatch pops B's and only B's, and A's later rows keep their
    order."""
    f = _Fleet(max_step_reads=2, read_timeout=6)
    a, b = SLOW, 3
    f.read(a, 2)                    # seqnos 0, 1
    f.read(b, 1)                    # seqno 2
    f.pump(1)                       # popped, dispatched, observed
    assert f.of(b) == [(2, OK)]
    assert f.of(a) == [] and f.plane._read_pend[a]
    assert not f.plane._read_pend[b]
    f.pump(1)                       # only A's batch is out
    blocks = f.plane.counters["read_blocks"]
    zeros = f.plane.counters["read_zero_blocks"]
    assert zeros >= 1               # A was out, nothing else staged
    f.read(a, 3)                    # seqnos 3, 4, 5: wait behind A's batch
    f.read(b, 1)                    # seqno 6
    f.pump(1)
    # B's row was popped by that pump, rode its dispatch and was
    # served; A's three were not popped
    assert f.plane.counters["read_blocks"] == blocks + 1
    assert f.plane.counters["read_zero_blocks"] == zeros
    assert f.plane.read_window.fill[a] == 3
    assert f.plane.read_window.fill[b] == 0
    assert f.of(b) == [(2, OK), (6, OK)]
    assert f.plane._read_pend[a] and not f.plane._read_pend[b]
    assert f.plane._read_seqnos[a, :2].tolist() == [0, 1]
    assert f.plane._read_ordinal[b] > f.plane._read_ordinal[a]
    assert f.plane.gauges()["read_lanes_pending"] == 1
    # A's batches go out one at a time, in the order they were staged,
    # and the device refuses each at its read_timeout
    f.plane.settle()
    assert f.of(a) == [(0, REJECT), (1, REJECT), (3, REJECT), (4, REJECT),
                       (5, REJECT)]
    assert f.plane.gauges()["read_lanes_pending"] == 0
    assert int(f.plane.ladder.used.sum()) == 0
    assert f.blind == 0


def test_settle_dispatches_nothing_without_its_read_half():
    """A batch that is out may be served by any dispatch and is seen
    only on that dispatch's reply tensors: the write block a pump
    dispatches while a batch is out goes with its read half (the zero
    block, no lane being free with a read staged), and so does every
    dispatch ``settle()`` makes."""
    f = _Fleet()
    seen = Dispatched(f.eng)
    f.read(SLOW, 1)
    f.pump(2)
    assert f.plane._read_pend[SLOW]
    h = f.on[3:4]
    f.plane.submit(h, f.plane.directory.next_seqnos(h),
                   np.ones((1, 1), np.int32))
    f.pump(1)                       # the write block goes with its half
    n_new, _p, n_read = seen.blocks[-1]
    assert int(np.asarray(n_new).sum()) == 1 and n_read is not None
    assert int(np.asarray(n_read).sum()) == 0
    f.plane.settle()
    assert f.blind == 0
    assert f.of(SLOW) == [(0, REJECT)]


def test_a_read_rides_the_first_dispatch_and_its_lane_the_next():
    """The read block is popped at the head of the pump whose dispatch
    it rides: a read staged before a pump is answered by that pump,
    and a lane served by dispatch d rides dispatch d + 1 again: a
    batch a cycle, not one in two."""
    f = _Fleet(cut=False)
    h = f.on[:1]
    f.plane.submit(h, f.plane.directory.next_seqnos(h),
                   np.ones((1, 1), np.int32))
    f.pump(1)                       # a write block is dispatched
    lane, rode = 4, []
    for i in range(10):
        f.read(lane, 1)
        f.pump(1)
        rode.append(int(f.plane._read_ordinal[lane]))
        assert f.of(lane) == [(s, OK) for s in range(i + 1)]
    assert rode == list(range(2, 12))
    assert f.plane.counters["read_blocks"] == 10
    assert f.plane.counters["read_zero_blocks"] == 0
    assert int(np.asarray(f.eng.state.read_shed).sum()) == 0


def test_a_lane_is_never_popped_while_its_batch_is_out():
    """(b) Reads offered to every lane at every dispatch, one lane slow
    for many dispatches: the device never sheds a batch at a busy slot
    (``read_shed`` stays 0), which a pop of a pending lane would make
    it do, and every staged read is settled once."""
    f = _Fleet(max_step_reads=2, read_timeout=9)
    rng = np.random.default_rng(35)
    placed = 0
    for _ in range(40):
        for lane in range(N):
            _seq, st = f.read(lane, int(rng.integers(0, 3)))
            placed += int((st <= 1).sum())
        f.pump(1)
    f.plane.settle()
    assert int(np.asarray(f.eng.state.read_shed).sum()) == 0
    assert f.plane.read_counters["shed"] == \
        f.plane.read_counters["submitted"] - placed
    seen = [s for _ln, s, _st, _wm in f.settled]
    assert len(seen) == placed == len(set(seen))
    assert {st for s, st in f.of(SLOW)} == {REJECT}
    fast = [(st, wm) for ln, _s, st, wm in f.settled if ln != SLOW]
    assert fast and all(st == OK and wm >= 0 for st, wm in fast)
    # the fast lanes did not wait for the slow one: they were served
    # in many more batches than the slow lane's timeouts allow
    assert f.plane.counters["read_blocks"] > 20
    ph = f.eng.phases.overview()["read_staged_wait"]
    assert ph["count"] == f.plane.counters["read_blocks"]
    assert ph["p50_ms"] >= 0
    assert f.blind == 0


@pytest.mark.parametrize("leaf", ["read_shed_lanes", "read_stale_lanes"])
def test_an_older_observation_cannot_settle_a_lane(leaf):
    """(c) A refusal counted by the dispatch before the one a batch
    rides is not that batch's: fed by hand, it leaves the lane
    pending; the same delta from the batch's own dispatch settles
    it."""
    f = _Fleet(cut=False)
    lane = 2
    h = f.on[:1]
    f.plane.submit(h, f.plane.directory.next_seqnos(h),
                   np.ones((1, 1), np.int32))
    f.pump(1)                       # a write block, nothing rides it
    plane, drv = f.plane, f.plane.driver
    f.read(lane, 2)
    assert plane._pop_read_block() is not None  # popped, not dispatched
    assert plane._read_pend[lane] and not drv.read_obs
    rides = int(plane._read_ordinal[lane])
    assert rides == drv.staged + 1 and drv.observed < rides

    def obs(ordinal, count):
        cum = {k: np.zeros(N, np.int32) for k in
               ("read_served_lanes", "read_shed_lanes", "read_stale_lanes")}
        cum[leaf][lane] = count
        return dict(cum, ordinal=ordinal)

    drv.read_obs.append(obs(rides - 1, 1))
    plane._harvest_reads()
    assert plane._read_pend[lane] and f.settled == []
    drv.read_obs.append(obs(rides, 2))
    plane._harvest_reads()
    assert not plane._read_pend[lane]
    want = SHED if leaf == "read_shed_lanes" else REJECT
    assert f.of(lane) == [(0, want), (1, want)]
    assert all(wm == -1 for _ln, _s, _st, wm in f.settled)
    assert int(plane.ladder.used.sum()) == 0


def test_a_read_popped_at_a_pump_head_rides_that_pumps_dispatch():
    """ISSUE 36, the ordinal: a read popped at the head of a pump,
    before that pump's write block is staged, is registered against
    the dispatch that pump makes, and not against the one before it.
    Held unobserved, the batch is not settled by a refusal counted on
    the previous dispatch's observation; its own dispatch's
    observation serves it."""
    f = _Fleet(cut=False)
    plane, drv = f.plane, f.plane.driver
    seen = Dispatched(f.eng)
    lane = 2
    h = f.on[:1]
    plane.submit(h, plane.directory.next_seqnos(h),
                 np.ones((1, 1), np.int32))
    f.pump(1)                       # dispatch 1: the write alone
    assert drv.observed == 1 and not plane._read_pend.any()
    poll = drv.poll
    drv.poll = lambda: 0            # dispatch 2 stays unobserved
    plane.submit(h, plane.directory.next_seqnos(h),
                 np.ones((1, 1), np.int32))
    f.read(lane, 2)
    f.pump(1)                       # read popped at the head, then the write
    assert len(seen.blocks) == drv.staged == 2
    n_new, _p, n_read = seen.blocks[-1]
    assert int(np.asarray(n_new).sum()) == 1
    assert int(np.asarray(n_read)[:, lane].sum()) == 2
    assert int(plane._read_ordinal[lane]) == 2 and plane._read_pend[lane]
    assert drv.observed == 1 and drv.in_flight() == 1
    # an observation of dispatch 1 that refused the lane: not this batch's
    cum = {k: np.zeros(N, np.int32) for k in
           ("read_served_lanes", "read_shed_lanes", "read_stale_lanes")}
    cum["read_shed_lanes"][lane] = 1
    drv.read_obs.append(dict(cum, ordinal=1))
    plane._harvest_reads()
    assert plane._read_pend[lane] and f.settled == []
    drv.poll = poll
    f.pump(1)                       # dispatch 2 observed: it served them
    assert f.of(lane) == [(0, OK), (1, OK)]
    assert f.blind == 0


@pytest.mark.parametrize("max_step_reads, offered", [(1, 7), (2, 11)])
def test_a_store_offered_more_than_it_stages(max_step_reads, offered):
    """(e) A store stages four blocks' worth of reads; the excess is
    refused at admission and told so, what was staged is answered a
    batch a dispatch, and no read or credit is lost."""
    f = _Fleet(max_step_reads=max_step_reads, cut=False)
    staged = 4 * max_step_reads
    seq, st = f.read(1, offered)
    assert (st[:staged] <= 1).all() and (st[staged:] == SHED).all()
    rc = f.plane.read_counters
    assert rc["accepted"] == staged and rc["shed"] == offered - staged
    f.plane.settle()
    assert f.of(1) == [(int(s), OK) for s in seq[:staged]]
    assert f.plane.counters["read_blocks"] == 4
    assert rc["served"] == rc["replies_sent"] == staged
    assert int(np.asarray(f.eng.state.read_shed).sum()) == 0
    assert int(f.plane.ladder.used.sum()) == 0
    # a refused read sent again is a fresh read
    _seq, st = f.read(1, offered - staged)
    assert (st <= 1).all()
    f.plane.settle()
    assert len(f.of(1)) == offered


def test_no_read_no_block():
    """A plane that is sent no read attaches no read block, zero or
    real, and notes nothing."""
    f = _Fleet(cut=False)
    h = f.on
    f.plane.submit(h, f.plane.directory.next_seqnos(h),
                   np.ones((len(h), 1), np.int32))
    f.pump(3)
    f.plane.settle()
    c = f.plane.counters
    assert c["read_blocks"] == c["read_zero_blocks"] == 0
    assert f.eng.phases.overview()["read_staged_wait"]["count"] == 0
    assert f.plane.overview()["read_lanes_pending"] == 0


# -- (d) the benchmark's fleet and kit ``ycsb_kv`` through sweep() / pump() --

CELL = "ycsb_kv_2k_x3.paced_ycsb_b"


@pytest.mark.parametrize("seed, rate", [(35, 700), (2**31 + 35, 1500)])
def test_ycsb_fleet_every_read_answered_once(monkeypatch, tmp_path, seed,
                                             rate):
    from benchmarks import manifest as mf
    from benchmarks import run as br
    import ra_tpu.ingress

    orig = br.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[0] == "configs":
            d.update(clusters=10, records=30)
        if parts[0] == "cells":
            d.update(warmup_s=0.5, rate_ops_per_s=rate)
        if parts[0] == "traffic":
            d.update(warmup_s=0.5, trace_after_s=0.2, trace_s=0.5)
        return d

    planes = []

    class Plane(IngressPlane):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            planes.append(self)

    monkeypatch.setattr(br, "load_json", load)
    monkeypatch.setattr(br, "RUN_ROOT", str(tmp_path / "bench_run"))
    # serve.open_served imports the plane's class where it builds it
    monkeypatch.setattr(ra_tpu.ingress, "IngressPlane", Plane)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.5,
                              trace=0, override=[])
    rc, res = br.run_cell(args, mf.committed(), require_tpu=False)
    assert rc == 0 and res["correct"] is True, res["compared"]
    assert res["attempted"] > 300 and res["failed"] == 0
    for tag in ("live", "reopen"):
        for count in ("ver_wrong", "sum_wrong", "fields_unknown",
                      "fields_stale", "replica_cells_wrong",
                      "replicas_behind"):
            assert res["compared"][f"{tag}_{count}"]["value"] == 0
    for count in ("reads_outside_consistency", "reads_not_present",
                  "reads_negative_watermark", "ops_never_acked"):
        assert res["compared"][count]["value"] == 0
    assert "read_staged_wait" in res["notes"]["phases_p50_ms"]
    plane, = planes
    c, rcnt = plane.counters, plane.read_counters
    # every read the lane accepted was settled once, and framed once
    assert rcnt["accepted"] > 200
    assert rcnt["accepted"] == \
        c["read_served_rows"] + c["read_refused_rows"] == \
        rcnt["replies_sent"]
    assert not plane._read_pend.any()
    assert plane.read_window.queue_rows() == 0
    assert int(np.asarray(plane.engine.state.read_shed).sum()) == 0
