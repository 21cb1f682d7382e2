"""Ingress plane: a million client sessions fanning into the lane
engine (ISSUE 10, ROADMAP item 2).

``IngressPlane`` composes the three tiers this package provides —

* :class:`~ra_tpu.ingress.sessions.SessionDirectory`: external id →
  (tenant, lane, shard) deterministic placement, reconnect-stable
  epochs, vectorized per-session seqno dedup (at-most-once end-to-end);
* :class:`~ra_tpu.ingress.coalesce.CoalesceWindow`: per-lane staging
  rings coalescing concurrent submissions into the
  ``[K, lanes, cmds_per_step, C]`` superstep blocks the engine eats
  (host-side pre-jit; a write block leaves the host as the rows it
  carries and takes its dense shape on the device; lint rule RA08
  keeps the block-build path free of per-session Python work);
* :class:`~ra_tpu.ingress.backpressure.CreditLadder`: per-session
  credit, per-tenant fairness, and the SLO-driven shed/defer/reject
  ladder (FifoClient's ok→slow→StopSending protocol generalized to all
  machines)

— and drives them against a ``LockstepEngine`` through the PR 5
``DispatchAheadDriver``, releasing session credit lane by lane within a
block as the driver's async committed-watermark readbacks land (no
per-command host work anywhere past admission).

Quickstart::

    eng = LockstepEngine(CounterMachine(), 10_000, 3)
    plane = IngressPlane(eng, superstep_k=4)
    handles = plane.connect_bulk(1_000_000, tenants=16, key="fleet")
    status = plane.submit(handles[:4096], seqnos, payloads)
    plane.pump()          # dispatch a block when the window triggers
"""
from __future__ import annotations

import gc
import time
from collections import deque
from typing import Optional

import numpy as np

from .. import devicewatch, trace
from ..blackbox import record
from ..engine.lockstep import PUMP_SPLIT, DispatchAheadDriver
from ..metrics import INGRESS_FIELDS, READ_FIELDS
from .backpressure import (DEFER, DUP, LEVEL_NAMES, OK, REJECT, SHED, SLOW,
                           STATUS_NAMES, CreditLadder)
from .coalesce import CoalesceWindow, batch_rank
from .sessions import SessionDirectory, default_directory

__all__ = [
    "IngressPlane", "SessionDirectory", "CoalesceWindow", "CreditLadder",
    "OK", "SLOW", "DEFER", "REJECT", "DUP", "SHED", "STATUS_NAMES",
    "LEVEL_NAMES", "batch_rank", "default_directory",
]

#: a ``pump()`` longer than this is a stall: counted in ``slow_pumps``
#: and recorded as a ``pump.slow`` event with its split (ISSUE 37)
PUMP_SLOW_S = 1.0


class IngressPlane:
    """The session tier over one lane engine: dedup → admission →
    coalesce → fused dispatch, with credit released lane by lane as
    blocks commit."""

    def __init__(self, engine, *, directory: Optional[SessionDirectory]
                 = None, superstep_k: int = 8,
                 max_in_flight: int = 2, window_s: float = 0.002,
                 fill_frac: float = 0.5, capacity: Optional[int] = None,
                 soft_credit: int = 128, hard_credit: int = 512,
                 tenant_quota: int = 65536, slo=None,
                 shardings: Optional[dict] = None) -> None:
        self.engine = engine
        self.directory = directory or default_directory(engine)
        if self.directory.n_lanes != engine.n_lanes:
            raise ValueError("directory/engine lane count mismatch")
        # a lane stages two blocks' worth, and no less than one session
        # may hold in flight: a session inside its credit waits behind
        # the block's window, it is not shed (ISSUE 27: after a stall of
        # the loop a hot session hands over seconds of operations at
        # once)
        width = superstep_k * engine.max_step_cmds
        self.window = CoalesceWindow(
            engine.n_lanes, engine.max_step_cmds, engine.payload_width,
            superstep_k=superstep_k,
            capacity=capacity or max(2 * width, hard_credit),
            window_s=window_s, fill_frac=fill_frac,
            payload_dtype=np.dtype(engine.payload_dtype))
        self.ladder = CreditLadder(self.directory,
                                   soft_credit=soft_credit,
                                   hard_credit=hard_credit,
                                   tenant_quota=tenant_quota)
        if shardings is None and getattr(engine, "_mesh", None) is not None:
            # mesh-native composition (ISSUE 11): a sharded engine's
            # plane stages its coalesced blocks pre-partitioned against
            # the mesh, so the fused dispatch consumes them with zero
            # resharding copies (shard_engine_state stamped the mesh)
            from ..parallel.mesh import superstep_block_shardings
            shardings = superstep_block_shardings(engine._mesh)
        self.driver = DispatchAheadDriver(engine,
                                          max_in_flight=max_in_flight,
                                          shardings=shardings)
        # the write block's flat entry: every ra_densify program the
        # pump can reach is compiled here, none inside a window; and
        # the confirm-only program a pump's tail may run
        self.driver.prepare_flat(superstep_k)
        engine.prepare_confirm()
        #: optional SloEngine whose commit-latency verdicts drive the
        #: ladder (polled at pump time — host dict work only)
        self.slo = slo
        #: optional block-retire hook (the wire plane's ack fan-out,
        #: ISSUE 12): called with the released handle array whenever a
        #: block's committed watermark lands — i.e. off the driver's
        #: EXISTING async readbacks, never a new host sync
        self.on_block_committed = None
        self.counters = {f: 0 for f in INGRESS_FIELDS}
        #: in-flight blocks awaiting commit: [per-lane cumulative
        #: dispatched-row target, the handles of the block's rows not
        #: yet released (None once it has retired and waits for the
        #: blocks ahead), the lane of each, block id = ``blocks_built``
        #: at pop, time.monotonic() at pop, the block's ordinal among
        #: the driver's staged blocks, the engine's number of its
        #: dispatch, and per WAL shard the last inner step of the
        #: dispatch that holds the block's rows in the shard's lanes
        #: (-1: none; None on an engine with no WAL)]
        self._inflight: deque = deque()
        dur = getattr(engine, "_dur", None)
        #: first lane of each WAL shard (the split of ``block_e2e``,
        #: ISSUE 37); None without one
        self._wal_lane_lo = None if dur is None else np.array(
            [lo for lo, _hi in dur.shard_layout()], np.intp)
        #: ``driver.observed`` at the last harvest: the committed
        #: watermark moves with an observation, or with a confirm-only
        #: run, which resets this to -1
        self._harvested = -1
        self._dispatched_rows = np.zeros(engine.n_lanes, np.int64)
        #: ``_dispatched_rows`` as of the newest block whose dispatch
        #: the driver has observed: what ``driver.last_ring_used``
        #: already counts
        self._observed_rows = np.zeros(engine.n_lanes, np.int64)
        # commit baseline: election noops also advance total_committed,
        # so the release join is >=, never ==, and credit may release a
        # hair early around an election — flow control, not correctness
        self._base_committed = \
            np.asarray(engine.state.total_committed).astype(np.int64)
        self._shedding = False
        # -- vectorized read lane (ISSUE 20) ---------------------------
        # A second, read-side CoalesceWindow stages consistent reads
        # into ``(n_read [K,N], read_q [K,N,Kr,Cq])`` blocks that RIDE
        # the write dispatches (superstep_k=1: the engine holds at most
        # ONE in-flight read batch per lane, so a block is exactly one
        # window of Kr rows per lane, registered at inner step 0 to
        # maximize confirm rounds within the dispatch).  The unit of
        # "in flight" is the lane (ISSUE 35): every dispatch carries a
        # block over the lanes whose last batch has settled, a lane
        # whose batch is still out keeps its rows staged, and each lane
        # settles on its own observation.  Reads consume
        # the same session credit as writes but shed FIRST: any
        # tightened ladder level refuses whole read waves at admission
        # (overload sheds reads before it delays writes).
        self.reads_enabled = bool(getattr(engine, "reads_enabled", False))
        self.read_counters = {f: 0 for f in READ_FIELDS}
        #: reply fan-out hook (the wire plane's READ_REPLY path):
        #: called with (handles, seqnos, statuses, watermarks, payloads)
        #: row vectors as read batches settle — off the driver's
        #: EXISTING async read-aux readbacks, never a new host sync
        self.on_reads_done = None
        self._read_shedding = False
        self._read_stale_flag = False
        n = engine.n_lanes
        self._zero_wn = np.zeros((superstep_k, n), np.int32)
        self._zero_wp = np.zeros(
            (superstep_k, n, engine.max_step_cmds, engine.payload_width),
            np.dtype(engine.payload_dtype))
        if self.reads_enabled:
            kr, cq = engine.read_window, engine.query_width
            qdt = np.dtype(engine.query_dtype)
            self.read_window = CoalesceWindow(
                n, kr, cq, superstep_k=1, capacity=4 * kr,
                window_s=window_s, fill_frac=fill_frac,
                payload_dtype=qdt, track_seqnos=True)
            #: zero read block attached when some lane's batch is out
            #: and no free lane has a read staged, so the outcomes and
            #: the asked rows' replies keep riding
            #: every dispatch until the batch serves or expires —
            #: settlement never waits on a new read arriving
            self._zero_read_blk = (
                np.zeros((superstep_k, n), np.int32),
                np.zeros((superstep_k, n, kr, cq), qdt))
            #: the batch each lane has out, one standing table for the
            #: plane's lifetime: its rows' handles and seqnos [N, Kr],
            #: how many of them are rows (``take``), whether it still
            #: awaits its outcome (``pend``), and the ordinal among the
            #: driver's staged blocks of the dispatch it rides
            self._read_handles = np.full((n, kr), -1, np.int64)
            self._read_seqnos = np.zeros((n, kr), np.int64)
            self._read_take = np.zeros(n, np.int64)
            self._read_pend = np.zeros(n, bool)
            self._read_ordinal = np.zeros(n, np.int64)
            # a refusal is joined on the engine's CUMULATIVE per-lane
            # outcome counters (shed/stale deltas per observed
            # dispatch; a serve on the dispatch's reply tensors) —
            # baselines from current state, like _base_committed above
            s = engine.state
            self._read_shed_base = \
                np.asarray(s.read_shed).astype(np.int64)
            self._read_stale_base = \
                np.asarray(s.read_stale).astype(np.int64)
        else:
            self.read_window = None
            self._zero_read_blk = None
        engine._ingress = self

    # -- sessions ----------------------------------------------------------

    def connect(self, external_id: str) -> int:
        """Resolve/create a named session; reconnects bump the epoch
        (recorded — reconnects are rare control-plane events)."""
        h, reconnected = self.directory.connect(external_id)
        if reconnected:
            self.counters["reconnects"] += 1
            record("ingress.connect", id=external_id, handle=int(h),
                   epoch=int(self.directory.epoch[h]))
        return h

    def connect_bulk(self, n: int, *, key: str = "bulk",
                     tenants: int = 1) -> np.ndarray:
        """Connect a synthetic fleet (one event for the whole fleet —
        the per-session path must not emit a million records)."""
        known = key in self.directory._bulk
        h = self.directory.connect_bulk(n, key=key, tenants=tenants)
        if known:
            self.counters["reconnects"] += n
        record("ingress.connect", bulk=key, n=int(n),
               reconnect=bool(known))
        return h

    # -- submission --------------------------------------------------------

    def submit(self, handles, seqnos, payloads) -> np.ndarray:
        """One ingress wave: per-row status (OK/SLOW/DEFER/REJECT/DUP/
        SHED, np.int8).  Dedup → admission → coalesce, all vectorized;
        only PLACED rows advance the at-most-once watermark, so a
        deferred/rejected/shed command's resend (same seqno) is fresh."""
        handles = np.asarray(handles, np.int64)
        seqnos = np.asarray(seqnos, np.int64)
        payloads = np.asarray(payloads)
        if payloads.ndim == 1:
            payloads = payloads[:, None]
        n = len(handles)
        c = self.counters
        c["submitted"] += n
        fresh = self.directory.fresh(handles, seqnos)
        status = np.full(n, DUP, np.int8)
        idx_fresh = np.flatnonzero(fresh)
        c["dup_dropped"] += n - len(idx_fresh)
        if not len(idx_fresh):
            return status
        fh = handles[idx_fresh]
        adm = self.ladder.admit(fh)
        status[idx_fresh] = adm
        ok = adm <= SLOW
        idx_ok = idx_fresh[ok]
        if len(idx_ok):
            placed = self.window.offer(self.directory.lane[handles[idx_ok]],
                                       payloads[idx_ok],
                                       handles[idx_ok])
            if not placed.all():
                # ring overflow: shed (bounded queues drop, they never
                # grow) — credit returned, seqno NOT marked, so the
                # client's resend survives the episode
                idx_shed = idx_ok[~placed]
                status[idx_shed] = SHED
                self.ladder.release(handles[idx_shed])
                c["shed_rows"] += len(idx_shed)
                if not self._shedding:
                    self._shedding = True
                    record("ingress.shed", rows=int(len(idx_shed)),
                           queue_rows=self.window.queue_rows(),
                           level=LEVEL_NAMES[self.ladder.level])
            else:
                self._shedding = False
            idx_placed = idx_ok[placed]
            self.directory.mark(handles[idx_placed], seqnos[idx_placed])
            c["accepted"] += len(idx_placed)
        c["slow_signals"] += int((adm == SLOW).sum())
        c["deferred"] += int((adm == DEFER).sum())
        c["rejected"] += int((adm == REJECT).sum())
        if len(idx_fresh) < n:
            # a within-wave twin of a row that was NOT placed must not
            # read as DUP ("already accepted — stop resending"): it
            # inherits its first occurrence's verdict instead.  One
            # stable lexsort groups equal (handle, seqno) runs; the run
            # head is the row fresh() kept (or a true watermark dup,
            # whose head status is already DUP)
            order = np.lexsort((seqnos, handles))
            sh, ss = handles[order], seqnos[order]
            new_run = np.empty(n, bool)
            new_run[0] = True
            new_run[1:] = (sh[1:] != sh[:-1]) | (ss[1:] != ss[:-1])
            run_ids = np.cumsum(new_run) - 1
            st_sorted = status[order]
            head_st = st_sorted[np.flatnonzero(new_run)][run_ids]
            # head placed -> the twin IS a duplicate of an accepted row;
            # head refused -> the twin shares the refusal (resendable)
            prop = np.where(head_st <= SLOW, np.int8(DUP), head_st)
            upd = ~new_run & (st_sorted == DUP)
            status[order[upd]] = prop[upd]
        return status

    def submit_auto(self, handles, payloads) -> np.ndarray:
        """Demo/test convenience: mint the next per-session seqnos
        server-side (a well-behaved resend-free client)."""
        handles = np.asarray(handles, np.int64)
        return self.submit(handles, self.directory.next_seqnos(handles),
                           payloads)

    def submit_reads(self, handles, seqnos, queries) -> np.ndarray:
        """One consistent-read wave: per-row status (OK/SLOW/REJECT/
        SHED, np.int8), vectorized end to end (rule RA08 gates this
        path like the write coalescer's).

        Reads are idempotent, so there is NO dedup watermark: ``seqnos``
        are pure reply-correlation ids, and a shed read's resend is
        always fresh.  Credit bias (the ISSUE 20 overload story): any
        tightened ladder level sheds the whole read wave at admission —
        reads shed BEFORE writes are delayed, and a shed read costs no
        credit."""
        handles = np.asarray(handles, np.int64)
        seqnos = np.asarray(seqnos, np.int64)
        queries = np.asarray(queries)
        if queries.ndim == 1:
            queries = queries[:, None]
        n = len(handles)
        rc = self.read_counters
        rc["submitted"] += n
        status = np.full(n, SHED, np.int8)
        if not self.reads_enabled or n == 0:
            rc["shed"] += n
            return status
        if self.ladder.level > 0:
            rc["shed"] += n
            if not self._read_shedding:
                self._read_shedding = True
                record("read.shed", rows=int(n),
                       level=LEVEL_NAMES[self.ladder.level])
            return status
        self._read_shedding = False
        adm = self.ladder.admit(handles)
        status[:] = adm
        ok = adm <= SLOW
        idx_ok = np.flatnonzero(ok)
        rc["rejected"] += int(n - len(idx_ok))
        if len(idx_ok):
            placed = self.read_window.offer(
                self.directory.lane[handles[idx_ok]], queries[idx_ok],
                handles[idx_ok], seqnos=seqnos[idx_ok])
            if not placed.all():
                idx_shed = idx_ok[~placed]
                status[idx_shed] = SHED
                self.ladder.release(handles[idx_shed])
                rc["shed"] += len(idx_shed)
            rc["accepted"] += int(placed.sum())
        return status

    # -- dispatch ----------------------------------------------------------

    def pump(self, now: Optional[float] = None,
             force: bool = False) -> bool:
        """Harvest committed blocks (credit release), poll the SLO
        ladder, and dispatch one superstep block if the window
        triggered (or ``force``).  Host dict/numpy work only — the
        dispatch itself is the driver's async staged submit.

        Reads ride the same dispatch (ISSUE 20): a read block — or the
        zero block that keeps a PENDING batch's outcomes flowing —
        goes to the driver in the one call that stages and dispatches
        this pump's write block (ISSUE 36).  With no write work at all,
        read work still dispatches against a cached zero write block
        (same geometry, same compiled executable — no retrace)."""
        split = self.engine.pump_split
        for k in PUMP_SPLIT:
            split[k] = 0.0
        marks = self._stall_marks()
        with trace.phase_span("ra.pump", self.engine.phases, "pump",
                              "ingress") as sp:
            out = self._pump(now, force)
        if sp.dt_s > PUMP_SLOW_S:
            self._note_slow_pump(sp.dt_s, marks)
        return out

    def _stall_marks(self) -> tuple:
        """What a stall of the serve thread might be made of, as of a
        pump's start: compiles, window syncs, collections a GC
        generation."""
        return (devicewatch.WATCH.counters["xla_compiles"],
                self.engine.pipeline_counters["window_syncs"],
                [g["collections"] for g in gc.get_stats()])

    def _note_slow_pump(self, dt_s: float, marks: tuple) -> None:
        """A pump over PUMP_SLOW_S: counted, and a ``pump.slow`` event
        with its split by child (ms), its start on the profiler's clock
        (ns, as the Tracer stamps) and what rose while it ran."""
        self.counters["slow_pumps"] += 1
        compiles, syncs, gcs = marks
        n_comp = devicewatch.WATCH.counters["xla_compiles"] - compiles
        record("pump.slow", ms=round(dt_s * 1e3, 3),
               start_ns=time.time_ns() - int(dt_s * 1e9),
               split={k: round(v * 1e3, 3)
                      for k, v in self.engine.pump_split.items()},
               xla_compiles=n_comp,
               compiled=devicewatch.WATCH.compiled_names(n_comp),
               window_syncs=self.engine.pipeline_counters["window_syncs"]
               - syncs,
               gc_collections=[g["collections"] - n for g, n in
                               zip(gc.get_stats(), gcs)])

    def _pump(self, now: Optional[float], force: bool) -> bool:
        self._harvest()
        if self.slo is not None:
            # memoized with evaluate(): a per-pump poll is a dict hit
            self.ladder.on_verdict(self.slo.verdict("commit_p99_ms"))
        write_ready = (force or self.window.ready(now)) and \
            self.window.queue_rows() > 0
        if not write_ready and not self._read_work():
            return False
        # the read half of the dispatch this pump makes, popped here:
        # behind the sweep that staged the reads and the harvest that
        # freed their lanes, and handed to the same submit as this
        # pump's write block (or the zero block), so a read rides the
        # dispatch of the pump after its sweep and a lane that dispatch
        # serves gives its next batch to the next one
        read_blk = self._pop_read_block()
        read_ask = self._read_ask(read_blk)
        if write_ready:
            # the block's identifier, shared by its spans from pop to
            # retire (ra.pump.pop_block, ra.driver.stage,
            # ra.driver.dispatch, ra.pump.retire)
            block = self.counters["blocks_built"]
            t_pop = time.monotonic()
            room = self._ring_room()
            # the block leaves the host as the rows it carries when
            # they fit one of the driver's buckets (ra_densify rebuilds
            # the dense shape on the device); a fuller block goes dense
            padded = self.driver.flat_rows(self.window.block_rows(room))
            with trace.phase_span("ra.pump.pop_block", self.engine.phases,
                                  "pop_block", "ingress",
                                  block=block) as sp:
                if padded is not None:
                    n_new, rows, handles, take, row_base = \
                        self.window.pop_rows(room)
                else:
                    n_new, payloads, handles, take = \
                        self.window.pop_block(room)
                    handles = handles[np.arange(handles.shape[1])[None, :]
                                      < take[:, None]]
            self.engine.pump_split["pop"] += sp.dt_s
            row_lane = np.repeat(np.arange(len(take)), take)
            # what is still staged after a pop is what a lane's limits
            # (the block's window, the room in its ring) kept out of
            # this dispatch
            self.counters["lane_capped_rows"] += self.window.queue_rows()
            if padded is not None:
                self.driver.submit_rows(n_new, rows, row_base, take,
                                        read_blk=read_blk, block=block,
                                        read_ask=read_ask)
                self.counters["flat_blocks"] += 1
                self.counters["flat_rows_padded"] += padded
            else:
                self.driver.submit(n_new, payloads, read_blk=read_blk,
                                   block=block, read_ask=read_ask)
            self._dispatched_rows += take
            last_j = None
            if self._wal_lane_lo is not None:
                # per WAL shard, the last inner step with the block's
                # rows (-1: none): a lane's rows fill the dispatch's
                # steps in order, so its fullest lane's decides
                last_j = ((np.maximum.reduceat(take, self._wal_lane_lo) - 1)
                          // self.engine.max_step_cmds).tolist()
            self._inflight.append([self._dispatched_rows.copy(), handles,
                                   row_lane, block, t_pop,
                                   self.driver.staged,
                                   self.engine.pipeline_counters[
                                       "dispatches"], last_j])
            self.counters["blocks_built"] += 1
            self.counters["block_rows"] += len(handles)
        else:
            # reads-only dispatch: zero write rows, no write
            # bookkeeping — the read plane serves with zero log appends
            self.driver.submit(self._zero_wn, self._zero_wp,
                               read_blk=read_blk, read_ask=read_ask)
        self._carry_late_confirm()
        self._harvest()
        return True

    def _carry_late_confirm(self) -> None:
        """At a pump's tail, after its dispatch and WAL hand-off: where
        the WAL's horizon now covers the rows of an in-flight block
        whose confirm this pump's dispatch missed (it sampled before
        the rows were durable), carry that confirm to the device with
        the confirm-only program instead of waiting for the next
        dispatch, a loop cycle later, so the trailing harvest releases
        what it commits.  Only where every dispatch has been
        observed (no step runs, nothing is ahead of its readback) and
        the engine's state stands as its last dispatch left it (no
        member failed since, no election in it).  Otherwise, and where
        confirms land inside a cycle, one comparison a pump."""
        eng, drv = self.engine, self.driver
        if self._wal_lane_lo is None or not eng.confirm_ready():
            return
        drv.poll()
        if drv.in_flight():
            return
        need = self._uncarried_need()
        if need is None:
            return
        sample = eng._dur.confirm_sample()
        if not all(c >= n for c, n in zip(sample[1], need)):
            return
        with trace.phase_span("ra.pump.confirm_only", None,
                              "confirm_only", "ingress") as sp:
            drv.confirm_only(sample)
        eng.pump_split["confirm_only"] += sp.dt_s
        # the committed watermark moved without an observation
        self._harvested = -1

    def _uncarried_need(self) -> Optional[list]:
        """Per WAL shard, the step an in-flight block's rows need
        durable, for the oldest block whose confirm missed the
        dispatch after its own: one dispatched before the newest
        dispatch, whose sample does not cover it.  None where there is
        none: the newest dispatch's own block has missed nothing yet,
        the next dispatch carries it."""
        eng = self.engine
        n = eng.pipeline_counters["dispatches"]
        newest = eng.confirm_samples.get(n)
        if newest is None:
            return None
        for entry in self._inflight:
            if entry[1] is None or entry[7] is None or entry[6] >= n:
                continue
            need = self._durable_need(entry[6], entry[7])
            if need is not None and not all(
                    c >= x for c, x in zip(newest[2], need)):
                return need
        return None

    def _durable_need(self, own: int, last_j) -> Optional[list]:
        """Per WAL shard, the step a block's rows are durable at there
        (0 where it has none): the first step of its dispatch ``own``
        plus ``last_j``.  None where that dispatch's sample is no longer
        kept."""
        rec = self.engine.confirm_samples.get(own)
        return None if rec is None else \
            [rec[0] + j if j >= 0 else 0 for j in last_j]

    def _ring_room(self) -> np.ndarray:
        """Rows each lane's ring on the device still has room for
        (int64[N]): its capacity less the entries in use at the newest
        observed dispatch, less the rows dispatched since, less a slot
        for a term-opening noop and the one the engine keeps free.  The
        engine clips an append that overflows the ring, and a clipped
        row would be lost after its pop: so a lane never pops more than
        this, and the rest waits staged (``lane_capped_rows``)."""
        used = self.driver.last_ring_used
        unobserved = self._dispatched_rows - self._observed_rows
        if used is not None:
            unobserved = unobserved + used
        return np.maximum(self.engine.ring_capacity - 3 - unobserved, 0)

    def _committed_rows(self) -> Optional[np.ndarray]:
        lc = self.driver.last_committed
        if lc is None:
            return None
        return np.asarray(lc, np.int64) - self._base_committed

    def _read_work(self) -> bool:
        """Whether the read lane needs a dispatch: a read staged, or a
        lane's batch out and unsettled."""
        return self.reads_enabled and bool(
            self.read_window.queue_rows() > 0 or self._read_pend.any())

    def _pop_read_block(self):
        """Pop the read half of the driver's next dispatch: nothing
        (reads off / nothing to do), one read window over the lanes
        that have no batch out (at most Kr rows per lane, registered at
        inner step 0), or the cached ZERO block (no such lane has a
        read staged and some lane's batch is out — keeps the reply
        tensors riding every dispatch until it settles).  The caller
        hands it to the very next ``submit``."""
        with trace.phase_span("ra.pump.reads_pop", None, "reads",
                              "ingress") as sp:
            blk = self._pop_reads() if self.reads_enabled else None
        self.engine.pump_split["reads"] += sp.dt_s
        return blk

    def _read_rows(self, take) -> np.ndarray:
        """bool[L, Kr]: which slots of L lanes' batches of ``take``
        rows hold a read."""
        return np.arange(self.engine.read_window)[None, :] < take[:, None]

    def _read_ask(self, read_blk):
        """The rows the dispatch that carries ``read_blk`` can serve,
        whose replies alone the driver reads back: every row of every
        lane with a batch out once the block is popped, the batches of
        earlier dispatches too, as ``(lanes, rows)`` int32 vectors,
        lane by lane and each lane's rows from 0
        (``_emit_read_replies`` finds a row by that order).  None with
        no read block."""
        if read_blk is None:
            return None
        lanes = np.flatnonzero(self._read_pend)
        li, ri = np.nonzero(self._read_rows(self._read_take[lanes]))
        return lanes[li].astype(np.int32), ri.astype(np.int32)

    def _pop_reads(self):
        pend, rw = self._read_pend, self.read_window
        # the device holds one batch a lane and sheds a second that
        # arrives while the first is out: a pending lane gives no rows
        cap = np.where(pend, 0, self.engine.read_window)
        if rw.block_rows(cap) <= 0:
            if not pend.any():
                return None
            self.counters["read_zero_blocks"] += 1
            return self._zero_read_blk
        now = time.monotonic()
        n_r, read_q, handles, take = rw.pop_block(cap)
        popped = take > 0
        rows = self._read_rows(take)
        # read_staged_wait phase: a read staged by submit_reads to the
        # pop that takes it, one sample a pop: the mean over its rows,
        # which are those just swept and those that waited a cycle
        # behind their lane's batch, so a median would sit in one mode
        self.engine.phases.note(
            "read_staged_wait",
            float(np.mean(now - rw.last_pop_staged_at[rows])))
        nr_blk, rq_blk = (np.zeros_like(self._zero_read_blk[0]),
                          np.zeros_like(self._zero_read_blk[1]))
        nr_blk[0] = n_r[0]
        rq_blk[0] = read_q[0]
        self._read_handles[popped] = handles[popped]
        self._read_seqnos[popped] = rw.last_pop_seqnos[popped]
        self._read_take[popped] = take[popped]
        pend[popped] = True
        # the driver's next dispatch is the one this batch rides: an
        # observation of an earlier dispatch cannot settle it
        self._read_ordinal[popped] = self.driver.staged + 1
        self.read_counters["blocks_built"] += 1
        self.read_counters["block_rows"] += int(take.sum())
        self.counters["read_blocks"] += 1
        return (nr_blk, rq_blk)

    def _harvest_reads(self) -> None:
        """Settle the lanes' batches against the driver's observed
        read aux (drained in dispatch order), lane by lane.  Because
        the engine accepts a lane's batch whole-or-nothing and
        registers at most one batch per lane, each pending lane settles
        as exactly one of served (OK + replies at a certified
        watermark), arrival-shed (SHED: leader down / slot busy at
        registration), or stale-expired (REJECT: the device refused
        rather than serve past lease/quorum cover) — joined on the
        cumulative per-lane outcome deltas, replies from the rows each
        read-carrying dispatch read back.  The join holds only while an
        observation is never credited to a batch popped after it, so a
        lane answers to the dispatch its batch rode and those behind
        it, never to an older one; a settled lane is free for the next
        pop whatever the rest of the fleet awaits."""
        robs = self.driver.read_obs
        while robs:  # ra08-ok: per-OBSERVED-DISPATCH drain (<= in-flight cap entries), not per-session work
            obs = robs.popleft()
            shed_c = np.asarray(obs["read_shed_lanes"], np.int64)
            stale_c = np.asarray(obs["read_stale_lanes"], np.int64)
            live = self._read_pend & (self._read_ordinal <= obs["ordinal"])
            if live.any():
                done = obs.get("read_done")
                if done is not None:
                    done = np.asarray(done)
                    served = (done.sum(axis=0) > 0) & live
                    if served.any():
                        lanes = np.flatnonzero(served)
                        self._emit_read_replies(
                            lanes, OK, obs,
                            np.argmax(done[:, lanes] > 0, axis=0))
                        live &= ~served
                shed = ((shed_c - self._read_shed_base) > 0) & live
                if shed.any():
                    self._emit_read_replies(np.flatnonzero(shed), SHED)
                    live &= ~shed
                stale = ((stale_c - self._read_stale_base) > 0) & live
                if stale.any():
                    self._emit_read_replies(np.flatnonzero(stale), REJECT)
            self._read_shed_base = shed_c
            self._read_stale_base = stale_c

    def _emit_read_replies(self, lanes, status, obs=None,
                           k_idx=None) -> None:
        """Settle ``lanes`` (indices) with one outcome and fan it out
        to reply rows (a serve's from the observed dispatch ``obs``, at
        the inner step ``k_idx`` [L] that served each lane): free the
        lanes, release read credit, bump counters, and fire
        ``on_reads_done`` (the wire plane's READ_REPLY path) — one
        vectorized gather per outcome, rule RA08-gated like the
        coalescer."""
        self._read_pend[lanes] = False
        # (lane, row) of every read the batches hold
        li, ri = np.nonzero(self._read_rows(self._read_take[lanes]))
        nrows = len(li)
        if not nrows:
            return
        ln = lanes[li]
        h, s = self._read_handles[ln, ri], self._read_seqnos[ln, ri]
        st = np.full(nrows, status, np.int8)
        if obs is None:
            wm_rows = np.full(nrows, -1, np.int32)
            pay = np.zeros((nrows, self.engine.query_reply_width),
                           np.int32)
        else:
            wm_rows = np.asarray(obs["read_watermark"], np.int32)[
                k_idx[li], ln]
            # a served lane's batch was out when the dispatch was
            # made, so its rows are among those the dispatch asked
            # for, lane by lane: row ri of lane ln sits ri places
            # after the lane's first
            at = np.searchsorted(obs["read_ask"][0], ln) + ri
            pay = np.asarray(obs["read_reply_rows"], np.int32)[at]
        self.ladder.release(h)
        rc = self.read_counters
        self.counters["read_served_rows" if status == OK
                      else "read_refused_rows"] += nrows
        if status == OK:
            rc["served"] += nrows
            self._read_stale_flag = False
        elif status == SHED:
            rc["shed"] += nrows
        else:
            rc["stale_refused"] += nrows
            if not self._read_stale_flag:
                self._read_stale_flag = True
                record("read.stale", rows=nrows)
        if self.on_reads_done is not None:
            self.on_reads_done(h, s, st, wm_rows, pay)
            rc["replies_sent"] += nrows

    def _harvest(self) -> None:
        """Release credit for the rows the engine's committed watermark
        now covers, lane by lane: a row is released (and its ACK fanned
        out) when its own lane's committed count reaches the block's
        target for that lane, one vectorized release a block and
        observation, driven by the driver's async watermark
        readbacks, which it polls first (those that have arrived; no
        host sync).  Not block by block: a hot
        lane's last round confirms last, and a block that waited for
        its slowest lane made every cold lane's ACK wait with it (and
        every block behind it).  A block retires when its last row
        is released."""
        split = self.engine.pump_split
        with trace.phase_span("ra.pump.harvest", None, "harvest",
                              "ingress") as sp:
            # take every watermark that has arrived (non-blocking), so
            # both harvests of a pump, and a pump that dispatches
            # nothing, release what the device has committed
            self.driver.poll()
            with trace.phase_span("ra.pump.reads_harvest", None, "reads",
                                  "ingress") as rsp:
                if self.reads_enabled:
                    self._harvest_reads()
            split["reads"] += rsp.dt_s
            self._retire()
        split["harvest"] += sp.dt_s - rsp.dt_s

    def _retire(self) -> None:
        done = self._committed_rows()
        if done is None or self._harvested == self.driver.observed:
            return
        self._harvested = self.driver.observed
        for entry in self._inflight:
            target, handles, row_lane, block, t_pop, ordinal = entry[:6]
            # blocks are dispatched and observed in the order they
            # were staged: the rows of every block up to the driver's
            # count of observed dispatches are in ``last_ring_used``
            if ordinal <= self.driver.observed:
                self._observed_rows = target
            if handles is None:             # retired, behind a live block
                continue
            ready = done[row_lane] >= target[row_lane]
            if ready.all():
                self._release("ra.pump.retire", block, handles)
                entry[1] = None
                # block_e2e phase: pop to the harvest that retires the
                # block (what a commit costs in loop cycles)
                now = time.monotonic()
                self.engine.phases.note("block_e2e", now - t_pop)
                if entry[7] is not None:
                    self._note_commit_split(t_pop, entry[6], entry[7], now)
            elif ready.any():
                self._release("ra.pump.release", block, handles[ready])
                entry[1], entry[2] = handles[~ready], row_lane[~ready]
        while self._inflight and self._inflight[0][1] is None:
            self._inflight.popleft()

    def _note_commit_split(self, t_pop: float, own: int, last_j,
                           now: float) -> None:
        """Split a retired block's ``block_e2e`` (``t_pop`` to ``now``)
        at the instant its rows became durable and at its carrier's
        sample of the confirm horizon (ISSUE 37).  Its rows in WAL
        shard s are durable once that shard's horizon reaches the
        dispatch's first step plus ``last_j[s]``; the carrier is the
        first dispatch after ``own`` whose sample covered each of
        those steps, the dispatch that brought the confirm to the
        device, which the commit waited for, or a confirm-only program
        run between two dispatches (``confirm_only_blocks``).  A block
        is late when its carrier comes after the sample of the dispatch
        directly after its own.  A stamp that is not
        there (no carrier in the engine's samples: an election's
        truncation lowered a confirm, or the block outlived
        CONFIRM_SAMPLES dispatches) falls on the next, so the three
        phases still sum to ``block_e2e``, each at least 0."""
        eng = self.engine
        samples, extra = eng.confirm_samples, eng.confirm_only_samples
        counters = eng.pipeline_counters
        need = self._durable_need(own, last_j)
        t_carry = t_dur = now
        if need is not None:
            for d in range(own, counters["dispatches"] + 1):
                # dispatch d's sample, then a confirm-only one after it
                rec = samples.get(d) if d > own else None
                if rec is not None and all(
                        c >= n for c, n in zip(rec[2], need)):
                    t_carry = rec[1]
                    if d > own + 1:
                        counters["confirm_late_blocks"] += 1
                    break
                rec = extra.get(d)
                if rec is not None and all(
                        c >= n for c, n in zip(rec[1], need)):
                    t_carry = rec[0]
                    counters["confirm_only_blocks"] += 1
                    if d > own:
                        counters["confirm_late_blocks"] += 1
                    break
            t_dur = eng._dur.confirmed_at(need)
            t_dur = t_carry if t_dur is None else min(t_dur, t_carry)
        phases = eng.phases
        phases.note("durable_wait", t_dur - t_pop)
        phases.note("confirm_carry", t_carry - t_dur)
        phases.note("commit_observe", now - t_carry)

    def _release(self, span: str, block: int, handles: np.ndarray) -> None:
        with trace.span(span, "ingress", block=block):
            self.counters["credits_released"] += \
                self.ladder.release(handles)
            if self.on_block_committed is not None and len(handles):
                self.on_block_committed(handles)

    def settle(self, timeout: float = 30.0) -> None:
        """Flush everything: drain the window, dispatch, and drive
        empty supersteps until the committed watermark covers every
        dispatched row (write-delay / durable-confirm settling), then
        release all remaining credit.  A barrier — never on the hot
        path."""
        with trace.span("ra.settle", "ingress"):
            self._settle(timeout)

    def _settle(self, timeout: float) -> None:
        while self.window.queue_rows() > 0:
            self.pump(force=True)
        self.driver.drain()
        self._harvest()
        deadline = time.monotonic() + timeout
        while self._inflight or self._read_work():
            # same block shapes as the pump path: reuses the compiled
            # fused executable rather than retracing a new geometry.
            # Pending reads ride along until they serve or the device
            # read_timeout expires them — settlement always terminates.
            # No dispatch without its read half: a batch that is out
            # may be served by any dispatch, and is seen only on a
            # read-carrying dispatch's outcomes
            read_blk = self._pop_read_block()
            self.driver.submit(self._zero_wn, self._zero_wp,
                               read_blk=read_blk,
                               read_ask=self._read_ask(read_blk))
            self.driver.drain()
            self._harvest()
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"ingress settle: {len(self._inflight)} blocks "
                    "still uncommitted")

    # -- observability -----------------------------------------------------

    def gauges(self, credit_in_use: Optional[int] = None) -> dict:
        out = {
            "sessions": int(self.directory.n_sessions),
            "tenants": self.directory.n_tenants,
            "queue_rows": self.window.queue_rows(),
            "inflight_blocks": len(self._inflight),
            # lanes whose read batch is out and unsettled
            "read_lanes_pending": int(self._read_pend.sum())
            if self.reads_enabled else 0,
            "level": self.ladder.level,
            # O(sessions) sum: overview() passes the ladder's value in
            # so one snapshot does the full-array reduction ONCE
            "credit_in_use": int(self.ladder.used.sum())
            if credit_in_use is None else credit_in_use,
        }
        dur = getattr(self.engine, "_dur", None)
        if dur is not None:
            # the durability half of the backlog: ingress queue depth
            # + unconfirmed steps = the node's uncommitted total
            out["wal_pending_steps"] = dur.pending_steps()
        return out

    def overview(self) -> dict:
        """The Observatory ``ingress`` source: INGRESS_FIELDS counters
        + flow gauges, one flat numeric namespace (ring keys
        ``ingress_<field>``)."""
        lad = self.ladder.overview()
        return {**self.counters,
                **self.gauges(credit_in_use=lad["credit_in_use"]),
                "ladder": lad,
                "window": self.window.overview()}

    def read_overview(self) -> dict:
        """The Observatory ``read`` source: READ_FIELDS counters + read
        flow gauges (flat ring keys ``read_<field>``).  ``lease_served``
        is filled from the device's cumulative served-under-lease
        counter at snapshot time (the observability pull path — the hot
        path never syncs for it); ``lease_coverage_pct`` is the
        served-under-lease share, the ra_top read panel's headline."""
        out = dict(self.read_counters)
        if self.reads_enabled:
            leased = int(np.asarray(
                self.engine.state.read_leased).astype(np.int64).sum())
            out["lease_served"] = leased
            served_dev = int(np.asarray(
                self.engine.state.read_served).astype(np.int64).sum())
            out["lease_coverage_pct"] = \
                100.0 * leased / max(1, served_dev)
            out["queue_rows"] = self.read_window.queue_rows()
            out["pending_lanes"] = int(self._read_pend.sum())
        return out

    def attach(self, observatory) -> "IngressPlane":
        """Register this plane as the Observatory's ``ingress`` (and,
        reads enabled, ``read``) source (``Observatory.for_engine``
        wires it automatically when the engine carries an attached
        plane)."""
        observatory.add_source("ingress", self.overview)
        if self.reads_enabled:
            observatory.add_source("read", self.read_overview)
        return self

    def bench_row(self, elapsed_s: float) -> dict:
        """A soak tail row carrying the ingress regression keys
        (``ingress_cmds_per_s`` higher-is-better,
        ``ingress_shed_rate`` lower-is-better), plus the
        device-plane stamp (ISSUE 16): the ingress pump is one of the
        four steady-state dispatch loops, so its tail carries
        ``n_compiles``/``compile_time_s``/``transfer_bytes``/
        ``peak_live_bytes`` like the other soak tails."""
        from .. import devicewatch
        c = self.counters
        accepted = c["accepted"]
        submitted = max(1, c["submitted"])
        row = {
            "value": accepted / max(elapsed_s, 1e-9),
            "ingress_cmds_per_s": accepted / max(elapsed_s, 1e-9),
            "ingress_shed_rate": c["shed_rows"] / submitted,
            "ingress_accepted": accepted,
            "ingress_submitted": c["submitted"],
            "ingress_dup_dropped": c["dup_dropped"],
            "elapsed_s": elapsed_s,
            **devicewatch.bench_tail_keys(commands=accepted),
        }
        if self.reads_enabled:
            # read-frontier regression keys (ISSUE 20, higher-better
            # read_cmds_per_s joined by the read_p99_ms phase key the
            # SLO engine stamps)
            rc = self.read_counters
            row["read_cmds_per_s"] = rc["served"] / max(elapsed_s, 1e-9)
            row["read_served"] = rc["served"]
            row["read_shed_rate"] = rc["shed"] / max(1, rc["submitted"])
            row["read_stale_refused"] = rc["stale_refused"]
        return row
