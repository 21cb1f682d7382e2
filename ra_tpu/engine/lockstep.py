"""Lockstep multi-Raft lane engine — thousands of co-hosted clusters as one
XLA program.

This is the TPU-native inversion of the reference's process-per-server
design (SURVEY.md §7.1): instead of one gen_statem per member
(ra_server_proc.erl), *all* members of *all* co-hosted clusters live in SoA
device arrays with a leading lane axis, and one jitted ``step`` advances
every cluster simultaneously:

  1. leader append     — host-enqueued command batches land in a device
                         payload ring (the host→HBM entry ring; the
                         fan-in role of ra_log_wal.erl:193-214)
  2. replication       — followers adopt the leader tail, bounded by the
                         per-peer pipeline window (ra_server.hrl:7)
  3. write confirm     — last_written tracks the WAL fsync confirm; with
                         ``write_delay=1`` it lags one step, reproducing
                         the async written-event protocol (ra_log.erl:474+)
  4. reply fold + quorum — ops.quorum.update_match_next / evaluate_quorum
                         (ra_server.erl:418-454, 2941-2993)
  5. apply fold        — lax.scan over the committed window, vmapped over
                         (lane, member), calling the machine's jit_apply
                         (the ra_machine_xla contract; host machines use
                         the oracle path instead)

Rare/divergent transitions (member failure, election, membership change)
are host-initiated: the host failure detector marks members down and
requests elections via mask inputs; the vote round itself runs on-device
— candidate selection by best durable log, per-voter grant decisions,
and counted quorum via ops.quorum.election_quorum — so a minority
partition cannot seat a leader (ra_server.erl:986-1002, 2260-2319).
Divergent follower tails (a healed deposed leader's uncommitted
entries) are truncated by an every-step consistency clamp before the
quorum fold reads them (ra_server.erl:1032-1156), and replication is
governed by the pipeline_credit flow-control kernel
(ra_server.erl:1862-1918).

The lane axis is embarrassingly parallel: sharding it over a
jax.sharding.Mesh scales co-hosted clusters across chips with zero
cross-lane collectives (see ra_tpu.parallel.mesh).
"""
from __future__ import annotations

import bisect
import collections
import functools
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import devicewatch, trace
from ..blackbox import record
from ..core.machine import JitMachine
from ..metrics import ENGINE_PIPELINE_FIELDS, TELEMETRY_FIELDS
from ..ops.exact import split16_matmul
from ..ops.quorum import (election_quorum, evaluate_quorum, pipeline_credit,
                          query_quorum, update_match_next)

Array = jax.Array


class _Dispatch(NamedTuple):
    """The appends of one dispatch as its rounds make them.
    The rounds leave the ring alone: each enters where its rows went
    (``tail``, ``n_acc``, ``elect_ok`` at row ``k``), the apply window
    reads this dispatch's rows beside the ring (`_dispatch_window`),
    and `_ring_write` places what survives once, after the last round.
    ``ring`` and ``rows`` are the dispatch's inputs, only read; the
    rest is small and rides the scan's carry."""

    ring: Array      # [N,R,C] the ring as the dispatch found it
    rows: Array      # [N,K*Kc,C] the dispatch's payload rows, round-major
    k: Array         # int32[]   the round running
    tail: Array      # int32[K,N] each round's leader tail before its append
    n_acc: Array     # int32[K,N] payload rows each round appended
    elect_ok: Array  # bool[K,N] rounds that appended a term's noop
    taken: Array     # int32[N]  window rows, with `do`, the round took
                     #           from ``rows`` (``window_dispatch_rows``)


def _open_dispatch(ring: Array, payloads_blk: Array) -> _Dispatch:
    """The `_Dispatch` of ``payloads_blk`` [K, N, Kc, C] before its first
    round: no round has appended yet."""
    K, N, Kc, C = payloads_blk.shape
    rows = jnp.moveaxis(payloads_blk, 0, 1).reshape(N, K * Kc, C)
    return _Dispatch(ring, rows.astype(ring.dtype), jnp.int32(0),
                     jnp.zeros((K, N), jnp.int32),
                     jnp.zeros((K, N), jnp.int32), jnp.zeros((K, N), bool),
                     jnp.zeros((N,), jnp.int32))


def _last_writes(slot: Array, tail: Array, n_acc: Array, elect_ok: Array,
                 R: int, Kc: int) -> tuple:
    """Which append of a dispatch each ring slot in ``slot`` (int32[N, S])
    holds once its rounds have run: ``(written, col)``, whether a round
    wrote the slot, and the column of `_Dispatch.rows` whose row it
    holds, -1 for a term's noop (zeros).  Round j appends its rows at
    slots (tail[j] + 0 .. n_acc[j] - 1) % R (entry i lives at slot
    (i - 1) % R) and, where elect_ok[j], the noop at the slot after
    them; the latest round that wrote a slot wins.  A round not yet run
    has n_acc 0 and no noop.  A loop over the K rounds, so that nothing
    here is larger than [N, S]."""
    written = jnp.zeros(slot.shape, bool)
    col = jnp.full(slot.shape, -1, jnp.int32)
    for j in range(tail.shape[0]):
        rel = (slot - tail[j][:, None]) % R
        row = rel < n_acc[j][:, None]
        hit = row | ((rel == n_acc[j][:, None]) & elect_ok[j][:, None])
        written = written | hit
        col = jnp.where(hit, jnp.where(row, j * Kc + rel, -1), col)
    return written, col


def _rows_at(rows: Array, col: Array, *, impl: str) -> Array:
    """``rows[n, col[n, s]]`` [N, S, C] for int32 ``col`` [N, S], zeros
    where ``col`` is outside the rows: a one-hot matmul ('onehot') or an
    along-axis gather ('gather')."""
    if impl == "onehot":
        oh = (col[..., None] == jnp.arange(rows.shape[1])).astype(
            jnp.float32)
        return split16_matmul(oh, rows)
    inside = (col >= 0) & (col < rows.shape[1])
    got = jnp.take_along_axis(
        rows, jnp.broadcast_to(jnp.where(inside, col, 0)[..., None],
                               col.shape + rows.shape[-1:]), axis=1)
    return jnp.where(inside[..., None], got, jnp.zeros((), rows.dtype))


def _ring_write(ring: Array, rows: Array, tail: Array, n_acc: Array,
                elect_ok: Array, *, impl: str) -> Array:
    """Place a dispatch's appends into the ring, once, after its K
    rounds: round j's ``n_acc[j]`` rows of ``rows`` [N, K*Kc,
    C] (its Kc columns from j*Kc) at the slots of entries tail[j]+1 ..
    tail[j]+n_acc[j] (slot (idx-1) % R), and on a won election
    (``elect_ok[j]``) the zero-payload term-opening noop after them; a
    later round's write of a slot wins.  This is what K rounds that each
    wrote their own rows leave, and it is written once because nothing
    in a dispatch reads the ring but stage 5's window, which takes this
    dispatch's rows from ``rows`` (`_dispatch_window`).  Written every
    round, it took 10.38 ms a round of a 26.56 ms step at 10,000 lanes
    x 1,024 slots x 64 words on a v5e (PERF.md section 6).

    impl='onehot': one-hot matmuls over the whole ring (MXU path), the
    top byte as uint8 (`split16_matmul`), in ceil(K*Kc / C) passes of
    at most C columns each, no more than K, so that no one-hot is wider
    than the ring's own words; a noop's slot has no column and is
    written as zeros through the mask.  No form that touches only the
    rows a lane appends came near a pass of it on that chip: a row
    scatter (lanes a batching dimension, or flat; masked rows dropped
    out of bounds or read back and merged) 50 to 66 ms, a block
    read-modify-write 59, a ``dynamic_update_slice`` a lane 92: it
    scatters an index at a time and keeps the ring slot-minor (PERF.md
    section 6).
    impl='gather': one put_along_axis of every candidate row, each
    carrying what its slot holds after the dispatch (the last write's
    row, or the slot's own words where nothing wrote it), so that
    repeated slots agree.  Cheap on a CPU; 262 ms a round on the v5e."""
    N, R, C = ring.shape
    K = tail.shape[0]
    Kc = rows.shape[1] // K
    if impl == "onehot":
        written, col = _last_writes(
            jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32), (N, R)),
            tail, n_acc, elect_ok, R, Kc)
        M = K * Kc
        passes = max(1, min(-(-M // C), K))
        width = -(-M // passes)
        for q in range(passes):
            lo = q * width
            mask = written & (col >= lo) & (col < lo + width)
            if q == 0:
                mask = mask | (written & (col < 0))          # the noops
            ring = jnp.where(
                mask[..., None],
                _rows_at(rows[:, lo:lo + width], col - lo, impl=impl),
                ring)
        return ring
    dest = jnp.moveaxis((tail[..., None] + jnp.arange(Kc + 1)) % R, 0, 1)
    dest = dest.reshape(N, K * (Kc + 1))                     # [N,K*(Kc+1)]
    written, col = _last_writes(dest, tail, n_acc, elect_ok, R, Kc)
    dest3 = jnp.broadcast_to(dest[..., None], dest.shape + (C,))
    vals = jnp.where(written[..., None], _rows_at(rows, col, impl=impl),
                     jnp.take_along_axis(ring, dest3, axis=1))
    return jnp.put_along_axis(ring, dest3, vals, axis=1, inplace=False)


def _ring_read_window(ring: Array, idx_lane: Array, *, impl: str) -> Array:
    """Read the per-lane entry window ``idx_lane`` (int32[N,A], entry
    indexes) from the ring: [N,A,C].  Slot mapping (idx-1) % R.  Inside
    a dispatch this is the ring as the dispatch found it: its own rows
    are placed after its last round (`_ring_write`), and
    `_dispatch_window` lays them over this read.

    On a v5e at 10,000 lanes x 1,024 slots x 64 words: impl='onehot'
    7.7 ms a round (two passes over the ring at the chip's bandwidth);
    impl='gather' (element by element) 172.  A row gather is 5.0 alone,
    but it wants the ring word-minor where the append and the device
    keep it slot-minor, and the transposition that forces every round
    made the whole step slower: 63.5 ms a round against 57.0 (PERF.md
    section 6, PR 30)."""
    N, R, C = ring.shape
    slot = (idx_lane - 1) % R
    if impl == "onehot":
        oh = (slot[:, :, None] ==
              jnp.arange(R)[None, None, :]).astype(jnp.float32)
        return split16_matmul(oh, ring)
    return jnp.take_along_axis(
        ring, jnp.broadcast_to(slot[..., None], slot.shape + (C,)),
        axis=1)


def _dispatch_window(window: Array, idx_lane: Array, d: _Dispatch, *,
                     impl: str) -> tuple:
    """Stage 5's window as a ring written every round would hold it:
    ``window`` (`_ring_read_window` of ``d.ring``, [N, A, C]) with each
    slot that a round of this dispatch up to ``d.k`` appended taken from
    that round's row of ``d.rows``, zeros for a noop, the latest round's
    where several wrote it.  A one-hot over the dispatch's K*Kc rows,
    [N, A, K*Kc].  Returns it and the bool[N, A] mask of the slots taken
    so.  Where the confirm horizon comes from WAL rows of earlier
    dispatches (the durable served path) no round applies a row taken
    here; ``window_dispatch_rows`` counts those that are."""
    R = d.ring.shape[1]
    written, col = _last_writes((idx_lane - 1) % R, d.tail, d.n_acc,
                                d.elect_ok, R,
                                d.rows.shape[1] // d.tail.shape[0])
    return jnp.where(written[..., None], _rows_at(d.rows, col, impl=impl),
                     window), written


class LaneTelemetry(NamedTuple):
    """Device-resident per-lane telemetry accumulators (ISSUE 6): the
    ``[lanes]``-shaped int32 pytree updated by every jitted step —
    which of 100k lanes is stuck, churning leaders or lagging commit,
    answerable without a host-syncing readback.  Field meanings are the
    registry's (metrics.TELEMETRY_FIELDS, parity pinned by tests);
    aggregation to histograms/top-K happens in :func:`_telemetry_summary`
    at sampling cadence, NOT per step.  All fields share int32[N] so the
    pytree donates, shards (lanes axis) and checkpoints exactly like
    the rest of LaneState — it rides inside it."""

    elections_requested: Array  # int32[N] host-requested vote rounds
    elections_won: Array        # int32[N] rounds that seated a leader
    leader_changes: Array       # int32[N] leader moved to another slot
    leader_age: Array           # int32[N] steps since last leader change
    commit_lag: Array           # int32[N] leader tail - leader commit
    apply_lag: Array            # int32[N] leader commit - apply frontier
    stall_steps: Array          # int32[N] consecutive no-progress rounds
                                #          with a nonempty commit backlog
    steps: Array                # int32[N] engine rounds observed


assert LaneTelemetry._fields == TELEMETRY_FIELDS  # registry parity


def _init_telemetry(n_lanes: int) -> LaneTelemetry:
    # one zeros() PER field: sharing a single array across the fields
    # would alias one device buffer 8 ways, and the donating superstep
    # path rejects a donated buffer appearing twice in an Execute()
    return LaneTelemetry(*(jnp.zeros((n_lanes,), jnp.int32)
                           for _ in LaneTelemetry._fields))


class LaneState(NamedTuple):
    """SoA state for N lanes × P member slots (ra_server_state() flattened —
    the per-lane scalars and per-lane×peer fields listed in SURVEY.md §7.1)."""

    term: Array           # int32[N]   shared current term (steady state)
    leader_slot: Array    # int32[N]   which slot leads the lane
    term_start: Array     # int32[N]   index of this term's noop (§5.4.2 gate)
    last_index: Array     # int32[N,P] per-member log tail
    last_written: Array   # int32[N,P] fsync-confirmed tail
    match: Array          # int32[N,P] leader's view (own slot = own written)
    next_index: Array     # int32[N,P] per-peer send cursor
    commit: Array         # int32[N,P] per-member commit index
    applied: Array        # int32[N,P] per-member last applied
    voter: Array          # bool[N,P]  voting members
    active: Array         # bool[N,P]  member exists and is up
    ring: Array           # int32/…[N,R,C] payload ring (device log window)
    ring_base: Array      # int32[N]   reclaim horizon (entries <= base may
                          #            be recycled; mapping is (idx-1) % R)
    total_committed: Array  # int32[N] cumulative committed entries per lane
    query_index: Array    # int32[N]   consistent-query counter
                          #            (ra_server.erl:3035-3071)
    peer_query: Array     # int32[N,P] per-member confirmed query index
                          #            (#heartbeat_reply, :3101-3170)
    query_agreed: Array   # int32[N]   majority-confirmed query index
    # -- vectorized read plane (ISSUE 20): leases + read-index state ------
    read_clock: Array     # int32[N]   monotone step clock (lease base)
    lease_until: Array    # int32[N]   leader lease expiry, read_clock units
    read_buf: Array       # [N,Kr,Cq]  pending read-query batch (device)
    read_n: Array         # int32[N]   pending read count (0 = slot free)
    read_ix: Array        # int32[N]   captured read index (commit at reg.)
    read_tok: Array       # int32[N]   captured heartbeat token (reg. round)
    read_reg: Array       # int32[N]   registration clock (timeout base)
    read_served: Array    # int32[N]   cumulative reads served
    read_shed: Array      # int32[N]   cumulative reads shed at arrival
    read_stale: Array     # int32[N]   cumulative stale-refusals (timeouts)
    read_leased: Array    # int32[N]   served-under-lease subset
    telem: Any            # LaneTelemetry pytree, int32[N] per field
    mac: Any              # machine state pytree, leading dims [N,P]


#: RA15 checkpoint schema registry (ISSUE 15): per-field restore
#: behaviour for archives written BEFORE the field existed.  Every
#: LaneState field MUST have an entry (the static gate pins parity
#: with ``LaneState._fields``), so adding a pytree field forces the
#: author to declare its forward-compat default here — and
#: :meth:`LockstepEngine.restore` fills it generically, so a
#: checkpoint format bump can never strand a durable dir again (the
#: PR 6 pre-telemetry ``restore()`` KeyError, closed for every future
#: field, not just ``telem``).
#:
#:   "require" — consensus-bearing state every archive has always
#:               carried; a missing leaf is a corrupt archive, refuse
#:   "zeros"   — derived/health state that restarts from zero
#:               (zeros_like the restoring engine's leaf)
#:   "init"    — keep the restoring engine's CURRENT value for the
#:               field (for fields whose zero is not the correct
#:               default, e.g. a future all-ones mask).  In the
#:               open_engine recovery path the engine is freshly
#:               constructed before restore(), so this IS the
#:               fresh-init value; a mid-run rollback keeps the live
#:               value — callers wanting a true re-init must restore
#:               into a fresh engine
CHECKPOINT_FIELD_DEFAULTS = {
    "term": "require",
    "leader_slot": "require",
    "term_start": "require",
    "last_index": "require",
    "last_written": "require",
    "match": "require",
    "next_index": "require",
    "commit": "require",
    "applied": "require",
    "voter": "require",
    "active": "require",
    "ring": "require",
    "ring_base": "require",
    "total_committed": "require",
    "query_index": "require",
    "peer_query": "require",
    "query_agreed": "require",
    # read plane (ISSUE 20): ALL "zeros" — a lease must never survive a
    # restart (the restarting process has no idea how long it was down,
    # so an archived lease could outlive the wall-clock grant), and a
    # pending read batch's client is gone; cumulative read counters are
    # health state like telem
    "read_clock": "zeros",
    "lease_until": "zeros",
    "read_buf": "zeros",
    "read_n": "zeros",
    "read_ix": "zeros",
    "read_tok": "zeros",
    "read_reg": "zeros",
    "read_served": "zeros",
    "read_shed": "zeros",
    "read_stale": "zeros",
    "read_leased": "zeros",
    "telem": "zeros",       # health counters: restart from zero
    "mac": "require",
}


def _init_state(n_lanes: int, n_members: int, ring_capacity: int,
                payload_width: int, mac_state: Any,
                payload_dtype=jnp.int32, read_window: int = 1,
                query_width: int = 1,
                query_dtype=jnp.int32) -> LaneState:
    N, P, R, C = n_lanes, n_members, ring_capacity, payload_width
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    return LaneState(
        term=jnp.ones((N,), jnp.int32),
        leader_slot=z(N),
        term_start=jnp.ones((N,), jnp.int32),
        last_index=z(N, P),
        last_written=z(N, P),
        match=z(N, P),
        next_index=jnp.ones((N, P), jnp.int32),
        commit=z(N, P),
        applied=z(N, P),
        voter=jnp.ones((N, P), bool),
        active=jnp.ones((N, P), bool),
        ring=jnp.zeros((N, R, C), payload_dtype),
        ring_base=z(N),
        total_committed=jnp.zeros((N,), jnp.int32),
        query_index=z(N),
        peer_query=z(N, P),
        query_agreed=z(N),
        read_clock=z(N),
        lease_until=z(N),
        read_buf=jnp.zeros((N, read_window, query_width), query_dtype),
        read_n=z(N),
        read_ix=z(N),
        read_tok=z(N),
        read_reg=z(N),
        read_served=z(N),
        read_shed=z(N),
        read_stale=z(N),
        read_leased=z(N),
        telem=_init_telemetry(N),
        mac=mac_state,
    )


def _lead(mask: Array, like: Array) -> Array:
    """``mask`` (over the leading dims of ``like``) shaped to broadcast
    against it."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


def _lane_fold_fits(mac, ring: Array) -> bool:
    """The shape rule of stage 5's lane path, decided at trace time:
    taken only where the machine's state, all leaves and all members,
    is no larger in bytes than the ring.  The lane path hands the
    representative's result to the members, which rewrites the state
    once a round; the step already streams the ring every round
    (stage 5's window read), so a hand-over no larger than it cannot
    set the step's pace.  A state larger than the ring is a table (6 GB of
    records beside a ring of 0.23 GB at ``ycsb_kv_2k_x3``), whose fold
    moves the words it writes and nothing else, and a table is never
    copied: its step keeps the per-member fold and lowers as it did
    before there was a lane path."""
    def nbytes(x):
        return x.size * x.dtype.itemsize
    return sum(nbytes(x) for x in jax.tree.leaves(mac)) <= nbytes(ring)


def _confirm_quorum(last_index: Array, last_written0: Array, match0: Array,
                    next0: Array, commit0: Array, total_committed0: Array,
                    active: Array, voter: Array, leader_slot: Array,
                    term_start: Array, confirm_upto: Array, *,
                    durable: bool, write_delay: int, elect_ok=None,
                    leader_written=None, last_index0=None) -> tuple:
    """Stages 3 (write confirm) and 4 (reply fold + quorum) of a round,
    the one copy of the commit rule: ``_step`` runs them after the
    round's append and replication, ``ra_confirm`` alone on the state
    a dispatch left (no election there: ``elect_ok`` None).  Returns
    ``(last_written, match, next_index, commit, total_committed,
    leader_commit0, delta)``: the leader's commit before the fold and
    what the fold added to it, which the later stages read."""
    # -- 3. write confirm (async WAL protocol) ----------------------------
    with jax.named_scope("ra.s3_confirm"):
        if durable:
            # real confirms: the host feeds back the fan-in WAL's durable
            # horizon; nothing beyond it enters the quorum median.  On a won
            # election the horizon is additionally capped at the new leader's
            # pre-noop written tail: the truncated suffix's indexes are being
            # REUSED by fresh entries, so a confirm that covered the old
            # suffix must not vouch for the replacements (the (index,term)
            # identity of the written-event protocol, ra_log.erl:474+)
            eff_confirm = confirm_upto if elect_ok is None else jnp.where(
                elect_ok, jnp.minimum(confirm_upto, leader_written),
                confirm_upto)
            last_written = jnp.where(active,
                                     jnp.minimum(last_index,
                                                 eff_confirm[:, None]),
                                     last_written0)
        elif write_delay == 0:
            last_written = jnp.where(active, last_index, last_written0)
        else:
            # confirms lag one step: this step confirms the *previous* tail
            last_written = jnp.where(active,
                                     jnp.minimum(last_index, last_index0),
                                     last_written0)
        last_written = jnp.minimum(last_written, last_index)

    # -- 4. reply fold + quorum -------------------------------------------
    with jax.named_scope("ra.s4_quorum"):
        match, _ = update_match_next(match0, next0,
                                     active, last_written, last_index + 1)
        # lockstep has perfect reply information, so the send cursor tracks the
        # follower tail directly — in particular it *decreases* after a
        # divergence truncation, reopening credit (the reference's next_index
        # decrement on failed AER, ra_server.erl:477-529)
        next_index = jnp.where(active, last_index + 1, next0)
        leader_commit0 = jnp.take_along_axis(
            commit0, leader_slot[:, None], axis=-1)[:, 0]
        # NB: down members stay in the quorum denominator (their match just
        # freezes) — a leader that lost a majority must stop committing
        new_leader_commit = evaluate_quorum(leader_commit0, match,
                                            voter, term_start)
        # followers learn commit via the (lockstep) AER broadcast, bounded by
        # their own log (evaluate_commit_index_follower: min(last_index, CI))
        commit = jnp.minimum(new_leader_commit[:, None], last_index)
        commit = jnp.where(active, jnp.maximum(commit, commit0), commit0)
        delta = jnp.take_along_axis(
            commit, leader_slot[:, None], axis=-1)[:, 0] - leader_commit0
        total_committed = total_committed0 + delta
    return (last_written, match, next_index, commit, total_committed,
            leader_commit0, delta)


def _step(state: LaneState, n_new: Array, payloads: Array,
          fail_mask: Array, elect_mask: Array, confirm_upto: Array,
          query_mask: Array, n_read: Array, read_q: Array, *,
          machine: JitMachine, ring_capacity: int, apply_window: int,
          pipeline_window: int, max_append_batch: int, write_delay: int,
          durable: bool = False, ring_io: str = "gather",
          lease_ttl: int = 8, read_timeout: int = 64,
          dispatch: Optional[_Dispatch] = None):
    """One lockstep round for every lane.  Pure; jitted by the engine.

    Returns ``(new_state, aux)`` where aux carries the per-lane append
    outcome the host needs to form the step's WAL record in durable mode:
    ``appended_hi`` (the leader tail after this step) and ``n_acc`` (how
    many of the host batch were accepted — the rest were clipped by ring
    backpressure or a down leader).

    ``confirm_upto`` (int32[N]) is the durability horizon fed back from
    the fan-in WAL: with ``durable=True``, ``last_written`` only advances
    to it, so the commit quorum counts nothing that has not really been
    fsynced (the {written,..} notify protocol, ra_log_wal.erl:753-800);
    the ``write_delay`` emulation is bypassed.

    ``dispatch``: the round is round ``dispatch.k`` of `_superstep`'s
    scan.  It then writes no ring (``state.ring`` is not read, and
    passes through): it enters its appends in the `_Dispatch`, reads
    its window from ``dispatch.ring`` and the dispatch's rows, and
    returns ``(new_state, aux, dispatch)``; the scan places the rows
    once after its last round.  Without it the round is a dispatch of
    its own (K = 1), its rows placed into the ring before it returns."""
    N, P = state.last_index.shape
    R = ring_capacity
    lane = jnp.arange(N)
    alone = dispatch is None
    if alone:  # ra13-ok: a Python bool, whether a scan passed its dispatch
        with jax.named_scope("ra.s1_append"):
            dispatch = _open_dispatch(state.ring, payloads[None])

    # -- 0. failures, divergence repair, elections ------------------------
    with jax.named_scope("ra.s0_elect"):
        active = state.active & ~fail_mask

        # divergence repair (the AER consistency-check outcome,
        # ra_server.erl:1032-1156): an active non-leader's tail can never
        # extend past its leader's log — entries beyond it are uncommitted
        # leftovers of a deposed leader and are truncated before anything
        # (quorum, apply) can read them.  Runs before the match fold so a
        # healed ex-leader's stale tail never enters the commit median.
        leader_arm0 = jax.nn.one_hot(state.leader_slot, P, dtype=jnp.bool_)
        cur_leader_last = jnp.take_along_axis(
            state.last_index, state.leader_slot[:, None], axis=-1)[:, 0]
        clamp = active & ~leader_arm0
        last_index0 = jnp.where(
            clamp, jnp.minimum(state.last_index, cur_leader_last[:, None]),
            state.last_index)
        last_written0 = jnp.minimum(state.last_written, last_index0)

        # election: the host requests one (elect_mask); the device runs the
        # vote round.  Candidate = active voter with the longest durable log
        # (the member a pre-vote round converges on, §5.4.1); each reachable
        # voter grants iff the candidate's log is up-to-date vs its own
        # (process_pre_vote/request_vote, ra_server.erl:2260-2319, 1211-1251);
        # the candidacy succeeds only on a counted quorum of grants
        # (election_quorum, ra_server.erl:986-1002).  A minority partition
        # therefore cannot elect: term and leader stay put.
        score = jnp.where(active & state.voter, last_written0, -1)
        cand = jnp.argmax(score, axis=-1).astype(jnp.int32)
        cand_written = jnp.take_along_axis(last_written0, cand[:, None],
                                           axis=-1)[:, 0]
        grants = active & state.voter & \
            (cand_written[:, None] >= last_written0)
        won = election_quorum(grants, state.voter)
        elect_ok = elect_mask & won

        leader_slot = jnp.where(elect_ok, cand, state.leader_slot)
        term = jnp.where(elect_ok, state.term + 1, state.term)
        leader_arm = jax.nn.one_hot(leader_slot, P, dtype=jnp.bool_)
        leader_last = jnp.take_along_axis(last_index0, leader_slot[:, None],
                                          axis=-1)[:, 0]
        leader_written = jnp.take_along_axis(
            last_written0, leader_slot[:, None], axis=-1)[:, 0]
        # new leader discards its own unwritten tail and opens its term at
        # written+1 (overwrite semantics; become-leader ra_server.erl:845-859)
        leader_last = jnp.where(elect_ok, leader_written, leader_last)
        term_start = jnp.where(elect_ok, leader_last + 1, state.term_start)
        # a won election appends the term-opening noop entry (payload 0)
        n_noop = jnp.where(elect_ok, 1, 0).astype(jnp.int32)

        # a lane whose leader is inactive cannot accept commands
        leader_up = jnp.take_along_axis(active, leader_slot[:, None],
                                        axis=-1)[:, 0]

    # -- 1. leader append into the ring (with backpressure) ---------------
    # ring headroom: entries not yet applied by every member must stay
    with jax.named_scope("ra.s1_append"):
        min_applied = jnp.min(jnp.where(active, state.applied,
                                        jnp.int32(2**30)), axis=-1)
        ring_base = jnp.maximum(state.ring_base, jnp.minimum(min_applied,
                                                             leader_last))
        used = leader_last - ring_base
        headroom = jnp.maximum(R - used - 1, 0)
        n_acc = jnp.minimum(jnp.where(leader_up, n_new, 0), headroom)
        n_acc = jnp.minimum(n_acc, payloads.shape[1])
        total_app = n_acc + jnp.where(leader_up, n_noop, 0)

        # entry index i lives at ring slot (i - 1) % R; ring_base only tracks
        # the reclaim horizon.  The payloads go to the slots for indexes
        # leader_last+1 .. leader_last+n_acc, plus the term-opening noop
        # (zeros — the machine-noop encoding) on a won election.  The
        # round only enters that in the dispatch: `_ring_write` places a
        # dispatch's rows once, after its last round.
        k = dispatch.k
        dispatch = dispatch._replace(
            tail=dispatch.tail.at[k].set(leader_last),
            n_acc=dispatch.n_acc.at[k].set(n_acc),
            elect_ok=dispatch.elect_ok.at[k].set(elect_ok))
        new_leader_last = leader_last + total_app

    # -- 2. replication, governed by per-peer pipeline credit --------------
    # a won election resets peer cursors (initialise_peers,
    # ra_server.erl:845-859: next := last+1, match := 0)
    with jax.named_scope("ra.s2_replicate"):
        next0 = jnp.where(elect_ok[:, None], new_leader_last[:, None] + 1,
                          state.next_index)
        match0 = jnp.where(elect_ok[:, None],
                           jnp.where(leader_arm, leader_written[:, None], 0),
                           state.match)
        # flow control: entries shipped this round are bounded by the in-flight
        # window and the AER batch size (make_pipelined_rpc_effects,
        # ra_server.erl:1862-1918; limits ra_server.hrl:7-8)
        n_send, _needs = pipeline_credit(next0, match0, new_leader_last,
                                         jnp.zeros((N,), jnp.int32),
                                         jnp.zeros((N, P), jnp.int32),
                                         pipeline_window, max_append_batch)
        send_hi = next0 + n_send - 1
        # adopt only when entries actually ship (n_send > 0): a truncated
        # member's stale send cursor must not resurrect its old tail via
        # send_hi before the cursor itself is repaired below
        last_index = jnp.where(active & (n_send > 0),
                               jnp.maximum(last_index0, send_hi),
                               last_index0)
        last_index = jnp.where(
            leader_arm, jnp.broadcast_to(new_leader_last[:, None], (N, P)),
            last_index)
        # on a won election, follower tails cap at the NEW leader's log in the
        # same round — the step-start clamp ran against the old leader, and
        # without this a longer follower tail would enter the match fold below
        # as a phantom replica for one step (§5.4 safety)
        last_index = jnp.where(elect_ok[:, None] & active,
                               jnp.minimum(last_index,
                                           new_leader_last[:, None]),
                               last_index)

    # -- 3. write confirm (async WAL protocol), 4. reply fold + quorum ---
    (last_written, match, next_index, commit, total_committed,
     leader_commit0, delta) = _confirm_quorum(
        last_index, last_written0, match0, next0, state.commit,
        state.total_committed, active, state.voter, leader_slot,
        term_start, confirm_upto, durable=durable, write_delay=write_delay,
        elect_ok=elect_ok, leader_written=leader_written,
        last_index0=last_index0)

    # -- 4a. lease grant/expiry + read-batch registration (ISSUE 20) ------
    # The leader lease is PURE per-lane arithmetic on the heartbeat
    # round the lockstep step already is: a leader whose lane holds a
    # counted quorum of active voters this round (the same grant
    # arithmetic the vote round uses) extends its lease to
    # read_clock + lease_ttl; a leader cut from its majority stops
    # extending and the lease expires lease_ttl rounds later; a won
    # election revokes it outright (the new leader earns its own).
    # Note the SoA model admits no split-brain within a lane —
    # leader_slot is lane-global, so a deposed leader cannot serve
    # anything; the lease here bounds serving under LOST quorum (the
    # partitioned-leader window before the host triggers an election),
    # which is exactly what the read oracle pins.
    with jax.named_scope("ra.s4a_lease"):
        read_clock = state.read_clock + 1
        lease_q = election_quorum(active & state.voter, state.voter)
        lease_until = jnp.where(elect_ok, 0, state.lease_until)
        lease_until = jnp.where(
            lease_q & leader_up,
            jnp.maximum(lease_until, read_clock + lease_ttl), lease_until)
        lease_ok = read_clock < lease_until

        # read registration: reads NEVER touch the ring (zero log appends).
        # A lane accepts an arriving batch only when its pending slot is
        # free (one in-flight batch per lane — the device-side backpressure
        # the ingress read lane leans on), its leader is up, and the
        # machine has a query kernel; everything else is shed at arrival
        # (counted, refused — never served stale).  The captured read index
        # is the leader commit AT registration: the linearization point
        # every write committed before the batch must be visible at
        # (consistent_query's registration, ra_server.erl:3035-3071).
        supports_read = machine.query_spec is not None
        Kr = state.read_buf.shape[1]
        if supports_read:
            acc_lane = (n_read > 0) & leader_up & (state.read_n == 0)
        else:
            acc_lane = jnp.zeros((N,), jnp.bool_)
        r_acc = jnp.where(acc_lane, jnp.minimum(n_read, Kr), 0)
        r_shed_now = n_read - r_acc
        read_buf = jnp.where(acc_lane[:, None, None], read_q, state.read_buf)
        read_ix = jnp.where(acc_lane, leader_commit0, state.read_ix)
        read_reg = jnp.where(acc_lane, read_clock, state.read_reg)
        read_n1 = jnp.where(acc_lane, r_acc, state.read_n)

    # -- 4b. consistent-query heartbeat quorum -----------------------------
    # The host registers reads by bumping the lane's query counter
    # (query_mask); every active member confirms the current counter in
    # the lockstep round (the #heartbeat_rpc/#heartbeat_reply exchange,
    # ra_server.erl:3082-3170 collapsed into one step); down voters'
    # stale confirmations hold the median back, so a leader cut off
    # from its majority can never certify a read.  A won election wipes
    # the confirmations of members that are NOT reachable this round
    # (active members re-ack immediately below): stale acks collected by
    # a deposed leader can never certify a read under the new one (the
    # new-leader pending_consistent_queries gate, :3174-3190).  A lane
    # accepting a read batch rides the same machinery: its registration
    # bumps the counter, and the batch's token is confirmed by the same
    # quorum fold (the read-index path when the lease is cold).
    with jax.named_scope("ra.s4b_query"):
        query_index = state.query_index + \
            jnp.where(query_mask | acc_lane, 1, 0)
        read_tok = jnp.where(acc_lane, query_index, state.read_tok)
        peer_q0 = jnp.where(elect_ok[:, None], 0, state.peer_query)
        peer_query = jnp.where(active, query_index[:, None], peer_q0)
        query_agreed = query_quorum(peer_query, state.voter)

    # -- 5. apply fold over the committed window ---------------------------
    # The window is LANE-uniform: all active members of a lane share the
    # same apply frontier (failed members freeze; recover/add re-seed
    # from the leader's replica), so the committed entries are read from
    # the ring ONCE per lane, not once a member (_ring_read_window has
    # what each lowering of that read costs on the chip).  Per-member
    # progress is enforced by the `do` mask.
    #
    # A batch machine's fold runs once a lane too (ISSUE 34): members
    # at the same applied index hold the same machine state (state
    # machine safety), so the fold runs on one representative of the
    # lane, the active member at the lane's apply frontier, and its
    # result is handed to every member that SHARES its interval
    # (active, applied == base, the same apply_to), bit for bit what
    # that member's own fold gives.  A round in which any active member
    # anywhere does not share (a follower whose commit lags with its
    # log, a member back at another index) takes the per-member fold
    # for the whole fleet: one `lax.cond` on one scalar, today's price
    # for that round and never a second semantics.  The hand-over
    # rewrites the machine's state once a round, so the lane path is
    # compiled only where `_lane_fold_fits`; elsewhere the per-member
    # fold stands alone, with no cond in the graph.
    with jax.named_scope("ra.s5_apply"):
        applied0 = state.applied
        apply_to = jnp.minimum(commit, applied0 + apply_window)
        A = apply_window
        big = jnp.int32(2 ** 30)
        base = jnp.min(jnp.where(active, applied0, big), axis=-1)
        base = jnp.where(jnp.any(active, axis=-1), base, 0)      # [N]

        a_idx = jnp.arange(A)
        idx_lane = base[:, None] + 1 + a_idx[None, :]            # [N,A]
        cmds_lane, own = _dispatch_window(
            _ring_read_window(dispatch.ring, idx_lane, impl=ring_io),
            idx_lane, dispatch, impl=ring_io)                    # [N,A,C]
        idx = idx_lane[:, None, :]                               # [N,1,A]
        do = (idx > applied0[..., None]) & (idx <= apply_to[..., None]) \
            & active[..., None]                                  # [N,P,A]
        dispatch = dispatch._replace(taken=jnp.sum(
            own & jnp.any(do, axis=1), axis=-1, dtype=jnp.int32))
        idx = jnp.broadcast_to(idx, do.shape)
        #: 1 where this round ran the per-member fold, else 0
        member_round = jnp.int32(0)
        #: whether the machine's fold has a sequential branch
        #: (``JitMachine.jit_fallback`` is not None), found as it is traced
        branches: list = []

        def fold(meta, cmds, do, mac0):
            # the machine's fold, and whether its window took the
            # sequential branch (False for a fold that has none)
            fell_back = machine.jit_fallback(cmds, do)
            branches.append(fell_back is not None)
            new = machine.jit_apply_batch(meta, cmds, do, mac0)
            return new, jnp.asarray(
                False if fell_back is None else fell_back, bool)

        if machine.supports_batch_apply:
            def member_fold(mac0):
                # one-shot masked window fold (machine-managed,
                # order-preserving): no scan depth
                cmds = jnp.broadcast_to(cmds_lane[:, None],
                                        do.shape + cmds_lane.shape[-1:])
                meta = {"index": idx, "term": term[:, None, None]}
                return fold(meta, cmds, do, mac0)

            if _lane_fold_fits(state.mac, dispatch.ring):  # ra13-ok: a Python bool from the state's shapes, the same under every trace
                at_base = active & (applied0 == base[:, None])
                rep = jnp.argmax(at_base, axis=-1)               # [N]
                top = jnp.take_along_axis(apply_to, rep[:, None],
                                          axis=-1)               # [N,1]
                uniform = jnp.all(~active | (at_base & (apply_to == top)))

                def lane_fold(mac0):
                    def of_rep(x):
                        # the representative's member of a leaf, by P
                        # selects over static slices: exact for every
                        # dtype, and no gather
                        out = x[:, 0]
                        for p in range(1, P):
                            out = jnp.where(_lead(rep == p, out), x[:, p],
                                            out)
                        return out

                    do_lane = (idx_lane > base[:, None]) & (idx_lane <= top)
                    new, fell_back = fold(
                        {"index": idx_lane, "term": term[:, None]},
                        cmds_lane, do_lane, jax.tree.map(of_rep, mac0))
                    return jax.tree.map(
                        lambda n, old: jnp.where(_lead(active, old),
                                                 n[:, None], old),
                        new, mac0), fell_back

                mac, fell_back = jax.lax.cond(uniform, lane_fold,
                                              member_fold, state.mac)
                member_round = (~uniform).astype(jnp.int32)
            else:
                mac, fell_back = member_fold(state.mac)
                member_round = jnp.int32(1)
            applied = jnp.where(
                active,
                jnp.maximum(applied0,
                            jnp.minimum(apply_to, (base + A)[:, None])),
                applied0)
        else:
            # Sequential machines: ONE lane-representative scan instead of a
            # per-member one.  Every active member of a lane applies the
            # same committed commands in the same order, so the per-member
            # scan did the machine fold P times over; instead the scan runs
            # on the representative state (the active member at the lane
            # apply frontier), records the trajectory, and each member's
            # final state is SELECTED from it at offset
            # (its own apply_to - base) via an exact one-hot matmul —
            # members that may not apply the full window (commit lag,
            # frozen failures) land on the right intermediate state.
            sel = jnp.argmax(active & (applied0 == base[:, None]),
                             axis=-1)                        # [N]

            def pick(x):
                idx = sel[:, None].reshape((N, 1) + (1,) * (x.ndim - 2))
                idx = jnp.broadcast_to(idx, (N, 1) + x.shape[2:])
                return jnp.take_along_axis(x, idx, axis=1)[:, 0]

            mac_lane = jax.tree.map(pick, state.mac)

            def body(mac0, xs):
                a, cmd_row = xs                              # [], [N,C]
                meta = {"index": base + 1 + a, "term": term}
                new_mac, _reply = machine.jit_apply(meta, cmd_row, mac0)
                return new_mac, new_mac

            _, traj = jax.lax.scan(body, mac_lane,
                                   (a_idx, jnp.moveaxis(cmds_lane, 1, 0)))
            # trajectory offsets 0..A (0 = nothing applied this step)
            stacked = jax.tree.map(
                lambda init, tr: jnp.concatenate([init[None], tr], axis=0),
                mac_lane, traj)                              # [A+1, N, ...]
            off = jnp.clip(apply_to - base[:, None], 0, A)   # [N,P]
            oh = (off[..., None] ==
                  jnp.arange(A + 1)[None, None, :]).astype(jnp.float32)

            def select(stk, old):
                # NB memory: the trajectory holds A+1 state snapshots per
                # lane (vs P replicas before) — an (A+1)/P multiplier on
                # apply-path peak memory, the price of the P-fold compute
                # cut.  Machines with very large per-lane state at large
                # apply windows should size ring/window accordingly.
                tail_shape = stk.shape[2:]
                S = 1
                for d in tail_shape:
                    S *= d
                flat = jnp.moveaxis(stk, 0, 1).reshape(N, A + 1, S)
                if old.dtype in (jnp.int32, jnp.int16, jnp.int8,
                                 jnp.uint8, jnp.uint16, jnp.bool_):
                    # exact one-hot matmul (MXU path): <=32-bit ints
                    # round-trip through the 16-bit split losslessly
                    picked = split16_matmul(
                        oh, flat.astype(jnp.int32)).astype(old.dtype)
                else:
                    # floats / 64-bit: gather (a matmul select would mix
                    # unselected offsets — 0*Inf=NaN — and wider types
                    # truncate); slower but exact and poison-free
                    idx = off[..., None]
                    idx3 = jnp.broadcast_to(idx, (N, P, S))
                    picked = jnp.take_along_axis(
                        jnp.broadcast_to(flat[:, None], (N, P, A + 1, S)),
                        idx3[:, :, None, :], axis=2)[:, :, 0]
                picked = picked.reshape((N, P) + tail_shape)
                m = active.reshape(active.shape + (1,) * (picked.ndim - 2))
                return jnp.where(m, picked, old)

            mac = jax.tree.map(select, stacked, state.mac)
            applied = jnp.where(
                active,
                jnp.maximum(applied0,
                            jnp.minimum(apply_to, (base + A)[:, None])),
                applied0)

    # -- 5b. per-lane telemetry accumulators (device-resident, ISSUE 6) --
    # A handful of [N] vector ops next to the step's [N,P]/[N,R,C] work:
    # the observability plane rides the dispatch it observes, so no
    # extra dispatch, readback or host sync is ever needed to know which
    # lane is stuck.  Aggregation (histograms/top-K) happens at sampling
    # cadence in _telemetry_summary, not here.
    with jax.named_scope("ra.s5b_telemetry"):
        tel = state.telem
        one = jnp.int32(1)
        leader_commit_new = leader_commit0 + delta
        lane_applied = jnp.min(jnp.where(active, applied, big), axis=-1)
        lane_applied = jnp.where(jnp.any(active, axis=-1), lane_applied, 0)
        lead_changed = leader_slot != state.leader_slot
        backlog = new_leader_last > leader_commit_new
        telem = LaneTelemetry(
            elections_requested=tel.elections_requested +
            jnp.where(elect_mask, one, 0),
            elections_won=tel.elections_won + jnp.where(elect_ok, one, 0),
            leader_changes=tel.leader_changes +
            jnp.where(lead_changed, one, 0),
            # reset only when the leader actually MOVED: an incumbent
            # re-elected at a higher term is still a stable leader, and
            # leader_age must agree with leader_changes, not elections_won
            leader_age=jnp.where(lead_changed, 0, tel.leader_age + 1),
            commit_lag=new_leader_last - leader_commit_new,
            apply_lag=leader_commit_new - lane_applied,
            # a stall is a lane that HAS a commit backlog and made no commit
            # progress this round (a leader cut from its quorum, a wedged
            # confirm path); idle lanes (no backlog) never count
            stall_steps=jnp.where((delta > 0) | ~backlog, 0,
                                  tel.stall_steps + 1),
            steps=tel.steps + 1)

    # -- 5c. read serve/refuse (the read-index confirm schedule) ----------
    # A pending batch serves the moment its lane can certify BOTH
    # authority and freshness, all as masked vector ops: authority is
    # the live lease OR the heartbeat quorum having confirmed the
    # batch's token (the read-index path — note it needs no fsync:
    # unlike the commit quorum, read certification gates on the apply
    # frontier, not last_written, so reads are never held back by the
    # fsync hold-back the write plane pays); freshness is the leader's
    # apply frontier having reached the captured read index.  Queries
    # evaluate against the leader replica via the machine's vectorized
    # query kernel — zero log appends, zero host syncs; the answers
    # ride the step aux and drain off the existing async readbacks.
    # A batch that cannot certify within read_timeout rounds is REFUSED
    # (stale-refusal counter) — a partitioned leader's lease reads can
    # never outlive the lease: once lease_until passes and the quorum
    # is gone, can_serve stays False until the batch expires.
    with jax.named_scope("ra.s5c_read"):
        lead_applied = jnp.take_along_axis(applied, leader_slot[:, None],
                                           axis=-1)[:, 0]
        authority = lease_ok | (query_agreed >= read_tok)
        can_serve = (read_n1 > 0) & leader_up & authority & \
            (lead_applied >= read_ix)
        expired = (read_n1 > 0) & ~can_serve & \
            (read_clock - read_reg >= read_timeout)
        if supports_read:
            # every member answers the lane's queries from its own
            # replica and the leader's answers are kept: the pick is
            # over [N, P, Kr, Wq] replies, never over the machine's
            # state (a leaf of a table machine is gigabytes, and a
            # copy of the leader's third of it every round, which an
            # along-axis gather of the state makes, costs more than
            # the step; PR 32)
            replies = machine.jit_query(
                jnp.broadcast_to(read_buf[:, None],
                                 (N, P) + read_buf.shape[1:]), mac)
            replies = jnp.sum(
                jnp.where((leader_arm & can_serve[:, None])[:, :, None, None],
                          replies, 0), axis=1, dtype=replies.dtype)
        else:
            replies = jnp.zeros((N, Kr, 1), jnp.int32)
        read_done = jnp.where(can_serve, read_n1, 0)
        stale_now = jnp.where(expired, read_n1, 0)
        read_served = state.read_served + read_done
        read_shed_tot = state.read_shed + r_shed_now
        read_stale_tot = state.read_stale + stale_now
        read_leased = state.read_leased + \
            jnp.where(can_serve & lease_ok, read_n1, 0)

    new_state = LaneState(term=term, leader_slot=leader_slot,
                          term_start=term_start, last_index=last_index,
                          last_written=last_written, match=match,
                          next_index=next_index, commit=commit,
                          applied=applied, voter=state.voter, active=active,
                          ring=state.ring, ring_base=ring_base,
                          total_committed=total_committed,
                          query_index=query_index, peer_query=peer_query,
                          query_agreed=query_agreed,
                          read_clock=read_clock, lease_until=lease_until,
                          read_buf=read_buf,
                          read_n=jnp.where(can_serve | expired, 0,
                                           read_n1),
                          read_ix=read_ix, read_tok=read_tok,
                          read_reg=read_reg, read_served=read_served,
                          read_shed=read_shed_tot,
                          read_stale=read_stale_tot,
                          read_leased=read_leased, telem=telem, mac=mac)
    aux = {"appended_hi": new_leader_last, "n_acc": n_acc,
           "n_app": total_app, "apply_member": member_round,
           # read-plane aux: per-step serve/refuse outcomes plus the
           # cumulative per-lane watermarks the driver's async
           # readbacks drain (the read twin of committed_lanes)
           "read_done": read_done, "read_shed": r_shed_now,
           "read_stale": stale_now,
           "read_watermark": jnp.where(can_serve, lead_applied, -1),
           "read_replies": replies,
           "read_served_lanes": read_served,
           "read_shed_lanes": read_shed_tot,
           "read_stale_lanes": read_stale_tot}
    if any(branches):
        # 1 where some lane's window took the machine's sequential
        # branch (``apply_fallback_rounds``); no key for a fold that
        # has none
        aux["apply_fallback"] = fell_back.astype(jnp.int32)
    if getattr(machine, "counts_keys", ()):
        # the machine's own counts, the leaders' summed over lanes
        cnt = machine.jit_counts(mac)
        aux["machine_counts"] = jnp.sum(jnp.take_along_axis(
            cnt, leader_slot[:, None, None], axis=1)[:, 0], axis=0,
            dtype=jnp.int32)
    if durable:
        # -- 6. on-device payload compaction for the WAL readback ---------
        # The WAL record stores only the ACCEPTED host rows (lane-major,
        # n_acc per lane); reading back the full [N,K,C] batch and
        # masking on the host moves every rejected/empty slot over the
        # host link first.  Instead a prefix-sum gather compacts the
        # accepted rows into a dense [N*K, C] buffer on device: output
        # row j's source lane is a length-preserving repeat of the lane
        # ids by their accept counts (jnp.repeat lowers to a cumsum +
        # gather — measured 3x cheaper than the searchsorted form and
        # 6x cheaper than a scatter on CPU), so the host pulls exactly
        # rows [0, csum[-1]) — the copy shrinks by the rejection/
        # occupancy factor.
        with jax.named_scope("ra.durable_compact"):
            K = payloads.shape[1]
            C = payloads.shape[2]
            csum = jnp.cumsum(n_acc).astype(jnp.int32)           # [N]
            j = jnp.arange(N * K, dtype=jnp.int32)
            src_lane = jnp.repeat(jnp.arange(N, dtype=jnp.int32), n_acc,
                                  total_repeat_length=N * K)
            row_base = csum[src_lane] - n_acc[src_lane]          # [N*K]
            k_off = jnp.clip(j - row_base, 0, max(K - 1, 0))
            flat_src = src_lane * K + k_off
            flat = jnp.take(
                payloads.reshape(N * K, C).astype(dispatch.ring.dtype),
                flat_src, axis=0)
            valid = j < (csum[-1] if N else jnp.int32(0))
            aux["flat_rows"] = jnp.where(valid[:, None], flat, 0)
            aux["row_csum"] = csum
    if not alone:  # ra13-ok: a Python bool, whether a scan passed its dispatch
        return new_state, aux, dispatch
    with jax.named_scope("ra.s1_append"):
        ring = _ring_write(state.ring, dispatch.rows, dispatch.tail,
                           dispatch.n_acc, dispatch.elect_ok, impl=ring_io)
    return new_state._replace(ring=ring), aux


def _superstep(state: LaneState, n_new_blk: Array, payloads_blk: Array,
               fail_mask: Array, elect_blk: Array, confirm_upto: Array,
               query_blk: Array, n_read_blk: Array, read_q_blk: Array,
               ring_io: str = "gather", **step_kwargs):
    """K lockstep rounds fused into ONE XLA dispatch via ``lax.scan``
    (the tentpole of ISSUE 5).  The scan consumes a device-staged
    ``[K, ...]`` schedule — per-inner-step command counts, payload
    blocks and elect/query masks — while the failure mask and the
    durability confirm horizon are dispatch-constant: failures are
    host-detected between dispatches, and the per-shard WAL confirm
    watermark is sampled ONCE per dispatch, so within a superstep
    confirms can only lag real fsyncs, never lead them (the
    write_delay/confirm contract of step 3 is preserved verbatim —
    the inner step body IS `_step`).

    The ring is written once a dispatch, not once a round: it stays
    out of the scan's carry, the rounds only read it (stage 5's window,
    with this dispatch's own rows laid over it by `_dispatch_window`),
    and `_ring_write` places the rows that survive the K rounds after
    the last one, under ``ra.s1_append``.  That is what K rounds that
    each wrote the ring leave, state and aux, bit for bit; and in the
    durable served path no round applies a row of its own dispatch,
    whose confirm horizon covers earlier dispatches only.

    Returns ``(new_state, aux)`` with every aux leaf stacked along a
    leading ``[K]`` axis (one entry per inner step), so the durable
    readback contract is unchanged: each inner step still yields the
    exact per-step WAL record inputs.  Three extra per-inner-step
    leaves ride along: ``committed_lanes`` (cumulative committed per
    lane — the on-device latency stamp the bench derives
    observed-commit steps from), ``applied_lanes`` (the lane apply
    frontier over active members) and ``window_dispatch_rows`` (the
    window rows, with ``do``, that the round took from this dispatch's
    own rows, summed over lanes after the scan)."""
    big = jnp.int32(2 ** 30)
    with jax.named_scope("ra.s1_append"):
        first = _open_dispatch(state.ring, payloads_blk)

    def body(carry, xs):
        st, (tail, n_acc, elect_ok) = carry
        k, n_new, payloads, elect, query, n_read, read_q = xs
        new_st, aux, d = _step(
            st, n_new, payloads, fail_mask, elect, confirm_upto, query,
            n_read, read_q, ring_io=ring_io, **step_kwargs,
            dispatch=first._replace(k=k, tail=tail, n_acc=n_acc,
                                    elect_ok=elect_ok))
        aux["committed_lanes"] = new_st.total_committed
        applied = jnp.min(jnp.where(new_st.active, new_st.applied, big),
                          axis=-1)
        aux["applied_lanes"] = jnp.where(
            jnp.any(new_st.active, axis=-1), applied, 0)
        aux["window_dispatch_rows"] = d.taken
        return (new_st, (d.tail, d.n_acc, d.elect_ok)), aux

    K = payloads_blk.shape[0]
    (st, (tail, n_acc, elect_ok)), aux = jax.lax.scan(
        body, (state._replace(ring=None),
               (first.tail, first.n_acc, first.elect_ok)),
        (jnp.arange(K, dtype=jnp.int32), n_new_blk, payloads_blk,
         elect_blk, query_blk, n_read_blk, read_q_blk))
    with jax.named_scope("ra.s1_append"):
        ring = _ring_write(state.ring, first.rows, tail, n_acc, elect_ok,
                           impl=ring_io)
    aux["window_dispatch_rows"] = jnp.sum(aux["window_dispatch_rows"],
                                          axis=1, dtype=jnp.int32)
    return st._replace(ring=ring), aux


#: durable dispatches whose confirm sample an engine keeps
#: (``confirm_samples``): a block retires a few dispatches after its own
CONFIRM_SAMPLES = 1024

#: the serve thread's work under one ``IngressPlane.pump()``, by child
#: (``pump_split``; ISSUE 37): the plane's two harvests less their read
#: settle, the read lane's pop and settle, the write block's pop, its
#: staging, the engine's wait for WAL room, the dispatch less its
#: children here, the hand-off to the WAL shards, the in-flight
#: cap's wait for the oldest watermark, and a confirm that missed its
#: dispatch carried by ``ra_confirm`` with the readback of its count
PUMP_SPLIT = ("harvest", "reads", "pop", "stage", "backpressure",
              "dispatch", "wal_submit", "window_sync", "confirm_only")


def ra_confirm(last_index: Array, active: Array, voter: Array,
               leader_slot: Array, term_start: Array, last_written: Array,
               match: Array, next_index: Array, commit: Array,
               total_committed: Array, stall_steps: Array,
               confirm_upto: Array) -> tuple:
    """Stages 3 and 4 of ``_step`` alone, on the state the last
    dispatch left and the WAL's confirm horizon sampled since: no
    election, no append, no apply.  Takes only the [N] / [N,P] leaves
    those stages read and write, never the ring or the machine's
    state, and returns the six it replaces (``last_written``,
    ``match``, ``next_index``, ``commit``, ``total_committed`` and the
    telemetry's ``stall_steps``, reset where the commit moved as a
    round that commits resets it), which its jit donates.  The round
    that follows samples a horizon no lower and recomputes the same
    commit."""
    (last_written, match, next_index, commit, total_committed, _c0,
     delta) = _confirm_quorum(
        last_index, jnp.minimum(last_written, last_index), match,
        next_index, commit, total_committed, active, voter, leader_slot,
        term_start, confirm_upto, durable=True, write_delay=0)
    return (last_written, match, next_index, commit, total_committed,
            jnp.where(delta > 0, 0, stall_steps))


#: shared jitted ``ra_confirm`` programs, keyed by the state's geometry
#: and the placement of what it returns (None: one device)
_CONFIRM_JIT_CACHE: dict = {}


def confirm_fn(key, out_shardings=None):
    fn = _CONFIRM_JIT_CACHE.get(key)
    if fn is None:
        kw = {} if out_shardings is None else \
            {"out_shardings": out_shardings}
        fn = devicewatch.wrap_jit(
            jax.jit(ra_confirm, donate_argnums=tuple(range(5, 11)), **kw),
            "confirm")
        _CONFIRM_JIT_CACHE[key] = fn
    return fn


def ra_watermarks(last_index: Array, leader_slot: Array, active: Array,
                  applied: Array, ring_base: Array,
                  total_committed: Array) -> Array:
    """int32[2, N] for the driver's one readback a dispatch: the
    cumulative committed count of every lane, and the entries of its
    ring in use (leader's tail less the reclaim horizon, as stage 1 of
    ``_step`` computes it: what the next append's headroom is taken
    from)."""
    lead_last = jnp.take_along_axis(last_index, leader_slot[:, None],
                                    axis=-1)[:, 0]
    min_applied = jnp.min(jnp.where(active, applied, jnp.int32(2 ** 30)),
                          axis=-1)
    base = jnp.maximum(ring_base, jnp.minimum(min_applied, lead_last))
    return jnp.stack([total_committed, lead_last - base])


#: shared jitted ``ra_watermarks`` programs, keyed by what tells one
#: compiled program from another (the state's geometry and placement),
#: so that each has a recompile sentinel of its own
_WATERMARKS_JIT_CACHE: dict = {}


def watermarks_fn(key):
    fn = _WATERMARKS_JIT_CACHE.get(key)
    if fn is None:
        fn = devicewatch.wrap_jit(jax.jit(ra_watermarks), "watermarks")
        _WATERMARKS_JIT_CACHE[key] = fn
    return fn


def ra_read_rows(read_done: Array, read_replies: Array, lanes: Array,
                 rows: Array) -> Array:
    """[M, Wq] for the driver's read readback: the reply of each asked
    ``(lanes[i], rows[i])`` pair out of a dispatch's stacked
    ``read_replies`` [K, N, Kr, Wq], at the first inner step that
    served the pair's lane (``read_done`` [K, N]; step 0 where none
    did, and nobody reads that row)."""
    k = jnp.argmax(read_done[:, lanes] > 0, axis=0)
    return read_replies[k, lanes, rows]


#: shared jitted ``ra_read_rows`` programs, one a padded length and
#: geometry, each compiled at its first use with a recompile sentinel
#: of its own
_READ_ROWS_JIT_CACHE: dict = {}


def read_rows_fn(key):
    fn = _READ_ROWS_JIT_CACHE.get(key)
    if fn is None:
        fn = devicewatch.wrap_jit(jax.jit(ra_read_rows), "read_rows")
        _READ_ROWS_JIT_CACHE[key] = fn
    return fn


def read_rows_padded(n_rows: int, cap: int) -> int:
    """The length ``ra_read_rows`` is given for ``n_rows`` asked rows:
    the least power of two that holds them and no less than 64, and
    never more than ``cap`` (every lane's every row)."""
    return min(cap, max(64, 1 << max(n_rows - 1, 0).bit_length()))


def _telemetry_summary(telem: LaneTelemetry, total_committed: Array,
                       reads: tuple, *,
                       top_k: int, hist_buckets: int,
                       stall_threshold: int) -> dict:
    """Aggregate the per-lane telemetry pytree ON DEVICE into a
    fixed-size snapshot: scalar rollups, a log2-bucket commit-lag
    histogram, and a ``lax.top_k`` offender summary.  Output size is
    O(top_k + hist_buckets) regardless of lane count — the readback the
    async sampler starts is a few hundred bytes, not [lanes].  Under a
    sharded mesh the jit lowers the reductions/top_k to cross-device
    collectives, so one call covers every device's lane slice."""
    f32 = jnp.float32
    lag = telem.commit_lag
    stalled = telem.stall_steps >= stall_threshold
    # offender score: any stalled lane outranks any merely-laggy lane;
    # both components clipped so the packed int32 score cannot overflow
    score = (jnp.clip(telem.stall_steps, 0, (1 << 15) - 1) * (1 << 15)
             + jnp.clip(lag + telem.apply_lag, 0, (1 << 15) - 1))
    _top_score, top_idx = jax.lax.top_k(score, top_k)
    # log2 bucketing: bucket b holds lanes with lag in [2^(b-1), 2^b)
    # (bucket 0 = lag 0); the last bucket absorbs the tail
    bucket = jnp.clip(
        jnp.ceil(jnp.log2(jnp.maximum(lag, 0).astype(f32) + 1.0))
        .astype(jnp.int32), 0, hist_buckets - 1)
    hist = jnp.sum(
        (bucket[:, None] == jnp.arange(hist_buckets)[None, :])
        .astype(jnp.int32), axis=0)
    return {
        "steps": jnp.max(telem.steps),
        "elections_requested": jnp.sum(
            telem.elections_requested.astype(f32)),
        "elections_won": jnp.sum(telem.elections_won.astype(f32)),
        "leader_changes": jnp.sum(telem.leader_changes.astype(f32)),
        "stalled_lanes": jnp.sum(stalled.astype(jnp.int32)),
        "commit_lag_max": jnp.max(lag),
        "commit_lag_mean": jnp.mean(lag.astype(f32)),
        "apply_lag_max": jnp.max(telem.apply_lag),
        "apply_lag_mean": jnp.mean(telem.apply_lag.astype(f32)),
        "leader_age_min": jnp.min(telem.leader_age),
        "commit_lag_hist": hist,
        "top_lanes": top_idx,
        "top_commit_lag": jnp.take(lag, top_idx),
        "top_apply_lag": jnp.take(telem.apply_lag, top_idx),
        "top_stall_steps": jnp.take(telem.stall_steps, top_idx),
        # float32: the node-wide sum can exceed int32; the Observatory
        # ring differentiates this into per-window commit rates
        "committed_total": jnp.sum(total_committed.astype(f32)),
        # read-plane rollups (ISSUE 20): cumulative like committed_total
        # — the ring differentiates them into reads/s and refusal rates,
        # and leased/served is the lease-coverage ratio ra_top renders
        "read_served_total": jnp.sum(reads[0].astype(f32)),
        "read_shed_total": jnp.sum(reads[1].astype(f32)),
        "read_stale_total": jnp.sum(reads[2].astype(f32)),
        "read_leased_total": jnp.sum(reads[3].astype(f32)),
    }


#: shared jitted telemetry-summary fns, keyed by aggregation geometry
#: (pure in (telem, total_committed) given the static config)
_SUMMARY_JIT_CACHE: dict = {}


def telemetry_summary_fn(top_k: int = 8, hist_buckets: int = 16,
                         stall_threshold: int = 8):
    key = (top_k, hist_buckets, stall_threshold)
    fn = _SUMMARY_JIT_CACHE.get(key)
    if fn is None:
        # recompile-sentinel wrap (ISSUE 16): the proxy lives in the
        # cache next to the jitted fn, so samplers sharing a geometry
        # share one compile count — a retrace of the summary path is
        # as much a steady-state bug as one of the step path
        fn = devicewatch.wrap_jit(jax.jit(functools.partial(
            _telemetry_summary, top_k=top_k, hist_buckets=hist_buckets,
            stall_threshold=stall_threshold)), "summary")
        _SUMMARY_JIT_CACHE[key] = fn
    return fn


#: shared jitted step fns (see _compile_step)
_STEP_JIT_CACHE: dict = {}


class LockstepEngine:
    """Host API around the jitted lockstep step function."""

    def __init__(self, machine: JitMachine, n_lanes: int, n_members: int = 3,
                 *, ring_capacity: int = 1024, max_step_cmds: int = 64,
                 apply_window: Optional[int] = None,
                 pipeline_window: int = 4096, max_append_batch: int = 128,
                 write_delay: int = 0, ring_io: str = "auto",
                 donate: bool = False,
                 superstep_donate: Optional[bool] = None,
                 max_step_reads: int = 16, lease_ttl: int = 8,
                 read_timeout: int = 0) -> None:
        # donate=False by default ON THE SINGLE-STEP PATH; the SUPERSTEP
        # path defaults donation ON (superstep_donate None -> True):
        # donating saves the full-state double buffer per dispatch.
        # Neither default has been measured on the attached chip with
        # current code (ROADMAP D3 decides them there).  See
        # docs/INTERNALS.md §8 for the dataflow.
        self.machine = machine
        self.n_lanes = n_lanes
        self.n_members = n_members
        if ring_capacity < max_step_cmds + 3:
            # a round's rows and its noop fit the ring with a slot to
            # spare (recovery replays rounds this wide)
            raise ValueError("ring_capacity must be >= max_step_cmds + 3")
        self.ring_capacity = ring_capacity
        self.max_step_cmds = max_step_cmds
        self.apply_window = apply_window or (max_step_cmds + 2)
        dtype, shape = machine.command_spec
        self.payload_width = int(np.prod(shape)) if shape else 1
        self.payload_dtype = jnp.dtype(dtype)
        # read-plane geometry (ISSUE 20): Kr pending-read slots per lane
        # ride LaneState; a machine without a query kernel still carries
        # the (minimal [N,1,1]) read fields so the step signature and
        # checkpoint schema stay uniform, but every read is refused
        self.reads_enabled = machine.query_spec is not None
        self.read_window = max(1, int(max_step_reads)) \
            if self.reads_enabled else 1
        if self.reads_enabled:
            qdtype, qshape = machine.query_spec
            self.query_width = int(np.prod(qshape)) if qshape else 1
            self.query_dtype = jnp.dtype(qdtype)
            _rd, rshape = machine.query_reply_spec
            self.query_reply_width = int(np.prod(rshape)) if rshape else 1
        else:
            self.query_width = 1
            self.query_dtype = jnp.int32
            self.query_reply_width = 1
        self.lease_ttl = int(lease_ttl)
        self.read_timeout = int(read_timeout) if read_timeout \
            else 8 * self.lease_ttl
        # broadcast machine state over member slots: [N,...] -> [N,P,...]
        # in one operation a leaf (an expanded copy of the leaf beside
        # it is 2 GB more at the peak for a table machine's)
        def over_members(x):
            x = jnp.asarray(x)
            return jax.lax.broadcast_in_dim(
                x, (n_lanes, n_members) + x.shape[1:],
                (0,) + tuple(range(2, x.ndim + 1)))
        mac = jax.tree.map(over_members, machine.jit_init(n_lanes))
        self.state = _init_state(n_lanes, n_members, ring_capacity,
                                 self.payload_width, mac,
                                 self.payload_dtype, self.read_window,
                                 self.query_width, self.query_dtype)
        if ring_io == "auto":
            # MXU one-hot IO on TPU backends (the gather is 8 times its
            # time a step on a v5e: 457.7 ms a round against 57.0, PR 30);
            # along-axis gather (fast and exact) on CPU and friends, where
            # the one-hot costs three to five times a step on a large ring
            ring_io = "onehot" if jax.default_backend() == "tpu" \
                else "gather"
        self.ring_io = ring_io
        self._step_kwargs = dict(machine=machine,
                                 ring_capacity=ring_capacity,
                                 apply_window=self.apply_window,
                                 pipeline_window=pipeline_window,
                                 max_append_batch=max_append_batch,
                                 write_delay=write_delay, ring_io=ring_io,
                                 lease_ttl=self.lease_ttl,
                                 read_timeout=self.read_timeout)
        self._donate = donate
        self._superstep_donate = superstep_donate \
            if superstep_donate is not None else True
        self._dur = None  # ra-type: ra_tpu.engine.durable.EngineDurability
        self._driver = None
        self._telemetry = None  # ra-type: ra_tpu.telemetry.TelemetrySampler
        self._ingress = None    # attached IngressPlane (ISSUE 10)
        self._mesh = None       # device mesh, set by shard_engine_state
                                # (ISSUE 11: drivers/ingress read it to
                                # stage blocks pre-partitioned)
        # phase-resolved latency attribution (ISSUE 9): host-side
        # monotonic stamps at the dispatch/staging edges land here; a
        # durability bridge brings its own accumulator (shared with the
        # WAL shards) and attach_durability adopts it
        from ..telemetry import PhaseStats
        self.phases = PhaseStats()
        #: host-side dispatch-pipeline bookkeeping (ENGINE_PIPELINE_FIELDS)
        self.pipeline_counters = {f: 0 for f in ENGINE_PIPELINE_FIELDS}
        #: durable dispatch number (``dispatches`` at its call) ->
        #: (its first WAL step, time.monotonic() of the WAL confirm
        #: sample it fed its step, the step each shard's part of that
        #: sample covered), the newest CONFIRM_SAMPLES: where
        #: IngressPlane finds a block's carrier (ISSUE 37)
        self.confirm_samples: dict = {}
        #: the newest dispatch number at a ``confirm_only()`` run ->
        #: (time.monotonic() of the sample it carried, the step each
        #: shard's part covered): a carrier between that dispatch's
        #: sample and the next's, the newest CONFIRM_SAMPLES
        self.confirm_only_samples: dict = {}
        #: whether the state stands as the last dispatch left it for
        #: stages 3 and 4: no member failed since and no election in
        #: that dispatch (``ra_confirm`` runs only then)
        self._confirm_ready = False
        #: seconds of the serve thread's spans under one
        #: ``IngressPlane.pump()``, by PUMP_SPLIT key, disjoint: the
        #: plane zeroes it as a pump begins and reads it as it ends
        #: (``pump.slow``); the engine and its driver add their parts
        self.pump_split = dict.fromkeys(PUMP_SPLIT, 0.0)
        #: ``apply_member`` flags (with ``apply_fallback`` flags and the
        #: machine's counts, where the step has them) of dispatches not
        #: yet counted into ``apply_member_rounds`` (see
        #: _count_member_rounds)
        self._apply_flags: collections.deque = collections.deque()
        #: the machine's counts (``JitMachine.counts_keys``), the leaders'
        #: summed over lanes, as of the last dispatch counted
        self._machine_counts: Optional[np.ndarray] = None
        self._superstep_k_last = 0
        self._wm = None
        #: the jitted ``ra_confirm`` (None until prepare_confirm), and the
        #: placement of the leaves it returns (None: one device)
        self._confirm = None
        self._confirm_out = None
        self._compile_step(durable=False)
        self._zero_fail = jnp.zeros((n_lanes, n_members), bool)
        self._zero_elect = jnp.zeros((n_lanes,), bool)
        self._zero_confirm = jnp.zeros((n_lanes,), jnp.int32)
        self._zero_nread = jnp.zeros((n_lanes,), jnp.int32)
        self._zero_readq = jnp.zeros(
            (n_lanes, self.read_window, self.query_width),
            self.query_dtype)
        self._fail_host = np.zeros((n_lanes, n_members), bool)

    def _build_jit(self, fn, durable: bool, donate: bool, tag: str):
        # share the jitted step across same-config engines: jax.jit
        # caches by function identity, so a per-instance partial forces
        # a full recompile for every engine construction (a fuzz seed,
        # a test case, a bench child).  Sound for machines whose config
        # is all scalars — jit_apply is pure in (meta, cmd, state)
        # given that config (the JitMachine contract), so same-config
        # instances are interchangeable; others keep per-instance jits.
        m = self.machine
        attrs = [(k, v) for k, v in sorted(m.__dict__.items())
                 if not k.startswith("_")]
        partial = functools.partial(fn, durable=durable,
                                    **self._step_kwargs)
        # jax names the compiled module after the function: a partial
        # has no name of its own, and the profiler's trace then calls
        # the step `jit__unknown`
        partial.__name__ = f"ra_{tag}"
        if all(isinstance(v, (int, float, str, bool)) for _k, v in attrs):
            key = (type(m), tuple(attrs), tag, durable, donate,
                   tuple(sorted((k, v)
                                for k, v in self._step_kwargs.items()
                                if k != "machine")))
            jitted = _STEP_JIT_CACHE.get(key)
            if jitted is None:
                # recompile-sentinel wrap (ISSUE 16): the sentinel
                # proxy is stored IN the cache next to the jitted fn,
                # so same-config engines share one compile count and a
                # cache hit costs no extra wrapping.  The proxy itself
                # is never traced (it wraps the jit OUTPUT) — RA13's
                # static guarantee is untouched; this is its runtime
                # mirror.
                jitted = devicewatch.wrap_jit(
                    jax.jit(partial,
                            donate_argnums=(0,) if donate else ()),
                    tag)
                _STEP_JIT_CACHE[key] = jitted
            return jitted
        return devicewatch.wrap_jit(
            jax.jit(partial, donate_argnums=(0,) if donate else ()), tag)

    def _compile_step(self, durable: bool) -> None:
        self._step = self._build_jit(_step, durable, self._donate, "step")
        # step(donate=True): built here, compiled only if it is called
        self._step_donating = self._step if self._donate else \
            self._build_jit(_step, durable, True, "step")
        self._sstep = self._build_jit(_superstep, durable,
                                      self._superstep_donate, "superstep")

    def attach_durability(self, dur) -> None:
        """Switch the engine into durable mode: ``dur`` (an
        engine-durability bridge, see ra_tpu.engine.durable) supplies the
        per-lane WAL-confirm horizon before each step and receives each
        step's append outcome after dispatch."""
        self._dur = dur
        # one attribution plane per engine: the bridge's accumulator is
        # already wired into its WAL shards, so the engine adopts it —
        # staging/dispatch stamps and fsync/confirm stamps merge
        self.phases = dur.phases
        self._compile_step(durable=True)

    # -- driving -----------------------------------------------------------

    def _host_mask(self, mask, like=None):
        """Coerce a HOST-side mask (election/query requests originate on
        the host failure detector) and record whether any lane is set —
        the host-side bookkeeping that lets the hot step path skip the
        post-dispatch ``np.asarray(elect_mask).any()`` device sync the
        old code paid on every masked step (ISSUE 5 satellite).  Callers
        must pass host data (numpy/list); a device array here would
        reintroduce the sync it exists to remove.

        ``like`` is the mask's cached all-zero twin (see _place)."""
        arr = np.asarray(mask)  # ra02-ok: host data by contract (docstring) — a device array here would reintroduce the sync this helper removes
        return self._place(arr, like), bool(arr.any())

    def _place(self, host: np.ndarray, like):
        """Put a host mask where its cached all-zero twin ``like``
        lives.  On a sharded engine the twins were committed to the
        mesh by ``shard_engine_state``, and a mask placed anywhere else
        is a different jit signature: the first election or failure
        would recompile the step mid-fault.  Unsharded, twin and mask
        are both plain uncommitted arrays (committing only the mask
        would be the same drift the other way round)."""
        if self._mesh is None or like is None:
            return jnp.asarray(host)
        return jax.device_put(host, like.sharding)

    def _fail_mask(self):
        if not self._fail_host.any():
            return self._zero_fail
        return self._place(self._fail_host, self._zero_fail)

    def step(self, n_new, payloads, elect_mask=None,
             query_mask=None, n_read=None, read_q=None, *,
             donate: bool = False):
        """Advance every lane one round.  n_new: int32[N]; payloads:
        [N, K, C] with K <= max_step_cmds.  In durable mode the step's
        accepted entries are compacted on device, read back off-thread
        by the WAL shards, and commits gate on the fsync confirm — host
        or device payloads both work (no host-side copy is taken).
        Masks are host data (see _host_mask).  ``n_read``/``read_q``
        (int32[N], [N, Kr, Cq]) register consistent-read batches on the
        lease/read-index plane (ISSUE 20).  Returns the step aux (device
        arrays) so read callers can drain serve outcomes.

        ``donate=True`` donates the state this step replaces, whatever
        the engine was built with: for a caller that holds no other
        reference to it and whose fleet fills the device (recovery's
        replay; the step's temporaries are 1.4 GiB at 20,000 x 5 on a
        v5e, beside two rings of 5.2 GB without it)."""
        step_fn = self._step_donating if donate else self._step
        fail = self._fail_mask()
        elect_any = False
        if elect_mask is None:
            elect = self._zero_elect
        else:
            elect, elect_any = self._host_mask(elect_mask,
                                               self._zero_elect)
        query = self._zero_elect if query_mask is None \
            else self._host_mask(query_mask, self._zero_elect)[0]
        nr = self._zero_nread if n_read is None else jnp.asarray(n_read)
        rq = self._zero_readq if read_q is None else jnp.asarray(read_q)
        self.pipeline_counters["dispatches"] += 1
        self.pipeline_counters["inner_steps"] += 1
        self._confirm_ready = not elect_any
        if self._dur is None:
            with trace.span("ra.engine.step", "engine"):
                self.state, aux = step_fn(self.state,
                                          jnp.asarray(n_new),
                                          jnp.asarray(payloads), fail,
                                          elect, self._zero_confirm,
                                          query, nr, rq)
            self._count_member_rounds(aux)
            if self._telemetry is not None:
                self._telemetry.tick(1)
            return aux
        confirm = self._backpressure_and_sample()
        with trace.span("ra.engine.step", "engine", durable=True):
            self.state, aux = step_fn(self.state, jnp.asarray(n_new),
                                      jnp.asarray(payloads), fail, elect,
                                      confirm, query, nr, rq)
        self._count_member_rounds(aux)
        if self._confirm is not None and self._mesh is not None:
            self.prepare_confirm()
        with trace.phase_span("ra.engine.wal_submit", self.phases,
                              "wal_submit", "engine") as ws:
            # no host payload copy here: the WAL shards read back the
            # device-compacted flat rows off-thread (see durable.py)
            self._dur.submit(aux)
        self.pump_split["wal_submit"] += ws.dt_s
        if elect_any:
            # elections truncate+reuse indexes: drain now so the next
            # dispatch reads a confirm horizon clamped at the new base
            # (elect_any is host bookkeeping — no device readback here)
            self._dur.drain_all()
        if self._telemetry is not None:
            # after dispatch, never blocking: the sampler only starts
            # async device work/readbacks on this path (rule RA04)
            self._telemetry.tick(1)
        return aux

    def superstep(self, n_new_blk, payloads_blk, elect_blk=None,
                  query_blk=None, n_read_blk=None,
                  read_q_blk=None, enqueued=None) -> dict:
        """Advance every lane K rounds in ONE XLA dispatch (the fused
        `lax.scan` path, ISSUE 5).  Inputs carry a leading inner-step
        axis: ``n_new_blk`` int32[K, N]; ``payloads_blk`` [K, N, Kc, C];
        optional elect/query schedules bool[K, N] (host data) for
        mid-superstep elections/reads.  The failure mask and — in
        durable mode — the WAL confirm horizon are sampled once per
        dispatch: within the superstep confirms only lag real fsyncs.

        Returns the stacked per-inner-step aux (device arrays, one [K]
        leading axis per leaf): ``committed_lanes`` [K, N] is the
        cumulative committed watermark after each inner step — start an
        async readback of it to observe commit progress without ever
        blocking the dispatch pipeline (what DispatchAheadDriver and
        the bench's step-stamped latency mode do).

        ``enqueued``: called with the aux as soon as the fused step is
        on the device's queue, before the WAL handoff (which holds this
        thread about as long as the device steps): where the driver
        starts the dispatch's readback, so that it sits directly behind
        the step (ISSUE 28)."""
        k = int(n_new_blk.shape[0]) if hasattr(n_new_blk, "shape") \
            else len(n_new_blk)
        fail = self._fail_mask()
        elect_any = False
        if elect_blk is None:
            elect = jnp.broadcast_to(self._zero_elect,
                                     (k, self.n_lanes))
        else:
            elect, elect_any = self._host_mask(elect_blk)
        query = jnp.broadcast_to(self._zero_elect, (k, self.n_lanes)) \
            if query_blk is None else jnp.asarray(query_blk)
        nr = jnp.broadcast_to(self._zero_nread, (k, self.n_lanes)) \
            if n_read_blk is None else jnp.asarray(n_read_blk)
        rq = jnp.broadcast_to(self._zero_readq,
                              (k,) + self._zero_readq.shape) \
            if read_q_blk is None else jnp.asarray(read_q_blk)
        self.pipeline_counters["dispatches"] += 1
        self.pipeline_counters["superstep_dispatches"] += 1
        self.pipeline_counters["inner_steps"] += k
        self._superstep_k_last = k
        self._confirm_ready = not elect_any
        if self._dur is None:
            with trace.span("ra.engine.superstep", "engine", k=k):
                self.state, aux = self._sstep(
                    self.state, jnp.asarray(n_new_blk),
                    jnp.asarray(payloads_blk), fail, elect,
                    self._zero_confirm, query, nr, rq)
            self._count_member_rounds(aux)
            if enqueued is not None:
                enqueued(aux)
            if self._telemetry is not None:
                self._telemetry.tick(k)
            return aux
        # confirm horizon sampled ONCE per dispatch — the scan's
        # (constant) confirm schedule; write_delay semantics preserved:
        # confirms may only lag, never lead fsync
        confirm = self._backpressure_and_sample()
        with trace.span("ra.engine.superstep", "engine", durable=True, k=k):
            self.state, aux = self._sstep(
                self.state, jnp.asarray(n_new_blk),
                jnp.asarray(payloads_blk), fail, elect, confirm, query,
                nr, rq)
        self._count_member_rounds(aux)
        if enqueued is not None:
            enqueued(aux)
        if self._confirm is not None and self._mesh is not None:
            self.prepare_confirm()
        # wal_submit phase: the serve thread handing the dispatch's aux
        # to the WAL shards (the per-step slices are taken here)
        with trace.phase_span("ra.engine.wal_submit", self.phases,
                              "wal_submit", "engine", k=k) as ws:
            self._dur.submit_block(aux, k)
        self.pump_split["wal_submit"] += ws.dt_s
        if elect_any:
            self._dur.drain_all()
        if self._telemetry is not None:
            self._telemetry.tick(k)
        return aux

    def _backpressure_and_sample(self):
        """A durable dispatch's wait for room under the WAL's
        unconfirmed window, then the confirm horizon it feeds its step
        (a device array), its sample logged in ``confirm_samples``
        under the dispatch's number beside the dispatch's first WAL
        step, which ``submit``/``submit_block`` assign next."""
        with trace.phase_span("ra.engine.backpressure", None,
                              "backpressure", "engine") as bp:
            self._dur.backpressure()
        self.pump_split["backpressure"] += bp.dt_s
        host, covered, t = self._dur.confirm_sample()
        n = self.pipeline_counters["dispatches"]
        samples = self.confirm_samples
        samples[n] = (self._dur.step_seq + 1, t, covered)
        samples.pop(n - CONFIRM_SAMPLES, None)
        return jnp.asarray(host)

    def _count_member_rounds(self, aux: Optional[dict] = None) -> None:
        """Count into ``apply_member_rounds`` the rounds that ran stage
        5's per-member fold, into ``apply_fallback_rounds`` those in
        which some lane's window took the machine's sequential branch,
        and into ``window_dispatch_rows`` the window rows a fused
        dispatch's rounds applied from its own rows.  A dispatch's flags
        (``aux["apply_member"]``, ``aux["apply_fallback"]`` and
        ``aux["window_dispatch_rows"]``, 4 bytes a round each, and the
        machine's counts where it has them) are device values: their
        copy to the host starts here, directly behind the step, and they
        are counted by a later call that finds them arrived, so the
        serve thread never waits for them and the counters trail by the
        dispatches still running.  With no ``aux`` (``overview()``) it
        waits for all."""
        flags = self._apply_flags
        if aux is not None:
            got = tuple(aux.get(k) for k in ("apply_member",
                                             "apply_fallback",
                                             "machine_counts",
                                             "window_dispatch_rows"))
            for x in got:
                if x is not None:
                    x.copy_to_host_async()
                    devicewatch.record_d2h("apply_flags", x.nbytes)
            flags.append(got)
        while flags and (aux is None or all(
                x is None or x.is_ready() for x in flags[0])):
            member, fallback, counts, own = flags.popleft()
            self.pipeline_counters["apply_member_rounds"] += int(
                np.asarray(member).sum())  # ra02-ok: arrived or overview()'s barrier
            if fallback is not None:
                self.pipeline_counters["apply_fallback_rounds"] += int(
                    np.asarray(fallback).sum())  # ra02-ok: arrived or overview()'s barrier
            if counts is not None:
                c = np.asarray(counts)  # ra02-ok: arrived or overview()'s barrier
                self._machine_counts = c.reshape((-1, c.shape[-1]))[-1]
            if own is not None:
                self.pipeline_counters["window_dispatch_rows"] += int(
                    np.asarray(own).sum())  # ra02-ok: arrived or overview()'s barrier

    def watermarks(self):
        """Device int32[2, N]: every lane's cumulative committed count
        and the entries of its ring in use, after the last dispatch (a
        fresh array: the next dispatch may donate the state)."""
        if self._wm is None:
            self._wm = watermarks_fn((self.n_lanes, self.n_members,
                                      self.mesh_shape()))
        st = self.state
        return self._wm(st.last_index, st.leader_slot, st.active,
                        st.applied, st.ring_base, st.total_committed)

    def _confirm_args(self, confirm_upto: np.ndarray) -> tuple:
        """``ra_confirm``'s arguments: the state's leaves it reads and
        replaces, then the WAL's horizon put where the lanes live."""
        st = self.state
        return (st.last_index, st.active, st.voter, st.leader_slot,
                st.term_start, st.last_written, st.match, st.next_index,
                st.commit, st.total_committed, st.telem.stall_steps,
                self._place(np.asarray(confirm_upto, np.int32),  # ra02-ok: the WAL's horizon is host data
                            st.total_committed))

    def prepare_confirm(self) -> None:
        """Compile ``ra_confirm`` for this engine's geometry and the
        placement of its state (durable mode; the program is never run
        otherwise): at the plane's open, and on a mesh again at the
        dispatch after which the state's leaves are placed anew, so
        that no window compiles it.  Nothing runs."""
        if self._dur is None:
            return
        out = None
        if self._mesh is not None:
            # what it returns stays where the leaves it replaces live,
            # so the next dispatch sees the signature the last one left;
            # a step places its outputs as the compiler chose, not
            # always as shard_engine_state did
            st = self.state
            out = (st.last_written.sharding, st.match.sharding,
                   st.next_index.sharding, st.commit.sharding,
                   st.total_committed.sharding,
                   st.telem.stall_steps.sharding)
            if self._confirm is not None and out == self._confirm_out:
                return
        self._confirm_out = out
        self._confirm = confirm_fn((self.n_lanes, self.n_members, out), out)
        self._confirm.lower(
            *self._confirm_args(self._dur.confirm_upto)).compile()

    def confirm_ready(self) -> bool:
        """Whether ``confirm_only()`` may run: a durable engine whose
        program is compiled (``prepare_confirm()``) and whose state
        stands as its last dispatch left it (no member failed since, no
        election in that dispatch)."""
        return self._confirm is not None and self._confirm_ready

    def confirm_only(self, sample: tuple):
        """Carry a WAL confirm to the device without a dispatch: run
        ``ra_confirm`` on ``sample`` (``confirm_sample()``'s
        ``(confirm_upto, covered, t)``) and put what it returns into the
        state; returns the new ``total_committed`` (device, int32[N]).
        No dispatch is counted; the sample is logged in
        ``confirm_only_samples`` under the newest dispatch's number,
        where ``IngressPlane`` finds the carrier of a block's confirm.
        Only where ``confirm_ready()``."""
        host, covered, t = sample
        st = self.state
        args = self._confirm_args(host)
        lw, match, nxt, commit, total, stall = self._confirm(*args)
        self.state = st._replace(
            last_written=lw, match=match, next_index=nxt, commit=commit,
            total_committed=total,
            telem=st.telem._replace(stall_steps=stall))
        n = self.pipeline_counters["dispatches"]
        self.confirm_only_samples[n] = (t, covered)
        self.confirm_only_samples.pop(n - CONFIRM_SAMPLES, None)
        self.pipeline_counters["confirm_only_runs"] += 1
        return total

    def checkpoint(self) -> str:
        """Durable mode: quiesce the WAL, snapshot the full lane state,
        and prune WAL files the snapshot covers (the release_cursor /
        snapshot-truncation role).  Returns the checkpoint path."""
        if self._dur is None:
            raise RuntimeError("checkpoint() requires durable mode")
        return self._dur.checkpoint(self)

    def close(self) -> None:
        """Flush and close the durability bridge (no-op when volatile)."""
        if self._dur is not None:
            self._dur.close()

    def uniform_step(self, cmds_per_lane: int, payload_value=1) -> None:
        """Bench helper: every lane's leader receives the same number of
        commands this round."""
        N, K, C = self.n_lanes, self.max_step_cmds, self.payload_width
        n_new = jnp.full((N,), min(cmds_per_lane, K), jnp.int32)
        payloads = jnp.full((N, K, C), payload_value, self.payload_dtype)
        self.step(n_new, payloads)

    def uniform_superstep(self, k: int, cmds_per_lane: int,
                          payload_value=1) -> dict:
        """Bench/soak helper: one fused dispatch of ``k`` rounds, every
        lane's leader receiving the same command count each round."""
        N, K, C = self.n_lanes, self.max_step_cmds, self.payload_width
        n_new = jnp.full((k, N), min(cmds_per_lane, K), jnp.int32)
        payloads = jnp.full((k, N, K, C), payload_value,
                            self.payload_dtype)
        return self.superstep(n_new, payloads)

    def uniform_read_block(self, k: int, reads_per_lane: int,
                           query_value=0):
        """Bench/soak helper: build a ``(n_read_blk, read_q_blk)``
        superstep read schedule registering one uniform batch of
        ``reads_per_lane`` queries per lane at inner step 0 (one batch
        per lane is in flight at a time — see step 4a — so scheduling
        at later inner steps would only shed)."""
        N, Kr, Cq = self.n_lanes, self.read_window, self.query_width
        r = min(int(reads_per_lane), Kr)
        n_read = jnp.zeros((k, N), jnp.int32).at[0].set(r)
        read_q = jnp.broadcast_to(
            jnp.full((N, Kr, Cq), query_value, self.query_dtype),
            (k, N, Kr, Cq))
        return n_read, read_q

    # -- failure injection / elections ------------------------------------

    def fail_member(self, lane: int, slot: int) -> None:
        # host-initiated transitions are RARE and exactly what a
        # post-mortem wants: flight events here, never per step
        record("engine.fail", lane=int(lane), slot=int(slot))
        self._fail_host[lane, slot] = True
        self._confirm_ready = False

    def recover_member(self, lane: int, slot: int) -> None:
        """Re-activate a member via *snapshot install* from the lane
        leader (the escalation the reference takes when a follower falls
        behind the log truncation horizon, ra_server.erl:1962-1981):
        machine state and cursors are copied from the leader's replica.
        A failed member's apply frontier freezes while it is down (the
        apply fold reads a lane-uniform window), so rejoin is always by
        snapshot rather than ring replay.

        Recovering the lane's CURRENT leader slot is refused: the install
        would seed the leader from its own stale applied frontier,
        truncating its durable tail — including entries the rest of the
        lane committed while it was down (a §5.4 violation).  Revive the
        other members first, ``trigger_election`` (the longest durable
        log wins, as a restarting reference leader would), then recover
        the deposed slot from the new leader."""
        if int(self.state.leader_slot[lane]) == slot:
            raise ValueError(
                f"slot {slot} is lane {lane}'s leader; recover the other "
                "members, trigger_election, then recover this slot")
        record("engine.recover", lane=int(lane), slot=int(slot))
        self._fail_host[lane, slot] = False
        self.state = self._snapshot_install(lane, slot)

    def recover_members(self, lanes, slots) -> None:
        """Vectorized :meth:`recover_member`: revive MANY (lane, slot)
        pairs in one state update (one masked snapshot-install over the
        whole fleet instead of ~6 device ops per member).  The
        multichip chaos phase heals thousands of members per round at
        the 64k-lane ladder rung — per-member eager updates there cost
        seconds of dispatch latency per heal (ISSUE 11).  Same contract
        as the scalar form: recovering a lane's CURRENT leader slot is
        refused, install seeds from the leader's APPLIED frontier."""
        lanes = np.atleast_1d(np.asarray(lanes)).astype(np.int64)
        slots = np.atleast_1d(np.asarray(slots)).astype(np.int64)
        if not len(lanes):
            return
        leads = np.asarray(self.state.leader_slot)[lanes]
        if (leads == slots).any():
            bad = lanes[leads == slots]
            raise ValueError(
                f"lanes {bad[:8].tolist()}: slot is the lane's leader; "
                "recover the other members, trigger_election, then "
                "recover this slot")
        record("engine.recover", lanes=lanes[:64].tolist(),
               n=int(len(lanes)))
        self._fail_host[lanes, slots] = False
        rv_host = np.zeros((self.n_lanes, self.n_members), bool)
        rv_host[lanes, slots] = True
        rv = jnp.asarray(rv_host)
        st = self.state
        lead = st.leader_slot[:, None]                        # [N,1]
        snap = jnp.take_along_axis(st.applied, lead, axis=1)  # [N,1]

        def from_leader(x):
            idx = lead.reshape((self.n_lanes, 1) + (1,) * (x.ndim - 2))
            idx = jnp.broadcast_to(idx, (self.n_lanes, 1) + x.shape[2:])
            lx = jnp.take_along_axis(x, idx, axis=1)
            m = rv.reshape(rv.shape + (1,) * (x.ndim - 2))
            return jnp.where(m, lx, x)

        self.state = st._replace(
            mac=jax.tree.map(from_leader, st.mac),
            applied=jnp.where(rv, snap, st.applied),
            commit=jnp.where(rv, snap, st.commit),
            last_index=jnp.where(rv, snap, st.last_index),
            last_written=jnp.where(rv, snap, st.last_written),
            active=st.active | rv)

    def _snapshot_install(self, lane: int, slot: int) -> LaneState:
        """Seed a (re)joining member from the lane leader at the leader's
        APPLIED index — the snapshot covers exactly the state the copied
        machine state reflects (snapshot idx <= commit, ra_snapshot
        semantics).  Seeding at the leader's written tail instead would
        hand the member a claim to entries it does not hold — a deposed
        minority leader's uncommitted suffix could then enter the match
        median as a phantom replica."""
        st = self.state
        leader = int(st.leader_slot[lane])
        snap_idx = st.applied[lane, leader]
        return st._replace(
            mac=jax.tree.map(
                lambda x: x.at[lane, slot].set(x[lane, leader]), st.mac),
            applied=st.applied.at[lane, slot].set(snap_idx),
            commit=st.commit.at[lane, slot].set(snap_idx),
            last_index=st.last_index.at[lane, slot].set(snap_idx),
            last_written=st.last_written.at[lane, slot].set(snap_idx),
            active=st.active.at[lane, slot].set(True))

    # -- membership (per-lane add/remove/promote, SURVEY §2.1 membership) --
    # NB durable mode: membership and recover_member are host-side state
    # edits outside the WAL block stream, so they are durable only from
    # the next checkpoint() on — call checkpoint() after changing
    # membership (the reference logs '$ra_join'/'$ra_leave' as commands;
    # the engine trades that for checkpoint-granularity durability).

    def add_member(self, lane: int, slot: int,
                   voter: bool = False) -> None:
        """Bring a member slot into a lane's cluster.  Joins as nonvoter
        by default (the reference's join→catch-up→promote flow,
        ra_server.erl:3218-3293): the new member is seeded from the
        leader's replica (snapshot install) and only counts toward
        quorum once promoted."""
        record("engine.member", op="add", lane=int(lane),
               slot=int(slot), voter=bool(voter))
        st = self._snapshot_install(lane, slot)
        self.state = st._replace(
            voter=st.voter.at[lane, slot].set(bool(voter)))
        self._fail_host[lane, slot] = False

    def promote_member(self, lane: int, slot: int) -> None:
        """Nonvoter -> voter once caught up ('$ra_join' promotion)."""
        record("engine.member", op="promote", lane=int(lane),
               slot=int(slot))
        self.state = self.state._replace(
            voter=self.state.voter.at[lane, slot].set(True))

    def remove_member(self, lane: int, slot: int) -> None:
        """Drop a member from a lane's cluster: it leaves the quorum
        denominator immediately ('$ra_leave').  Removing the lane's
        current leader is refused — transfer leadership first (trigger an
        election for the lane), as the reference does when the leader is
        asked to leave; silently deactivating the leader slot would stall
        the lane forever with no error."""
        if int(self.state.leader_slot[lane]) == slot:
            raise ValueError(
                f"slot {slot} is lane {lane}'s leader; "
                "trigger_election first")
        record("engine.member", op="remove", lane=int(lane),
               slot=int(slot))
        st = self.state
        self.state = st._replace(
            active=st.active.at[lane, slot].set(False),
            voter=st.voter.at[lane, slot].set(False))

    def trigger_election(self, lanes) -> None:
        mask = np.zeros((self.n_lanes,), bool)
        mask[np.asarray(lanes)] = True
        record("engine.elect",
               lanes=np.atleast_1d(np.asarray(lanes)).tolist()[:64])
        N, K, C = self.n_lanes, self.max_step_cmds, self.payload_width
        self.step(jnp.zeros((N,), jnp.int32),
                  jnp.zeros((N, K, C), self.payload_dtype),
                  elect_mask=mask)

    # -- consistent (linearizable) reads -----------------------------------

    def consistent_read(self, lanes, fn=None, timeout_steps: int = 256):
        """Linearizable read of the given lanes' machine state — the
        engine-path ra:consistent_query (ra_server.erl:3032-3190).

        Registers a query token (bumps the lanes' query counters), then
        drives empty rounds until (a) a majority of voters have
        confirmed the token — certifying this leader's authority after
        registration — and (b) the leader has applied at least its
        commit index as of registration.  Together these guarantee the
        returned state reflects every write that completed before this
        call, including across elections (a new leader must re-collect
        confirmations and commit its noop first).

        Returns the per-lane leader machine state (a pytree with one
        leading lane axis), or ``fn(state_pytree)`` if given.  Raises
        TimeoutError when no quorum certifies within ``timeout_steps``
        rounds (e.g. the lanes' leaders lost their majority)."""
        lanes = np.atleast_1d(np.asarray(lanes))
        qm = np.zeros((self.n_lanes,), bool)
        qm[lanes] = True
        zero_n = np.zeros((self.n_lanes,), np.int32)
        # full payload width: reuses the executable the normal step
        # loop already compiled (a narrower shape would retrace)
        zero_p = np.zeros((self.n_lanes, self.max_step_cmds,
                           self.payload_width), self.payload_dtype)
        self.step(zero_n, zero_p, query_mask=qm)
        st = self.state
        token = np.asarray(st.query_index)[lanes]
        lead = np.asarray(st.leader_slot)[lanes]
        commit_reg = np.asarray(st.commit)[lanes, lead]
        for _ in range(timeout_steps):
            st = self.state
            agreed = np.asarray(st.query_agreed)[lanes]
            lead = np.asarray(st.leader_slot)[lanes]
            applied = np.asarray(st.applied)[lanes, lead]
            if (agreed >= token).all() and (applied >= commit_reg).all():
                mac = jax.tree.map(
                    lambda x: np.asarray(x)[lanes, lead], st.mac)
                return fn(mac) if fn is not None else mac
            self.step(zero_n, zero_p)
        raise TimeoutError(
            "consistent_read: no heartbeat quorum within "
            f"{timeout_steps} rounds (leader lost its majority?)")

    def read_lanes(self, lanes, queries, timeout_steps: int = 256):
        """Consistent reads through the VECTORIZED read plane (ISSUE 20)
        — the lease/read-index twin of :meth:`consistent_read`, serving
        from the jitted step with zero log appends.

        Registers ONE encoded query per given lane in a single
        zero-command step, then drives empty rounds until every batch
        settles.  ``queries``: [len(lanes), Cq] encoded rows (see the
        machine's ``encode_query``).  Returns ``(replies, watermark,
        ok)`` — np arrays aligned with ``lanes``: per-lane decoded-width
        reply rows, the apply watermark each read was served at, and
        ``ok`` False where the lane REFUSED the read (stale-refusal:
        lease expired / quorum lost / timeout) rather than serve it
        stale.  Raises TimeoutError if any batch neither serves nor
        refuses within ``timeout_steps`` rounds."""
        if not self.reads_enabled:
            raise ValueError("machine has no query kernel "
                             "(query_spec is None)")
        lanes = np.atleast_1d(np.asarray(lanes))
        n = len(lanes)
        q = np.asarray(queries).reshape(n, -1)
        nr = np.zeros((self.n_lanes,), np.int32)
        nr[lanes] = 1
        rq = np.zeros((self.n_lanes, self.read_window, self.query_width),
                      self.query_dtype)
        rq[lanes, 0] = q
        zero_n = np.zeros((self.n_lanes,), np.int32)
        zero_p = np.zeros((self.n_lanes, self.max_step_cmds,
                           self.payload_width), self.payload_dtype)
        Wq = self.query_reply_width
        replies = np.zeros((n, Wq), np.int32)
        wm = np.full((n,), -1, np.int32)
        ok = np.zeros((n,), bool)
        settled = np.zeros((n,), bool)
        aux = self.step(zero_n, zero_p, n_read=nr, read_q=rq)
        for _ in range(timeout_steps):
            done = np.asarray(aux["read_done"])[lanes] > 0
            # refused at arrival (leader down / slot busy) or by
            # timeout — either way the batch settles with ok=False
            stale = (np.asarray(aux["read_stale"])[lanes] > 0) | \
                (np.asarray(aux["read_shed"])[lanes] > 0)
            fresh = done & ~settled
            if fresh.any():
                rep = np.asarray(aux["read_replies"])[lanes[fresh], 0]
                replies[fresh] = rep.reshape(fresh.sum(), -1)
                wm[fresh] = np.asarray(
                    aux["read_watermark"])[lanes[fresh]]
                ok[fresh] = True
            settled |= done | stale
            if settled.all():
                return replies, wm, ok
            aux = self.step(zero_n, zero_p)
        raise TimeoutError(
            f"read_lanes: {int((~settled).sum())} batches neither "
            f"served nor refused within {timeout_steps} rounds")

    # -- checkpoint / resume (device-state snapshot, SURVEY §5) ------------

    def save(self, path: str) -> None:
        """Write the full lane state to one .npz (atomic replace): the
        lockstep analogue of the checkpoint/snapshot subsystem — all
        clusters' Raft cursors + machine states in one device pull.

        Archive keys are SCHEMA-NAMED since ISSUE 15
        (``<field>:<leaf-index>`` per LaneState field) so restore can
        resolve fields by name and default the ones an old archive
        predates — the forward-compat contract
        ``CHECKPOINT_FIELD_DEFAULTS`` declares and rule RA15 pins."""
        import os

        arrays = {}
        for name in LaneState._fields:
            leaves = jax.tree.flatten(getattr(self.state, name))[0]
            for j, x in enumerate(leaves):
                arrays[f"{name}:{j}"] = np.asarray(x)
        meta = {"n_lanes": self.n_lanes, "n_members": self.n_members,
                "ring_capacity": self.ring_capacity,
                "schema": list(LaneState._fields)}
        tmp = path + ".partial"
        with open(tmp, "wb") as f:
            np.savez(f, __meta__=np.frombuffer(
                repr(meta).encode(), dtype=np.uint8), **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def restore(self, path: str) -> None:
        """Load a .npz written by :meth:`save` into this engine.  Engine
        geometry (lanes/members/ring) must match construction — the
        snapshot is state, not config.

        Forward compat (ISSUE 15, generalizing the PR 6 pre-telemetry
        fix): fields the archive predates restore through their
        ``CHECKPOINT_FIELD_DEFAULTS`` entry — ``"zeros"`` zero-fills
        (health counters), ``"init"`` keeps the restoring engine's
        current value (the fresh-init value in the open_engine
        recovery path), ``"require"`` refuses (consensus state every
        archive has always carried).  A durable dir is never stranded behind a
        pytree format bump.  Archives from a NEWER schema (unknown
        field names) are refused — silently dropping consensus state
        is not a degrade this layer may choose.  Positional pre-ISSUE-
        15 archives (``a<i>`` keys, with or without the telemetry
        plane) still restore."""
        with np.load(path) as z:
            names = [k for k in z.files if k != "__meta__"]
            if not any(":" in k for k in names):
                self._restore_positional(z)
                return
            by_field: dict = {}
            for k in names:
                by_field.setdefault(k.split(":", 1)[0], []).append(k)
            unknown = sorted(set(by_field) - set(LaneState._fields))
            if unknown:
                raise ValueError(
                    f"checkpoint carries unknown schema fields "
                    f"{unknown[:6]} (written by a newer engine?); "
                    "refusing to drop state")
            loaded = []
            for name in LaneState._fields:
                cur = getattr(self.state, name)
                leaves, treedef = jax.tree.flatten(cur)
                if not leaves:
                    # a zero-leaf field (e.g. a stateless machine's
                    # empty mac pytree) writes no archive keys — there
                    # is nothing to load OR default; keep the
                    # structure as-is (a 'require' mode must not
                    # refuse a checkpoint the same engine just wrote)
                    loaded.append(cur)
                    continue
                if name not in by_field:
                    mode = CHECKPOINT_FIELD_DEFAULTS.get(name,
                                                         "require")
                    if mode == "require":
                        raise ValueError(
                            f"checkpoint is missing required field "
                            f"{name!r}")
                    new = [jnp.zeros_like(x) for x in leaves] \
                        if mode == "zeros" else list(leaves)
                elif len(by_field[name]) != len(leaves):
                    raise ValueError(
                        f"checkpoint leaf count mismatch for "
                        f"{name!r}: archive has "
                        f"{len(by_field[name])}, engine needs "
                        f"{len(leaves)}")
                else:
                    new = []
                    for j, x in enumerate(leaves):
                        got = jnp.asarray(z[f"{name}:{j}"])
                        if x.shape != got.shape:
                            raise ValueError(
                                f"checkpoint geometry mismatch: "
                                f"{got.shape} != {x.shape}")
                        new.append(got)
                loaded.append(jax.tree.unflatten(treedef, new))
            self.state = LaneState(*loaded)

    def _restore_positional(self, z) -> None:
        """Legacy archive format: index-flattened ``a<i>`` keys.
        Archives written before the telemetry plane existed (LaneState
        without ``telem``) restore with zero-filled telemetry — the
        original PR 6 special case, kept verbatim for old dirs."""
        flat, treedef = jax.tree.flatten(self.state)
        n = len(flat)
        n_arch = sum(1 for k in z.files if k != "__meta__")
        n_tel = len(LaneTelemetry._fields)
        tel_at = len(jax.tree.flatten(
            tuple(self.state[:LaneState._fields.index("telem")]))[0])
        legacy = n_arch == n - n_tel
        if not legacy and n_arch != n:
            raise ValueError(
                f"checkpoint leaf count mismatch: archive has "
                f"{n_arch} arrays, engine state needs {n}")
        loaded, j = [], 0
        for i in range(n):
            if legacy and tel_at <= i < tel_at + n_tel:
                loaded.append(jnp.zeros_like(flat[i]))
                continue
            got = jnp.asarray(z[f"a{j}"])
            j += 1
            if flat[i].shape != got.shape:
                raise ValueError(
                    f"checkpoint geometry mismatch: {got.shape} "
                    f"!= {flat[i].shape}")
            loaded.append(got)
        self.state = jax.tree.unflatten(treedef, loaded)

    # -- readback ----------------------------------------------------------

    def mesh_shape(self) -> str:
        """``"<members>x<lanes>"`` device-mesh stamp (``""`` when
        unsharded) — rides the engine_pipeline overview so multichip
        bench tails/ring windows always carry the mesh the rates were
        measured on (ISSUE 11 satellite)."""
        if self._mesh is None:
            return ""
        shape = dict(self._mesh.shape)
        return f"{shape.get('members', 1)}x{shape.get('lanes', 1)}"

    def committed_total(self) -> int:
        # per-lane counters are int32 (wrap needs 2^31 commits in ONE lane —
        # unreachable in practice); the node-wide sum can exceed 2^31, so
        # sum on host in int64
        return int(np.asarray(self.state.total_committed)
                   .astype(np.int64).sum())

    def committed_per_lane(self) -> np.ndarray:
        return np.asarray(self.state.total_committed)

    def committed_lanes_async(self):
        """Non-blocking commit readback: returns a fresh device array of
        per-lane cumulative committed counts with a host copy already in
        flight.  Poll ``.is_ready()``; convert with ``np.asarray`` once
        ready.  The copy (`+ 0`) decouples the readback from buffer
        donation, so the next ``step`` can be dispatched immediately —
        this is the async host<->device overlap latency mode is built on
        (the applied-notification edge of ra_bench.erl:153-190 without a
        device barrier)."""
        tc = self.state.total_committed + 0
        try:
            tc.copy_to_host_async()
        except AttributeError:  # pragma: no cover — older jax arrays
            pass
        # transfer ledger (ISSUE 16): one d2h copy starts here —
        # counted at copy START, so an awaited handle is never counted
        # twice (.nbytes is host metadata, no sync)
        devicewatch.record_d2h("lanes_async", tc.nbytes)
        return tc

    def machine_states(self) -> Any:
        return jax.tree.map(np.asarray, self.state.mac)

    def block_until_ready(self) -> None:
        jax.block_until_ready(self.state)

    def overview(self, lane: int = 0) -> dict:
        s = self.state
        self._count_member_rounds()
        out = {
            "term": int(s.term[lane]),
            "leader_slot": int(s.leader_slot[lane]),
            "last_index": np.asarray(s.last_index[lane]).tolist(),
            "last_written": np.asarray(s.last_written[lane]).tolist(),
            "commit": np.asarray(s.commit[lane]).tolist(),
            "applied": np.asarray(s.applied[lane]).tolist(),
            "active": np.asarray(s.active[lane]).tolist(),
            "total_committed": int(s.total_committed[lane]),
        }
        # dispatch-pipeline stamp (ISSUE 5): last fused K, the attached
        # driver's stage-ahead depth + live in-flight count, and the
        # host-side pipeline counters
        out["pipeline"] = {
            "superstep_k": self._superstep_k_last,
            # the autotuner-tunable knobs ride the overview (RA07: no
            # silent knob turns — knob value next to the rates it moves)
            "cmds_per_step": self.max_step_cmds,
            "mesh_shape": self.mesh_shape(),
            "wal_max_batch_interval_ms": (
                self._dur.batch_interval_ms()
                if self._dur is not None else -1.0),
            "dispatch_ahead": (self._driver.max_in_flight
                               if self._driver is not None else 0),
            "dispatches_in_flight": (self._driver.in_flight()
                                     if self._driver is not None else 0),
            **self.pipeline_counters,
        }
        name = getattr(self.machine, "counts_name", None)
        if name and self._machine_counts is not None:
            # the machine's own counts, the leaders' summed over lanes,
            # as of the last dispatch
            out[name] = {k: int(v) for k, v in zip(
                self.machine.counts_keys, self._machine_counts)}
        if self.reads_enabled:
            # read-plane health (ISSUE 20): cumulative serve/refuse
            # ledger + lease coverage (the ra_top read panel's source)
            i64 = np.int64
            served = int(np.asarray(s.read_served).astype(i64).sum())
            leased = int(np.asarray(s.read_leased).astype(i64).sum())
            out["reads"] = {
                "served_total": served,
                "shed_total": int(
                    np.asarray(s.read_shed).astype(i64).sum()),
                "stale_refusals": int(
                    np.asarray(s.read_stale).astype(i64).sum()),
                "leased_total": leased,
                "lease_coverage_pct": (100.0 * leased / served)
                if served else 0.0,
                "pending_lanes": int((np.asarray(s.read_n) > 0).sum()),
                "lease_ttl": self.lease_ttl,
                "read_timeout": self.read_timeout,
                "read_window": self.read_window,
            }
        if self._dur is not None:
            # durability-plane health (ENGINE_WAL_FIELDS + per-shard
            # WAL_FIELDS/stats), the key_metrics merge of PR 2's
            # RPC_FIELDS pattern
            out["wal"] = self._dur.wal_overview()
        if self._ingress is not None:
            # the session tier's flow gauges ride the engine overview
            # (queue depth next to the pipeline it feeds, ISSUE 10)
            out["ingress"] = self._ingress.gauges()
        return out


def _densify(rows: Array, row_base: Array, take: Array, *, k: int,
             kc: int) -> Array:
    """The dense ``[K, N, Kc, C]`` write block from the rows it
    carries: ``block[k, n, c] = rows[row_base[n] + k*Kc + c]`` where
    ``k*Kc + c < take[n]``, else 0.  One gather of ``N*K*Kc`` single
    rows, clipped where a lane's window runs past the table and masked
    there: on a v5e 2.5 ms at 10,000 lanes whatever the table's size,
    where one slice of ``K*Kc`` contiguous rows a lane (a loop over the
    lanes once compiled) took 17 ms and a scatter of the rows present
    3.5 to 32 ms by bucket (PERF.md section 6, PR 26)."""
    width = k * kc
    n, c = row_base.shape[0], rows.shape[1]
    j = jnp.arange(width, dtype=row_base.dtype)
    idx = row_base[:, None] + j[None, :]
    win = jnp.take(rows, idx.reshape(-1), axis=0, mode="clip")
    live = j[None, :] < take[:, None]
    win = jnp.where(live[..., None], win.reshape(n, width, c), 0)
    return win.reshape(n, k, kc, c).transpose(1, 0, 2, 3)


#: shared jitted densify fns, keyed by (K, Kc, the block's sharding,
#: whatever else tells one compiled program from another)
_DENSIFY_JIT_CACHE: dict = {}


def densify_fn(k: int, kc: int, out_sharding=None, shape=None):
    """The jitted ``ra_densify`` program (a program of its own in the
    trace, never fused into ``ra_superstep``), shared by every driver
    of one geometry like the step's.  ``shape`` names the one input
    geometry the caller will give it, so each compiled program has a
    recompile sentinel of its own and a second compile under one
    sentinel is a retrace, as it is for the step."""
    key = (k, kc, out_sharding, shape)
    fn = _DENSIFY_JIT_CACHE.get(key)
    if fn is None:
        partial = functools.partial(_densify, k=k, kc=kc)
        partial.__name__ = "ra_densify"
        fn = devicewatch.wrap_jit(
            jax.jit(partial, out_shardings=out_sharding), "densify")
        _DENSIFY_JIT_CACHE[key] = fn
    return fn


def flat_buckets(k: int, n_lanes: int, kc: int) -> tuple:
    """Padded row counts of the flat write block, from the block's
    geometry alone: 1/64, 1/16 and 1/4 of its ``K*N*Kc`` rows, each
    rounded up to a multiple of 8.  A block with more rows than the
    last goes dense."""
    full = k * n_lanes * kc
    sizes = {-(-full // (8 * d)) * 8 for d in (64, 16, 4)}
    return tuple(sorted(b for b in sizes if b < full))


class DispatchAheadDriver:
    """Dispatch-ahead host pipeline for the superstep path (ISSUE 5).

    :meth:`submit` starts the host->device transfer (``device_put``) of
    the block it is given and dispatches that block in the same call:
    the copy is asynchronous and the device runs its programs in
    order, so the block's copy (and its ``ra_densify``) already sits
    ahead of its step on the device's queue.  Nothing is held over to
    the next call: a block staged one submit ahead waited a whole loop
    cycle for nothing the device saw (ISSUE 36).  No
    ``block_until_ready`` anywhere in the loop: each dispatch starts an
    asynchronous readback of its watermarks directly behind the step,
    and :meth:`poll` — after every dispatch, and from
    ``IngressPlane`` at every harvest — observes those that have
    arrived, oldest first, without waiting (``early_observes``).  Only
    when more than ``max_in_flight`` dispatches are still unobserved
    does the driver await the OLDEST readback — the window-boundary
    sync, the single blocking point (counted in ``window_syncs``; lint
    rule RA04 polices the bench loops this feeds).

    ``shardings`` (optional, from
    :func:`ra_tpu.parallel.mesh.superstep_block_shardings`) places the
    staged ``n_new``/``payloads`` blocks on a device mesh so a sharded
    engine's fused dispatch consumes them without a resharding copy.
    Elect schedules are NOT staged: they are host data by the
    `_host_mask` contract (the any-election bookkeeping runs on the
    host), so the driver hands them to :meth:`LockstepEngine.superstep`
    untouched.

    The flat entry (ISSUE 26): after :meth:`prepare_flat`,
    :meth:`submit_rows` takes a write block as the rows it carries
    (``CoalesceWindow.pop_rows``), puts those to the device padded to
    one of :func:`flat_buckets`' row counts, and stages the output of
    the jitted ``ra_densify`` program: the same dense device array
    :meth:`submit` stages, for a hundredth of the host copy and H2D
    bytes when the block is mostly empty.

    Each dispatch's readback is ``engine.watermarks()`` (ISSUE 27): the
    committed count (``last_committed``) and the ring entries in use
    (``last_ring_used``) of every lane, observed together;
    ``staged`` and ``observed`` count the blocks staged (each
    dispatched in the call that staged it) and the dispatches observed
    so far, in one order.  A read-carrying dispatch of a reads-enabled
    engine also reads back the replies of the read rows its caller
    asks for (``read_ask``), gathered from the ``[K, N, Kr, Wq]``
    reply tensor on the device by the jitted ``ra_read_rows``, never
    the tensor itself (``pipeline_counters`` ``read_window_rows`` /
    ``read_window_padded``).
    """

    def __init__(self, engine: "LockstepEngine", max_in_flight: int = 2,
                 shardings: Optional[dict] = None) -> None:
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self.engine = engine
        self.max_in_flight = max_in_flight
        self.shardings = shardings or {}
        self._handles: collections.deque = collections.deque()
        self.last_committed: Optional[np.ndarray] = None
        #: ring entries in use per lane (np.int32[N]) as of the same
        #: observation, and how many dispatches have been observed
        self.last_ring_used: Optional[np.ndarray] = None
        self.observed = 0
        #: blocks staged (and dispatched) so far: a block's ordinal,
        #: which ``observed`` reaches when its dispatch has been
        #: observed; the next submit's block gets ``staged + 1``
        self.staged = 0
        #: newest OBSERVED cumulative read watermarks (np.int32[N]) —
        #: the read twin of last_committed, advanced at the same
        #: observations; the ingress read lane settles its
        #: in-flight blocks against these (ISSUE 20)
        self.last_read_served: Optional[np.ndarray] = None
        self.last_read_shed: Optional[np.ndarray] = None
        self.last_read_stale: Optional[np.ndarray] = None
        #: observed read aux (the outcome counters, and on a
        #: read-carrying dispatch its [K, N] outcomes and watermarks and
        #: the replies of the rows it was asked for, np arrays) in
        #: dispatch order, drained by IngressPlane read harvest —
        #: bounded so a driver with no read consumer (bench loops that
        #: only need the served counters) cannot grow host memory
        self.read_obs: collections.deque = collections.deque(maxlen=64)
        #: the flat entry's row counts and the program of each (by
        #: padded rows), set by prepare_flat(); empty = every block
        #: goes dense
        self._flat_buckets: tuple = ()
        self._densify: dict = {}
        engine._driver = self

    def in_flight(self) -> int:
        return len(self._handles)

    def prepare_flat(self, superstep_k: int) -> None:
        """Open the flat entry for ``[superstep_k, N, Kc, C]`` write
        blocks: derive the buckets and run ``ra_densify`` once at each,
        so every program it will use is compiled (or fetched from the
        compile cache) here and none inside a serving window."""
        eng = self.engine
        k, kc = int(superstep_k), int(eng.max_step_cmds)
        self._flat_buckets = flat_buckets(k, eng.n_lanes, kc)
        dtype = np.dtype(eng.payload_dtype)
        rows = np.zeros((0, eng.payload_width), dtype)
        zero = np.zeros(eng.n_lanes, np.int32)
        for padded in self._flat_buckets:
            self._densify[padded] = densify_fn(
                k, kc, self.shardings.get("payloads"),
                (padded, eng.payload_width, dtype.str, eng.n_lanes))
            self._put_rows(rows, zero, zero, padded)

    def flat_rows(self, n_rows: int) -> Optional[int]:
        """Rows put to the device for a flat block of ``n_rows``: the
        least bucket that holds them.  None when the block has more
        rows than the top bucket (or the entry is not open) and goes
        dense through :meth:`submit`."""
        i = bisect.bisect_left(self._flat_buckets, n_rows)
        return self._flat_buckets[i] if i < len(self._flat_buckets) \
            else None

    def _put_rows(self, rows, row_base, take, padded: int):
        """(dense block on the device, bytes put) of one flat block."""
        put = jax.device_put
        table = np.zeros((padded, rows.shape[1]), rows.dtype)
        table[:len(rows)] = rows
        r = put(table, self.shardings.get("rows"))
        b = put(np.asarray(row_base, np.int32),  # ra02-ok: host block -> staging encode (async H2D; no device readback)
                self.shardings.get("row_base"))
        t = put(np.asarray(take, np.int32),  # ra02-ok: host block -> staging encode (async H2D; no device readback)
                self.shardings.get("take"))
        return (self._densify[padded](r, b, t),
                r.nbytes + b.nbytes + t.nbytes)

    def _stage(self, n_new_blk, payloads_blk, elect_blk=None,
               read_blk=None, block=None, flat=None,
               read_ask=None) -> list:
        """Put one block on the device (async) and return it as the
        dispatch takes it: ``[n_new, payloads, elect, read, block,
        staged at, read_ask]``."""
        put = jax.device_put
        # host_staging phase: the host-side encode + H2D submit cost of
        # this block (device_put is async, so this is the edge the host
        # pays, not the wire time — rule RA04: no sync here)
        with trace.phase_span("ra.driver.stage", self.engine.phases,
                              "host_staging", "engine", block=block) as sp:
            n = put(np.asarray(n_new_blk, np.int32),  # ra02-ok: host block -> staging encode (async H2D; no device readback)
                    self.shardings.get("n_new"))
            if flat is None:
                p = put(np.asarray(payloads_blk), self.shardings.get("payloads"))  # ra02-ok: host block -> staging encode (async H2D; no device readback)
                nbytes, nev = n.nbytes + p.nbytes, 2
            else:
                # the block as its rows: p is ra_densify's output, the
                # same dense device array the other branch puts
                p, nbytes = self._put_rows(*flat)
                nbytes, nev = nbytes + n.nbytes, 4
            if read_blk is not None:
                read_blk = self._put_reads(read_blk)
                nbytes += read_blk[0].nbytes + read_blk[1].nbytes
                nev += 2
        self.engine.pump_split["stage"] += sp.dt_s
        self.engine.pipeline_counters["blocks_staged"] += 1
        self.staged += 1
        # transfer ledger (ISSUE 16): the steady-state loop's h2d
        # budget is exactly these staged blocks per submit —
        # measured here so the "fixed per-window transfer budget" is a
        # number, not an RA04 lint promise (.nbytes = host metadata)
        devicewatch.record_h2d("driver_stage", nbytes, events=nev)
        return [n, p, elect_blk, read_blk, block, time.monotonic(),
                read_ask]

    def _put_reads(self, read_blk):
        """A host read block ``(n_read, read_q)`` on the device."""
        put = jax.device_put
        rn = put(np.asarray(read_blk[0], np.int32),  # ra02-ok: host read block -> staging encode (async H2D; no device readback)
                 self.shardings.get("n_read"))
        rq = put(np.asarray(read_blk[1]), self.shardings.get("read_q"))  # ra02-ok: host read block -> staging encode (async H2D; no device readback)
        return rn, rq

    def submit(self, n_new_blk, payloads_blk, elect_blk=None,
               read_blk=None, block=None, read_ask=None):
        """Stage this block (async H2D) and dispatch it.
        ``read_blk``: optional ``(n_read_blk [K,N], read_q_blk
        [K,N,Kr,Cq])`` read schedule riding the same dispatch.
        ``read_ask``: with it, optional ``(lanes, rows)`` int vectors,
        the read rows this dispatch can serve (every lane's batch out,
        its own and those of earlier dispatches): only their replies
        are read back, as ``read_reply_rows`` in the same order.
        ``block``: the caller's identifier of this block (the ingress
        plane's ``blocks_built`` at pop), carried by the block's
        ``ra.driver.stage`` and ``ra.driver.dispatch`` spans.
        Returns this dispatch's async watermark handle."""
        return self._dispatch(self._stage(n_new_blk, payloads_blk,
                                          elect_blk, read_blk, block,
                                          read_ask=read_ask))

    def submit_rows(self, n_new_blk, rows, row_base, take,
                    read_blk=None, block=None, read_ask=None):
        """:meth:`submit` for a write block given as the rows it
        carries (``CoalesceWindow.pop_rows``: ``rows`` [M, C] lane by
        lane, ``row_base`` / ``take`` [N]).  M has to fit a bucket:
        ask :meth:`flat_rows` first, and go dense where it says None."""
        padded = self.flat_rows(len(rows))
        if padded is None:
            raise ValueError(
                f"submit_rows: {len(rows)} rows fit no bucket of "
                f"{self._flat_buckets}; such a block goes through submit()")
        return self._dispatch(self._stage(
            n_new_blk, None, None, read_blk, block,
            flat=(rows, row_base, take, padded), read_ask=read_ask))

    def _dispatch(self, blk):
        eng = self.engine
        t_sub = time.monotonic()
        # staged_wait phase: end of this block's _stage to the start of
        # its dispatch, inside the one submit() that does both
        eng.phases.note("staged_wait", t_sub - blk[5])
        # the block's WAL steps, known before the call: the join from a
        # block to its ra.wal.encode / ra.wal.batch spans
        steps = None
        if eng._dur is not None and trace.active():
            first = eng._dur.step_seq + 1
            steps = f"{first}-{first + int(blk[0].shape[0]) - 1}"
        split = eng.pump_split
        inner = split["backpressure"] + split["wal_submit"] + \
            split["window_sync"]
        with trace.phase_span("ra.driver.dispatch", None, "dispatch",
                              "engine", block=blk[4], step=steps) as sp:
            h = self._launch(blk, t_sub)
            if steps is not None:
                self._annotate_carries(sp)
        split["dispatch"] += sp.dt_s - (split["backpressure"] +
                                        split["wal_submit"] +
                                        split["window_sync"] - inner)
        return h

    def _annotate_carries(self, sp) -> None:
        """``carries=<first>-<last>`` on this dispatch's span: the WAL
        steps whose merged confirm its sample is the first to cover,
        the join from the blocks whose rows became durable before it
        to their carrier (ISSUE 37)."""
        samples = self.engine.confirm_samples
        n = self.engine.pipeline_counters["dispatches"]
        cur, prev = samples.get(n), samples.get(n - 1)
        if prev is not None:
            # a confirm-only program since that dispatch carried more
            prev = self.engine.confirm_only_samples.get(n - 1,
                                                        prev[1:])[1]
        if cur is not None and prev is not None:
            first, last = min(prev) + 1, min(cur[2])
            if first <= last:
                sp.set_metadata(carries=f"{first}-{last}")

    def _launch(self, blk, t_sub):
        read_blk, read_ask = blk[3], blk[6]
        self.engine.superstep(
            blk[0], blk[1], elect_blk=blk[2],
            n_read_blk=None if read_blk is None else read_blk[0],
            read_q_blk=None if read_blk is None else read_blk[1],
            enqueued=lambda aux: self._start_readback(aux, t_sub,
                                                      read_blk, read_ask))
        h = self._handles[-1][1]
        # whatever arrived while the WAL took the aux, this dispatch's
        # own watermark included where the step is already done
        self.poll()
        while len(self._handles) > self.max_in_flight:
            # window boundary: await the OLDEST dispatch's watermark.
            # Only a pop that actually had to WAIT counts as a
            # window_sync — a ready readback is poll()'s, the pipeline
            # working, not blocking (the counter backs the
            # "window_syncs << dispatches" health rule, so it must
            # distinguish the two)
            waited = not self._ready(self._handles[0])
            if not waited:
                self._take()
                continue
            self.engine.pipeline_counters["window_syncs"] += 1
            # the serve thread blocked on the oldest readback: a span
            # once per wait, never for a ready readback popped in
            # passing
            with trace.phase_span("ra.driver.window_sync", None,
                                  "window_sync", "engine") as sync:
                self._take()
            self.engine.pump_split["window_sync"] += sync.dt_s
        return h

    def _start_readback(self, aux, t_sub, read_blk, read_ask) -> None:
        """Enqueue this dispatch's watermark program and start its copy
        to the host: called by ``engine.superstep`` directly after the
        step is enqueued, so the program sits behind the step on the
        device's queue and the copy is under way while the serve
        thread hands the aux to the WAL."""
        # a fresh array, so the next dispatch's buffer donation cannot
        # touch the readback (same contract as committed_lanes_async)
        h = self.engine.watermarks()
        try:
            h.copy_to_host_async()
        except AttributeError:  # pragma: no cover — older jax arrays
            pass
        # transfer ledger (ISSUE 16): one watermark readback per
        # dispatch, counted at copy start (the pop that observes it
        # takes the SAME copy — never double-counted)
        devicewatch.record_d2h("driver_watermark", h.nbytes)
        robs = None
        if self.engine.reads_enabled:
            # read answers drain off the same async-readback rhythm as
            # the committed watermark: copies START here (no sync), and
            # are OBSERVED with it (ISSUE 20 — no new host sync points
            # for the read plane).  The cumulative [N] outcome counters
            # ride EVERY dispatch (a batch registered in dispatch i may
            # serve or expire during a read-less dispatch i+k —
            # settlement must still see it); a read-carrying dispatch
            # adds its [K, N] outcomes and the replies of the rows it
            # was asked for, gathered on the device
            robs = {"read_served_lanes": aux["read_served_lanes"][-1] + 0,
                    "read_shed_lanes": aux["read_shed_lanes"][-1] + 0,
                    "read_stale_lanes": aux["read_stale_lanes"][-1] + 0}
            if read_blk is not None:
                robs.update({k: aux[k] + 0 for k in
                             ("read_done", "read_watermark")})
                if read_ask is not None and len(read_ask[0]):
                    robs["read_reply_rows"] = self._gather_replies(
                        aux, read_ask)
            rb = 0
            for v in robs.values():
                try:
                    v.copy_to_host_async()
                except AttributeError:  # pragma: no cover
                    pass
                rb += v.nbytes
            devicewatch.record_d2h("driver_read", rb, events=len(robs))
        self._handles.append((t_sub, h, robs, read_ask))

    def _gather_replies(self, aux, read_ask):
        """``ra_read_rows`` over this dispatch's reply tensor for the
        asked ``(lanes, rows)``, padded to :func:`read_rows_padded`'s
        length (the pad asks for lane 0's row 0, and nobody reads it):
        ``[M, Wq]`` on the device, enqueued here, on the dispatch
        thread, behind the step (a sharded aux takes a gather across
        the mesh, which no other thread may start)."""
        eng = self.engine
        n = len(read_ask[0])
        m = read_rows_padded(n, eng.n_lanes * eng.read_window)
        lanes, rows = np.zeros(m, np.int32), np.zeros(m, np.int32)
        lanes[:n], rows[:n] = read_ask
        pc = eng.pipeline_counters
        pc["read_window_rows"] += n
        pc["read_window_padded"] += m - n
        fn = read_rows_fn((eng.n_lanes, eng.read_window,
                           eng.query_reply_width, eng.mesh_shape(), m))
        return fn(aux["read_done"], aux["read_replies"], lanes, rows)

    @staticmethod
    def _ready(entry) -> bool:
        """Whether a dispatch's readbacks (its watermark and, on a
        reads-enabled engine, its read aux) have all arrived."""
        _t0, h, robs, _ask = entry
        try:
            return h.is_ready() and (
                robs is None or all(v.is_ready() for v in robs.values()))
        except AttributeError:  # pragma: no cover — older jax arrays
            return False

    def _take(self) -> None:
        """Observe the oldest unobserved dispatch (blocks until its
        readbacks have arrived)."""
        t0, h, robs, read_ask = self._handles.popleft()
        self._observe(np.asarray(h))  # ra02-ok: the driver's one readback conversion: a handle poll() found ready (no wait), the in-flight cap's window-boundary wait (window_syncs), or drain()'s barrier
        # device_dispatch phase stamp: submit -> the dispatch's
        # committed watermark observed on the host
        self.engine.phases.note("device_dispatch", time.monotonic() - t0)
        self._observe_reads(t0, robs, read_ask)

    def poll(self) -> int:
        """Observe every dispatch whose readbacks have arrived, oldest
        first, and return how many (counted in ``early_observes``).
        Never waits: it stops at the first that is not ready, so
        ``observed`` still counts dispatches in staging order.  Called
        after every dispatch and by ``IngressPlane`` at every harvest,
        so a watermark is seen when it is there and not when the
        in-flight cap pushes it out (ISSUE 28)."""
        n = 0
        while self._handles and self._ready(self._handles[0]):
            self._take()
            n += 1
        self.engine.pipeline_counters["early_observes"] += n
        return n

    def confirm_only(self, sample: tuple) -> None:
        """Carry a WAL confirm that missed the last dispatch: the
        engine's ``confirm_only(sample)`` and a wait for the committed
        count it returns (int32[N]), which no step is ahead of: every
        dispatch has been observed.  ``last_committed`` advances;
        ``last_ring_used`` stands (the program appends and applies
        nothing, so the entries in use are the last observation's);
        ``observed`` and the dispatch count do not move, since no
        dispatch ran (the read lane settles on dispatch ordinals)."""
        if self._handles:
            raise RuntimeError("confirm_only: a dispatch is unobserved")
        total = self.engine.confirm_only(sample)
        devicewatch.record_d2h("confirm_only", total.nbytes)
        # a copy: the array is the state's, which the next dispatch
        # donates
        self.last_committed = np.asarray(total).copy()  # ra02-ok: the confirm-only program's one readback, N int32 with no step ahead of it on the device; its release is what it is run for

    def _observe(self, marks: np.ndarray) -> None:
        """One dispatch's ``engine.watermarks()``, on the host."""
        self.last_committed, self.last_ring_used = marks[0], marks[1]
        self.observed += 1

    def _observe_reads(self, t_sub, robs, read_ask) -> None:
        """Convert a popped dispatch's read-aux copies to host data —
        called only where its watermark is observed (the copies were
        started at dispatch, and poll() takes a dispatch only when they
        have all arrived; observing them here adds no new sync point
        beyond the committed-watermark one)."""
        if robs is None:
            return
        obs = {k: np.asarray(v) for k, v in robs.items()}  # ra02-ok: window-boundary read observation — same pop as last_committed, copies started async at dispatch
        # which dispatch this is, among the staged blocks: the read
        # lane settles a batch on its own dispatch's outcome or a later
        # one's, never on an older one's (ISSUE 35)
        obs["ordinal"] = self.observed
        if "read_reply_rows" in obs:
            # the (lanes, rows) the dispatch asked for: row i of
            # read_reply_rows answers pair i
            obs["read_ask"] = read_ask
        self.last_read_served = obs["read_served_lanes"]
        self.last_read_shed = obs["read_shed_lanes"]
        self.last_read_stale = obs["read_stale_lanes"]
        self.read_obs.append(obs)
        # read_e2e phase stamp: read-block submit -> serve outcome
        # observed on the host (the continuous signal behind the
        # read_p99_ms SLO objective) — stamped only for dispatches
        # that actually served reads, so write-only dispatches on a
        # reads-enabled engine don't dilute the read latency signal
        if "read_done" in obs and obs["read_done"].any():
            self.engine.phases.note("read_e2e",
                                    time.monotonic() - t_sub)

    def drain(self) -> Optional[np.ndarray]:
        """Await every in-flight readback; returns the newest observed
        per-lane committed watermark (np.int32[N])."""
        while self._handles:
            self._take()
        return self.last_committed
