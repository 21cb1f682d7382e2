"""JitFifoMachine — the FIFO capability machine on the device apply path.

The host :class:`~ra_tpu.models.fifo.FifoMachine` mirrors the reference's
``test/ra_fifo.erl`` (1,520 LoC) with unbounded Python state, consumer
processes, and delivery effects.  That shape cannot fold on-device.  This
machine is the TPU-native counterpart for the BASELINE.md "5,000 clusters
x 5 members, fifo machine" row: a **fixed-capacity** per-lane queue whose
state is a handful of dense arrays, covering the ra_fifo command
vocabulary — ordered enqueue, settled and unsettled dequeue, settlement,
return-with-redelivery-count, purge, **registered consumers with
per-consumer credit, consumer cancel, and consumer-down requeue**
(ra_fifo.erl apply clauses :254-368) — as a shape-stable fold.

Queue ops do not commute, but the machine still supports the engine's
one-shot window fold (``jit_apply_batch``): a window of only noop/
enqueue/dequeue-settled commands — the ra_bench workload and the
quorum-queue steady state — folds vectorized via a clamped-add
``associative_scan`` (see the method comment); anything else falls back
to an in-order masked ``lax.scan`` of ``jit_apply`` under a
``lax.cond``.

Scope split vs the host machine: pull-style checkout (the device cannot
emit delivery effects), death == cancel (the host's ``noconnection``
suspect/nodeup dance and enqueuer seq-dedup stay host-side), and a
bounded consumer table.  Everything that IS here is differentially
tested against the host oracle (tests/test_jit_fifo.py).

State (leading lane axis added by ``jit_init``; the engine broadcasts a
member axis):

* ``buf/dc/mid int32[Q]`` — ready-message ring: payload value, delivery
  count, and enqueue ticket (the host machine's ``msg_in_id``).  The
  window is always ticket-sorted: enqueues append fresh tickets, returns
  re-insert at ticket rank.
* ``head/tail int32`` — ready window is ``head..tail-1`` (slot = idx % Q)
* ``co_id/co_val/co_dc/co_mid int32[K]`` — checked-out (unsettled) table;
  ``co_id < 0`` marks a free row
* ``co_owner int32[K]`` — consumer slot owning the row; ``C`` (the
  consumer-table size) marks an anonymous (op 3) checkout
* ``con_pid/con_credit int32[C]`` — registered consumers; pid < 0 free
* ``next_id int32`` — monotonic message-id source for unsettled dequeues
* ``next_mid int32`` — monotonic enqueue-ticket source
* ``n_dropped int32`` — messages discarded by the drop_head policy

**Capacity contract**: ``capacity`` bounds LIVE messages (ready +
checked-out), so a return/cancel requeue can never overflow the ring.
``overflow`` picks the full-queue enqueue policy: ``"reject"`` replies
-2 (ra_fifo's implicit backpressure); ``"drop_head"`` discards the
oldest READY message and admits the new one (the quorum-queue
max-length drop-head policy), counting drops in ``n_dropped``.

Command encoding (command_spec int32[3]): ``[op, a, b]``

  op 0  noop                       (term-opening entry)
  op 1  enqueue(value)             reply  1 ok | -2 queue full (reject)
  op 2  dequeue settled            reply  value | -1 empty
  op 3  dequeue unsettled (anon)   reply  msg_id | -1 empty | -3 table full
  op 4  settle(msg_id)             reply  1 | 0 unknown id
  op 5  return(msg_id)             reply  1 | 0 unknown id
  op 6  purge                      reply  number of ready messages dropped
  op 7  attach(pid, credit)        reply  1 | -4 consumer table full
  op 8  cancel(pid)                reply  #messages requeued (0 unknown)
  op 9  down(pid)                  alias of cancel (death semantics)
  op 10 checkout(pid)              reply  msg_id | -4 unknown consumer |
                                          -1 empty | -5 no credit |
                                          -3 checkout table full
  op 11 set_credit(pid, credit)    reply  1 | 0 unknown consumer

A returned/requeued message re-enters the ready window at its **original
enqueue position** relative to the other ready messages (insert at
ticket rank), exactly like the host machine's sorted re-insert
(fifo.py ``_return_entries``), with delivery_count+1.  Return and
cancel share one rank-merge: each requeued row lands at its ticket rank
and ready entries gather from their shifted source slot — O(Q*K)
comparisons plus one gather per array, shape-stable, no sequential
loop.  Payload values and pids must be >= 0 so they never collide with
error replies / free markers.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..core.machine import JitMachine, cond_concrete
from ..ops.exact import place16

_I32 = jnp.int32


def _take(arr, idx):
    return jnp.take_along_axis(arr, idx[..., None], axis=-1)[..., 0]


class JitFifoMachine(JitMachine):
    command_spec = ("int32", (3,))
    reply_spec = ("int32", ())
    version = 0
    #: queue ops do NOT commute — batch apply is still sound because
    #: jit_apply_batch folds the window IN ORDER (vectorized fast path
    #: for noop/enqueue/dequeue windows, masked sequential fold else)
    supports_batch_apply = True

    def __init__(self, capacity: int = 64, checkout_slots: int = 8,
                 consumer_slots: int = 4,
                 overflow: str = "reject") -> None:
        if overflow not in ("reject", "drop_head"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        self.capacity = capacity
        self.checkout_slots = checkout_slots
        self.consumer_slots = consumer_slots
        self.overflow = overflow

    def jit_init(self, n_lanes: int):
        N, Q, K, C = (n_lanes, self.capacity, self.checkout_slots,
                      self.consumer_slots)
        return {
            "buf": jnp.zeros((N, Q), _I32),
            "dc": jnp.zeros((N, Q), _I32),
            "mid": jnp.zeros((N, Q), _I32),
            "head": jnp.zeros((N,), _I32),
            "tail": jnp.zeros((N,), _I32),
            "co_id": jnp.full((N, K), -1, _I32),
            "co_val": jnp.zeros((N, K), _I32),
            "co_dc": jnp.zeros((N, K), _I32),
            "co_mid": jnp.zeros((N, K), _I32),
            "co_owner": jnp.zeros((N, K), _I32),
            "con_pid": jnp.full((N, C), -1, _I32),
            "con_credit": jnp.zeros((N, C), _I32),
            "next_id": jnp.zeros((N,), _I32),
            "next_mid": jnp.zeros((N,), _I32),
            "n_dropped": jnp.zeros((N,), _I32),
        }

    def jit_apply(self, meta, command, state):
        Q, K, C = self.capacity, self.checkout_slots, self.consumer_slots
        op = command[..., 0]
        a = command[..., 1]
        b = command[..., 2]
        head, tail = state["head"], state["tail"]
        next_id, next_mid = state["next_id"], state["next_mid"]
        buf, dc, mid = state["buf"], state["dc"], state["mid"]
        co_id, co_val = state["co_id"], state["co_val"]
        co_dc, co_mid = state["co_dc"], state["co_mid"]
        co_owner = state["co_owner"]
        con_pid, con_credit = state["con_pid"], state["con_credit"]
        n_dropped = state["n_dropped"]

        size = tail - head
        empty = size <= 0
        checked = jnp.sum((co_id >= 0).astype(_I32), axis=-1)
        full = (size + checked) >= Q          # capacity bounds LIVE msgs

        # -- consumer-table resolution (ops 7-11) -------------------------
        cr = jnp.arange(C)
        pid_match = (con_pid == a[..., None]) & (a[..., None] >= 0)
        pid_found = jnp.any(pid_match, axis=-1)
        pid_slot = jnp.argmax(pid_match, axis=-1).astype(_I32)
        con_free = con_pid < 0
        have_con_free = jnp.any(con_free, axis=-1)
        free_con_slot = jnp.argmax(con_free, axis=-1).astype(_I32)

        # -- enqueue -------------------------------------------------------
        drop_head = self.overflow == "drop_head"
        enq_ok = (op == 1) & ~full
        enq_drop = ((op == 1) & full & (size > 0)) if drop_head \
            else jnp.zeros_like(enq_ok)
        enq = enq_ok | enq_drop
        tail_slot = jnp.mod(tail, Q)
        n_dropped = n_dropped + enq_drop.astype(_I32)

        # -- dequeue (settled / unsettled / consumer checkout) ------------
        head_slot = jnp.mod(head, Q)
        head_val = _take(buf, head_slot)
        head_dc = _take(dc, head_slot)
        head_mid = _take(mid, head_slot)
        free_mask = co_id < 0                              # [..., K]
        have_free = jnp.any(free_mask, axis=-1)
        free_slot = jnp.argmax(free_mask, axis=-1).astype(_I32)
        deq_s = (op == 2) & ~empty
        deq_u = (op == 3) & ~empty & have_free
        owned = (co_id >= 0) & (co_owner == pid_slot[..., None])
        used = jnp.sum(owned.astype(_I32), axis=-1)
        credit = _take(con_credit, pid_slot)
        deq_c = ((op == 10) & pid_found & ~empty & have_free &
                 (used < credit))
        take = deq_u | deq_c
        pop = deq_s | take

        # -- settle / return: locate the checked-out row -------------------
        match = (co_id == a[..., None]) & (a[..., None] >= 0)
        found = jnp.any(match, axis=-1)
        match_slot = jnp.argmax(match, axis=-1).astype(_I32)
        m_val = _take(co_val, match_slot)
        m_dc = _take(co_dc, match_slot)
        m_mid = _take(co_mid, match_slot)
        settle = (op == 4) & found
        # return never overflows: live count is unchanged by a requeue
        ret = (op == 5) & found

        purge = op == 6
        cancel = ((op == 8) | (op == 9)) & pid_found
        req_n = jnp.where(cancel, used, 0)    # messages this cancel requeues

        # -- cursor updates ------------------------------------------------
        head = head + pop.astype(_I32) + enq_drop.astype(_I32)
        head = jnp.where(purge, tail, head)
        new_tail = tail + enq.astype(_I32)

        # -- enqueue ring write -------------------------------------------
        qr = jnp.arange(Q)
        enq_hot = (qr == tail_slot[..., None]) & enq[..., None]
        buf = jnp.where(enq_hot, a[..., None], buf)
        dc = jnp.where(enq_hot, 0, dc)
        mid = jnp.where(enq_hot, next_mid[..., None], mid)
        new_next_mid = next_mid + enq.astype(_I32)

        # -- unified requeue merge (op-5 return AND cancel/down) ----------
        # Source rows: the returned row, or every row owned by the
        # canceled consumer.  Each lands at its global ticket rank in
        # the merged window (host _return_entries sorted rebuild); ready
        # entries shift back by the number of requeued tickets below
        # them.  One rank computation + one gather per array — O(Q*K)
        # comparisons, no sequential loop (a masked-per-row fori_loop
        # was ~9x this cost and ran for EVERY command).  The whole merge
        # sits behind a lax.cond: its [..., K, Q] intermediates dominate
        # the apply (~25x on TPU at Q=256) yet are dead work for every
        # command that is not a return/cancel/down — the common case.
        kr = jnp.arange(K)
        req = (cancel[..., None] & owned) | \
            (ret[..., None] & (kr == match_slot[..., None]))
        n_req = jnp.sum(req.astype(_I32), axis=-1)
        new_head = head - n_req

        def _requeue_merge(ops):
            buf, dc, mid, co_val, co_dc, co_mid = ops
            size2 = new_tail - head
            in_win = jnp.mod(qr - head[..., None], Q) < size2[..., None]
            # rank over ready mids [...,K,Q] + fellow requeues [...,K,K]
            rank = jnp.sum((in_win[..., None, :] &
                            (mid[..., None, :] < co_mid[..., :, None]))
                           .astype(_I32), axis=-1)
            rank = rank + jnp.sum(
                (req[..., None, :] &
                 (co_mid[..., None, :] < co_mid[..., :, None]))
                .astype(_I32), axis=-1)
            rank = jnp.where(req, rank, -1)      # inactive rows never land
            jd = jnp.mod(qr - new_head[..., None], Q)        # [..., Q]
            valid = jd < (size2 + n_req)[..., None]
            eq = rank[..., :, None] == jd[..., None, :]      # [..., K, Q]
            land = jnp.any(eq, axis=-2)
            req_val_at = jnp.sum(jnp.where(eq, co_val[..., :, None], 0),
                                 axis=-2)
            req_dc_at = jnp.sum(jnp.where(eq, (co_dc + 1)[..., :, None], 0),
                                axis=-2)
            req_mid_at = jnp.sum(jnp.where(eq, co_mid[..., :, None], 0),
                                 axis=-2)
            cnt_lt = jnp.sum(((rank[..., :, None] >= 0) &
                              (rank[..., :, None] < jd[..., None, :]))
                             .astype(_I32), axis=-2)
            src_slot = jnp.mod(head[..., None] + jd - cnt_lt, Q)
            g_buf = jnp.take_along_axis(buf, src_slot, axis=-1)
            g_dc = jnp.take_along_axis(dc, src_slot, axis=-1)
            g_mid = jnp.take_along_axis(mid, src_slot, axis=-1)
            buf = jnp.where(valid, jnp.where(land, req_val_at, g_buf), buf)
            dc = jnp.where(valid, jnp.where(land, req_dc_at, g_dc), dc)
            mid = jnp.where(valid, jnp.where(land, req_mid_at, g_mid), mid)
            return buf, dc, mid

        buf, dc, mid = cond_concrete(
            jnp.any(n_req > 0), _requeue_merge, lambda ops: ops[:3],
            (buf, dc, mid, co_val, co_dc, co_mid))
        head = new_head

        # -- checkout-table writes ----------------------------------------
        take_hot = (kr == free_slot[..., None]) & take[..., None]
        rel_hot = (kr == match_slot[..., None]) & (settle | ret)[..., None]
        co_val = jnp.where(take_hot, head_val[..., None], co_val)
        co_dc = jnp.where(take_hot, head_dc[..., None], co_dc)
        co_mid = jnp.where(take_hot, head_mid[..., None], co_mid)
        co_owner = jnp.where(
            take_hot,
            jnp.where(deq_c, pid_slot, jnp.full_like(pid_slot, C))[..., None],
            co_owner)
        co_id = jnp.where(take_hot, next_id[..., None], co_id)
        co_id = jnp.where(rel_hot | (cancel[..., None] & owned), -1, co_id)
        new_next_id = next_id + take.astype(_I32)

        # -- consumer attach / credit / cancel ----------------------------
        attach_ok = (op == 7) & (pid_found | have_con_free)
        attach_slot = jnp.where(pid_found, pid_slot, free_con_slot)
        attach_hot = (cr == attach_slot[..., None]) & attach_ok[..., None]
        setc = (op == 11) & pid_found
        setc_hot = (cr == pid_slot[..., None]) & setc[..., None]
        con_pid = jnp.where(attach_hot, a[..., None], con_pid)
        con_credit = jnp.where(attach_hot | setc_hot, b[..., None],
                               con_credit)
        cancel_hot = (cr == pid_slot[..., None]) & cancel[..., None]
        con_pid = jnp.where(cancel_hot, -1, con_pid)

        # -- reply ---------------------------------------------------------
        reply = jnp.where(op == 1, jnp.where(enq, 1, -2), 0)
        reply = jnp.where(op == 2, jnp.where(deq_s, head_val, -1), reply)
        reply = jnp.where(op == 3,
                          jnp.where(deq_u, next_id,
                                    jnp.where(empty, -1, -3)), reply)
        reply = jnp.where(op == 4, settle.astype(_I32), reply)
        reply = jnp.where(op == 5, ret.astype(_I32), reply)
        reply = jnp.where(op == 6, size, reply)
        reply = jnp.where(op == 7, jnp.where(attach_ok, 1, -4), reply)
        reply = jnp.where((op == 8) | (op == 9), req_n, reply)
        reply = jnp.where(
            op == 10,
            jnp.where(deq_c, next_id,
                      jnp.where(~pid_found, -4,
                                jnp.where(empty, -1,
                                          jnp.where(used >= credit, -5,
                                                    -3)))), reply)
        reply = jnp.where(op == 11, setc.astype(_I32), reply)

        new_state = {"buf": buf, "dc": dc, "mid": mid, "head": head,
                     "tail": new_tail, "co_id": co_id, "co_val": co_val,
                     "co_dc": co_dc, "co_mid": co_mid,
                     "co_owner": co_owner, "con_pid": con_pid,
                     "con_credit": con_credit, "next_id": new_next_id,
                     "next_mid": new_next_mid, "n_dropped": n_dropped}
        return new_state, reply

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # supports_batch_apply is True NOT because queue ops commute (they do
    # not) but because a window whose commands are all noop/enqueue/
    # dequeue-settled — the ra_bench workload shape and the common
    # quorum-queue steady state — folds in one vectorized pass:
    #
    #   * the ready-size recurrence  s' = clamp(s + d, 0, Qeff)  is a
    #     composition of clamped-add maps  x -> clamp(x+a, lo, hi),
    #     a family closed under composition, so a log-depth
    #     lax.associative_scan yields every command's pre-state;
    #   * ring positions are exclusive cumsums of the admit/pop flags;
    #   * ring writes are scatter-free: positional wheres plus one
    #     exact one-hot matmul for the payload values (see the
    #     _batch_fast comment — TPU's scatter lowering was ~70ms/step
    #     here, the matmul form ~3ms).
    #
    # Windows containing any consumer/settlement op fall back to
    # sequential_window_fold (an in-order masked lax.scan of jit_apply)
    # under the same lax.cond.  The engine discards per-command replies
    # on this path (lockstep.py step 5), so the fold only has to
    # produce the new state.
    #
    # A round-5 record, from before the benchmark first ran on a TPU
    # v5e and not reproduced on one since (no benchmark cell runs this
    # machine), at 5,000 lanes x 5 members, Q=256, window 130: the
    # engine's representative-scan branch (supports_batch_apply=False)
    # paid the [K,Q] requeue merge on every command and ran 5.42
    # s/step (0.12M cmds/s) even on a pure enqueue/dequeue workload;
    # the vectorized fast path ran ~0.026 s/step (~25M cmds/s) on that
    # workload, and the fallback scan ~0.50 s/step on a worst-case
    # consumer-mix window (the lax.cond inside jit_apply pays the
    # requeue merge only on the commands that actually return/cancel).
    # The quorum-queue deployment runs ``QuorumQueueMachine``
    # (models/quorum_queue.py), whose fold has no such branch;
    # ``apply_fallback_rounds`` counts the rounds that take this one.

    def jit_fallback(self, commands, mask):
        # fast only for noop/enqueue/dequeue-settled windows.
        # DEMOTION CLIFF: this gate is all-or-nothing per window — one
        # consumer/settlement op (opcode > 2) anywhere in the window
        # demotes the WHOLE window to the sequential fold, ~19x the step
        # (~0.026s -> ~0.50s at 5k lanes in the round-5 record above,
        # not reproduced on a TPU).  Throughput therefore scales with
        # the fraction of CLEAN windows, not the per-op mix —
        # callers who can batch consumer ops into dedicated windows
        # keep the fast path for the rest.
        return jnp.any(mask & (commands[..., 0] > 2))

    def jit_apply_batch(self, meta, commands, mask, state):
        return self.window_fold_dispatch(meta, commands, mask, state)

    def _batch_fast(self, commands, mask, state):
        """Vectorized noop/enqueue/dequeue-settled window fold."""
        Q = self.capacity
        BIG = jnp.int32(1 << 20)
        op = jnp.where(mask, commands[..., 0], 0)           # [..., A]
        val = commands[..., 1]
        head, tail = state["head"], state["tail"]           # [...]
        checked = jnp.sum((state["co_id"] >= 0).astype(_I32), axis=-1)
        qeff = Q - checked                                  # live-msg room
        size0 = tail - head

        is_enq = op == 1
        is_deq = op == 2
        # clamped-add element per command: enqueue tops out at qeff
        # (reject AND drop_head both leave the ready size pinned there),
        # dequeue floors at 0, noop is the identity.
        a_el = is_enq.astype(_I32) - is_deq.astype(_I32)
        lo_el = jnp.broadcast_to(jnp.int32(0), a_el.shape)
        hi_el = jnp.where(is_enq, qeff[..., None], Q)

        def combine(c1, c2):                     # c2 AFTER c1
            a1, l1, h1 = c1
            a2, l2, h2 = c2
            return (a1 + a2,
                    jnp.clip(l1 + a2, l2, h2),
                    jnp.clip(h1 + a2, l2, h2))

        a_in, lo_in, hi_in = lax.associative_scan(
            combine, (a_el, lo_el, hi_el), axis=-1)
        # exclusive prefix: command i sees the composition of 0..i-1
        ident = (jnp.zeros_like(a_el[..., :1]),
                 jnp.full_like(a_el[..., :1], -BIG),
                 jnp.full_like(a_el[..., :1], BIG))
        a_ex = jnp.concatenate([ident[0], a_in[..., :-1]], axis=-1)
        lo_ex = jnp.concatenate([ident[1], lo_in[..., :-1]], axis=-1)
        hi_ex = jnp.concatenate([ident[2], hi_in[..., :-1]], axis=-1)
        s = jnp.clip(size0[..., None] + a_ex, lo_ex, hi_ex)  # pre-cmd size

        drop_head = self.overflow == "drop_head"
        at_cap = s >= qeff[..., None]
        if drop_head:
            enq_adm = is_enq & (~at_cap | (s > 0))
            enq_drop = is_enq & at_cap & (s > 0)
        else:
            enq_adm = is_enq & ~at_cap
            enq_drop = jnp.zeros_like(enq_adm)
        deq_ok = is_deq & (s > 0)
        head_adv = deq_ok.astype(_I32) + enq_drop.astype(_I32)

        w_rank = jnp.cumsum(enq_adm.astype(_I32), axis=-1) \
            - enq_adm.astype(_I32)                           # exclusive
        n_enq = jnp.sum(enq_adm.astype(_I32), axis=-1)

        # Ring writes WITHOUT a scatter (TPU scatter lowering costs
        # ~70ms/step at this scale; this form ~3ms): written slots are
        # ring indexes tail0..tail0+n_enq-1, so a slot's window offset
        # jd = (q - tail0) mod Q says everything positional — dc is 0
        # and the enqueue tickets are CONSECUTIVE in ring order, so
        # only buf needs real value placement: an exact one-hot matmul
        # (ops/exact.py place16) contracting the admitted-enqueue rank
        # one-hot against the payload column on the MXU.
        #
        # Windows WIDER than the queue (A > Q) are fine: when several
        # admitted enqueues alias one slot mod Q, only the LAST can
        # survive (its predecessors were dequeued within the window —
        # the live count never exceeds Q — and pops read nothing on
        # this reply-free path), so each slot selects the maximal
        # aliasing rank rank_win = jd + Q*floor((n_enq-1-jd)/Q), which
        # degenerates to jd when A <= Q.
        qr2 = jnp.arange(Q)
        jd = jnp.mod(qr2 - tail[..., None], Q)               # [..., Q]
        written = jd < n_enq[..., None]
        rank_win = jd + Q * ((n_enq[..., None] - 1 - jd) // Q)
        onehot = (enq_adm[..., None, :] &
                  (w_rank[..., None, :] == rank_win[..., None])
                  ).astype(jnp.float32)                      # [..., Q, A]
        placed = place16(onehot, val)

        new_state = dict(state)
        new_state["buf"] = jnp.where(written, placed, state["buf"])
        new_state["dc"] = jnp.where(written, 0, state["dc"])
        new_state["mid"] = jnp.where(
            written, state["next_mid"][..., None] + rank_win,
            state["mid"])
        new_state["head"] = head + jnp.sum(head_adv, axis=-1)
        new_state["tail"] = tail + n_enq
        new_state["next_mid"] = state["next_mid"] + n_enq
        new_state["n_dropped"] = state["n_dropped"] + \
            jnp.sum(enq_drop.astype(_I32), axis=-1)
        return new_state

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        try:
            if isinstance(command, tuple) and command:
                kind = command[0]
                if kind == "enqueue" and len(command) == 2:
                    v = int(command[1])
                    if v >= 0:
                        return jnp.asarray([1, v, 0], _I32)
                elif kind == "dequeue" and len(command) == 2:
                    if command[1] == "settled":
                        return jnp.asarray([2, 0, 0], _I32)
                    if command[1] == "unsettled":
                        return jnp.asarray([3, 0, 0], _I32)
                elif kind == "settle" and len(command) == 2:
                    return jnp.asarray([4, int(command[1]), 0], _I32)
                elif kind == "return" and len(command) == 2:
                    return jnp.asarray([5, int(command[1]), 0], _I32)
                elif kind == "purge":
                    return jnp.asarray([6, 0, 0], _I32)
                elif kind == "attach" and len(command) == 3:
                    return jnp.asarray([7, int(command[1]),
                                        int(command[2])], _I32)
                elif kind == "cancel" and len(command) == 2:
                    return jnp.asarray([8, int(command[1]), 0], _I32)
                elif kind == "down" and len(command) == 2:
                    return jnp.asarray([9, int(command[1]), 0], _I32)
                elif kind == "checkout" and len(command) == 2:
                    return jnp.asarray([10, int(command[1]), 0], _I32)
                elif kind == "credit" and len(command) == 3:
                    return jnp.asarray([11, int(command[1]),
                                        int(command[2])], _I32)
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((3,), _I32)

    def decode_reply(self, reply) -> int:
        return int(reply)


def query_depth(state) -> int:
    """Ready-message count (host-path query fun)."""
    return int(state["tail"]) - int(state["head"])


def query_checked_out(state) -> int:
    import numpy as np
    return int((np.asarray(state["co_id"]) >= 0).sum())


def query_consumers(state) -> int:
    import numpy as np
    return int((np.asarray(state["con_pid"]) >= 0).sum())


def query_dropped(state) -> int:
    return int(state["n_dropped"])
