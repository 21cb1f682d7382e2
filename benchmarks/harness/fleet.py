"""The benchmark's own client fleet and ledger.

N in-process wire connections into a ``WireListener`` through its
loopback transport (``loopback_connect`` / ``loopback_feed`` /
``collect_loopback``), as flat numpy state.  It follows the client
rules of docs/INGRESS.md that ``ra_tpu.wire.client.LoopbackFleet``
follows (ascending op ids per session, one un-credited batch per
session, a refused never-placed op is re-keyed), but the schedule, the
256-byte payload and the ledger of what was sent, when it was due and
when its ACK was seen are the benchmark's, so that a later change to
the program's client cannot move the yardstick.

Every op keeps: its session, op id, delta, body (a row of the seeded
pool and a salt), the time it was due, the time it was first fed to the
transport, and the time the client saw the ACK that covers it.
"""
from __future__ import annotations

import numpy as np

from ra_tpu.ingress.backpressure import DUP, OK, SLOW
from ra_tpu.wire.framing import encode_data

from .reference import body_words

_SEQ_BITS = 40


def batch_rank(keys: np.ndarray) -> np.ndarray:
    """Rank of each element among the equal keys before it."""
    n = len(keys)
    if not n:
        return np.zeros(0, np.int64)
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    starts = np.flatnonzero(np.append(True, sk[1:] != sk[:-1]))
    counts = np.diff(np.append(starts, n))
    out = np.empty(n, np.int64)
    out[order] = np.arange(n) - np.repeat(starts, counts)
    return out


def ragged_arange(counts: np.ndarray) -> np.ndarray:
    """[0..c0), [0..c1), ... concatenated."""
    total = int(counts.sum())
    starts = np.cumsum(counts) - counts
    return np.arange(total) - np.repeat(starts, counts)


class BenchFleet:
    def __init__(self, listener, n_conns: int, sessions_per_conn: int,
                 pool: np.ndarray, *, max_ops: int, rank_cap: int = 64,
                 key: str = "bench") -> None:
        self.listener = listener
        self.spc = int(sessions_per_conn)
        self.conns = listener.loopback_connect(
            n_conns, sessions_per_conn=self.spc, key=key, tenants=1)
        self.n_sessions = n_conns * self.spc
        self.base = int(listener.hbase[self.conns[0]])
        self.handles = self.base + np.arange(self.n_sessions,
                                             dtype=np.int64)
        # the session's address as the client learns it: its cluster
        # (lane) and its dedup slot there (HELLO_ACK)
        self.slots = listener.session_slots(self.handles).astype(np.int64)
        self.lanes = listener.plane.directory.lane[
            self.handles].astype(np.int64)
        self.width = listener.payload_width
        self.pool = pool
        self.next_seq = np.ones(self.n_sessions, np.int64)
        self.next_op = np.ones(self.n_sessions, np.int64)
        self.placed_cnt = np.zeros(self.n_sessions, np.int64)
        self.watermark = np.zeros(self.n_sessions, np.int64)
        self.max_ops = int(max_ops)
        m = self.max_ops
        self.op_sess = np.zeros(m, np.int64)
        self.op_id = np.zeros(m, np.int64)
        self.op_delta = np.zeros(m, np.int32)
        self.op_row = np.zeros(m, np.int32)
        self.op_salt = np.zeros(m, np.int32)
        self.op_rank = np.full(m, -1, np.int64)
        self.op_due = np.zeros(m, np.float64)
        self.op_sent = np.full(m, np.nan)
        self.op_acked = np.full(m, np.nan)
        self.n_ops = 0
        self.refusals = 0
        # (session, placement rank) -> op, a ring per session: ACKs are
        # cumulative per session, so an ACK names a range of ranks
        self.rank_cap = int(rank_cap)
        self._rank_op = np.full((self.n_sessions, self.rank_cap), -1,
                                np.int64)
        self._queued = np.zeros(0, np.int64)
        self._pend_key = np.zeros(0, np.int64)
        self._pend_op = np.zeros(0, np.int64)
        self._pend_per_sess = np.zeros(self.n_sessions, np.int64)
        #: test hook: called with the payload rows just before they are
        #: encoded (the place where a fault alters what is sent)
        self.tamper = None

    # -- ops ----------------------------------------------------------------

    def new_ops(self, sess, deltas, rows, salts, due) -> np.ndarray:
        n = len(sess)
        while self.n_ops + n > self.max_ops:
            self._grow()
        lo = self.n_ops
        idx = np.arange(lo, lo + n)
        self.n_ops += n
        sess = np.asarray(sess, np.int64)
        self.op_sess[idx] = sess
        self.op_id[idx] = self.next_op[sess] + batch_rank(sess)
        np.add.at(self.next_op, sess, 1)
        self.op_delta[idx] = deltas
        self.op_row[idx] = rows
        self.op_salt[idx] = salts
        self.op_due[idx] = due
        self._queued = np.concatenate([self._queued, idx])
        return idx

    def _grow(self) -> None:
        fills = {"op_sess": 0, "op_id": 0, "op_delta": 0, "op_row": 0,
                 "op_salt": 0, "op_rank": -1, "op_due": 0.0,
                 "op_sent": np.nan, "op_acked": np.nan}
        for name, fill in fills.items():
            arr = getattr(self, name)
            setattr(self, name, np.concatenate(
                [arr, np.full(self.max_ops, fill, arr.dtype)]))
        self.max_ops *= 2

    def payload(self, idx: np.ndarray) -> np.ndarray:
        pay = np.empty((len(idx), self.width), np.int32)
        sess = self.op_sess[idx]
        pay[:, 0] = self.slots[sess]
        pay[:, 1] = self.op_id[idx]
        pay[:, 2] = self.op_delta[idx]
        pay[:, 3:] = body_words(self.pool, self.op_row[idx],
                                self.op_salt[idx])
        return pay

    # -- send ---------------------------------------------------------------

    def send_queued(self, now: float) -> int:
        idx = self._queued
        if not len(idx):
            return 0
        free = self._pend_per_sess[self.op_sess[idx]] == 0
        held, idx = idx[~free], idx[free]
        if not len(idx):
            return 0
        sess = self.op_sess[idx]
        conn_i = sess // self.spc
        order = np.lexsort((self.op_id[idx], sess, conn_i))
        idx, sess, conn_i = idx[order], sess[order], conn_i[order]
        seq = self.next_seq[sess] + batch_rank(sess)
        np.add.at(self.next_seq, sess, 1)
        pay = self.payload(idx)
        if self.tamper is not None:
            self.tamper(idx, pay)
        rec_bytes = encode_data(sess % self.spc, seq, pay)
        new = np.empty(len(conn_i), bool)
        new[0] = True
        new[1:] = conn_i[1:] != conn_i[:-1]
        starts = np.flatnonzero(new)
        counts = np.diff(np.append(starts, len(conn_i)))
        take = self.listener.loopback_feed(self.conns[conn_i[starts]],
                                           rec_bytes, counts)
        fed = ragged_arange(counts) < np.repeat(take, counts)
        f_idx = idx[fed]
        first = np.isnan(self.op_sent[f_idx])
        self.op_sent[f_idx[first]] = now
        np.add.at(self._pend_per_sess, sess[fed], 1)
        key = (self.handles[sess[fed]] << _SEQ_BITS) | seq[fed]
        pk = np.concatenate([self._pend_key, key])
        po = np.concatenate([self._pend_op, f_idx])
        order = np.argsort(pk, kind="stable")
        self._pend_key, self._pend_op = pk[order], po[order]
        self._queued = np.concatenate([held, idx[~fed]])
        return int(fed.sum())

    # -- receive ------------------------------------------------------------

    def collect(self, now: float) -> np.ndarray:
        """Drain the credit and ACK outboxes.  Returns the ops newly
        acknowledged."""
        credit, ack = self.listener.collect_loopback()
        for conns, counts, rec in credit:
            handles = self.listener.hbase[np.repeat(conns, counts)] + \
                rec["sess"].astype(np.int64)
            self._on_credit(handles, rec["seqno"].astype(np.int64),
                            rec["status"].astype(np.int8))
        newly = []
        for conns, counts, rec in ack:
            sess = self.listener.hbase[np.repeat(conns, counts)] + \
                rec["sess"].astype(np.int64) - self.base
            newly.append(self._on_ack(sess, rec["acked"].astype(np.int64),
                                      now))
        return np.concatenate(newly) if newly else np.zeros(0, np.int64)

    def _on_ack(self, sess, acked, now: float) -> np.ndarray:
        # a frame may name a session twice: keep its highest watermark
        order = np.lexsort((acked, sess))
        sess, acked = sess[order], acked[order]
        last = np.append(sess[1:] != sess[:-1], True)
        sess, acked = sess[last], acked[last]
        # flow-control grade (docs/INGRESS.md): never above what this
        # client has had placed
        acked = np.minimum(acked, self.placed_cnt[sess])
        old = self.watermark[sess]
        grow = acked > old
        sess, acked, old = sess[grow], acked[grow], old[grow]
        if not len(sess):
            return np.zeros(0, np.int64)
        self.watermark[sess] = acked
        counts = acked - old
        ranks = np.repeat(old, counts) + ragged_arange(counts)
        ops = self._rank_op[np.repeat(sess, counts), ranks % self.rank_cap]
        if (ops < 0).any() or (self.op_rank[ops] != ranks).any():
            raise RuntimeError("ledger: an ACK names a rank the client "
                               "has no op for (rank ring too small?)")
        self.op_acked[ops] = now
        return ops

    def _on_credit(self, handles, seqnos, statuses) -> None:
        if not len(self._pend_key):
            return
        key = (handles << _SEQ_BITS) | seqnos
        pos = np.clip(np.searchsorted(self._pend_key, key), 0,
                      len(self._pend_key) - 1)
        match = self._pend_key[pos] == key
        ops = self._pend_op[pos[match]]
        st = statuses[match]
        np.add.at(self._pend_per_sess, self.op_sess[ops], -1)
        # DUP cannot come to a fleet that never replays a seqno; were it
        # to, the op stays unranked and is counted as never acknowledged
        placed = (st == OK) | (st == SLOW) | (st == DUP)
        p_ops = ops[(st == OK) | (st == SLOW)]
        sess = self.op_sess[p_ops]
        rank = self.placed_cnt[sess] + batch_rank(sess)
        np.add.at(self.placed_cnt, sess, 1)
        self.op_rank[p_ops] = rank
        cell = self._rank_op[sess, rank % self.rank_cap]
        if ((cell >= 0) & np.isnan(self.op_acked[np.maximum(cell, 0)])).any():
            raise RuntimeError("ledger: more than rank_cap ops of one "
                               "session placed and unacknowledged")
        self._rank_op[sess, rank % self.rank_cap] = p_ops
        # a refused op was never placed (this fleet never replays a
        # placed op): it gets a fresh id above the session's last and
        # goes back to the queue, still timed from when it was due
        refused = ops[~placed]
        self.refusals += len(refused)
        sess_r = self.op_sess[refused]
        self.op_id[refused] = self.next_op[sess_r] + batch_rank(sess_r)
        np.add.at(self.next_op, sess_r, 1)
        self._queued = np.concatenate([self._queued, refused])
        keep = np.ones(len(self._pend_key), bool)
        keep[pos[match]] = False
        self._pend_key = self._pend_key[keep]
        self._pend_op = self._pend_op[keep]

    # -- progress -----------------------------------------------------------

    def idle(self) -> bool:
        """Nothing queued and no verdict awaited: what is still
        unacknowledged waits on the server alone."""
        return not len(self._queued) and not len(self._pend_key)

    def outstanding(self) -> int:
        """Ops minted and not yet acknowledged."""
        return int(np.isnan(self.op_acked[:self.n_ops]).sum())
