"""The plain reference of the quorum queue: numpy only.

Imports nothing of ``ra_tpu`` and nothing of the machine's module, and
takes nothing the program computed but the state it is asked to judge.
Its inputs are the configuration's sizes and seed and the client's own
ledger: for every operation its queue (cluster), session, op id, kind,
consumer and salt, and the host-clock times it was first fed to the
transport, last fed, and its answer seen.  It states again, on purpose,
what the machine states: the message a loaded queue holds at a ticket,
what a publish writes, where the message of ticket ``t`` lives in a
replica's store, which delivery ids a consumer holds.

The commit order of sessions is the system's to choose, so the
reference folds no queue; it judges one:

* ``loaded_messages``: the backlog every queue is loaded with, a pure
  function of ``load_seed``, queue, ticket and word;
* ``publish_words``: the message a publish writes: its op id, its
  session, then words mixed from session, op id and salt, so that a
  message names its writer and no two publishes write the same one;
* ``held``: the messages a replica holds (the ready tickets and each
  consumer's checked-out ones, with delivery id and delivery count),
  read from its leaves as the machine lays them out;
* ``removals_exact``: the queues whose settles and returns, by the
  ledger alone, each removed exactly what it named;
* ``state_judgments``: what the held messages and the counts may be,
  given the acknowledged operations;
* ``Clocks``: per queue and consumer, how many settles and returns
  were acknowledged before an instant and first sent before one;
* ``removal_bounds``, ``read_consistency``, ``read_judgments``: each
  answered delivery read against what it may return: its first delivery
  id between what the settles and returns around it allow, its messages
  those of their delivery ids, the fresh ones in ticket order.
"""
from __future__ import annotations

import numpy as np

_U32 = np.uint32
_MASK31 = np.int64(0x7FFFFFFF)
#: a clock reading in integer nanoseconds takes this many bits of a
#: key, the queue and consumer the rest (2^42 ns: 73 minutes of ledger)
_NS_BITS = 42


def _mix32(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> _U32(15))) * _U32(0x2C1B3C6D)
    x = (x ^ (x >> _U32(12))) * _U32(0x297A2D39)
    return x ^ (x >> _U32(15))


def loaded_messages(seed: int, lane, ticket, words: int) -> np.ndarray:
    """int32[..., words]: the loaded message of ticket ``ticket`` in
    queue ``lane``, every word 31 bits of a 32-bit mix of lane, cell =
    ticket * words + word, and the seed."""
    lane = np.asarray(lane, np.int64)[..., None]
    cell = np.asarray(ticket, np.int64)[..., None] * words \
        + np.arange(words)
    with np.errstate(over="ignore"):
        x = (lane.astype(_U32) * _U32(0x9E3779B1)) \
            ^ (cell.astype(_U32) * _U32(0x85EBCA77)) \
            ^ _U32((int(seed) * 0xC2B2AE3D) & 0xFFFFFFFF)
        return (_mix32(x) >> _U32(1)).astype(np.int32)


def _mix64(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def publish_words(sess, op_id, salt, words: int) -> np.ndarray:
    """int32[n, words], each in [0, 2^31): word 0 the op id, word 1 the
    session, the rest a mix of session, op id, salt and position.
    (session, op id) names a publish, so no two are equal."""
    sess = np.asarray(sess, np.int64)
    op_id = np.asarray(op_id, np.int64)
    salt = np.asarray(salt, np.int64).astype(np.uint64)
    with np.errstate(over="ignore"):
        h = _mix64(sess.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                   + op_id.astype(np.uint64) * np.uint64(0xD1B54A32D192ED03)
                   + (salt << np.uint64(33)))
        out = _mix64(h[:, None] + np.arange(words, dtype=np.uint64)
                     * np.uint64(0xA24BAED4963EE407))
    out = (out >> np.uint64(33)).astype(np.int64) & _MASK31
    out[:, 0] = op_id & _MASK31
    out[:, 1] = sess & _MASK31
    return out.astype(np.int32)


def message_hash(msgs: np.ndarray) -> np.ndarray:
    """uint64[...]: a hash of each message's words (the last axis)."""
    h = np.zeros(msgs.shape[:-1], np.uint64)
    with np.errstate(over="ignore"):
        for w in range(msgs.shape[-1]):
            h = _mix64(h ^ (msgs[..., w].astype(np.uint64)
                            + np.uint64(w) * np.uint64(0x9E3779B97F4A7C15)))
    return h


class Publishes:
    """The publishes a queue may hold, by (session, op id), with the
    queue each was sent to."""

    def __init__(self, words: int, *, lane, sess, op_id, salt) -> None:
        self.words = int(words)
        key = (np.asarray(sess, np.int64) << 32) \
            | (np.asarray(op_id, np.int64) & _MASK31)
        order = np.argsort(key, kind="stable")
        self.key = key[order]
        self.lane = np.asarray(lane, np.int64)[order]
        self.sess = np.asarray(sess, np.int64)[order]
        self.op_id = np.asarray(op_id, np.int64)[order]
        self.salt = np.asarray(salt, np.int64)[order]

    def per_lane(self, n_lanes: int) -> np.ndarray:
        return np.bincount(self.lane, minlength=n_lanes)

    def find(self, lane, held: np.ndarray) -> np.ndarray:
        """For each message ``held`` int32[k, words] found in queue
        ``lane`` (int64[k]): the index of the publish of that queue that
        wrote exactly it, or -1."""
        lane = np.asarray(lane, np.int64)
        if not len(self.key) or not len(held):
            return np.full(len(held), -1, np.int64)
        key = (held[:, 1].astype(np.int64) << 32) \
            | (held[:, 0].astype(np.int64) & _MASK31)
        pos = np.minimum(np.searchsorted(self.key, key), len(self.key) - 1)
        hit = (self.key[pos] == key) & (self.lane[pos] == lane)
        want = publish_words(self.sess[pos], self.op_id[pos], self.salt[pos],
                             self.words)
        hit &= (want == held).all(axis=1)
        return np.where(hit, pos, -1)


class Loaded:
    """The loaded messages, found by their first word: ``find`` names
    the ticket of the loaded message of a queue that a message is."""

    def __init__(self, seed: int, n_lanes: int, loaded: int,
                 words: int) -> None:
        self.seed, self.words = int(seed), words
        lane = np.repeat(np.arange(n_lanes, dtype=np.int64), loaded)
        ticket = np.tile(np.arange(loaded, dtype=np.int64), n_lanes)
        # word 0 of each: cell ticket * words
        first = loaded_messages(seed, lane, ticket * words, 1)[:, 0]
        key = (lane << 32) | first.astype(np.int64)
        order = np.argsort(key, kind="stable")
        self.key, self.ticket = key[order], ticket[order]

    def find(self, lane, msgs: np.ndarray) -> np.ndarray:
        """The loaded ticket each message ``msgs`` int32[k, words] of
        queue ``lane`` is, or -1."""
        lane = np.asarray(lane, np.int64)
        if not len(self.key) or not len(msgs):
            return np.full(len(msgs), -1, np.int64)
        key = (lane << 32) | msgs[:, 0].astype(np.int64)
        lo = np.searchsorted(self.key, key)
        out = np.full(len(msgs), -1, np.int64)
        # a first word is 31 bits: a few queues' tickets may share one
        for step in range(4):
            pos = np.minimum(lo + step, len(self.key) - 1)
            cand = self.ticket[pos]
            same = (self.key[pos] == key) & (out < 0)
            if not same.any():
                break
            want = loaded_messages(self.seed, lane[same], cand[same],
                                   self.words)
            ok = (want == msgs[same]).all(axis=1)
            out[np.flatnonzero(same)[ok]] = cand[same][ok]
        return out


def held(leaves: dict, lanes: np.ndarray, *, capacity: int, words: int):
    """The messages the listed queues' replicas hold, from their leaves
    (``store`` [n, Q / 10, 10 * words], ``head``, ``tail``,
    ``out_ticket`` / ``out_count`` [n, C, P], ``lo``, ``next_id``
    [n, C]): (lane, ticket, consumer (-1 ready), delivery id, delivery
    count, the message's words int32[k, words]), ready messages first in
    ticket order, then each consumer's by delivery id."""
    store = leaves["store"][lanes].reshape((len(lanes), capacity, words))
    head = leaves["head"][lanes].astype(np.int64)
    tail = leaves["tail"][lanes].astype(np.int64)
    span = int((tail - head).max(initial=0))
    t = head[:, None] + np.arange(span)
    li, ti = np.nonzero(t < tail[:, None])
    ready = (li, t[li, ti], np.full(len(li), -1), np.full(len(li), -1),
             np.zeros(len(li), np.int64))
    lo = leaves["lo"][lanes].astype(np.int64)
    nx = leaves["next_id"][lanes].astype(np.int64)
    P = leaves["out_ticket"].shape[-1]
    d = lo[..., None] + np.arange(P)                     # [n, C, P]
    li, ci, pi = np.nonzero(d < nx[..., None])
    dd = d[li, ci, pi]
    out = (li, leaves["out_ticket"][lanes][li, ci, dd % P].astype(np.int64),
           ci, dd, leaves["out_count"][lanes][li, ci, dd % P]
           .astype(np.int64))
    parts = [np.concatenate([a, b]) for a, b in zip(ready, out)]
    lane_i, ticket = parts[0], parts[1]
    msgs = store[lane_i, ticket % capacity]
    return (lanes[lane_i].astype(np.int64), ticket, parts[2], parts[3],
            parts[4], msgs)


def removals_exact(*, settles, returns, loaded: int, consumers: int,
                   prefetch: int, settle_n: int, return_n: int) -> np.ndarray:
    """bool[n_lanes]: the queues in which every settle and every return
    removed exactly the ``settle_n`` or ``return_n`` messages it named,
    judged from the ledger alone: ``settles`` / ``returns``
    int64[n_lanes, C] every one sent to a queue and consumer.  A queue
    holds at least its loaded backlog less all they could remove (a
    publish only adds); while that is ``consumers * prefetch`` or more,
    the checkout behind every command fills each consumer's prefetch,
    so a settle or a return of no more than the prefetch finds all it
    names.  Elsewhere a clamp may have bound, and only upper bounds
    hold."""
    most = settle_n * np.asarray(settles, np.int64).sum(axis=1) \
        + return_n * np.asarray(returns, np.int64).sum(axis=1)
    return (max(settle_n, return_n) <= prefetch) \
        & (loaded - most >= consumers * prefetch)


def state_judgments(leaves: dict, *, seed: int, publishes: Publishes,
                    loaded: int, capacity: int, words: int, prefetch: int,
                    delivery_limit: int, settle_n: int, return_n: int,
                    settles: np.ndarray, returns: np.ndarray,
                    exact: np.ndarray, block: int = 256) -> tuple:
    """One replica set's leaves (the leaders') against the acknowledged
    operations: ``settles`` / ``returns`` int64[n_lanes, C] how many of
    each were acknowledged a queue and consumer, ``publishes`` those
    acknowledged, ``exact`` bool[n_lanes] the queues whose removals
    were exact (``removals_exact``).  Returns (counts, the held
    messages' hashes by (lane, consumer, delivery id) for the reads'
    judgment).

    ``settled_wrong`` counts each (queue, consumer) whose oldest
    delivery id ``lo`` (the messages it settled and returned) is not
    what its acknowledged settles and returns named, and each queue
    whose settle count is not theirs or whose removed tickets are not
    its settle and dead-letter counts: in an exact queue to the
    message, elsewhere no more than they named."""
    n_lanes = len(leaves["head"])
    counts = leaves["counts"].astype(np.int64)           # [n, 5]
    delivered, settled, requeued, dead, refused = counts.T
    tail = leaves["tail"].astype(np.int64)
    unknown = duplicated = out_of_order = 0
    found_all, ids, hashes = [], [], []
    held_n = np.zeros(n_lanes, np.int64)
    dc_sum = np.zeros(n_lanes, np.int64)
    dc_bad = np.zeros(n_lanes, bool)
    for lo in range(0, n_lanes, block):
        lanes = np.arange(lo, min(lo + block, n_lanes))
        lane, ticket, cons, did, dc, msgs = held(
            leaves, lanes, capacity=capacity, words=words)
        held_n += np.bincount(lane, minlength=n_lanes)
        np.add.at(dc_sum, lane, dc)
        dc_bad[lane[(dc < 0) | (dc >= delivery_limit)]] = True
        old = ticket < loaded
        want = loaded_messages(seed, lane[old], ticket[old], words)
        unknown += int((want != msgs[old]).any(axis=1).sum())
        idx = publishes.find(lane[~old], msgs[~old])
        unknown += int((idx < 0).sum())
        found_all.append(np.stack([idx[idx >= 0],
                                   ticket[~old][idx >= 0]], axis=1))
        mine = cons >= 0
        ids.append(np.stack([lane[mine], cons[mine], did[mine],
                             dc[mine]], axis=1))
        hashes.append(message_hash(msgs[mine]))
    found = np.concatenate(found_all) if found_all else \
        np.zeros((0, 2), np.int64)
    duplicated = len(found) - len(np.unique(found[:, 0]))
    # a session's held messages, by ticket, in op-id order
    sess = publishes.sess[found[:, 0]]
    order = np.lexsort((found[:, 1], sess))
    op = publishes.op_id[found[:, 0]][order]
    sess = sess[order]
    out_of_order = int(((sess[1:] == sess[:-1]) & (op[1:] <= op[:-1])).sum())
    pub = publishes.per_lane(n_lanes)
    # every ticket below the tail is held, or was settled or dead-lettered
    gone = tail - held_n
    removed = settled + dead
    lost = np.maximum(0, (loaded + pub) - held_n - removed - refused)
    lo_ = leaves["lo"].astype(np.int64)
    nx = leaves["next_id"].astype(np.int64)
    has = nx - lo_
    credit = leaves["credit"].astype(np.int64)
    exact = np.asarray(exact, bool)

    def off(got, named, ex):
        # exactly what was named where the removals were exact, else
        # no more than that
        return np.where(ex, got != named, got > named) | (got < 0)

    named_settled = settle_n * settles.sum(axis=1)
    named_returned = return_n * returns.sum(axis=1)
    lo_wrong = off(lo_, settle_n * settles + return_n * returns,
                   exact[:, None])
    settled_bad = off(settled, named_settled, exact) | (gone != removed)
    out = {
        "tail_wrong": int((tail != loaded + pub - refused).sum()),
        "messages_unknown": unknown,
        "messages_duplicated": int(duplicated),
        "publishes_lost": int(lost.sum()),
        "publishes_refused": int(refused.sum()),
        "publisher_order": out_of_order,
        "settled_wrong": int(lo_wrong.sum() + settled_bad.sum()),
        "credit_exceeded": int(((has > prefetch) | (has < 0)
                                | (credit != prefetch - has)).sum()),
        "delivery_counts_wrong": int(
            (dc_bad | (dc_sum > requeued)
             | off(requeued + dead, named_returned, exact)
             | (requeued < 0) | (dead < 0)
             | (delivered != lo_.sum(axis=1) + has.sum(axis=1))).sum()),
    }
    return out, (np.concatenate(ids), np.concatenate(hashes))


def _ns(t, t0: float) -> np.ndarray:
    return np.round((np.asarray(t, np.float64) - t0) * 1e9).astype(np.int64)


class Clocks:
    """Per queue and consumer (``who = lane * consumers + consumer``),
    the clock readings of its settles or returns, to count those
    acknowledged before an instant and those first sent before one."""

    def __init__(self, *, who, sent, acked) -> None:
        sent = np.asarray(sent, np.float64)
        acked = np.asarray(acked, np.float64)
        who = np.asarray(who, np.int64)
        known = np.concatenate([sent[~np.isnan(sent)],
                                acked[~np.isnan(acked)]])
        self.t0 = float(known.min()) - 1.0 if len(known) else 0.0
        self._sent = self._keys(who, sent)
        self._acked = self._keys(who, acked)

    def _keys(self, who, t) -> np.ndarray:
        had = ~np.isnan(t)
        return np.sort((who[had] << _NS_BITS) | _ns(t[had], self.t0))

    def _before(self, keys, who, t) -> np.ndarray:
        who = np.asarray(who, np.int64) << _NS_BITS
        at = who | np.clip(_ns(t, self.t0), 0, (1 << _NS_BITS) - 1)
        return np.searchsorted(keys, at) - np.searchsorted(keys, who)

    def acked_before(self, who, t) -> np.ndarray:
        return self._before(self._acked, who, t)

    def sent_before(self, who, t) -> np.ndarray:
        return self._before(self._sent, who, t)


def removal_bounds(settles: Clocks, returns: Clocks, who, fed, seen, *,
                   settle_n: int, return_n: int) -> tuple:
    """(least, most) a consumer's oldest delivery id may be at a point
    between ``fed`` and ``seen``: each settle removes at most
    ``settle_n`` of its messages, each return at most ``return_n``, and
    exactly that many where the consumer never held fewer (the least is
    only a bound then: the caller knows)."""
    least = settle_n * settles.acked_before(who, fed) \
        + return_n * returns.acked_before(who, fed)
    most = settle_n * settles.sent_before(who, seen) \
        + return_n * returns.sent_before(who, seen)
    return least, most


def read_consistency(reply: np.ndarray, *, most, wm, prefetch: int,
                     chunk: int) -> dict:
    """From the ledger alone, of the answered delivery reads ``reply``
    int32[r, 3 + chunk * words] (``[n, first delivery id, redelivered
    bits, messages]``): ``reads_outside_consistency``, a first delivery
    id above what the settles and returns sent before the reply was seen
    could have removed (``most``, from ``removal_bounds``), or more
    messages than the chunk or the consumer's credit; and
    ``reads_negative_watermark``."""
    reply = np.asarray(reply)
    n = reply[:, 0].astype(np.int64)
    first = reply[:, 1].astype(np.int64)
    outside = (first < 0) | (n < 0) | (n > min(chunk, prefetch)) \
        | (first > most)
    return {"reads_outside_consistency": int(outside.sum()),
            "reads_negative_watermark": int((np.asarray(wm) < 0).sum())}


def read_judgments(reply: np.ndarray, *, lane, consumer, least, exact,
                   chunk: int, words: int, loaded: Loaded,
                   publishes_sent: Publishes, held_ids, held_hashes,
                   n_consumers: int) -> dict:
    """The answered delivery reads ``reply`` of queue ``lane`` and
    consumer ``consumer`` against a replica set's final state:

    * ``reads_stale``: a first delivery id below what the settles and
      returns acknowledged before the read was last fed removed
      (``least``), where that is exact: ``exact``, the read's queue is
      one of ``removals_exact``'s;
    * ``reads_wrong_messages``: a message that is no loaded message of
      the queue and no publish sent to it, one whose delivery id another
      read or the final state gives another message (or, for one still
      held at the end, another redelivered flag), words past ``n`` not
      0;
    * ``reads_out_of_order``: two messages of one read, neither
      redelivered, out of ticket order (a consumer takes ready messages
      in ticket order: the loaded ones before the published, a
      session's in op-id order)."""
    reply = np.asarray(reply)
    lane = np.asarray(lane, np.int64)
    consumer = np.asarray(consumer, np.int64)
    n = reply[:, 0].astype(np.int64)
    first = reply[:, 1].astype(np.int64)
    bits = reply[:, 2].astype(np.int64)
    stale = np.asarray(exact, bool) & (first < least)
    r = len(reply)
    msgs = reply[:, 3:].reshape((r, chunk, words))
    used = np.arange(chunk) < n[:, None]
    wrong = (~used[..., None] & (msgs != 0)).any(axis=(1, 2))
    ri, ji = np.nonzero(used)
    m = msgs[ri, ji]
    ml = lane[ri]
    tk = loaded.find(ml, m)
    pub = publishes_sent.find(ml, m)
    np.logical_or.at(wrong, ri, (tk < 0) & (pub < 0))
    red = (bits[ri] >> ji) & 1
    # one message a (queue, consumer, delivery id): across reads, and
    # the final state's where it still holds it
    key = ((ml * n_consumers + consumer[ri]) << 32) | (first[ri] + ji)
    hid = np.asarray(held_ids, np.int64).reshape((-1, 4))
    hkey = ((hid[:, 0] * n_consumers + hid[:, 1]) << 32) | hid[:, 2]
    allk = np.concatenate([hkey, key])
    allh = np.concatenate([np.asarray(held_hashes, np.uint64),
                           message_hash(m)])
    allr = np.concatenate([(hid[:, 3] > 0).astype(np.int64), red])
    order = np.lexsort((allh, allk))
    sk, sh, sr = allk[order], allh[order], allr[order]
    clash = (sk[1:] == sk[:-1]) & ((sh[1:] != sh[:-1]) | (sr[1:] != sr[:-1]))
    np.logical_or.at(wrong, ri, np.isin(key, np.unique(sk[1:][clash])))
    # ticket order among a read's messages that are not redelivered
    late = np.zeros(r, bool)
    f = np.flatnonzero(red == 0)
    if len(f):
        fr, ft, fp = ri[f], tk[f], pub[f]
        fs = np.where(fp >= 0, publishes_sent.sess[np.maximum(fp, 0)], -1)
        fo = np.where(fp >= 0, publishes_sent.op_id[np.maximum(fp, 0)], -1)
        pair = (fr[1:] == fr[:-1]) & (
            ((ft[1:] >= 0) & (ft[:-1] >= 0) & (ft[1:] <= ft[:-1]))
            | ((ft[1:] >= 0) & (fp[:-1] >= 0))
            | ((fs[1:] >= 0) & (fs[1:] == fs[:-1]) & (fo[1:] <= fo[:-1])))
        np.logical_or.at(late, fr[1:], pair)
    return {"reads_stale": int(stale.sum()),
            "reads_wrong_messages": int(wrong.sum()),
            "reads_out_of_order": int(late.sum())}
