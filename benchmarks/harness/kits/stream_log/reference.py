"""The plain reference of the partitioned stream: numpy only.

Imports nothing of ``ra_tpu`` and nothing of the machine's module, and
takes nothing the program computed but the state it is asked to judge.
Its inputs are the configuration's sizes and seed and the client's own
ledger: for every operation its partition (cluster), session, op id,
lag and salt, and the host-clock times it was first fed to the
transport and its answer was seen.  It states again, on purpose, what
the machine states: the message a loaded tail holds at an offset, what
an append writes, where offset ``o`` lives in a replica's log, what a
chunk read returns.

The commit order of appends from different sessions of one partition
is the system's to choose, so the reference never folds the log; it
judges one:

* ``loaded_messages``: the history every partition is loaded with, a
  pure function of ``load_seed``, partition, offset and word;
* ``append_words``: the message an append writes: its op id, its
  session, then words mixed from session, op id and salt, so that a
  message names its writer and no two appends write the same one;
* ``held_counts``: what a replica's retained tail may hold: at an
  offset below ``retention`` the loaded message, above it an
  acknowledged append of that partition, none of them twice, each
  session's in op-id order;
* ``Clocks``, ``tail_bounds``: how many appends a partition's log must
  hold, and may hold, at a point fixed by two clock readings: at least
  those acknowledged before the first, at most those first sent before
  the second;
* ``cursor_bounds``: the range a consumer group's stored offset may lie
  in, from its acknowledged stores (each max-merges the cursor with the
  tail at its place in the log less its lag);
* ``chunk_reply``: a chunk read of a log, as the machine answers one;
  ``read_message_counts``: the answered reads against the judged log;
  ``read_consistency_counts``: each read's tail against its clocks.
"""
from __future__ import annotations

import numpy as np

_U32 = np.uint32
_MASK31 = np.int64(0x7FFFFFFF)
#: a clock reading in integer nanoseconds takes this many bits of a
#: key, the partition the rest (2^42 ns: 73 minutes of ledger)
_NS_BITS = 42


def _mix32(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> _U32(15))) * _U32(0x2C1B3C6D)
    x = (x ^ (x >> _U32(12))) * _U32(0x297A2D39)
    return x ^ (x >> _U32(15))


def loaded_messages(seed: int, lane, offset, words: int) -> np.ndarray:
    """int32[..., words]: the loaded message at (lane, offset), every
    word 31 bits of a 32-bit mix of lane, cell = offset * words + word,
    and the seed."""
    lane = np.asarray(lane, np.int64)[..., None]
    cell = np.asarray(offset, np.int64)[..., None] * words \
        + np.arange(words)
    x = (lane.astype(_U32) * _U32(0x9E3779B1)) \
        ^ (cell.astype(_U32) * _U32(0x85EBCA77)) \
        ^ _U32((int(seed) * 0xC2B2AE3D) & 0xFFFFFFFF)
    return (_mix32(x) >> _U32(1)).astype(np.int32)


def _mix64(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def append_words(sess, op_id, salt, words: int) -> np.ndarray:
    """int32[n, words], each in [0, 2^31): word 0 the op id, word 1 the
    session, the rest a mix of session, op id, salt and position.
    (session, op id) names an append, so no two are equal."""
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


def held_offsets(tail, retention: int) -> np.ndarray:
    """int64[n, retention]: the offset whose message a slot of each
    replica's log holds, ``o % retention == slot`` and ``tail -
    retention <= o < tail``."""
    t = np.asarray(tail, np.int64)[:, None]
    s = np.arange(retention, dtype=np.int64)[None, :]
    return t - retention + (s - t) % retention


class Appends:
    """The appends a log may hold, by (session, op id), with the
    partition each was sent to."""

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

    def __len__(self) -> int:
        return len(self.key)

    def find(self, lane, held: np.ndarray) -> np.ndarray:
        """For each message ``held`` int32[k, words] found in partition
        ``lane``: the index of the append of that partition that wrote
        exactly it, or -1."""
        lane = np.asarray(lane, np.int64)
        if not len(self.key) or not len(held):
            return np.full(len(held), -1, np.int64)
        key = (held[:, 1].astype(np.int64) << 32) \
            | (held[:, 0].astype(np.int64) & _MASK31)
        pos = np.minimum(np.searchsorted(self.key, key), len(self.key) - 1)
        hit = (self.key[pos] == key) & (self.lane[pos] == lane)
        want = append_words(self.sess[pos], self.op_id[pos], self.salt[pos],
                            self.words)
        hit &= (want == held).all(axis=1)
        return np.where(hit, pos, -1)


def held_counts(seed: int, appends: Appends, log: np.ndarray, tail, base,
                *, retention: int, words: int, block: int = 64) -> dict:
    """One replica set's logs ``log`` int32[N, retention / chunk, chunk *
    words] (the leader's) at ``tail`` / ``base`` against what they may
    hold: ``messages_unknown`` (a retained message that is neither the
    loaded one at its offset below ``retention`` nor an append of the
    partition above it), ``messages_duplicated`` (an append held at two
    offsets) and ``appends_out_of_order`` (two appends of one session
    whose offsets are in the other order than their op ids)."""
    n = log.shape[0]
    msgs = log.reshape((n, retention, words))
    tail = np.asarray(tail, np.int64)
    base = np.asarray(base, np.int64)
    unknown = 0
    found_off, found = [], []
    for lo in range(0, n, block):       # blocks: 64 partitions x 800 kB
        hi = min(lo + block, n)
        off = held_offsets(tail[lo:hi], retention)
        kept = off >= base[lo:hi, None]
        held = msgs[lo:hi]
        old = kept & (off < retention)
        lane = np.arange(lo, hi)[:, None]
        want = loaded_messages(seed, lane, np.where(old, off, 0), words)
        unknown += int((old & (held != want).any(axis=-1)).sum())
        li, si = np.nonzero(kept & (off >= retention))
        idx = appends.find(lo + li, held[li, si])
        unknown += int((idx < 0).sum())
        found_off.append(off[li, si][idx >= 0])
        found.append(idx[idx >= 0])
    found_off = np.concatenate(found_off)
    found = np.concatenate(found)
    duplicated = len(found) - len(np.unique(found))
    sess = appends.sess[found]
    order = np.lexsort((found_off, sess))
    op, sess = appends.op_id[found][order], sess[order]
    late = (sess[1:] == sess[:-1]) & (op[1:] <= op[:-1])
    return {"messages_unknown": unknown,
            "messages_duplicated": int(duplicated),
            "appends_out_of_order": int(late.sum())}


def _ns(t, t0: float) -> np.ndarray:
    return np.round((np.asarray(t, np.float64) - t0) * 1e9).astype(np.int64)


class Clocks:
    """Per partition, its appends' two clock readings, to count those
    acknowledged before an instant and those first sent before one."""

    def __init__(self, *, lane, sent, acked) -> None:
        sent = np.asarray(sent, np.float64)
        acked = np.asarray(acked, np.float64)
        lane = np.asarray(lane, np.int64)
        known = np.concatenate([sent[~np.isnan(sent)],
                                acked[~np.isnan(acked)]])
        self.t0 = float(known.min()) - 1.0 if len(known) else 0.0
        self._sent = self._keys(lane, sent)
        self._acked = self._keys(lane, acked)

    def _keys(self, lane, t) -> np.ndarray:
        had = ~np.isnan(t)
        return np.sort((lane[had] << _NS_BITS) | _ns(t[had], self.t0))

    def _before(self, keys, lane, t) -> np.ndarray:
        lane = np.asarray(lane, np.int64) << _NS_BITS
        at = lane | np.clip(_ns(t, self.t0), 0, (1 << _NS_BITS) - 1)
        return np.searchsorted(keys, at) - np.searchsorted(keys, lane)

    def acked_before(self, lane, t) -> np.ndarray:
        return self._before(self._acked, lane, t)

    def sent_before(self, lane, t) -> np.ndarray:
        return self._before(self._sent, lane, t)


def tail_bounds(retention: int, clocks: Clocks, lane, fed, seen) -> tuple:
    """(least, most) a partition's tail may be at a point of its log
    that lies between the two clock readings ``fed`` (first fed to the
    transport) and ``seen`` (answer seen): the loaded history plus the
    appends acknowledged before ``fed``, and plus those first sent
    before ``seen``."""
    return (retention + clocks.acked_before(lane, fed),
            retention + clocks.sent_before(lane, seen))


def cursor_bounds(n_lanes: int, groups: int, retention: int,
                  clocks: Clocks, *, lane, group, lag, fed,
                  seen) -> tuple:
    """(lo, hi) int64[n_lanes, groups]: the least and the most a
    group's cursor may read once the listed stores were each applied
    once, from a cursor of 0: each moves it to ``max(cursor, tail -
    lag)`` with the tail at its place between its clock readings."""
    least, most = tail_bounds(retention, clocks, lane, fed, seen)
    lag = np.asarray(lag, np.int64)
    at = np.asarray(lane, np.int64) * groups + np.asarray(group, np.int64)
    lo = np.zeros(n_lanes * groups, np.int64)
    hi = np.zeros(n_lanes * groups, np.int64)
    np.maximum.at(lo, at, least - lag)
    np.maximum.at(hi, at, most - lag)
    return lo.reshape((n_lanes, groups)), hi.reshape((n_lanes, groups))


def chunk_reply(log: np.ndarray, tail, base, lag, *, retention: int,
                chunk: int, words: int) -> np.ndarray:
    """int32[r, 2 + chunk * words]: read(lag) of each listed replica's
    log ``log[r]`` at ``tail[r]`` / ``base[r]``: ``[n, first, the n
    messages from first on, zeros]``, ``first = tail - lag`` held to
    ``[base, tail]``, ``n = min(chunk, tail - first)``; a negative lag
    answers ``[0, -1, zeros]``."""
    tail = np.asarray(tail, np.int64)
    base = np.asarray(base, np.int64)
    lag = np.asarray(lag, np.int64)
    r = len(tail)
    out = np.zeros((r, 2 + chunk * words), np.int32)
    first = np.clip(tail - np.maximum(lag, 0), base, tail)
    n = np.where(lag >= 0, np.minimum(chunk, tail - first), 0)
    out[:, 0] = n
    out[:, 1] = np.where(lag >= 0, first, -1)
    msgs = np.asarray(log).reshape((r, retention, words))
    for j in range(chunk):
        have = j < n
        slot = (first + j) % retention
        out[have, 2 + j * words:2 + (j + 1) * words] = \
            msgs[np.flatnonzero(have), slot[have]]
    return out


def read_message_counts(seed: int, log: np.ndarray, tail, base, *, lane,
                        lag, reply, retention: int, chunk: int,
                        words: int, block: int = 1 << 15) -> dict:
    """The answered reads' messages against the judged logs (the
    leader's, as ``held_counts`` judges them): a read from ``first``
    returns ``min(chunk, lag)`` messages, each what the log holds at its
    offset (an offset below ``retention`` is the loaded message whether
    or not it is still held), and zeros after them.
    ``reads_wrong_messages`` counts the reads that do not."""
    n_lanes = log.shape[0]
    msgs = log.reshape((n_lanes, retention, words))
    tail = np.asarray(tail, np.int64)
    base = np.asarray(base, np.int64)
    lane = np.asarray(lane, np.int64)
    lag = np.asarray(lag, np.int64)
    wrong = 0
    for lo in range(0, len(lane), block):
        sl = slice(lo, min(lo + block, len(lane)))
        ln, rep = lane[sl], np.asarray(reply[sl])
        n, first = rep[:, 0].astype(np.int64), rep[:, 1].astype(np.int64)
        bad = (n != np.minimum(chunk, np.maximum(lag[sl], 0))) | (first < 0)
        got = rep[:, 2:].reshape((len(ln), chunk, words))
        off = first[:, None] + np.arange(chunk)
        used = np.arange(chunk) < n[:, None]
        old = used & (off < retention)
        new = used & ~old
        held = new & (off >= base[ln, None]) & (off < tail[ln, None])
        want = loaded_messages(seed, ln[:, None], np.where(old, off, 0),
                               words)
        r, j = np.nonzero(held)
        want[r, j] = msgs[ln[r], off[r, j] % retention]
        bad |= (new & ~held).any(axis=1)
        bad |= (used[..., None] & (got != want)).any(axis=(1, 2))
        bad |= (~used[..., None] & (got != 0)).any(axis=(1, 2))
        wrong += int(bad.sum())
    return {"reads_wrong_messages": wrong}


def read_consistency_counts(retention: int, clocks: Clocks, *, lane, lag,
                            first, fed, seen, wm) -> dict:
    """Each answered read's tail (``first + lag``: the read is of the
    ``lag`` messages before the tail, fewer than ``retention``) against
    what linearizability allows between its clock readings (first fed,
    reply seen)."""
    least, most = tail_bounds(retention, clocks, lane, fed, seen)
    tail = np.asarray(first, np.int64) + np.asarray(lag, np.int64)
    return {"reads_outside_consistency": int(((tail < least)
                                              | (tail > most)).sum()),
            "reads_negative_watermark": int((np.asarray(wm) < 0).sum())}
