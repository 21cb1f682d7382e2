"""The plain reference of the YCSB record store: numpy only.

Imports nothing of ``ra_tpu`` and nothing of the machine's module, and
takes nothing the program computed.  Its inputs are the configuration's
sizes and seed and the client's own ledger: for every operation its
store (cluster), key, field, op id and salt, and the host-clock times it
was first fed to the transport and its answer was seen.  It states
again, on purpose, what the machine states: the word a loaded table
holds, what an update writes, what a record's counters count.

What it gives:

* ``loaded`` / ``loaded_rows``: the table as YCSB's load phase leaves
  it (a pure function of the configuration's ``load_seed``, store, key
  and word);
* ``value_words``: the ``field_words`` words an update writes: its op
  id, then words mixed from session, op id and salt, so that no two
  updates write the same value;
* ``fold``: ``ver`` (updates applied to each record) and ``sum``
  (wrapping sum of their op ids) once every listed update was applied
  once.  Both commute: the commit order is not needed;
* ``field_counts``: what the fields of a replica may hold.  The order
  in which two concurrent updates to one field commit is the system's
  to choose, so a field may hold the loaded value or the value of any
  update to it, but for those another update is known to follow: one
  first fed only after the holder was acknowledged;
* ``read_counts``: what a linearizable read may return, field by field,
  by the same rule between the read's two clock readings.
"""
from __future__ import annotations

import numpy as np

_U32 = np.uint32
_MASK31 = np.int64(0x7FFFFFFF)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> _U32(15))) * _U32(0x2C1B3C6D)
    x = (x ^ (x >> _U32(12))) * _U32(0x297A2D39)
    return x ^ (x >> _U32(15))


def _loaded(seed: int, lane: np.ndarray, cell: np.ndarray) -> np.ndarray:
    """The loaded word at (store, cell), cell = key * words + word."""
    x = (lane.astype(_U32) * _U32(0x9E3779B1)) \
        ^ (cell.astype(_U32) * _U32(0x85EBCA77)) \
        ^ _U32((int(seed) * 0xC2B2AE3D) & 0xFFFFFFFF)
    return (_mix32(x) >> _U32(1)).astype(np.int32)


def loaded(seed: int, n_lanes: int, records: int, words: int) -> np.ndarray:
    """int32[n_lanes, records, words]: every store's table after the
    load phase, ``words`` = fields x field_words to a record."""
    lane = np.arange(n_lanes, dtype=_U32)[:, None, None]
    cell = np.arange(records * words, dtype=_U32).reshape(
        (1, records, words))
    return _loaded(seed, lane, cell)


def loaded_rows(seed: int, lane, key, words: int) -> np.ndarray:
    """int32[n, words]: the loaded records (lane[i], key[i])."""
    lane = np.asarray(lane, np.int64)[:, None]
    cell = np.asarray(key, np.int64)[:, None] * words + np.arange(words)
    return _loaded(seed, lane, cell)


def _mix64(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def value_words(sess, op_id, salt, words: int) -> np.ndarray:
    """int32[n, words], each in [0, 2^31): word 0 is the op id (what the
    record's ``sum`` adds), the rest a mix of session, op id, salt and
    position.  (session, op id) names an update, so no two are equal."""
    sess = np.asarray(sess, np.int64).astype(np.uint64)
    op_id = np.asarray(op_id, np.int64)
    salt = np.asarray(salt, np.int64).astype(np.uint64)
    with np.errstate(over="ignore"):
        h = _mix64(sess * np.uint64(0x9E3779B97F4A7C15)
                   + op_id.astype(np.uint64) * np.uint64(0xD1B54A32D192ED03)
                   + (salt << np.uint64(33)))
        out = _mix64(h[:, None] + np.arange(words, dtype=np.uint64)
                     * np.uint64(0xA24BAED4963EE407))
    out = (out >> np.uint64(33)).astype(np.int64) & _MASK31
    out[:, 0] = op_id & _MASK31
    return out.astype(np.int32)


def wrap32(x: np.ndarray) -> np.ndarray:
    """int64 -> the int32 the device holds (two's-complement wrap)."""
    return (x & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def fold(n_lanes: int, records: int, *, lane, key, op_id) -> dict:
    """Every listed update applied once: ``ver`` and ``sum`` int32
    [n_lanes, records]."""
    lane = np.asarray(lane, np.int64)
    key = np.asarray(key, np.int64)
    if len(key) and (key.min() < 0 or key.max() >= records):
        raise ValueError("reference: an update's key is outside the store")
    ver = np.zeros((n_lanes, records), np.int64)
    tot = np.zeros((n_lanes, records), np.int64)
    np.add.at(ver, (lane, key), 1)
    np.add.at(tot, (lane, key), np.asarray(op_id, np.int64) & _MASK31)
    return {"ver": wrap32(ver), "sum": wrap32(tot)}


class Updates:
    """The run's updates, grouped by the field they write (store, key,
    field), with what each wrote and the two clock readings of each:
    ``sent`` (first fed to the transport) and ``acked`` (answer seen;
    NaN: never)."""

    def __init__(self, records: int, fields: int, field_words: int, *,
                 lane, key, field, sess, op_id, salt, sent, acked) -> None:
        self.records, self.fields = int(records), int(fields)
        self.words = int(field_words)
        addr = self.address(lane, key, field)
        order = np.argsort(addr, kind="stable")
        self.addr = addr[order]
        self.value = value_words(np.asarray(sess)[order],
                                 np.asarray(op_id)[order],
                                 np.asarray(salt)[order], self.words)
        self.sent = np.asarray(sent, np.float64)[order]
        self.acked = np.asarray(acked, np.float64)[order]
        self.uniq, self.start, self.count = np.unique(
            self.addr, return_index=True, return_counts=True)

    def address(self, lane, key, field) -> np.ndarray:
        return (np.asarray(lane, np.int64) * self.records
                + np.asarray(key, np.int64)) * self.fields \
            + np.asarray(field, np.int64)

    def judge(self, addr, held, loaded_held, seen_by: np.ndarray,
              settled_by: np.ndarray) -> tuple:
        """For each observed field (its address, the ``words`` it held,
        the loaded words of that field): (unknown, stale) bool arrays.

        ``unknown``: the value is neither the loaded one nor that of an
        update to this field first fed by ``seen_by`` (the observer's
        later clock reading).  ``stale``: an update to the field that
        was acknowledged before ``settled_by`` (the observer's earlier
        reading) is known to follow what was held: the held value is
        the loaded one, or the holder was acknowledged before that
        update was first fed."""
        n = len(addr)
        is_loaded = (held == loaded_held).all(axis=1)
        start = count = np.zeros(n, np.int64)
        if len(self.uniq):
            g = np.minimum(np.searchsorted(self.uniq, addr),
                           len(self.uniq) - 1)
            found = self.uniq[g] == addr
            start = np.where(found, self.start[g], 0)
            count = np.where(found, self.count[g], 0)
        holder = np.full(n, -1, np.int64)
        for r in range(int(count.max(initial=0))):
            rows = np.flatnonzero((count > r) & ~is_loaded & (holder < 0))
            if not len(rows):
                continue
            u = start[rows] + r
            hit = (self.value[u] == held[rows]).all(axis=1) \
                & (self.sent[u] <= seen_by[rows])
            holder[rows[hit]] = u[hit]
        unknown = ~is_loaded & (holder < 0)
        # what is held was in place by: the load (always), or the
        # holder's acknowledgement (NaN: not known to be in place)
        held_by = np.where(is_loaded, -np.inf,
                           self.acked[np.maximum(holder, 0)])
        stale = np.zeros(n, bool)
        for r in range(int(count.max(initial=0))):
            rows = np.flatnonzero((count > r) & ~unknown)
            if not len(rows):
                continue
            u = start[rows] + r
            with np.errstate(invalid="ignore"):
                follows = (u != holder[rows]) \
                    & (self.acked[u] < settled_by[rows]) \
                    & (self.sent[u] > held_by[rows])
            stale[rows[follows]] = True
        return unknown, stale


def field_counts(seed: int, updates: Updates, rec: np.ndarray) -> dict:
    """One replica set's records ``rec`` int32[N, S, F*W] after the run
    against what they may hold: ``fields_unknown`` (neither loaded nor
    any update's value, untouched fields included) and ``fields_stale``
    (an acknowledged update is known to follow the holder)."""
    n, s, fw = rec.shape
    w, f = updates.words, updates.fields
    differs = np.zeros((n, s, f), bool)
    for lo in range(0, n, 64):          # blocks: 64 stores x 1 MB
        hi = min(lo + 64, n)
        want = _loaded(seed,
                       np.arange(lo, hi, dtype=_U32)[:, None, None],
                       np.arange(s * fw, dtype=_U32).reshape((1, s, fw)))
        differs[lo:hi] = (rec[lo:hi] != want).reshape(
            (hi - lo, s, f, w)).any(axis=-1)
    touched = np.zeros(n * s * f, bool)
    touched[updates.uniq] = True
    unknown = int((differs.reshape(-1) & ~touched).sum())
    addr = updates.uniq
    lane, rest = np.divmod(addr, s * f)
    key, field = np.divmod(rest, f)
    cols = field[:, None] * w + np.arange(w)
    held = rec[lane[:, None], key[:, None], cols]
    loaded_held = _loaded(seed, lane[:, None],
                          key[:, None] * fw + cols)
    end = np.full(len(addr), np.inf)
    unk, stale = updates.judge(addr, held, loaded_held, end, end)
    return {"fields_unknown": unknown + int(unk.sum()),
            "fields_stale": int(stale.sum())}


def read_counts(seed: int, updates: Updates, *, lane, key, fed, seen,
                present, wm, reply, block: int = 1 << 15) -> dict:
    """The answered reads against what linearizability allows.  ``fed``
    and ``seen`` are each read's clock readings (first fed, reply
    seen), ``reply`` int32[n, F*W] the record it returned."""
    f, w = updates.fields, updates.words
    lane = np.asarray(lane, np.int64)
    key = np.asarray(key, np.int64)
    outside = 0
    for lo in range(0, len(lane), block):
        sl = slice(lo, min(lo + block, len(lane)))
        m = sl.stop - sl.start
        want = loaded_rows(seed, lane[sl], key[sl], f * w)
        addr = updates.address(np.repeat(lane[sl], f), np.repeat(key[sl], f),
                               np.tile(np.arange(f), m))
        unk, stale = updates.judge(
            addr, np.asarray(reply[sl]).reshape((m * f, w)),
            want.reshape((m * f, w)), np.repeat(seen[sl], f),
            np.repeat(fed[sl], f))
        outside += int((unk | stale).sum())
    return {"reads_outside_consistency": outside,
            "reads_not_present": int((np.asarray(present) != 1).sum()),
            "reads_negative_watermark": int((np.asarray(wm) < 0).sum())}
