"""JitKvMachine — the replicated KV store on the device apply path.

The host :class:`~ra_tpu.models.kv.KvMachine` (the ra-kv-store role,
README.md:33-35) keeps a Python dict plus watcher effects.  This is its
TPU-native counterpart for the BASELINE.md "2,000 clusters, kv machine,
mixed put/get, jittable apply/3" row: a fixed key space of ``n_keys``
int32 cells per lane, folded on-device under ``lax.scan`` (put/cas
sequences are order-dependent; cas-free windows still fold one-shot
via ``jit_apply_batch`` — see the method comment).

Absence is encoded as -1 (mirroring the host machine's ``None`` reply for
a missing key), so stored values must be >= 0.  ``get`` exists as a
committed command — a linearizable read through the log, the device-path
stand-in for ``consistent_query`` — while the host path keeps using query
funs.

Command encoding (command_spec int32[4]): ``[op, key, value, expected]``

  op 0 noop
  op 1 put(key, value)            reply [1, old]         (old -1 if absent)
  op 2 get(key)                   reply [present, value]
  op 3 delete(key)                reply [present, old]
  op 4 cas(key, expected, value)  reply [ok, current]    (expected/value -1
                                   mean absent: expect-missing / delete-on-
                                   success, matching KvMachine's None args)

Reply is int32[2] = [code, value].  A key outside [0, n_keys) makes the
command a no-op with reply [-2, -1] (never aliased onto a boundary cell).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.machine import JitMachine
from ..ops.exact import place16
from ..ops.table import write_in_place

_I32 = jnp.int32


class JitKvMachine(JitMachine):
    command_spec = ("int32", (4,))
    reply_spec = ("int32", (2,))
    version = 0
    #: put/cas do not commute — batch apply stays sound because
    #: jit_apply_batch folds the window IN ORDER (last-writer-wins
    #: vectorized fast path for cas-free windows, masked scan else)
    supports_batch_apply = True

    def __init__(self, n_keys: int = 64) -> None:
        self.n_keys = n_keys

    def jit_init(self, n_lanes: int):
        # -1 = absent
        return jnp.full((n_lanes, self.n_keys), -1, _I32)

    def jit_apply(self, meta, command, state):
        S = self.n_keys
        op = command[..., 0]
        raw_key = command[..., 1]
        key_ok = (raw_key >= 0) & (raw_key < S)
        key = jnp.clip(raw_key, 0, S - 1)
        value = command[..., 2]
        expected = command[..., 3]
        cur = jnp.take_along_axis(state, key[..., None], axis=-1)[..., 0]
        present = (cur >= 0).astype(_I32)

        # an out-of-range key must not alias onto the boundary cell, and a
        # negative value must not smuggle the absent sentinel into a cell
        # (stored values are >= 0 by contract; cas value -1 is the
        # intentional delete-on-success, anything below is malformed):
        # either way the command degrades to a no-op with the error reply
        val_bad = ((op == 1) & (value < 0)) | ((op == 4) & (value < -1))
        put = (op == 1) & key_ok & ~val_bad
        dele = (op == 3) & key_ok
        cas_ok = (op == 4) & key_ok & ~val_bad & (cur == expected)
        new_val = jnp.where(put, value,
                            jnp.where(dele, -1,
                                      jnp.where(cas_ok, value, cur)))
        write = put | dele | cas_ok
        onehot = (jnp.arange(S) == key[..., None])
        new_state = jnp.where(onehot & write[..., None],
                              new_val[..., None], state)

        code = jnp.where(put, 1,
                         jnp.where(op == 4, cas_ok.astype(_I32),
                                   jnp.where((op == 2) | dele, present, 0)))
        bad = ((op > 0) & ~key_ok) | val_bad
        code = jnp.where(bad, -2, code)
        reply = jnp.stack([code, jnp.where(bad, -1, cur)], axis=-1)
        return new_state, reply

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # put/cas do not commute, but a window WITHOUT cas folds in one
    # vectorized pass: gets read, puts/deletes write, and the final cell
    # value is simply the LAST write targeting that key — last-writer-
    # wins needs no sequential fold.  Per key: the winning command is
    # the max window position among its writes (a masked max-reduce),
    # and its value lands via the exact split16 one-hot matmul (ops/exact.py) so placement rides the MXU
    # instead of a scatter.  Windows containing cas fall back to an
    # in-order masked lax.scan of jit_apply — cas reads the evolving
    # cell, the one true sequential dependency in the vocabulary.
    # The engine discards per-command replies on this path
    # (lockstep.py step 5), so the fold only produces the new state.

    def jit_apply_batch(self, meta, commands, mask, state):
        fast_ok = ~jnp.any(mask & (commands[..., 0] >= 4))  # no cas
        return self.window_fold_dispatch(meta, commands, mask, state,
                                         fast_ok)

    def _batch_fast(self, commands, mask, state):
        """Vectorized cas-free window fold: last write per key wins."""
        S = self.n_keys
        A = commands.shape[-2]
        op = jnp.where(mask, commands[..., 0], 0)           # [..., A]
        raw_key = commands[..., 1]
        value = commands[..., 2]
        key_ok = (raw_key >= 0) & (raw_key < S)
        val_bad = (op == 1) & (value < 0)
        is_write = ((op == 1) | (op == 3)) & key_ok & ~val_bad
        wval = jnp.where(op == 1, value, -1)                # delete = -1

        kr = jnp.arange(S)
        hits = (raw_key[..., None, :] == kr[..., :, None]) & \
            is_write[..., None, :]                          # [..., S, A]
        pos = jnp.arange(A)
        maxpos = jnp.max(jnp.where(hits, pos, -1), axis=-1)  # [..., S]
        winner = hits & (pos == maxpos[..., None])
        placed = place16(winner.astype(jnp.float32), wval)
        return jnp.where(maxpos >= 0, placed, state)

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        def _v(x):
            return -1 if x is None else int(x)
        try:
            if isinstance(command, tuple) and command:
                kind = command[0]
                if kind == "put" and len(command) == 3:
                    return jnp.asarray(
                        [1, int(command[1]), _v(command[2]), 0], _I32)
                if kind == "get" and len(command) == 2:
                    return jnp.asarray([2, int(command[1]), 0, 0], _I32)
                if kind == "delete" and len(command) == 2:
                    return jnp.asarray([3, int(command[1]), 0, 0], _I32)
                if kind == "cas" and len(command) == 4:
                    # host order: ("cas", key, expected, new)
                    return jnp.asarray(
                        [4, int(command[1]), _v(command[3]),
                         _v(command[2])], _I32)
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((4,), _I32)

    def decode_reply(self, reply):
        code, val = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if val < 0 else val)

    # -- vectorized read path (ISSUE 20) -----------------------------------
    # Query encoding (query_spec int32[2]): ``[op, key]``
    #   op 0 size()    reply [n_present, 0]
    #   op 1 get(key)  reply [present, value]   (absent/bad key -> [0,-1])

    query_spec = ("int32", (2,))
    query_reply_spec = ("int32", (2,))

    def jit_query(self, queries, state):
        # queries: [..., Kr, 2]; state: [..., S] — pure gathers, no
        # state mutation (reads never enter the log)
        S = self.n_keys
        op = queries[..., 0]
        raw_key = queries[..., 1]
        key_ok = (raw_key >= 0) & (raw_key < S)
        key = jnp.clip(raw_key, 0, S - 1)
        val = jnp.take_along_axis(state[..., None, :],
                                  key[..., None], axis=-1)[..., 0]
        present = key_ok & (val >= 0)
        size = jnp.sum((state >= 0).astype(_I32),
                       axis=-1)[..., None]                    # [..., 1]
        code = jnp.where(op == 0, size, present.astype(_I32))
        value = jnp.where(op == 0, 0, jnp.where(present, val, -1))
        return jnp.stack([code, value], axis=-1)

    def encode_query(self, query):
        if isinstance(query, tuple) and query and query[0] == "get":
            return jnp.asarray([1, int(query[1])], _I32)
        return jnp.zeros((2,), _I32)  # size()

    def decode_query_reply(self, reply):
        code, val = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if val < 0 else val)


class JitRecordKvMachine(JitMachine):
    """The KV store at a benchmark's record shape (YCSB's): a lane holds
    ``records`` records of ``fields`` fields of ``field_words`` int32
    words, loaded before the first command, and a read returns a whole
    record.  The sibling of :class:`JitKvMachine` for tables far larger
    than an apply window: the batch fold moves the words it writes and
    nothing else, so its cost follows the updates in the window, not
    ``records x window``, and the state is written in place (on a TPU a
    replica set of 2,000 x 3 x 1,000 x 1,000 bytes is 6 GB: a second
    copy does not fit beside it).

    State per lane: ``{"rec": int32[S, F*W], "ver": int32[S], "sum":
    int32[S]}``.  ``ver`` counts the updates applied to a record and
    ``sum`` adds their first value word (int32, wrapping), which a
    client sets to its op id: both commute, so "every acknowledged
    update applied once" is checkable without the commit order, while
    ``rec`` holds what the last writer of each field wrote.

    Command (command_spec int32[3 + W]): ``[op, key, field, W value
    words]``

      op 0 noop
      op 1 update(key, field, value)   reply [1, ver]  (ver after it)

    A key or field out of range, or any other op, makes the command a
    no-op with reply [-2, -1].

    Query (query_spec int32[2]): ``[op, key]``; op 1 is ``read(key)``,
    reply int32[1 + F*W] = ``[present, the record's F*W words]``; a key
    out of range (or another op) answers all zeros.

    ``jit_init`` returns the table LOADED: every word a pure function
    of ``seed``, lane, key and word (:func:`loaded_words`), in
    [0, 2^31).  Loading is set-up, not traffic: it never goes through
    the log.
    """
    reply_spec = ("int32", (2,))
    version = 0
    #: updates to one field do not commute; jit_apply_batch applies the
    #: window in order (the last writer of a field wins)
    supports_batch_apply = True
    query_spec = ("int32", (2,))
    #: compacted updates the batch fold applies a pass
    CHUNK = 256

    def __init__(self, records: int = 1000, fields: int = 10,
                 field_words: int = 25, seed: int = 0) -> None:
        if min(records, fields, field_words) < 1:
            raise ValueError("records, fields and field_words must be >= 1")
        self.records = int(records)
        self.fields = int(fields)
        self.field_words = int(field_words)
        self.seed = int(seed)

    # specs as properties: the instance's __dict__ stays all scalars,
    # which is what lets same-config engines share one jitted step
    @property
    def command_spec(self):
        return ("int32", (3 + self.field_words,))

    @property
    def query_reply_spec(self):
        return ("int32", (1 + self.fields * self.field_words,))

    def jit_init(self, n_lanes: int):
        S, FW = self.records, self.fields * self.field_words

        def load():
            lane = jnp.arange(n_lanes, dtype=jnp.uint32)[:, None, None]
            cell = jnp.arange(S * FW, dtype=jnp.uint32).reshape((1, S, FW))
            return loaded_words(jnp, self.seed, lane, cell)

        # one program, one table: op by op the mix's eight temporaries
        # of the table's size are all alive at once behind the
        # dispatch queue (16.4 GB at 2,000 x 1,000 x 250 words on a
        # v5e, for a table of 2 GB)
        return {"rec": jax.jit(load)(),
                "ver": jnp.zeros((n_lanes, S), _I32),
                "sum": jnp.zeros((n_lanes, S), _I32)}

    def _decode(self, commands):
        """(is a valid update, key, field), key and field clipped."""
        S, F = self.records, self.fields
        op, key, field = commands[..., 0], commands[..., 1], commands[..., 2]
        ok = (op == 1) & (key >= 0) & (key < S) & (field >= 0) & (field < F)
        return ok, jnp.clip(key, 0, S - 1), jnp.clip(field, 0, F - 1)

    def jit_apply(self, meta, command, state):
        # the one-command definition: plain selects over the whole table
        S, F, W = self.records, self.fields, self.field_words
        ok, key, field = self._decode(command)
        row = (jnp.arange(S) == key[..., None]) & ok[..., None]   # [..., S]
        col = jnp.arange(F * W) // W == field[..., None]          # [..., FW]
        value = jnp.tile(command[..., 3:], F)                     # [..., FW]
        rec = jnp.where(row[..., :, None] & col[..., None, :],
                        value[..., None, :], state["rec"])
        ver = state["ver"] + row.astype(_I32)
        tot = state["sum"] + jnp.where(row, command[..., 3, None], 0)
        bad = (command[..., 0] != 0) & ~ok
        ver_now = jnp.take_along_axis(ver, key[..., None], axis=-1)[..., 0]
        reply = jnp.stack([jnp.where(bad, -2, ok.astype(_I32)),
                           jnp.where(ok, ver_now, -1)], axis=-1)
        return {"rec": rec, "ver": ver, "sum": tot}, reply

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # The window's valid updates go through the table writer
    # (``ops/table.py``): compacted by one stable sort, applied CHUNK at
    # a time, the table only ever written into, a field at a time.  A
    # pass adds its updates to ``ver`` and ``sum`` (a scatter-add each)
    # before their words are written.

    def jit_apply_batch(self, meta, commands, mask, state):
        S, F, W = self.records, self.fields, self.field_words
        batch, A = mask.shape[:-1], mask.shape[-1]
        B = math.prod(batch)
        M = B * A
        rows = commands.reshape((M, 3 + W))
        ok, _key, _field = self._decode(rows)

        def locate(pos, live, carry):
            ver, tot = carry
            c = rows[pos]                                    # [ch, 3+W]
            _ok, k, f = self._decode(c)
            cell = (pos // A) * S + k                        # record, flat
            # a slot past the window's last update points past the
            # table and is dropped
            at = jnp.where(live, cell, B * S)
            ver = ver.at[at].add(1, mode="drop")
            tot = tot.at[at].add(c[:, 3], mode="drop")
            return cell, f * W, c[:, 3:], (ver, tot)

        rec, (ver, tot) = write_in_place(
            state["rec"].reshape((B * S, F * W)), mask.reshape((M,)) & ok,
            locate, chunk=self.CHUNK,
            carry=(state["ver"].reshape((B * S,)),
                   state["sum"].reshape((B * S,))))
        return {"rec": rec.reshape(batch + (S, F * W)),
                "ver": ver.reshape(batch + (S,)),
                "sum": tot.reshape(batch + (S,))}

    # -- vectorized read path ----------------------------------------------

    def jit_query(self, queries, state):
        # queries: [..., Kr, 2]; state rec [..., S, F*W]: one row gather
        # a query, no state mutation (reads never enter the log)
        S, FW = self.records, self.fields * self.field_words
        batch, Kr = queries.shape[:-2], queries.shape[-2]
        raw = queries[..., 1]
        ok = (queries[..., 0] == 1) & (raw >= 0) & (raw < S)
        B = math.prod(batch)
        base = (jnp.arange(B, dtype=_I32) * S).reshape(batch + (1,))
        flat = (base + jnp.clip(raw, 0, S - 1)).reshape((B * Kr,))
        row = state["rec"].reshape((B * S, FW))[flat].reshape(
            batch + (Kr, FW))
        return jnp.concatenate(
            [ok.astype(_I32)[..., None],
             jnp.where(ok[..., None], row, 0)], axis=-1)

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        W = self.field_words
        try:
            if isinstance(command, tuple) and len(command) == 4 \
                    and command[0] == "update":
                value = jnp.asarray(command[3], _I32).reshape((W,))
                head = jnp.asarray([1, int(command[1]), int(command[2])],
                                   _I32)
                return jnp.concatenate([head, value])
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((3 + W,), _I32)

    def decode_reply(self, reply):
        code, ver = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if ver < 0 else ver)

    def encode_query(self, query):
        if isinstance(query, tuple) and len(query) == 2 \
                and query[0] == "read":
            return jnp.asarray([1, int(query[1])], _I32)
        return jnp.zeros((2,), _I32)

    def decode_query_reply(self, reply):
        import numpy as np
        arr = np.asarray(reply)
        return (int(arr[..., 0]), arr[..., 1:])


def loaded_words(xp, seed: int, lane, cell):
    """The word a loaded table holds at (lane, cell), cell = key * F*W +
    word: 31 bits of a 32-bit mix of the three (``seed`` a Python int),
    so never negative.  ``xp`` is the array module: uint32 arithmetic
    wraps in numpy and in jax.numpy alike."""
    u32 = xp.uint32
    x = (lane.astype(u32) * u32(0x9E3779B1)) ^ \
        (cell.astype(u32) * u32(0x85EBCA77)) ^ \
        u32((seed * 0xC2B2AE3D) & 0xFFFFFFFF)
    x = (x ^ (x >> u32(15))) * u32(0x2C1B3C6D)
    x = (x ^ (x >> u32(12))) * u32(0x297A2D39)
    x = x ^ (x >> u32(15))
    return (x >> u32(1)).astype(xp.int32)


def query_kv(state) -> dict:
    """Query fun: present keys as a plain dict (host path)."""
    import numpy as np
    arr = np.asarray(state)
    return {int(k): int(v) for k, v in enumerate(arr) if v >= 0}
