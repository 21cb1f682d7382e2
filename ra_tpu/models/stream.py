"""StreamMachine — an offset-addressed log with consumer cursors.

The RabbitMQ-streams shape: an append-only log addressed by absolute
offset, a retention window (oldest ``capacity`` entries survive; older
offsets fall off the tail), and named consumer GROUPS whose committed
cursors advance monotonically through the log — stream consumers track
their own position, the machine only stores the committed cursor.  This
is the second machine of the ISSUE 20 read library: the interesting
workload is read-dominated (consumers replaying offsets), which is
exactly what the engine's lease/read-index plane serves with zero log
appends.

State per lane: ``buf int32[capacity]`` ring (slot = offset % capacity),
``tail`` (next offset to write), ``base`` (oldest retained offset —
``base <= offset < tail`` is readable), ``cursors int32[groups]``.

Command encoding (command_spec int32[3]): ``[op, a, b]``

  op 0 noop                   (term-opening entry)
  op 1 append(value)          reply [1, offset]        (value >= 0)
  op 2 commit_cursor(g, off)  reply [1, cursor]   (max-merge, clamped
                               to tail — a cursor never outruns the log)
  op 3 truncate(upto)         reply [1, base]     (advance retention)

Reply is int32[2].  Bad group / negative value degrade to a no-op with
reply [-2, -1].

Query encoding (query_spec int32[2]): ``[op, a]`` — the ISSUE 20
vectorized read path:

  op 0 bounds()        reply [tail, base]
  op 1 read(offset)    reply [1, value] if base <= offset < tail
                              else [0, -1]
  op 2 cursor(g)       reply [1, cursor]         (bad g -> [0, -1])

Batch apply: a window of only noop/append — the firehose steady state —
folds in one vectorized pass (append positions are an exclusive cumsum
of the admit flags; values land via the exact one-hot matmul, and when
the window is wider than the ring only the LAST append aliasing each
slot survives, as in jit_fifo's fold).  Windows containing cursor/
truncate ops fall back to the in-order masked sequential fold.

:class:`StreamLogMachine` below is the same stream at a partitioned
log's shape (messages of many words, thousands retained a partition,
chunk reads, offsets named by their lag behind the tail), with a batch
fold that writes in place and folds its whole vocabulary in one pass.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.machine import JitMachine
from ..ops.exact import place16
from ..ops.table import write_in_place
from .jit_kv import loaded_words

_I32 = jnp.int32


class StreamMachine(JitMachine):
    command_spec = ("int32", (3,))
    reply_spec = ("int32", (2,))
    query_spec = ("int32", (2,))
    query_reply_spec = ("int32", (2,))
    version = 0
    #: append order IS offset order — batch apply stays sound because
    #: jit_apply_batch folds the window IN ORDER (vectorized fast path
    #: for append-only windows, masked sequential fold else)
    supports_batch_apply = True

    def __init__(self, capacity: int = 64, groups: int = 4) -> None:
        self.capacity = capacity
        self.groups = groups

    def jit_init(self, n_lanes: int):
        N, Q, G = n_lanes, self.capacity, self.groups
        return {
            "buf": jnp.zeros((N, Q), _I32),
            "tail": jnp.zeros((N,), _I32),
            "base": jnp.zeros((N,), _I32),
            "cursors": jnp.zeros((N, G), _I32),
        }

    def jit_apply(self, meta, command, state):
        Q, G = self.capacity, self.groups
        op = command[..., 0]
        a = command[..., 1]
        b = command[..., 2]
        buf, tail, base = state["buf"], state["tail"], state["base"]
        cursors = state["cursors"]

        app = (op == 1) & (a >= 0)
        slot = jnp.mod(tail, Q)
        hot = (jnp.arange(Q) == slot[..., None]) & app[..., None]
        buf = jnp.where(hot, a[..., None], buf)
        new_tail = tail + app.astype(_I32)

        g_ok = (a >= 0) & (a < G)
        commit = (op == 2) & g_ok
        g = jnp.clip(a, 0, G - 1)
        cur = jnp.take_along_axis(cursors, g[..., None], axis=-1)[..., 0]
        # max-merge clamped to tail: replayed/duplicate commits are
        # no-ops and a cursor can never point past the log end
        new_cur = jnp.clip(jnp.maximum(cur, b), 0, new_tail)
        chot = (jnp.arange(G) == g[..., None]) & commit[..., None]
        cursors = jnp.where(chot, new_cur[..., None], cursors)

        trunc = op == 3
        new_base = jnp.where(trunc,
                             jnp.clip(jnp.maximum(base, a), 0, new_tail),
                             base)
        # retention: an append that laps the ring evicts the oldest offset
        new_base = jnp.maximum(new_base, new_tail - Q)

        reply_v = jnp.where(op == 1, tail,
                            jnp.where(commit, new_cur,
                                      jnp.where(trunc, new_base, 0)))
        ok = (op == 0) | app | commit | trunc
        code = jnp.where(ok, jnp.where(op == 0, 0, 1), -2)
        reply = jnp.stack([code, jnp.where(ok, reply_v, -1)], axis=-1)
        new_state = {"buf": buf, "tail": new_tail, "base": new_base,
                     "cursors": cursors}
        return new_state, reply

    # -- one-shot window fold (engine batch path) --------------------------

    def jit_apply_batch(self, meta, commands, mask, state):
        # fast only for noop/append windows (the firehose steady state);
        # cursor commits and truncates read evolving state in order
        fast_ok = ~jnp.any(mask & (commands[..., 0] >= 2))
        return self.window_fold_dispatch(meta, commands, mask, state,
                                         fast_ok)

    def _batch_fast(self, commands, mask, state):
        """Vectorized append-only window fold."""
        Q = self.capacity
        op = jnp.where(mask, commands[..., 0], 0)           # [..., A]
        val = commands[..., 1]
        app = (op == 1) & (val >= 0)
        rank = jnp.cumsum(app.astype(_I32), axis=-1) \
            - app.astype(_I32)                               # exclusive
        n_app = jnp.sum(app.astype(_I32), axis=-1)
        tail = state["tail"]

        # scatter-free ring write (see jit_fifo._batch_fast): written
        # slots are offsets tail0..tail0+n_app-1; when A > Q several
        # appends alias one slot mod Q and only the LAST survives, so
        # each slot selects the maximal aliasing rank
        qr = jnp.arange(Q)
        jd = jnp.mod(qr - tail[..., None], Q)                # [..., Q]
        written = jd < n_app[..., None]
        rank_win = jd + Q * ((n_app[..., None] - 1 - jd) // Q)
        onehot = (app[..., None, :] &
                  (rank[..., None, :] == rank_win[..., None])
                  ).astype(jnp.float32)                      # [..., Q, A]
        placed = place16(onehot, val)

        new_tail = tail + n_app
        new_state = dict(state)
        new_state["buf"] = jnp.where(written, placed, state["buf"])
        new_state["tail"] = new_tail
        new_state["base"] = jnp.maximum(state["base"], new_tail - Q)
        return new_state

    # -- vectorized read path (ISSUE 20) -----------------------------------

    def jit_query(self, queries, state):
        # queries: [..., Kr, 2]; state buf [..., Q], tail/base [...],
        # cursors [..., G] — pure gathers, no state mutation (consumer
        # replay reads never enter the log)
        Q, G = self.capacity, self.groups
        op = queries[..., 0]
        a = queries[..., 1]
        tail = state["tail"][..., None]                      # [..., 1]
        base = state["base"][..., None]

        off_ok = (a >= base) & (a < tail)
        slot = jnp.mod(jnp.clip(a, 0, None), Q)
        val = jnp.take_along_axis(state["buf"][..., None, :],
                                  slot[..., None], axis=-1)[..., 0]
        g_ok = (a >= 0) & (a < G)
        g = jnp.clip(a, 0, G - 1)
        cur = jnp.take_along_axis(state["cursors"][..., None, :],
                                  g[..., None], axis=-1)[..., 0]

        code = jnp.where(op == 0, tail,
                         jnp.where(op == 1, off_ok.astype(_I32),
                                   g_ok.astype(_I32)))
        value = jnp.where(op == 0, base,
                          jnp.where(op == 1,
                                    jnp.where(off_ok, val, -1),
                                    jnp.where(g_ok, cur, -1)))
        return jnp.stack([code, value], axis=-1)

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        try:
            if isinstance(command, tuple) and command:
                kind = command[0]
                if kind == "append" and len(command) == 2:
                    return jnp.asarray([1, int(command[1]), 0], _I32)
                if kind == "commit" and len(command) == 3:
                    return jnp.asarray([2, int(command[1]),
                                        int(command[2])], _I32)
                if kind == "truncate" and len(command) == 2:
                    return jnp.asarray([3, int(command[1]), 0], _I32)
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((3,), _I32)

    def decode_reply(self, reply):
        code, val = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if val < 0 else val)

    def encode_query(self, query):
        try:
            if isinstance(query, tuple) and query:
                kind = query[0]
                if kind == "read" and len(query) == 2:
                    return jnp.asarray([1, int(query[1])], _I32)
                if kind == "cursor" and len(query) == 2:
                    return jnp.asarray([2, int(query[1])], _I32)
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((2,), _I32)  # bounds()

    def decode_query_reply(self, reply):
        code, val = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if val < 0 else val)


class StreamLogMachine(JitMachine):
    """The stream at a partitioned log's shape: a partition keeps the
    newest ``retention`` messages of ``message_words`` int32 words on
    the device, a read returns a chunk of up to ``chunk`` consecutive
    messages, and consumer groups store their offsets in the stream.
    The sibling of :class:`StreamMachine` for a log far larger than an
    apply window: the batch fold writes the appended messages into the
    retained tail and nothing else, in place, and folds the whole
    vocabulary (appends, offset stores, truncations) in one pass, with
    no sequential fallback.

    State per lane: ``log int32[retention / chunk, chunk * W]`` (offset
    ``o`` lives at slot ``o % retention``, a row of ``chunk`` messages:
    250 words at 10 messages of 25, which the TPU pads to 256, where a
    row a message would pad 25 words to 128), ``tail`` (next offset),
    ``base`` (oldest retained offset), ``cursors int32[groups]``.

    Command (command_spec int32[3 + W]): ``[op, a, b, W body words]``

      op 0 noop
      op 1 append(body)        reply [1, offset]
      op 2 store(g=a, lag=b)   reply [1, cursor]   cursor of group g
                                max-merged with tail - lag
      op 3 truncate(lag=a)     reply [1, base]     base max-merged with
                                tail - lag

    Stores and truncations name an offset relative to the tail at their
    place in the log (a client of the fleet cannot learn an absolute
    offset before it writes), so neither can pass the tail.  A bad group
    or a negative lag, or any other op, makes the command a no-op with
    reply [-2, -1].  An append beyond ``retention`` evicts the oldest
    message: ``base >= tail - retention``.

    Query (query_spec int32[2]): ``[op, lag]``; op 1 is ``read(lag)``:
    the chunk from ``first = tail - lag`` (held to ``[base, tail]``),
    reply int32[2 + chunk * W] = ``[n, first, the n messages, zeros]``
    with ``n = min(chunk, tail - first)``; another op or a negative lag
    answers ``[0, -1, zeros]``.

    ``jit_init`` returns every tail LOADED with ``retention`` messages
    of history (offsets 0 .. retention - 1), every word a pure function
    of ``seed``, lane, offset and word (``jit_kv.loaded_words`` of cell
    ``offset * W + word``).  Loading is set-up, not traffic.
    """
    reply_spec = ("int32", (2,))
    version = 0
    #: appends do not commute (the offset is the order); jit_apply_batch
    #: folds the window in order
    supports_batch_apply = True
    query_spec = ("int32", (2,))
    #: compacted appends the batch fold writes a pass
    CHUNK = 256

    def __init__(self, message_words: int = 25, chunk: int = 10,
                 retention: int = 8000, groups: int = 1,
                 seed: int = 0) -> None:
        if min(message_words, chunk, retention, groups) < 1:
            raise ValueError("message_words, chunk, retention and groups "
                             "must be >= 1")
        if retention % chunk:
            raise ValueError("retention must be a whole number of chunks")
        self.message_words = int(message_words)
        self.chunk = int(chunk)
        self.retention = int(retention)
        self.groups = int(groups)
        self.seed = int(seed)

    # specs as properties: the instance's __dict__ stays all scalars
    @property
    def command_spec(self):
        return ("int32", (3 + self.message_words,))

    @property
    def query_reply_spec(self):
        return ("int32", (2 + self.chunk * self.message_words,))

    def jit_init(self, n_lanes: int):
        Q, CW = self.retention, self.chunk * self.message_words

        def load():
            lane = jnp.arange(n_lanes, dtype=jnp.uint32)[:, None, None]
            cell = jnp.arange(Q * self.message_words,
                              dtype=jnp.uint32).reshape((1, Q // self.chunk,
                                                         CW))
            return loaded_words(jnp, self.seed, lane, cell)

        # one program, one log (see JitRecordKvMachine.jit_init)
        return {"log": jax.jit(load)(),
                "tail": jnp.full((n_lanes,), Q, _I32),
                "base": jnp.zeros((n_lanes,), _I32),
                "cursors": jnp.zeros((n_lanes, self.groups), _I32)}

    def _decode(self, commands):
        """(append, store, truncate): which commands are valid ones."""
        op, a, b = commands[..., 0], commands[..., 1], commands[..., 2]
        store = (op == 2) & (a >= 0) & (a < self.groups) & (b >= 0)
        return op == 1, store, (op == 3) & (a >= 0)

    def jit_apply(self, meta, command, state):
        # the one-command definition: plain selects over the whole log
        Q, C, W, G = (self.retention, self.chunk, self.message_words,
                      self.groups)
        app, store, trunc = self._decode(command)
        a, b = command[..., 1], command[..., 2]
        tail, base = state["tail"], state["base"]
        slot = tail % Q
        row = (jnp.arange(Q // C) == (slot // C)[..., None]) & app[..., None]
        col = jnp.arange(C * W) // W == (slot % C)[..., None]
        body = jnp.tile(command[..., 3:], C)
        log = jnp.where(row[..., :, None] & col[..., None, :],
                        body[..., None, :], state["log"])
        new_tail = tail + app.astype(_I32)

        g = jnp.clip(a, 0, G - 1)
        cur = jnp.take_along_axis(state["cursors"], g[..., None],
                                  axis=-1)[..., 0]
        new_cur = jnp.maximum(cur, tail - b)
        cursors = jnp.where((jnp.arange(G) == g[..., None]) & store[..., None],
                            new_cur[..., None], state["cursors"])
        new_base = jnp.where(trunc, jnp.maximum(base, tail - a), base)
        new_base = jnp.maximum(new_base, new_tail - Q)

        ok = app | store | trunc
        value = jnp.where(app, tail, jnp.where(store, new_cur, new_base))
        code = jnp.where(ok, 1, jnp.where(command[..., 0] == 0, 0, -2))
        reply = jnp.stack([code, jnp.where(ok, value,
                                           jnp.where(code == 0, 0, -1))],
                          axis=-1)
        return {"log": log, "tail": new_tail, "base": new_base,
                "cursors": cursors}, reply

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # Every position's tail is the lane's tail before the window plus
    # the appends before it in the window (an [A, A] block of compares
    # summed, not a prefix sum: see ops/table.py).  An append writes at
    # that tail modulo the retention, through the table writer, which
    # keeps window order, so a later append to a slot wins; a store or a
    # truncation max-merges its cursor or the base with that tail less
    # its lag, and max-merges commute.  So the whole window folds in
    # one pass and nothing demotes a lane, or the fleet, to the
    # in-order sequential fold.

    def jit_apply_batch(self, meta, commands, mask, state):
        Q, C, W, G = (self.retention, self.chunk, self.message_words,
                      self.groups)
        R = Q // C
        batch, A = mask.shape[:-1], mask.shape[-1]
        B = math.prod(batch)
        cmds = commands.reshape((B, A, 3 + W))
        app, store, trunc = self._decode(cmds)
        do = mask.reshape((B, A))
        app, store, trunc = app & do, store & do, trunc & do
        a, b = cmds[..., 1], cmds[..., 2]

        tail0 = state["tail"].reshape((B,))
        pos = jnp.arange(A)
        before = pos[None, :] < pos[:, None]                 # [A, A]: j < i
        at = tail0[:, None] + jnp.sum(app[:, None, :] & before, axis=-1,
                                      dtype=_I32)            # [B, A]
        tail = tail0 + jnp.sum(app, axis=-1, dtype=_I32)

        hit = store[:, None, :] & (a[:, None, :] == jnp.arange(G)[:, None])
        cursors = jnp.maximum(
            state["cursors"].reshape((B, G)),
            jnp.max(jnp.where(hit, (at - b)[:, None, :], 0), axis=-1))
        base = jnp.maximum(state["base"].reshape((B,)),
                           jnp.max(jnp.where(trunc, at - a, 0), axis=-1))
        base = jnp.maximum(base, tail - Q)

        rows = cmds.reshape((B * A, 3 + W))
        slot = (at % Q).reshape((B * A,))

        def locate(p, live, carry):
            s = slot[p]
            return (p // A) * R + s // C, (s % C) * W, rows[p][:, 3:], carry

        log, _ = write_in_place(
            state["log"].reshape((B * R, C * W)), app.reshape((B * A,)),
            locate, chunk=self.CHUNK)
        return {"log": log.reshape(batch + (R, C * W)),
                "tail": tail.reshape(batch), "base": base.reshape(batch),
                "cursors": cursors.reshape(batch + (G,))}

    # -- vectorized read path ----------------------------------------------

    def jit_query(self, queries, state):
        # queries: [..., Kr, 2]; state log [..., R, C*W]: the chunk's two
        # rows gathered a query (a chunk of C messages from any slot
        # spans at most two rows), no state mutation
        Q, C, W = self.retention, self.chunk, self.message_words
        R, CW = Q // C, C * W
        batch, Kr = queries.shape[:-2], queries.shape[-2]
        B = math.prod(batch)
        lag = queries[..., 1]
        ok = (queries[..., 0] == 1) & (lag >= 0)
        tail, base = state["tail"][..., None], state["base"][..., None]
        first = jnp.clip(tail - jnp.maximum(lag, 0), base, tail)
        n = jnp.where(ok, jnp.minimum(C, tail - first), 0)
        slot = first % Q
        lane = (jnp.arange(B, dtype=_I32) * R).reshape(batch + (1,))
        flat = state["log"].reshape((B * R, CW))
        row0 = (lane + slot // C).reshape((B * Kr,))
        row1 = (lane + (slot // C + 1) % R).reshape((B * Kr,))
        both = jnp.concatenate([flat[row0], flat[row1]], axis=-1)
        # the chunk starts k messages into its first row: one select
        # over static slices a k, no gather over the words
        k0 = (slot % C).reshape((B * Kr, 1))
        words = both[:, :CW]
        for k in range(1, C):
            words = jnp.where(k0 == k, both[:, k * W:k * W + CW], words)
        words = words.reshape(batch + (Kr, CW))
        words = jnp.where(jnp.arange(CW) // W < n[..., None], words, 0)
        return jnp.concatenate(
            [n[..., None], jnp.where(ok, first, -1)[..., None], words],
            axis=-1)

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        W = self.message_words
        try:
            if isinstance(command, tuple) and command:
                kind = command[0]
                if kind == "append" and len(command) == 2:
                    body = jnp.asarray(command[1], _I32).reshape((W,))
                    return jnp.concatenate([jnp.asarray([1, 0, 0], _I32),
                                            body])
                head = None
                if kind == "store" and len(command) == 3:
                    head = [2, int(command[1]), int(command[2])]
                if kind == "truncate" and len(command) == 2:
                    head = [3, int(command[1]), 0]
                if head is not None:
                    return jnp.concatenate([jnp.asarray(head, _I32),
                                            jnp.zeros((W,), _I32)])
        except (TypeError, ValueError, OverflowError):
            pass
        return jnp.zeros((3 + W,), _I32)

    def decode_reply(self, reply):
        code, val = int(reply[..., 0]), int(reply[..., 1])
        return (code, None if val < 0 else val)

    def encode_query(self, query):
        if isinstance(query, tuple) and len(query) == 2 \
                and query[0] == "read":
            return jnp.asarray([1, int(query[1])], _I32)
        return jnp.zeros((2,), _I32)

    def decode_query_reply(self, reply):
        """(first offset, the messages) of one chunk: messages int32[n, W];
        (None, None) for a refused query."""
        import numpy as np
        arr = np.asarray(reply)
        n, first = int(arr[..., 0]), int(arr[..., 1])
        if first < 0:
            return None, None
        return first, arr[..., 2:2 + n * self.message_words].reshape(
            (n, self.message_words))


def query_bounds(state) -> tuple:
    """(base, tail) readable-offset window (host-path query fun)."""
    return (int(state["base"]), int(state["tail"]))
