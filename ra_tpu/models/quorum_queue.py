"""QuorumQueueMachine — a RabbitMQ quorum queue on the device apply path.

The host :class:`~ra_tpu.models.fifo.FifoMachine` follows
``ra_fifo.erl`` clause by clause (credit, ``delivery_count``, the
service queue's round robin, settle and return by message id) and
delivers by ``SendMsg`` effects.  This is the same queue at a
deployment's shape, one queue a lane: message bodies held on the device
at a real backlog, competing consumers with prefetch credit, batched
acknowledgements and requeues, and a batch fold that folds a whole
window of any of them with no sequential fallback.

State per lane (capacity Q, C consumers, prefetch P, W words a message):

* ``store int32[Q / 10, 10 * W]``: message bodies, the message of
  ticket ``t`` at slot ``t % Q``, ten to a row (250 words at W = 25,
  which the TPU pads to 256; a row a message would pad 25 words to 128);
* ``head``, ``tail``: the ready queue is the tickets ``[head, tail)``,
  in ticket order (a publish takes ticket ``tail``);
* ``out_ticket``, ``out_count`` ``int32[C, P]``: the checked-out table,
  a row a consumer and a place a delivery id ``d`` at ``d % P``: the
  message's ticket (so its slot) and its delivery count; a ready
  message's delivery count is 0 (below);
* ``lo``, ``next_id`` ``int32[C]``: a consumer holds the delivery ids
  ``[lo, next_id)``, since it settles and returns its oldest first;
  ``credit int32[C]`` = P less what it holds;
* ``turn int32[C]``: each consumer's place in the service queue (0 is
  the front), -1 where it is not in it;
* ``counts int32[5]``: messages delivered, settled, requeued,
  dead-lettered, and publishes refused.

Command (command_spec int32[3 + W]): ``[op, a, b, W body words]``

  op 0 noop                     reply [0, 0]
  op 1 publish(body)            reply [1, ticket]; refused [-1, -1]
  op 2 settle(consumer a, n b)  reply [1, settled]: the consumer's b
                                 oldest (by delivery id), clamped to
                                 what it holds
  op 3 return(consumer a, n b)  reply [1, returned]: ``basic.nack``
                                 with requeue of the b oldest: each goes
                                 back to the ready queue at its ticket's
                                 rank with ``delivery_count + 1``, or is
                                 dead-lettered (removed and counted)
                                 where that reaches ``delivery_limit``

A bad consumer, a negative n or another op: no-op, reply [-2, -1].  A
publish is refused when its slot is taken: when Q messages are held,
or when the message ``Q`` tickets older is still checked out.  After
every command ready messages are checked out in ticket order to the
consumers with credit, round robin by the service queue, as
``FifoMachine._deliver_ready`` does.

Query (query_spec int32[2]): ``[1, consumer]``: the consumer's oldest
checked-out messages, reply int32[3 + 10 * W] = ``[n, first delivery
id, redelivered bitmask, the n messages, zeros]`` (bit i set where
message i has ``delivery_count > 0``, AMQP's ``redelivered``); another
op or a bad consumer answers ``[0, -1, 0, zeros]``.

``jit_init`` loads every queue with ``loaded`` ready messages (tickets
0 .. loaded - 1, every word a pure function of ``seed``, lane, ticket
and word: ``jit_kv.loaded_words`` of cell ``ticket * W + word``) and
attaches the consumers in turn with credit P, each checking out what it
can, as ``FifoMachine`` does given ``("checkout", ("auto", P), c)``.
Loading is set-up, not traffic.

The fold rests on one invariant: **every checked-out message is older
than every ready one**.  Checkouts take the front of the ticket-ordered
ready queue and publishes land at its back; a return frees at least as
much credit as it puts messages back, and they are older than every
ready message, so they are the front and are checked out again by the
command that returned them.  So no message's body moves, a ready
message's delivery count is 0, the ready queue stays the run of tickets
``[head, tail)``, and a window's checkouts are, command by command, its
requeued messages in ticket order and then the next tickets from
``head``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..core.machine import JitMachine
from ..ops.table import run_rows, write_run
from .jit_kv import loaded_words

_I32 = jnp.int32

#: the leaves that pass through the window's per-command scan: a few
#: words a consumer and a replica, never the store
_SMALL = ("head", "tail", "out_ticket", "out_count", "lo", "next_id",
          "credit", "turn", "counts")


def _rotate(x, shift, size: int, axis: int = -1):
    """``out[..., j, ...] = x[..., (j + shift) % size, ...]`` along
    ``axis`` with ``shift`` int32 broadcasting against x without that
    axis, ``0 <= shift < size``: a select per bit of the shift over
    static rolls, no gather."""
    shift = jnp.expand_dims(shift, axis)
    for b in range(max(1, (size - 1).bit_length())):
        rolled = jnp.roll(x, -(1 << b), axis=axis)
        x = jnp.where(((shift >> b) & 1) == 1, rolled, x)
    return x


class QuorumQueueMachine(JitMachine):
    reply_spec = ("int32", (2,))
    query_spec = ("int32", (2,))
    version = 0
    #: publishes, settles and returns do not commute; jit_apply_batch
    #: folds the window in order
    supports_batch_apply = True
    #: messages a row of the store and a delivery read
    CHUNK = 10
    #: ``jit_counts``'s names, and the ``overview()`` section they go in
    counts_name = "queue"
    counts_keys = ("delivered", "settled", "requeued", "dead_lettered",
                   "refused")

    def __init__(self, message_words: int = 25, capacity: int = 2000,
                 loaded: int = 1000, consumers: int = 2,
                 prefetch: int = 32, delivery_limit: int = 20,
                 seed: int = 0) -> None:
        if min(message_words, capacity, consumers, prefetch,
               delivery_limit) < 1 or loaded < 0:
            raise ValueError("message_words, capacity, consumers, "
                             "prefetch and delivery_limit must be >= 1")
        if capacity % self.CHUNK or loaded > capacity:
            raise ValueError("capacity must be a whole number of rows of "
                             f"{self.CHUNK}, and no smaller than loaded")
        self.message_words = int(message_words)
        self.capacity = int(capacity)
        self.loaded = int(loaded)
        self.consumers = int(consumers)
        self.prefetch = int(prefetch)
        self.delivery_limit = int(delivery_limit)
        self.seed = int(seed)

    # specs as properties: the instance's __dict__ stays all scalars
    @property
    def command_spec(self):
        return ("int32", (3 + self.message_words,))

    @property
    def query_reply_spec(self):
        return ("int32", (3 + self.CHUNK * self.message_words,))

    def jit_init(self, n_lanes: int):
        Q, W, CH = self.capacity, self.message_words, self.CHUNK
        C, P, n0 = self.consumers, self.prefetch, self.loaded

        def load():
            lane = jnp.arange(n_lanes, dtype=jnp.uint32)[:, None, None]
            cell = jnp.arange(Q * W, dtype=jnp.uint32).reshape(
                (1, Q // CH, CH * W))
            return jnp.where(cell < n0 * W,
                             loaded_words(jnp, self.seed, lane, cell), 0)

        # consumer c checks out the tickets [c * P, (c + 1) * P) that
        # were loaded; one with credit left is in the service queue
        held = [min(P, max(0, n0 - c * P)) for c in range(C)]
        ring = jnp.arange(P, dtype=_I32)
        ticket = jnp.stack([jnp.where(ring < h, c * P + ring, 0)
                            for c, h in enumerate(held)])
        spare = [c for c in range(C) if held[c] < P]
        turn = [spare.index(c) if c in spare else -1 for c in range(C)]
        lanes = (n_lanes,)

        def full(x):
            x = jnp.asarray(x, _I32)
            return jnp.broadcast_to(x, lanes + x.shape)

        out = sum(held)
        # one program, one store (see JitRecordKvMachine.jit_init)
        return {"store": jax.jit(load)(),
                "head": full(out), "tail": full(n0),
                "out_ticket": full(ticket),
                "out_count": full(jnp.zeros((C, P), _I32)),
                "lo": full([0] * C), "next_id": full(held),
                "credit": full([P - h for h in held]),
                "turn": full(turn),
                "counts": full([out, 0, 0, 0, 0])}

    # -- one command, on the small leaves ----------------------------------

    def _step(self, s, cmd, do):
        """One command on every replica, the replica axis LAST (it is
        the long one, so it takes the TPU's lanes: a credit table
        ``[C, P, B]`` is dense where ``[B, C, P]`` pads each replica's
        2 x 32 to a tile of 8 x 128): ``s`` the leaves of ``_SMALL``,
        ``out_ticket`` / ``out_count`` [C, P, B], ``lo`` / ``next_id`` /
        ``credit`` / ``turn`` [C, B], ``head`` / ``tail`` [B],
        ``counts`` [5, B]; ``cmd`` int32[>= 3, B] (op, a, b: never the
        body); ``do`` bool[B].  Returns (s, whether a publish was
        accepted [B], the reply [2, B])."""
        Q, C, P = self.capacity, self.consumers, self.prefetch
        op = jnp.where(do, cmd[0], 0)
        who, n = cmd[1], cmd[2]
        good = (who >= 0) & (who < C) & (n >= 0)
        pub = op == 1
        settle = (op == 2) & good
        ret = (op == 3) & good
        take = settle | ret
        tk, dc = s["out_ticket"], s["out_count"]            # [C, P, B]
        lo, nx, turn = s["lo"], s["next_id"], s["turn"]     # [C, B]
        head, tail = s["head"], s["tail"]                   # [B]
        ring = jnp.arange(P, dtype=_I32)[:, None]           # [P, 1]
        cons = jnp.arange(C, dtype=_I32)[:, None]           # [C, 1]

        # a publish takes ticket ``tail`` unless its slot is taken
        held = nx - lo
        live = jnp.mod(ring - lo[:, None], P) < held[:, None]
        slot = jnp.mod(tail, Q)
        taken = (tail - head >= Q) | jnp.any(
            live & (jnp.mod(tk, Q) == slot), axis=(0, 1))
        acc = pub & ~taken
        ticket = tail
        tail = tail + acc.astype(_I32)

        # the consumer's m oldest leave it, in delivery order from lo
        mine = (cons == who) & take                         # [C, B]

        def of_mine(x):
            return jnp.sum(jnp.where(mine[:, None], x, 0), axis=0)

        m = jnp.where(take, jnp.minimum(
            n, jnp.sum(jnp.where(mine, held, 0), axis=0)), 0)
        lo_w = jnp.mod(jnp.sum(jnp.where(mine, lo, 0), axis=0), P)
        tk_w = _rotate(of_mine(tk), lo_w, P, axis=0)        # [P, B]
        dc_w = _rotate(of_mine(dc), lo_w, P, axis=0) + 1
        gone = (ring < m) & ret
        back = gone & (dc_w < self.delivery_limit)
        n_back = jnp.sum(back, axis=0, dtype=_I32)
        n_dead = jnp.sum(gone, axis=0, dtype=_I32) - n_back
        # requeued in ticket order: the k-th is the one with k smaller
        # tickets among them (tickets are distinct)
        rank = jnp.sum(back[None] & (tk_w[None] < tk_w[:, None]), axis=1,
                       dtype=_I32)                          # [P, B]
        kth = back[None] & (rank[None] == ring[:, :, None])  # [k, i, B]
        rq_tk = jnp.sum(jnp.where(kth, tk_w[None], 0), axis=1)
        rq_dc = jnp.sum(jnp.where(kth, dc_w[None], 0), axis=1)
        lo = lo + jnp.where(mine, m, 0)
        cap = P - (nx - lo)

        # the service queue: a consumer given credit back joins its back
        queued = turn >= 0
        turn = jnp.where(mine & ~queued & (cap > 0),
                         jnp.sum(queued, axis=0, dtype=_I32), turn)
        qcap = jnp.where(turn >= 0, cap, 0)
        # round robin: the j-th delivery of the consumer at place i of
        # the queue is number  sum_i' min(qcap_i', j)  +  the consumers
        # ahead of it with more than j
        n_ready = n_back + tail - head
        D = jnp.minimum(n_ready, jnp.sum(qcap, axis=0))
        ahead = (turn[None] >= 0) & (turn[None] < turn[:, None])  # [C, C, B]
        num = jnp.sum(jnp.minimum(qcap[:, None], ring), axis=0)[None] \
            + jnp.sum(ahead[:, :, None] & (qcap[None, :, None] > ring),
                      axis=1, dtype=_I32)                   # [C, P, B]
        d = jnp.sum((ring < qcap[:, None]) & (num < D), axis=1, dtype=_I32)
        # the new deliveries go to the places next_id .. next_id + d - 1:
        # the requeued ones first, then the tickets from head
        k = _rotate(num, jnp.mod(-nx, P), P, axis=1)
        fresh = jnp.mod(ring - nx[:, None], P) < d[:, None]
        requeued = k < n_back
        kk = requeued[:, :, None] & (k[:, :, None] == ring)  # [C, P, P, B]
        tk = jnp.where(fresh, jnp.where(
            requeued, jnp.sum(jnp.where(kk, rq_tk, 0), axis=2),
            head + k - n_back), tk)
        dc = jnp.where(fresh, jnp.where(
            requeued, jnp.sum(jnp.where(kk, rq_dc, 0), axis=2), 0), dc)
        last = jnp.sum(jnp.where(ring == (d - 1)[:, None], num, 0), axis=1)
        nx = nx + d
        head = head + D - n_back
        credit = cap - d
        # a served consumer went to the back, in the order served; one
        # left with no credit leaves the queue
        key = jnp.where(d > 0, C + last, turn)
        stays = credit > 0
        turn = jnp.where(stays, jnp.sum(
            stays[None] & (key[None] < key[:, None]), axis=1,
            dtype=_I32), -1)

        counts = s["counts"] + jnp.stack(
            [D, jnp.where(settle, m, 0), n_back, n_dead,
             (pub & ~acc).astype(_I32)])
        new = {"head": head, "tail": tail, "out_ticket": tk, "out_count": dc,
               "lo": lo, "next_id": nx, "credit": credit, "turn": turn,
               "counts": counts}
        code = jnp.where(acc | take, 1, jnp.where(
            op == 0, 0, jnp.where(pub, -1, -2)))
        value = jnp.where(acc, ticket, jnp.where(
            take, m, jnp.where(op == 0, 0, -1)))
        return new, acc, jnp.stack([code, value])

    def _lanes_last(self, state, batch):
        """The leaves of ``_SMALL`` with the replicas (the leading
        ``batch`` dims, flattened) moved to the last axis."""
        B = math.prod(batch)
        out = {}
        for k in _SMALL:
            x = state[k].reshape((B,) + state[k].shape[len(batch):])
            out[k] = jnp.moveaxis(x, 0, -1)
        return out

    def _lanes_first(self, s, batch):
        return {k: jnp.moveaxis(v, -1, 0).reshape(batch + v.shape[:-1])
                for k, v in s.items()}

    def jit_apply(self, meta, command, state):
        # the one-command definition: _step, and plain selects over the
        # whole store for a publish's body
        Q, W, CH = self.capacity, self.message_words, self.CHUNK
        batch = command.shape[:-1]
        B = math.prod(batch)
        cmd = command.reshape((B, 3 + W))
        s = self._lanes_last(state, batch)
        slot = jnp.mod(s["tail"], Q)
        s, acc, reply = self._step(s, cmd[:, :3].T, jnp.ones((B,), bool))
        store = state["store"].reshape((B, Q // CH, CH * W))
        row = (jnp.arange(Q // CH) == (slot // CH)[:, None]) & acc[:, None]
        col = jnp.arange(CH * W) // W == (slot % CH)[:, None]
        store = jnp.where(row[..., None] & col[:, None, :],
                          jnp.tile(cmd[:, 3:], CH)[:, None, :], store)
        out = self._lanes_first(s, batch)
        out["store"] = store.reshape(state["store"].shape)
        return out, reply.T.reshape(batch + (2,))

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # A scan over the window's commands carries the small leaves alone
    # (``_SMALL``: the credit table of C x P tickets and delivery counts
    # and a dozen counts a replica, replicas on the last axis) and reads
    # of a command its op,
    # consumer and n, never its body: by the invariant above no body
    # moves on a checkout or a return, so the store is not in the loop.
    # The scan hands out which publishes were accepted; they took the
    # consecutive tickets from the window's first tail, so their bodies
    # land in the store once a window as one run of slots a replica (one
    # row gather, selects, one row scatter: ``ops/table.py``
    # ``write_run``).  No ``lax.cond``, no sequential branch.  A store
    # of fewer rows than a window's run of publishes can touch (a run
    # that would wrap onto its own rows) is refused.

    def jit_apply_batch(self, meta, commands, mask, state):
        Q, W, CH = self.capacity, self.message_words, self.CHUNK
        R = Q // CH
        batch, A = mask.shape[:-1], mask.shape[-1]
        n_rows = run_rows(CH, Q, A)
        if n_rows is None:
            raise ValueError(
                f"a window of {A} commands can publish onto more rows than "
                f"the store's {R}: capacity must be at least "
                f"{CH * -(-(CH - 1 + A) // CH)} for this window")
        B = math.prod(batch)
        cmds = commands.reshape((B, A, 3 + W))
        s = self._lanes_last(state, batch)
        tail0 = s["tail"]

        def one(s, xs):
            cmd, do = xs
            s, acc, _reply = self._step(s, cmd, do)
            return s, acc

        s, acc = lax.scan(one, s, (jnp.transpose(cmds[..., :3], (1, 2, 0)),
                                   mask.reshape((B, A)).T))
        acc = acc.T                                          # [B, A]
        pos = jnp.arange(A)
        before = pos[None, :] < pos[:, None]                 # [A, A]: j < i
        rank = jnp.sum(acc[:, None, :] & before, axis=-1, dtype=_I32)
        store = write_run(state["store"].reshape((B * R, CH * W)), acc,
                          rank, tail0, cmds[..., 3:], n_rows, chunk=CH,
                          slots=Q)
        out = self._lanes_first(s, batch)
        out["store"] = store.reshape(state["store"].shape)
        return out

    def jit_counts(self, state):
        """int32[..., 5] per replica, in ``counts_keys``' order."""
        return state["counts"]

    # -- vectorized read path ----------------------------------------------

    def jit_query(self, queries, state):
        # queries: [..., Kr, 2].  Each replica answers for each of its C
        # consumers once (the rows of its oldest CHUNK messages
        # gathered, the message picked out of its row by a select over
        # the row's CHUNK static places), and a query takes its
        # consumer's answer: no state mutation
        Q, W, CH = self.capacity, self.message_words, self.CHUNK
        C, P = self.consumers, self.prefetch
        R = Q // CH
        batch = queries.shape[:-2]
        B = math.prod(batch)
        s = {k: state[k].reshape((B,) + state[k].shape[len(batch):])
             for k in ("lo", "next_id", "out_ticket", "out_count")}
        lo = s["lo"]
        n = jnp.minimum(CH, s["next_id"] - lo)               # [B, C]
        j = jnp.arange(CH, dtype=_I32)
        K = min(CH, P)

        def oldest(x):
            x = _rotate(x, jnp.mod(lo, P), P)[..., :K]
            return jnp.pad(x, ((0, 0), (0, 0), (0, CH - K)))

        tk, dc = oldest(s["out_ticket"]), oldest(s["out_count"])
        used = j < n[..., None]                              # [B, C, CH]
        slot = jnp.mod(tk, Q)
        row = (jnp.arange(B, dtype=_I32)[:, None, None] * R
               + slot // CH).reshape((B * C * CH,))
        flat = state["store"].reshape((B * R, CH * W))
        rows = flat.at[row].get(mode="promise_in_bounds")    # [BCK, CH*W]
        place = (slot % CH).reshape((B * C * CH, 1))
        words = rows[:, :W]
        for k in range(1, CH):
            words = jnp.where(place == k, rows[:, k * W:(k + 1) * W], words)
        words = jnp.where(used[..., None], words.reshape((B, C, CH, W)), 0)
        redelivered = jnp.sum(jnp.where(used & (dc > 0), 1 << j, 0),
                              axis=-1, dtype=_I32)
        answer = jnp.concatenate(
            [n[..., None], lo[..., None], redelivered[..., None],
             words.reshape((B, C, CH * W))], axis=-1)        # [B, C, 3+CH*W]
        q = queries.reshape((B, -1, 2))
        who = q[..., 1]
        ok = (q[..., 0] == 1) & (who >= 0) & (who < C)
        pick = jnp.zeros(q.shape[:2] + (answer.shape[-1],), _I32)
        for c in range(C):
            pick = jnp.where((ok & (who == c))[..., None],
                             answer[:, c][:, None, :], pick)
        pick = pick.at[..., 1].set(jnp.where(ok, pick[..., 1], -1))
        return pick.reshape(queries.shape[:-1] + (answer.shape[-1],))

    # -- host protocol -----------------------------------------------------

    def encode_command(self, command):
        W = self.message_words
        try:
            if isinstance(command, tuple) and command:
                kind = command[0]
                if kind == "publish" and len(command) == 2:
                    body = jnp.asarray(command[1], _I32).reshape((W,))
                    return jnp.concatenate([jnp.asarray([1, 0, 0], _I32),
                                            body])
                head = None
                if kind == "settle" and len(command) == 3:
                    head = [2, int(command[1]), int(command[2])]
                if kind == "return" and len(command) == 3:
                    head = [3, int(command[1]), int(command[2])]
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
                and query[0] == "deliveries":
            return jnp.asarray([1, int(query[1])], _I32)
        return jnp.zeros((2,), _I32)

    def decode_query_reply(self, reply):
        """(first delivery id, redelivered flags bool[n], messages
        int32[n, W]) of a consumer's oldest checked-out messages; (None,
        None, None) for a refused query."""
        import numpy as np
        arr = np.asarray(reply)
        n, first, mask = int(arr[..., 0]), int(arr[..., 1]), int(arr[..., 2])
        if first < 0:
            return None, None, None
        flags = np.array([(mask >> i) & 1 == 1 for i in range(n)], bool)
        return first, flags, arr[..., 3:3 + n * self.message_words].reshape(
            (n, self.message_words))
