"""The benchmark's machine: a user-defined Ra machine for 256-byte
commands, written against the program's public ``JitMachine`` contract
the way a user of the library writes theirs.

Command, int32[64] = 256 bytes (upstream ``ra_bench``'s DATA_SIZE):
``[slot, op_id, delta, 61 body words]``.

* ``(slot, op_id)`` is the client identity the wire plane's
  at-least-once replay needs: an op applies once, exactly as
  ``ra_tpu.wire.dedup.DedupCounterMachine`` does it (``op_id`` 0 is the
  engine's no-op padding and never applies).
* ``delta`` adds to the lane's ``value``.
* every body word adds, times its position's odd weight, to the lane's
  ``check`` (int32, wrapping), so each replica's state depends on all
  256 bytes of every applied command.  Addition commutes, so the plain
  reference needs no commit order.

State per lane: ``{"value": int32, "check": int32, "seq": int32[slots]}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ra_tpu.core.machine import JitMachine

WIDTH = 64
BODY = WIDTH - 3
#: odd per-position weights of the body checksum (odd, so that no bit
#: of a word is lost under the wrapping product)
WEIGHTS = (2 * np.arange(BODY, dtype=np.int64) + 3).astype(np.int32)

_I32 = jnp.int32


def _scatter_max(seq, slot, val):
    s = seq.shape[-1]
    seqf = seq.reshape((-1, s))
    slotf = slot.reshape((seqf.shape[0], -1))
    valf = val.reshape(slotf.shape)
    out = jax.vmap(lambda q, i, v: q.at[i].max(v))(seqf, slotf, valf)
    return out.reshape(seq.shape)


def _body_sum(commands):
    w = jnp.asarray(WEIGHTS)
    return jnp.sum(commands[..., 3:] * w, axis=-1, dtype=_I32)


class BodyCounterMachine(JitMachine):
    command_spec = ("int32", (WIDTH,))
    reply_spec = ("int32", ())
    version = 0
    supports_batch_apply = True

    def __init__(self, slots: int = 64) -> None:
        if slots < 1:
            raise ValueError("slots must be >= 1")
        self.slots = int(slots)

    def jit_init(self, n_lanes: int):
        return {"value": jnp.zeros((n_lanes,), _I32),
                "check": jnp.zeros((n_lanes,), _I32),
                "seq": jnp.zeros((n_lanes, self.slots), _I32)}

    def jit_apply(self, meta, command, state):
        s = self.slots
        raw = command[..., 0]
        ok = (raw >= 0) & (raw < s)
        slot = jnp.clip(raw, 0, s - 1)
        op = command[..., 1]
        cur = jnp.take_along_axis(state["seq"], slot[..., None],
                                  axis=-1)[..., 0]
        fresh = ok & (op > cur)
        value = state["value"] + jnp.where(fresh, command[..., 2], 0)
        check = state["check"] + jnp.where(fresh, _body_sum(command), 0)
        seq = _scatter_max(state["seq"], slot[..., None],
                           jnp.where(fresh, op, 0)[..., None])
        return {"value": value, "check": check, "seq": seq}, value

    def jit_apply_batch(self, meta, commands, mask, state):
        # commands [..., A, 64], mask bool[..., A]; order-equivalent to
        # the sequential masked apply through the running per-slot
        # watermark (an [A, A] pairwise block), as DedupCounterMachine
        s = self.slots
        raw = commands[..., 0]
        ok = mask & (raw >= 0) & (raw < s)
        slot = jnp.clip(raw, 0, s - 1)
        op = commands[..., 1]
        cur = jnp.take_along_axis(state["seq"], slot, axis=-1)
        a = op.shape[-1]
        same_slot = slot[..., :, None] == slot[..., None, :]
        earlier = jnp.tril(jnp.ones((a, a), bool), k=-1)
        prior_op = jnp.max(
            jnp.where(same_slot & earlier & ok[..., None, :],
                      op[..., None, :], 0), axis=-1)
        fresh = ok & (op > jnp.maximum(cur, prior_op))
        value = state["value"] + jnp.sum(
            jnp.where(fresh, commands[..., 2], 0), axis=-1, dtype=_I32)
        check = state["check"] + jnp.sum(
            jnp.where(fresh, _body_sum(commands), 0), axis=-1, dtype=_I32)
        seq = _scatter_max(state["seq"], slot, jnp.where(fresh, op, 0))
        return {"value": value, "check": check, "seq": seq}

    def encode_command(self, command):
        return jnp.asarray(command, _I32).reshape((WIDTH,))

    def decode_reply(self, reply):
        return int(reply)
