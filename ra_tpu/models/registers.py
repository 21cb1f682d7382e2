"""Register-file machine — a jittable KV with order-dependent semantics.

Second `ra_machine_xla`-contract machine family (after the commutative
CounterMachine): each lane replicates a fixed file of ``n_slots`` int32
registers supporting put / fetch-add / compare-and-set.  CAS makes the
fold **order-dependent**; cas-free windows still fold one-shot via
``jit_apply_batch`` (last-put + subsequent adds per slot — see the
method comment), cas windows take the in-order masked scan fallback.
The device analogue of the host KvMachine's cas counters, and the
shape of a metadata/config store replicated per cluster.

Encoding (command_spec int32[4]): ``[op, slot, value, expected]``
  op 0 = noop (term-opening entry)
  op 1 = put:  reg[slot] := value;                   reply old value
  op 2 = add:  reg[slot] += value;                   reply new value
  op 3 = cas:  if reg[slot] == expected: := value;   reply 1/0 (ok flag)

Reference parity: this is the ra-kv-store register workload folded
on-device; the host path (Machine.apply via JitMachine's bridge) gives
the same machine to classic RaServer deployments.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.machine import JitMachine


class RegisterMachine(JitMachine):
    command_spec = ("int32", (4,))
    reply_spec = ("int32", ())
    version = 0
    #: CAS does not commute — batch apply stays sound because
    #: jit_apply_batch folds the window IN ORDER (vectorized fast path
    #: for cas-free windows, masked sequential fold once a cas appears)
    supports_batch_apply = True

    def __init__(self, n_slots: int = 8) -> None:
        self.n_slots = n_slots

    def jit_init(self, n_lanes: int):
        return jnp.zeros((n_lanes, self.n_slots), jnp.int32)

    def jit_apply(self, meta, command, state):
        # command: [..., 4]; state: [..., S]
        op = command[..., 0]
        slot = jnp.clip(command[..., 1], 0, self.n_slots - 1)
        value = command[..., 2]
        expected = command[..., 3]
        current = jnp.take_along_axis(state, slot[..., None],
                                      axis=-1)[..., 0]
        cas_ok = (current == expected)
        new_val = jnp.where(
            op == 1, value,
            jnp.where(op == 2, current + value,
                      jnp.where((op == 3) & cas_ok, value, current)))
        write = (op == 1) | (op == 2) | ((op == 3) & cas_ok)
        # scatter the single-slot write (one-hot select: static shapes,
        # no dynamic-slice — vmap/scan friendly)
        onehot = (jnp.arange(self.n_slots) == slot[..., None])
        updated = jnp.where(onehot & write[..., None],
                            new_val[..., None], state)
        reply = jnp.where(op == 1, current,
                          jnp.where(op == 2, new_val,
                                    jnp.where(op == 3,
                                              cas_ok.astype(jnp.int32),
                                              0)))
        return updated, reply

    # -- one-shot window fold (engine batch path) --------------------------
    #
    # A window WITHOUT cas folds in one vectorized pass: the final value
    # of a slot is (value of its LAST put) + (sum of the adds AFTER that
    # put), or (current value + sum of all its adds) when no put landed.
    # With a small slot file the [..., S, A] masked sums are exact plain
    # int32 ops (int32 addition wraps identically to the sequential
    # fold), no matmul tricks needed.  Windows containing cas fall back
    # to JitMachine.sequential_window_fold under a lax.cond — cas reads
    # the evolving register, the one sequential dependency.  The engine
    # discards per-command replies on this path (lockstep.py step 5).

    def jit_fallback(self, commands, mask):
        return jnp.any(mask & (commands[..., 0] == 3))      # a cas

    def jit_apply_batch(self, meta, commands, mask, state):
        return self.window_fold_dispatch(meta, commands, mask, state)

    def _batch_fast(self, commands, mask, state):
        """Vectorized cas-free window fold: last-put + subsequent adds."""
        S = self.n_slots
        A = commands.shape[-2]
        op = jnp.where(mask, commands[..., 0], 0)           # [..., A]
        slot = jnp.clip(commands[..., 1], 0, S - 1)         # jit_apply clips
        value = commands[..., 2]
        sr = jnp.arange(S)
        at_slot = slot[..., None, :] == sr[..., :, None]    # [..., S, A]
        hits_put = at_slot & (op == 1)[..., None, :]
        hits_add = at_slot & (op == 2)[..., None, :]
        pos = jnp.arange(A)
        lastput = jnp.max(jnp.where(hits_put, pos, -1), axis=-1)
        base_put = jnp.sum(
            jnp.where(hits_put & (pos == lastput[..., None]), value[..., None, :], 0),
            axis=-1)                                        # single selection
        base = jnp.where(lastput >= 0, base_put, state)
        adds_after = jnp.sum(
            jnp.where(hits_add & (pos > lastput[..., None]), value[..., None, :], 0),
            axis=-1)
        return base + adds_after

    def encode_command(self, command) -> jnp.ndarray:
        """Host commands: ("put", slot, v) | ("add", slot, v) |
        ("cas", slot, expected, new) | anything else -> noop.

        Malformed commands (wrong arity, non-int fields) also encode as
        noop rather than raising: this runs inside the replicated apply
        fold on EVERY member (core/server.py _apply_one), where an
        exception for one bad committed client input would crash the
        whole cluster's apply path."""
        try:
            if isinstance(command, tuple):
                if command[0] == "put" and len(command) == 3:
                    return jnp.asarray([1, int(command[1]),
                                        int(command[2]), 0], jnp.int32)
                if command[0] == "add" and len(command) == 3:
                    return jnp.asarray([2, int(command[1]),
                                        int(command[2]), 0], jnp.int32)
                if command[0] == "cas" and len(command) == 4:
                    return jnp.asarray([3, int(command[1]),
                                        int(command[3]),
                                        int(command[2])], jnp.int32)
        except (TypeError, ValueError, IndexError, OverflowError):
            # IndexError: empty tuple; OverflowError: out-of-int32 field
            pass
        return jnp.zeros((4,), jnp.int32)

    def decode_reply(self, reply) -> int:
        return int(reply)


def query_registers(state) -> list:
    """Query fun: the register file as a plain list (host path)."""
    import numpy as np
    return np.asarray(state).tolist()
