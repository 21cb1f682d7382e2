"""The in-place writer of a table machine's batch fold.

A machine whose state is a table far larger than an apply window (the
YCSB record store, the stream log's retained tail) folds a window by
moving the words it writes and nothing else, so its cost follows the
writes in the window and not the table's size, and the table is only
ever written into: under a donating jit it is updated where it lies (on
a TPU a replica set of 6 GB has no room for a second copy beside it).

:func:`write_in_place` compacts the window's writes with one stable
sort of the window's positions (the writes first, in window order),
then applies them ``chunk`` at a time in a ``while_loop`` that runs as
many passes as there are writes to hold: none, on a round that applies
none.  A pass asks the machine where its writes go (and lets it fold
whatever else it keeps beside the table), then writes their word rows
one after the other, in window order, so the last writer of a place
wins as it does one command at a time.  (Every operation here carries
the caller's scope: a window scatter and a prefix sum are rewritten by
the TPU's compiler into loops that carry none, and the benchmark's
``xla.stage_named_pct`` lost a fifth of the step to them.)
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_I32 = jnp.int32


def write_in_place(table, writes, locate, *, chunk: int, carry=()):
    """Write the word rows of the window's writes into ``table``.

    ``table``: the machine's table, 2-D, written with one
    ``dynamic_update_slice`` a write.  ``writes``: bool[M], which of the
    window's M positions (flattened) write.  ``locate(pos, live,
    carry)``: for one pass's window positions ``pos`` int32[ch] (``live``
    is False past the window's last write) returns ``(row, col, words,
    carry)``: each write's row and first column in ``table``, its word
    rows [ch, W], and ``carry`` folded.  Returns ``(table, carry)``."""
    M = writes.shape[0]
    total = jnp.sum(writes, dtype=_I32)
    ch = min(chunk, M)
    # the positions of the window's writes, in window order, then the
    # rest; padded so that every pass slices a whole chunk
    order = jnp.pad(jnp.argsort(~writes, stable=True).astype(_I32),
                    (0, -M % ch))
    slot = jnp.arange(ch, dtype=_I32)

    def more(state):
        return state[0] * ch < total

    def one_pass(state):
        i, table, carry = state
        pos = lax.dynamic_slice(order, (i * ch,), (ch,))
        row, col, words, carry = locate(pos, i * ch + slot < total, carry)
        W = words.shape[-1]

        def put(j, table):
            return lax.dynamic_update_slice(
                table, lax.dynamic_slice(words, (j, 0), (1, W)),
                (row[j], col[j]))

        table = lax.fori_loop(0, jnp.minimum(total - i * ch, ch), put, table)
        return i + 1, table, carry

    _, table, carry = lax.while_loop(more, one_pass,
                                     (jnp.int32(0), table, carry))
    return table, carry
