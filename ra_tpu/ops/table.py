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

:func:`write_run` is the writer of a log laid out in rows of ``chunk``
messages whose writes of a window land at consecutive slots from a
tail (the stream log's appends, the quorum queue's publishes): one row
gather, selects and one row scatter a replica, no loop, wherever
:func:`run_rows` says the run cannot wrap onto its own rows.
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


def run_rows(chunk: int, slots: int, window: int):
    """Rows of a log of ``slots`` messages in rows of ``chunk`` that a
    run of ``window`` consecutive slots can touch, or None where they
    would wrap onto one another (the stream log writes such a window
    through :func:`write_in_place`; the quorum queue refuses it)."""
    n = -(-(chunk - 1 + window) // chunk)
    return n if n <= slots // chunk else None


def write_run(log, app, rank, tail0, body, n_rows: int, *, chunk: int,
              slots: int):
    """A window's writes laid into ``log`` [B * R, chunk * W] (R =
    ``slots / chunk`` rows a replica) as one run a replica: ``app``
    bool[B, A] which positions write, ``rank`` int32[B, A] the writes
    before each, ``tail0`` int32[B] the slot number of the first,
    ``body`` int32[B, A, W].  The j-th write goes to slot ``(tail0 + j)
    % slots``.  The run starts ``off`` messages into the replica's row
    ``(tail0 % slots) // chunk`` and covers ``n_rows`` rows (from
    :func:`run_rows`), each gathered, merged and scattered back whole,
    unchanged where the replica writes nothing.  (The TPU's scatter
    costs about the same for each of its updates, one row here, and a
    row dropped by an index past the log's end costs no less; a window
    of several rows a replica, in the scatter or the gather, the TPU's
    compiler makes a loop again.)"""
    C, Q, R = chunk, slots, slots // chunk
    B, A, W = body.shape
    L = n_rows * C * W
    n = jnp.sum(app, axis=-1, dtype=_I32)
    # the j-th write of the window at place j: one compare a pair of
    # positions, summed (one term is not 0), no sort and no gather
    hit = app[:, None, :] & (rank[:, None, :] == jnp.arange(A)[:, None])
    run = jnp.sum(jnp.where(hit[..., None], body[:, None], 0), axis=2)
    # shifted ``off`` messages into its first row: one select over the
    # C static shifts, no gather over the words
    slot = tail0 % Q
    off = (slot % C)[:, None]
    padded = jnp.pad(run.reshape((B, A * W)),
                     ((0, 0), ((C - 1) * W, L - A * W)))
    words = padded[:, (C - 1) * W:]
    for k in range(1, C):
        at = (C - 1 - k) * W
        words = jnp.where(off == k, padded[:, at:at + L], words)
    row = (jnp.arange(B, dtype=_I32)[:, None] * R
           + ((slot // C)[:, None] + jnp.arange(n_rows, dtype=_I32)) % R
           ).reshape((B * n_rows,))
    msg = jnp.arange(L, dtype=_I32) // W - off
    old = log.at[row].get(mode="promise_in_bounds").reshape((B, L))
    new = jnp.where((msg >= 0) & (msg < n[:, None]), words, old)
    return log.at[row].set(new.reshape((B * n_rows, C * W)),
                           mode="promise_in_bounds", unique_indices=True)
