"""Exact integer one-hot contraction on the MXU.

The TPU's generic per-element gather/scatter lowering is the slowest way
to move per-lane variable-index data; contracting a {0,1} one-hot f32
tensor against the values routes the same movement onto the systolic
array.  An f32 product against a one-hot is exact for a 24-bit operand
(the significand), so an int32 value rides as its low 24 bits and its
top byte (two matmuls) and recombines bitwise — negatives included,
since the (lo | hi<<24) recombination is modular.

Shared by the lockstep engine's ring IO / trajectory select
(engine/lockstep.py) and the machines' vectorized window folds
(models/jit_fifo.py, models/jit_kv.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def split16_matmul(onehot_f32: jax.Array, values: jax.Array) -> jax.Array:
    """Exact int32 gather/scatter-by-matmul: contract a {0,1} one-hot
    f32 tensor [..., A, R] with int32 values [..., R, C] -> [..., A, C].
    Each one-hot row has at most one 1, so every product and sum is
    exact in f32 for an operand of 24 bits or fewer.
    Precision.HIGHEST for the 24-bit piece: TPU otherwise lowers f32
    matmuls through fewer bf16 passes, which silently rounds the
    operand.  The top byte needs none of that: 0..255 and the one-hot
    are exact in bfloat16, so the default single pass is exact too.

    The split is 24 + 8 bits (the name is from when it was 16 + 16; the
    benchmark's control replaces the function under it): on a TPU the
    two matmuls are two fusions, the first one's result written out
    and read back by the second, and a top byte carried as uint8 is a
    quarter of a 16-bit half carried as int32.  Over the engine's ring
    at 10,000 lanes on a v5e that is 10.9 ms an append where 16 + 16
    took 16.2 (the same at Precision.HIGH; 12.5 with the half as
    uint16), and 12.8 with the top byte's matmul at HIGHEST as well
    (PERF.md section 6, PR 30).  A matmul of its own for each byte, or
    the pieces side by side along C in one, cost more."""
    lo = (values & 0xFFFFFF).astype(jnp.float32)
    hi = ((values >> 24) & 0xFF).astype(jnp.float32)
    ghi = jnp.einsum("...ar,...rc->...ac", onehot_f32,
                     hi).astype(jnp.uint8)
    glo = jnp.einsum("...ar,...rc->...ac", onehot_f32, lo,
                     precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    return glo | (ghi.astype(jnp.int32) << 24)


def place16(onehot_f32: jax.Array, values: jax.Array) -> jax.Array:
    """split16_matmul for a value VECTOR: [..., A, R] x [..., R] ->
    [..., A] — the window-fold placement shape."""
    return split16_matmul(onehot_f32, values[..., None])[..., 0]
