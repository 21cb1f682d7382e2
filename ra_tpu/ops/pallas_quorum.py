"""Pallas TPU kernel for the fused quorum/commit step — a DOCUMENTED
EXPERIMENT, off by default.

Status: on the round-5 chip record the kernel LOST to the plain-XLA
sort-median lowering on the headline config; its speed is not measured
on current code.  A hand kernel that trails the compiler is negative
value on the hottest path, so ``auto`` resolution picks XLA
everywhere.  The installed Mosaic (jax 0.9.0, libtpu 0.0.34) compiles
the kernel for the v5e and chip_smoke.py checks it against the oracle
there; whether it stays is ROADMAP D2.  Opt back in with
``RA_TPU_ENABLE_PALLAS_QUORUM=1`` (or an explicit ``impl="pallas"``).

The hot per-step arithmetic of the lockstep engine is
``evaluate_quorum`` (ra_tpu.ops.quorum): a voter-masked majority median
over the per-member match indexes, the §5.4.2 term gate, and the
commit-index monotonicity clamp (ra_server.erl:2941-2993).  The jnp
reference implementation lowers the median through a generic sort; this
kernel instead uses a **count-based selection** — for tiny member counts
(P <= 15) the quorum-agreed index is

    max over voters i of  match[i]  such that
        #{ voters j : match[j] >= match[i] }  >=  trunc(n/2)+1

which is an O(P^2) pairwise-compare reduction: pure VPU work with no
sort, fused with the gate in one VMEM pass over the lane axis.

Layout: lanes ride the 128-wide lane axis; the member axis is padded to
the int32 sublane tile (8).  The wrapper transposes/pads [N,P] inputs —
XLA fuses that into the surrounding program.

Equivalence against the jnp oracle: tests/test_pallas_quorum.py (the
interpreter, by explicit argument, on the CPU) and chip_smoke.py (the
compiled kernel, on the chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array

_LANE_TILE = 512     # lanes per grid step (multiple of 128)
_SUBLANE = 8         # int32 sublane tile


def _kernel(commit_ref, match_ref, voter_ref, tstart_ref, out_ref):
    match = match_ref[:]                    # [P8, T] int32
    voter = voter_ref[:]                    # [P8, T] int32 (0/1)
    commit = commit_ref[:]                  # [1, T]  int32
    tstart = tstart_ref[:]                  # [1, T]  int32
    masked = jnp.where(voter > 0, match, -1)
    n = jnp.sum(voter, axis=0, keepdims=True)            # [1, T]
    needed = n // 2 + 1
    # support_i = #{ voters j : match_j >= match_i }; pairwise over the
    # (tiny, padded) member axis
    ge = (masked[None, :, :] >= masked[:, None, :]).astype(jnp.int32)
    support = jnp.sum(ge * voter[None, :, :], axis=1)    # [P8, T]
    cand = jnp.where((support >= needed) & (voter > 0), masked, -1)
    agreed = jnp.maximum(jnp.max(cand, axis=0, keepdims=True), 0)  # [1, T]
    ok = (agreed > commit) & (agreed >= tstart)
    out_ref[:] = jnp.where(ok, agreed, commit)


@functools.partial(jax.jit, static_argnames=("interpret",))
def evaluate_quorum_pallas(commit_index: Array, match_index: Array,
                           voter_mask: Array, term_start_index: Array,
                           interpret: bool = False) -> Array:
    """Drop-in replacement for ops.quorum.evaluate_quorum.

    commit_index: int32[N]; match_index: int32[N, P];
    voter_mask: bool[N, P]; term_start_index: int32[N].
    """
    from jax.experimental import pallas as pl

    N, P = match_index.shape
    n_pad = (-N) % _LANE_TILE
    p_pad = (-P) % _SUBLANE
    # transpose to [P8, Npad]: members on sublanes, lanes on the lane axis
    match_t = jnp.pad(match_index.T.astype(jnp.int32),
                      ((0, p_pad), (0, n_pad)))
    voter_t = jnp.pad(voter_mask.T.astype(jnp.int32),
                      ((0, p_pad), (0, n_pad)))
    commit_t = jnp.pad(commit_index.astype(jnp.int32),
                       ((0, n_pad),))[None, :]
    tstart_t = jnp.pad(term_start_index.astype(jnp.int32),
                       ((0, n_pad),))[None, :]
    Np = N + n_pad
    Pp = P + p_pad
    grid = (Np // _LANE_TILE,)
    lane_block = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, _LANE_TILE), lambda i: (0, i))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        grid=grid,
        in_specs=[lane_block(1), lane_block(Pp), lane_block(Pp),
                  lane_block(1)],
        out_specs=lane_block(1),
        interpret=interpret,
    )(commit_t, match_t, voter_t, tstart_t)
    return out[0, :N]


def make_evaluate_quorum(impl: str = "auto"):
    """Resolve the quorum implementation: 'xla' (jnp sort-median oracle),
    'pallas' (this kernel, compiled), or 'auto'.  'auto' resolves to
    XLA unless RA_TPU_ENABLE_PALLAS_QUORUM=1 opts the kernel back in on
    a TPU backend.  An explicit 'pallas' on a backend that cannot
    compile the kernel raises here, at build time: it never falls back
    to the interpreter, which would answer correctly at a speed nobody
    deploys.  Tests that want the interpreter call
    ``evaluate_quorum_pallas(..., interpret=True)`` themselves."""
    import os

    from .quorum import evaluate_quorum as xla_impl

    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        gate = os.environ.get("RA_TPU_ENABLE_PALLAS_QUORUM", "")
        impl = "pallas" if gate not in ("", "0") and on_tpu else "xla"
    if impl == "pallas":
        if not on_tpu:
            raise ValueError(
                "quorum impl 'pallas' needs a TPU backend; JAX runs on "
                f"{jax.default_backend()!r}")
        return evaluate_quorum_pallas
    if impl != "xla":
        raise ValueError(f"unknown quorum impl {impl!r}")
    return xla_impl
