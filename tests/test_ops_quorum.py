"""Kernel-vs-oracle equivalence: the batched XLA quorum kernels must agree
with the scalar pure core on randomized inputs (the TPU analogue of driving
ra_server's quorum functions directly in ra_server_SUITE)."""
import numpy as np
import pytest

import jax.numpy as jnp

from ra_tpu.core.server import RaServer
from ra_tpu.ops import (
    agreed_commit,
    election_quorum,
    evaluate_quorum,
    update_match_next,
)

rng = np.random.default_rng(42)


def test_agreed_commit_matches_oracle_randomized():
    N, P = 257, 7
    match = rng.integers(0, 1000, size=(N, P)).astype(np.int32)
    # random voter masks with at least 1 voter
    mask = rng.random((N, P)) < 0.7
    mask[:, 0] = True
    got = np.asarray(agreed_commit(jnp.asarray(match), jnp.asarray(mask)))
    for i in range(N):
        voters = [int(match[i, p]) for p in range(P) if mask[i, p]]
        assert got[i] == RaServer.agreed_commit(voters), (i, voters, got[i])


def test_agreed_commit_known_cases():
    cases = [
        ([5], [True], 5),
        ([5, 3], [True, True], 3),
        ([5, 3, 1], [True, True, True], 3),
        ([7, 5, 3, 1], [True] * 4, 3),
        ([9, 7, 5, 3, 1], [True] * 5, 5),
        ([9, 7, 5, 3, 1], [True, True, True, False, False], 7),  # non-voters
        ([0, 0, 9], [True] * 3, 0),
    ]
    for vals, mask, want in cases:
        P = len(vals)
        got = int(agreed_commit(jnp.asarray([vals], jnp.int32),
                                jnp.asarray([mask]))[0])
        assert got == want, (vals, mask, got, want)


def test_evaluate_quorum_term_gate():
    # agreed=5 but term_start=6 -> not committable (§5.4.2)
    match = jnp.asarray([[5, 5, 5], [5, 5, 5]], jnp.int32)
    mask = jnp.ones((2, 3), bool)
    commit = jnp.asarray([2, 2], jnp.int32)
    term_start = jnp.asarray([6, 3], jnp.int32)
    out = np.asarray(evaluate_quorum(commit, match, mask, term_start))
    assert out.tolist() == [2, 5]


def test_evaluate_quorum_never_regresses():
    N, P = 128, 5
    match = rng.integers(0, 50, size=(N, P)).astype(np.int32)
    mask = np.ones((N, P), bool)
    commit = rng.integers(0, 60, size=N).astype(np.int32)
    ts = rng.integers(0, 60, size=N).astype(np.int32)
    out = np.asarray(evaluate_quorum(jnp.asarray(commit), jnp.asarray(match),
                                     jnp.asarray(mask), jnp.asarray(ts)))
    assert (out >= commit).all()


def test_election_quorum():
    granted = jnp.asarray([
        [True, True, False, False, False],   # 2/5 -> no
        [True, True, True, False, False],    # 3/5 -> yes
        [True, False, False, False, False],  # 1/1 voter -> yes
        [True, True, False, False, False],   # 2/3 voters -> yes
    ])
    mask = jnp.asarray([
        [True] * 5,
        [True] * 5,
        [True, False, False, False, False],
        [True, True, True, False, False],
    ])
    out = np.asarray(election_quorum(granted, mask))
    assert out.tolist() == [False, True, True, True]


def test_update_match_next_fold():
    match = jnp.asarray([[3, 0, 7]], jnp.int32)
    nxt = jnp.asarray([[4, 1, 8]], jnp.int32)
    success = jnp.asarray([[True, False, True]])
    r_last = jnp.asarray([[6, 9, 5]], jnp.int32)
    r_next = jnp.asarray([[7, 10, 6]], jnp.int32)
    m, n = update_match_next(match, nxt, success, r_last, r_next)
    assert np.asarray(m).tolist() == [[6, 0, 7]]   # only replied slots move
    assert np.asarray(n).tolist() == [[7, 1, 8]]   # max() never regresses


def test_kernels_jit_and_vmap():
    import jax
    f = jax.jit(evaluate_quorum)
    out = f(jnp.zeros((16,), jnp.int32),
            jnp.ones((16, 5), jnp.int32) * 3,
            jnp.ones((16, 5), bool),
            jnp.ones((16,), jnp.int32))
    assert np.asarray(out).tolist() == [3] * 16


def _numpy_quorum(commit, match, voter, term_start):
    """An independent fold, lane by lane: sort the voters' match
    indexes, take the majority position, and hold the commit where
    that median does not pass it or lies below ``term_start``."""
    out = commit.copy()
    for i in range(len(commit)):
        voters = np.sort(match[i][voter[i]])[::-1]
        median = voters[len(voters) // 2]
        if median > commit[i] and median >= term_start[i]:
            out[i] = median
    return out


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("n,p", [(64, 3), (200, 5), (1024, 7), (513, 2)])
def test_evaluate_quorum_matches_a_numpy_reference(seed, n, p):
    """The fold the step runs (``_step`` stage 4), under partial voter
    masks and the term gate together."""
    r = np.random.default_rng(seed)
    commit = r.integers(0, 50, size=(n,)).astype(np.int32)
    match = r.integers(0, 100, size=(n, p)).astype(np.int32)
    voter = r.random((n, p)) < 0.8
    voter[:, 0] = True    # a lane without voters is padding
    tstart = r.integers(0, 80, size=(n,)).astype(np.int32)
    got = evaluate_quorum(jnp.asarray(commit), jnp.asarray(match),
                          jnp.asarray(voter), jnp.asarray(tstart))
    np.testing.assert_array_equal(
        np.asarray(got), _numpy_quorum(commit, match, voter, tstart))


def test_quorum_properties():
    """Commit never regresses; never advances past the agreed median;
    respects the term gate."""
    r = np.random.default_rng(7)
    n, p = 256, 5
    commit = r.integers(0, 40, size=(n,)).astype(np.int32)
    match = r.integers(0, 90, size=(n, p)).astype(np.int32)
    tstart = r.integers(0, 90, size=(n,)).astype(np.int32)
    out = np.asarray(evaluate_quorum(
        jnp.asarray(commit), jnp.asarray(match), jnp.ones((n, p), bool),
        jnp.asarray(tstart)))
    assert (out >= commit).all()
    med = np.sort(match, axis=1)[:, (p - 1) // 2]  # trunc(5/2)+1-th desc
    advanced = out > commit
    assert (out[advanced] == med[advanced]).all()
    assert (out[advanced] >= tstart[advanced]).all()
    # gate holds: where the median is below term_start, no advance
    blocked = (med > commit) & (med < tstart)
    assert (out[blocked] == commit[blocked]).all()
