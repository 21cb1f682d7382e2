"""Pallas quorum kernel vs the jnp oracle.  The suite is a CPU harness
(tests/conftest.py), so the kernel runs under the interpreter here, by
explicit argument; chip_smoke.py checks the compiled kernel on the
chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu.ops.pallas_quorum import (evaluate_quorum_pallas,
                                      make_evaluate_quorum)
from ra_tpu.ops.quorum import evaluate_quorum

ON_TPU = jax.default_backend() == "tpu"
INTERPRET = not ON_TPU


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("n,p", [(64, 3), (200, 5), (1024, 7), (513, 2)])
def test_pallas_matches_oracle(seed, n, p):
    rng = np.random.default_rng(seed)
    commit = jnp.asarray(rng.integers(0, 50, size=(n,)), jnp.int32)
    match = jnp.asarray(rng.integers(0, 100, size=(n, p)), jnp.int32)
    voter = jnp.asarray(rng.random((n, p)) < 0.8)
    # guarantee at least one voter per lane (lanes without voters are
    # padding in practice)
    voter = voter.at[:, 0].set(True)
    tstart = jnp.asarray(rng.integers(0, 80, size=(n,)), jnp.int32)
    want = evaluate_quorum(commit, match, voter, tstart)
    got = evaluate_quorum_pallas(commit, match, voter, tstart,
                                 interpret=INTERPRET)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quorum_properties():
    """Commit never regresses; never advances past the agreed median;
    respects the term gate."""
    rng = np.random.default_rng(7)
    n, p = 256, 5
    commit = jnp.asarray(rng.integers(0, 40, size=(n,)), jnp.int32)
    match = jnp.asarray(rng.integers(0, 90, size=(n, p)), jnp.int32)
    voter = jnp.ones((n, p), bool)
    tstart = jnp.asarray(rng.integers(0, 90, size=(n,)), jnp.int32)
    out = np.asarray(evaluate_quorum_pallas(commit, match, voter, tstart,
                                            interpret=INTERPRET))
    commit_np = np.asarray(commit)
    match_np = np.asarray(match)
    tstart_np = np.asarray(tstart)
    assert (out >= commit_np).all()
    med = np.sort(match_np, axis=1)[:, (p - 1) // 2]  # trunc(5/2)+1-th desc
    advanced = out > commit_np
    assert (out[advanced] == med[advanced]).all()
    assert (out[advanced] >= tstart_np[advanced]).all()
    # gate holds: where the median is below term_start, no advance
    blocked = (med > commit_np) & (med < tstart_np)
    assert (out[blocked] == commit_np[blocked]).all()


def test_make_evaluate_quorum_resolution():
    fn = make_evaluate_quorum("xla")
    assert fn is not None
    fn2 = make_evaluate_quorum("auto")
    commit = jnp.zeros((8,), jnp.int32)
    match = jnp.ones((8, 3), jnp.int32)
    voter = jnp.ones((8, 3), bool)
    tstart = jnp.ones((8,), jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(commit, match, voter,
                                                tstart)),
                                  np.ones(8, np.int32))
    if not ON_TPU:
        # auto resolves to the xla path off-TPU and must agree
        np.testing.assert_array_equal(
            np.asarray(fn2(commit, match, voter, tstart)),
            np.ones(8, np.int32))


def test_auto_resolves_to_xla_unless_env_gated(monkeypatch):
    """The kernel is a demoted experiment (it trailed the compiler on
    the round-5 chip record): 'auto' resolves to the XLA oracle on
    EVERY backend unless RA_TPU_ENABLE_PALLAS_QUORUM opts back in."""
    from ra_tpu.ops.quorum import evaluate_quorum as xla_impl

    monkeypatch.delenv("RA_TPU_ENABLE_PALLAS_QUORUM", raising=False)
    assert make_evaluate_quorum("auto") is xla_impl
    monkeypatch.setenv("RA_TPU_ENABLE_PALLAS_QUORUM", "0")
    assert make_evaluate_quorum("auto") is xla_impl
    monkeypatch.setenv("RA_TPU_ENABLE_PALLAS_QUORUM", "1")
    fn = make_evaluate_quorum("auto")
    if ON_TPU:
        assert fn is not xla_impl     # env gate re-enables the kernel
    else:
        assert fn is xla_impl         # off-TPU auto stays on the oracle
    # an explicit 'pallas' choice wins where the kernel can compile,
    # gate or no gate; elsewhere it raises — it never interprets
    monkeypatch.delenv("RA_TPU_ENABLE_PALLAS_QUORUM", raising=False)
    if ON_TPU:
        assert make_evaluate_quorum("pallas") is not xla_impl
    else:
        with pytest.raises(ValueError, match="needs a TPU backend"):
            make_evaluate_quorum("pallas")
