"""What the chip's ring I/O (``ring_io="onehot"``) costs is held in
place (ISSUE 30): nothing in `_step` is larger than the ring, and a ring
sharded over ``lanes`` is appended to and read where it lives."""
import functools
import re

import jax
import numpy as np
import pytest

from benchmarks.harness.machine import BodyCounterMachine
from harness import step_args, superstep_args
from ra_tpu.engine import LockstepEngine
from ra_tpu.engine.lockstep import _step
from ra_tpu.parallel.mesh import (lane_mesh, shard_engine_state,
                                  superstep_block_shardings)

N, P, R, K, C = 64, 3, 1024, 16, 64
A = K + 2


def _engine():
    eng = LockstepEngine(BodyCounterMachine(slots=64), N, P,
                         ring_capacity=R, max_step_cmds=K,
                         ring_io="onehot")      # what a TPU resolves
    assert eng.state.ring.shape == (N, R, C) and eng.apply_window == A
    return eng


def _values(jaxpr):
    """(shape, dtype) of every value a jaxpr computes, sub-jaxprs
    included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield tuple(var.aval.shape), str(var.aval.dtype)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _values(sub)


def test_nothing_in_the_step_is_larger_than_the_ring():
    eng = _engine()
    closed = jax.make_jaxpr(functools.partial(
        _step, durable=True, **eng._step_kwargs))(*step_args(eng))
    values = set(_values(closed.jaxpr))
    assert ((N, R, C), "int32") in values     # the appended ring itself
    assert ((N, A, C), "int32") in values     # the window read
    assert max(int(np.prod(shape)) for shape, _ in values) == N * R * C
    # and nothing ring-shaped is wider than the ring's own words
    assert all(np.dtype(dt).itemsize <= 4
               for shape, dt in values if shape == (N, R, C))


_COLLECTIVE = re.compile(
    r"= (.+?) (all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start)?\(")


def test_a_sharded_ring_is_appended_to_and_read_on_its_own_device():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four forced CPU devices")
    eng = _engine()
    eng._compile_step(durable=True)
    mesh = shard_engine_state(eng, lane_mesh(devices[:4], member_axis=1))
    sh = superstep_block_shardings(mesh)
    args = list(superstep_args(eng, k=2))
    for i, key in ((1, "n_new"), (2, "payloads"), (6, "query"),
                   (7, "n_read"), (8, "read_q")):
        args[i] = jax.device_put(args[i], sh[key])
    text = eng._sstep.lower(*args).compile().as_text()
    assert "ra.s1_append" in text and "ra.s5_apply" in text
    ring_on_a_device = (N // 4) * R * C
    found = []
    for line in text.splitlines():
        m = _COLLECTIVE.search(line)
        if not m:
            continue
        elems = max(int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\[([\d,]*)\]", m.group(1)))
        scope = re.search(r'op_name="([^"]*)"', line)
        found.append((m.group(2), elems, scope.group(1) if scope else ""))
    # the one value of stage 5 that crosses chips is the lane path's
    # predicate "every active member shares its lane's interval"
    # (ISSUE 34): one element a round
    assert [(op, elems) for op, elems, scope in found
            if "ra.s5_apply" in scope] == [("all-reduce", 1)], found
    for op, elems, scope in found:
        assert elems < ring_on_a_device, (op, elems, scope)
        assert "ra.s1_append" not in scope, (op, elems, scope)
