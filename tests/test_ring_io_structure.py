"""What the chip's ring I/O (``ring_io="onehot"``) costs is held in
place (ISSUE 30): nothing in `_step` is larger than the ring, and a ring
sharded over ``lanes`` is appended to and read where it lives.  A fused
dispatch writes the ring once, after its rounds: its scan
carries no ring, and the placement's passes are counted."""
import functools
import re

import jax
import numpy as np
import pytest

from benchmarks.harness.machine import BodyCounterMachine
from harness import step_args, superstep_args
from ra_tpu.engine import LockstepEngine
from ra_tpu.engine.lockstep import _step, _superstep
from ra_tpu.models.stream import StreamLogMachine
from ra_tpu.parallel.mesh import (lane_mesh, shard_engine_state,
                                  superstep_block_shardings)

N, P, R, K, C = 64, 3, 1024, 16, 64
A = K + 2


def _engine(words=C):
    """An engine of ``words``-word commands: the ``ra_bench`` cells' 64,
    or the 28 of the stream, queue and record cells."""
    machine = BodyCounterMachine(slots=64) if words == C else \
        StreamLogMachine(message_words=words - 3, chunk=10, retention=40)
    eng = LockstepEngine(machine, N, P, ring_capacity=R, max_step_cmds=K,
                         ring_io="onehot")      # what a TPU resolves
    assert eng.state.ring.shape == (N, R, words) and eng.apply_window == A
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


#: rounds of a fused dispatch in the tests below
KF = 4


def _superstep_jaxpr(eng):
    return jax.make_jaxpr(functools.partial(
        _superstep, durable=True, **eng._step_kwargs))(
            *superstep_args(eng, k=KF)).jaxpr


def _under(eqn, scope):
    return scope in str(eqn.source_info.name_stack)


@pytest.mark.parametrize("words", [C, 28])
def test_nothing_in_the_superstep_is_larger_than_the_ring(words):
    """The placement's one-hot has at most C columns a pass, and the
    scan's carry holds no [N, R, C] value: the rounds only read the
    ring, which the scan takes as a constant."""
    eng = _engine(words)
    jaxpr = _superstep_jaxpr(eng)
    ring = (N, R, words)
    values = set(_values(jaxpr))
    assert (ring, "int32") in values
    assert max(int(np.prod(shape)) for shape, _ in values) == N * R * words
    assert all(np.dtype(dt).itemsize <= 4
               for shape, dt in values if shape == ring)
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    carry = scan.outvars[:scan.params["num_carry"]]
    assert len(carry) > 30
    assert all(tuple(v.aval.shape) != ring for v in carry)


@pytest.mark.parametrize("words", [C, 28])
def test_the_superstep_writes_the_ring_once_after_its_rounds(words):
    """Under ``ra.s1_append`` the dispatch merges a ring-sized result
    into the ring ceil(K*Kc / C) times, no more than K, all after the
    scan: one pass at 64 words (4 x 16 rows = 64 columns), three at
    28.  Inside the scan nothing under that scope is ring-sized."""
    eng = _engine(words)
    jaxpr = _superstep_jaxpr(eng)
    ring = (N, R, words)
    merges = [e for e in jaxpr.eqns if _under(e, "ra.s1_append")
              and e.primitive.name in ("jit", "pjit", "select_n")
              and [tuple(v.aval.shape) for v in e.outvars] == [ring]]
    assert len(merges) == min(-(-KF * K // words), KF) == \
        {C: 1, 28: 3}[words]
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    assert any(_under(e, "ra.s1_append") for e in body.eqns)
    inside = [(e.primitive.name, tuple(v.aval.shape))
              for e in body.eqns if _under(e, "ra.s1_append")
              for v in e.outvars if hasattr(v.aval, "shape")]
    assert all(shape != ring for _p, shape in inside), inside


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
