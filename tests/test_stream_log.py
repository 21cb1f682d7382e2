"""``StreamLogMachine``: the partitioned stream against the plain
reference of the ``stream_log`` kit on seeded data, at sizes a CPU test
holds; the table writer it shares with ``JitRecordKvMachine``; the
apply stage's roofline reader; and the benchmark's stream cell
rehearsed at 12 partitions.

The machine's one-command ``jit_apply``, the engine's sequential
window fold, its one-pass batch fold and a numpy loop written here
agree on every window of appends, offset stores and truncations
(masked positions, bad groups, negative lags, windows wider than the
retention); a chunk read is the reference's, across the retention's
wrap and near the base; every operation the fold and the read lower to
carries its stage's scope.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks import manifest as mf
from benchmarks import run as br
from benchmarks.harness.kits.stream_log import reference
from ra_tpu.engine import LockstepEngine
from ra_tpu.models import JitRecordKvMachine, StreamLogMachine

W, C, Q, G = 3, 4, 12, 2        # words a message, chunk, retention, groups
SEED = 9


def _machine():
    return StreamLogMachine(message_words=W, chunk=C, retention=Q, groups=G,
                            seed=SEED)


def _state(rng, batch):
    """A reachable state: every replica's log loaded and then appended
    to (random words), tails past the retention's first wrap."""
    m = _machine()
    init = m.jit_init(batch[0])
    state = jax.tree.map(
        lambda x: np.array(jnp.broadcast_to(
            x.reshape(x.shape[:1] + (1,) * (len(batch) - 1) + x.shape[1:]),
            batch + x.shape[1:])), init)
    tail = Q + rng.integers(0, 3 * Q, batch)
    state["tail"] = tail.astype(np.int32)
    state["base"] = (tail - Q + rng.integers(0, 3, batch)).astype(np.int32)
    state["cursors"] = rng.integers(0, Q, batch + (G,)).astype(np.int32)
    state["log"] = rng.integers(0, 1 << 31, state["log"].shape) \
        .astype(np.int32)
    return state


def _window(rng, batch, a):
    """Commands [*batch, A, 3+W] and a mask: ops 0..4 (4 is no op of the
    machine's), groups one past each end, lags from -1 to past the
    retention."""
    cmds = np.zeros(batch + (a, 3 + W), np.int32)
    cmds[..., 0] = rng.choice([0, 1, 1, 1, 2, 2, 3, 4], batch + (a,))
    cmds[..., 1] = np.where(cmds[..., 0] == 3,
                            rng.integers(-1, 2 * Q, batch + (a,)),
                            rng.integers(-1, G + 1, batch + (a,)))
    cmds[..., 2] = rng.integers(-1, 2 * Q, batch + (a,))
    cmds[..., 3:] = rng.integers(0, 1 << 31, batch + (a, W))
    return cmds, rng.random(batch + (a,)) < 0.8


def _numpy_fold(state, cmds, mask):
    """The window applied in order, one command at a time, in numpy."""
    log, tail, base, cur = (np.array(state[k]) for k in
                            ("log", "tail", "base", "cursors"))
    flat = log.reshape((-1, Q, W))
    t, b, c = tail.reshape(-1), base.reshape(-1), cur.reshape((-1, G))
    cm, mk = cmds.reshape((-1,) + cmds.shape[-2:]), mask.reshape(
        (-1, mask.shape[-1]))
    for lane in range(len(cm)):
        for a in range(cm.shape[1]):
            op, x, y = (int(v) for v in cm[lane, a, :3])
            if not mk[lane, a]:
                continue
            if op == 1:
                flat[lane, t[lane] % Q] = cm[lane, a, 3:]
                t[lane] += 1
            elif op == 2 and 0 <= x < G and y >= 0:
                c[lane, x] = max(c[lane, x], t[lane] - y)
            elif op == 3 and x >= 0:
                b[lane] = max(b[lane], t[lane] - x)
            b[lane] = max(b[lane], t[lane] - Q)
    return {"log": log, "tail": tail, "base": base, "cursors": cur}


def _one_by_one(m, state, cmds, mask):
    """``jit_apply`` over the window's positions, masked."""
    for a in range(cmds.shape[-2]):
        new, _reply = m.jit_apply({}, jnp.asarray(cmds[..., a, :]), state)
        do = jnp.asarray(mask[..., a])
        state = jax.tree.map(
            lambda n, o: jnp.where(
                do.reshape(do.shape + (1,) * (n.ndim - do.ndim)), n, o),
            new, state)
    return state


def _equal(got, want):
    for leaf in ("log", "tail", "base", "cursors"):
        assert np.array_equal(np.asarray(got[leaf]), want[leaf]), leaf


@pytest.mark.parametrize("batch", [(5,), (3, 2)], ids=["lanes", "members"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_one_pass_fold_is_the_sequential_fold_and_a_numpy_loop(
        seed, batch):
    rng = np.random.default_rng([seed, len(batch)])
    m = _machine()
    state = _state(rng, batch)
    idx = np.broadcast_to(np.arange(1, 19), batch + (18,))
    for a in (18, 5, 1):     # a window wider than the retention, then more
        cmds, mask = _window(rng, batch, a)
        want = _numpy_fold(state, cmds, mask)
        meta = {"index": jnp.asarray(idx[..., :a]),
                "term": jnp.ones(batch + (1,), jnp.int32)}
        folded = jax.jit(m.jit_apply_batch)(meta, jnp.asarray(cmds),
                                            jnp.asarray(mask), state)
        _equal(folded, want)
        _equal(m.sequential_window_fold(meta, jnp.asarray(cmds),
                                        jnp.asarray(mask), state), want)
        _equal(_one_by_one(m, state, cmds, mask), want)
        state = jax.tree.map(np.asarray, folded)


@pytest.mark.parametrize("chunk", [1, 4, 256])
def test_a_window_of_more_appends_than_a_pass_holds_takes_more_passes(chunk):
    rng = np.random.default_rng(chunk)
    m = _machine()
    m.CHUNK = chunk
    state = _state(rng, (4,))
    cmds, mask = _window(rng, (4,), 11)
    cmds[..., 0] = 1
    got = jax.jit(m.jit_apply_batch)({}, jnp.asarray(cmds),
                                     jnp.asarray(mask), state)
    _equal(got, _numpy_fold(state, cmds, mask))
    assert int(mask.sum()) > 4 * 4      # more appends than one pass of 4


def test_the_fold_has_no_sequential_branch():
    """One pass for the whole vocabulary: no cond, and no scan over the
    window's positions, whatever the window holds."""
    m = _machine()
    state = _state(np.random.default_rng(3), (3, 2))
    cmds, mask = _window(np.random.default_rng(4), (3, 2), 9)
    text = str(jax.make_jaxpr(m.jit_apply_batch)(
        {}, jnp.asarray(cmds), jnp.asarray(mask), state))
    assert "cond[" not in text and "scan[" not in text
    assert "while[" in text             # the table writer's passes


@pytest.mark.parametrize("seed", [5, 6])
def test_a_chunk_read_is_the_reference_s_at_the_wrap_and_near_the_base(seed):
    rng = np.random.default_rng(seed)
    m = _machine()
    n, kr = 4, 9
    state = _state(rng, (n,))
    state["tail"] = np.array([Q, Q + C - 1, 3 * Q + 1, 5 * Q - 2], np.int32)
    state["base"] = (state["tail"] - Q + np.array([0, 0, 3, Q - 2])) \
        .astype(np.int32)
    lag = np.concatenate([np.zeros((n, 1)), np.full((n, 1), -1),
                          np.full((n, 1), Q), np.full((n, 1), Q - 1),
                          rng.integers(0, Q + 2, (n, kr - 4))],
                         axis=1).astype(np.int32)
    q = np.stack([np.ones_like(lag), lag], axis=-1)
    q[0, -1, 0] = 2                             # no op of the machine's
    got = np.asarray(jax.jit(m.jit_query)(jnp.asarray(q), state))
    assert got.shape == (n, kr, 2 + C * W)
    lane = np.repeat(np.arange(n), kr)
    want = reference.chunk_reply(
        state["log"][lane], state["tail"][lane], state["base"][lane],
        np.where(q[..., 0] == 1, lag, -1).reshape(-1), retention=Q,
        chunk=C, words=W).reshape(got.shape)
    assert np.array_equal(got, want)
    # lag 0 reads nothing at the tail; a lag past the base reads from it
    assert (got[:, 0, 0] == 0).all() and (got[:, 0, 1] == state["tail"]).all()
    assert (got[:, 2, 1] == state["base"]).all()
    assert (got[:, 1, :2] == [0, -1]).all() and not got[:, 1, 2:].any()
    first, msgs = m.decode_query_reply(got[0, 3])
    assert first == int(state["tail"][0]) - (Q - 1)
    assert np.array_equal(msgs, state["log"][0].reshape(Q, W)[
        (first + np.arange(C)) % Q])


def test_the_loaded_log_is_the_reference_s_and_the_stream_starts_full():
    m = _machine()
    st = m.jit_init(3)
    msgs = np.asarray(st["log"]).reshape((3, Q, W))
    want = reference.loaded_messages(SEED, np.arange(3)[:, None],
                                     np.arange(Q)[None, :], W)
    assert np.array_equal(msgs, want)
    assert np.asarray(st["tail"]).tolist() == [Q] * 3
    assert not np.asarray(st["base"]).any()
    assert m.command_spec == ("int32", (3 + W,))
    assert m.query_reply_spec == ("int32", (2 + C * W,))
    with pytest.raises(ValueError, match="whole number of chunks"):
        StreamLogMachine(message_words=W, chunk=5, retention=12)


def test_the_host_protocol_encodes_every_command_and_a_read():
    m = _machine()
    body = [7, 8, 9]
    cmd = np.asarray(m.encode_command(("append", body)))
    assert cmd.tolist() == [1, 0, 0] + body
    assert np.asarray(m.encode_command(("store", 1, 4))).tolist() \
        == [2, 1, 4, 0, 0, 0]
    assert np.asarray(m.encode_command(("truncate", 3))).tolist() \
        == [3, 3, 0, 0, 0, 0]
    assert not np.asarray(m.encode_command("nonsense")).any()
    assert np.asarray(m.encode_query(("read", 5))).tolist() == [1, 5]
    one = jax.tree.map(lambda x: x[0], m.jit_init(1))
    state, reply = m.jit_apply({}, jnp.asarray(cmd), one)
    assert m.decode_reply(reply) == (1, Q)
    assert int(state["tail"]) == Q + 1 and int(state["base"]) == 1
    _, reply = m.jit_apply({}, m.encode_command(("store", 1, 4)), state)
    assert m.decode_reply(reply) == (1, Q + 1 - 4)
    _, reply = m.jit_apply({}, m.encode_command(("store", G, 4)), state)
    assert m.decode_reply(reply) == (-2, None)
    _, reply = m.jit_apply({}, m.encode_command(("truncate", 2)), state)
    assert m.decode_reply(reply) == (1, Q - 1)


def test_appends_and_a_read_through_the_read_plane():
    """Through ``LockstepEngine``: appends commit and apply on every
    member, then a read of the tail's chunk returns them after the
    loaded history's newest messages, at reply width 2 + 10 x 25."""
    m = StreamLogMachine(message_words=25, chunk=10, retention=40, seed=SEED)
    eng = LockstepEngine(m, 4, 3, ring_capacity=64, max_step_cmds=4,
                         max_step_reads=2)
    assert eng.query_reply_width == 252 and eng.payload_width == 28
    bodies = np.arange(75, dtype=np.int32).reshape(3, 25) + 100
    pay = np.zeros((4, 4, 28), np.int32)
    for j in range(3):
        pay[1, j] = np.asarray(m.encode_command(("append", bodies[j])))
    eng.step(np.array([0, 3, 0, 0], np.int32), pay)
    for _ in range(4):                  # commit and apply on every member
        eng.step(np.zeros(4, np.int32), np.zeros_like(pay))
    replies, wm, ok = eng.read_lanes(np.array([1, 2]),
                                     np.array([[1, 5], [1, 5]], np.int32))
    assert ok.all() and (wm >= 0).all() and replies.shape == (2, 252)
    assert replies[:, :2].tolist() == [[5, 38], [5, 35]]
    got = replies[0, 2:].reshape(10, 25)
    assert np.array_equal(got[:2], reference.loaded_messages(
        SEED, 1, [38, 39], 25))
    assert np.array_equal(got[2:5], bodies) and not got[5:].any()
    assert np.asarray(eng.state.mac["tail"])[1].tolist() == [43] * 3


def test_the_reference_judges_a_log_made_by_hand():
    """Two sessions append to one partition: the log the machine builds
    from them passes; a message held twice, two of a session's appends
    swapped, an unknown word, and a cursor outside its stores' range
    each fail their own count."""
    m = _machine()
    sess = np.array([4, 5, 4, 4, 5, 4, 4, 5, 4, 4, 4, 4, 5, 4])
    op_id = np.array([1, 1, 2, 3, 2, 4, 5, 3, 6, 7, 8, 9, 4, 10])
    salt = np.arange(len(sess)) * 7
    body = reference.append_words(sess, op_id, salt, W)
    cmds = np.zeros((1, len(sess), 3 + W), np.int32)
    cmds[0, :, 0], cmds[0, :, 3:] = 1, body
    st = jax.jit(m.jit_apply_batch)({}, jnp.asarray(cmds),
                                    jnp.ones((1, len(sess)), bool),
                                    m.jit_init(1))
    log, tail, base = (np.array(st[k]) for k in ("log", "tail", "base"))
    assert tail.tolist() == [Q + 14] and base.tolist() == [14]
    ups = reference.Appends(W, lane=np.zeros(len(sess)), sess=sess,
                            op_id=op_id, salt=salt)

    def counts(lg):
        return reference.held_counts(SEED, ups, lg, tail, base, retention=Q,
                                     words=W)

    assert counts(log) == {"messages_unknown": 0, "messages_duplicated": 0,
                           "appends_out_of_order": 0}
    msgs = log.reshape(Q, W)
    twice = msgs.copy()
    twice[(Q + 13) % Q] = twice[(Q + 12) % Q]
    assert counts(twice.reshape(log.shape))["messages_duplicated"] == 1
    swapped = msgs.copy()
    swapped[[(Q + 2) % Q, (Q + 3) % Q]] = swapped[[(Q + 3) % Q, (Q + 2) % Q]]
    assert counts(swapped.reshape(log.shape))["appends_out_of_order"] == 1
    bad = msgs.copy()
    bad[(Q + 6) % Q, 2] += 1
    assert counts(bad.reshape(log.shape))["messages_unknown"] == 1
    # a store sent after 3 appends were acknowledged and seen before the
    # 6th was sent, at lag 2: the cursor lies in [Q + 3 - 2, Q + 5 - 2]
    clocks = reference.Clocks(lane=np.zeros(6, int),
                              sent=np.arange(6.0), acked=np.arange(6.0) + 0.5)
    lo, hi = reference.cursor_bounds(1, G, Q, clocks, lane=[0], group=[1],
                                     lag=[2], fed=[3.0], seen=[5.0])
    assert lo.tolist() == [[0, Q + 1]] and hi.tolist() == [[0, Q + 3]]


# -- the table writer, shared with the record store --------------------------

def _record_fold_as_it_was(m, commands, mask, state):
    """``JitRecordKvMachine.jit_apply_batch`` as it stood before its
    writer was shared (kept here as the bits to hold it to)."""
    S, F, Wr = m.records, m.fields, m.field_words
    batch, A = mask.shape[:-1], mask.shape[-1]
    B = int(np.prod(batch))
    M = B * A
    rows = commands.reshape((M, 3 + Wr))
    ok, _key, _field = m._decode(rows)
    upd = mask.reshape((M,)) & ok
    total = jnp.sum(upd, dtype=jnp.int32)
    ch = min(m.CHUNK, M)
    order = jnp.pad(jnp.argsort(~upd, stable=True).astype(jnp.int32),
                    (0, -M % ch))
    slot = jnp.arange(ch, dtype=jnp.int32)

    def apply_chunk(carry):
        i, rec, ver, tot = carry
        pos = lax.dynamic_slice(order, (i * ch,), (ch,))
        c = rows[pos]
        _ok, k, f = m._decode(c)
        cell = (pos // A) * S + k
        at = jnp.where(i * ch + slot < total, cell, B * S)
        ver = ver.at[at].add(1, mode="drop")
        tot = tot.at[at].add(c[:, 3], mode="drop")

        def put(j, rec):
            return lax.dynamic_update_slice(
                rec, lax.dynamic_slice(c, (j, 3), (1, Wr)),
                (cell[j], f[j] * Wr))

        rec = lax.fori_loop(0, jnp.minimum(total - i * ch, ch), put, rec)
        return i + 1, rec, ver, tot

    _, rec, ver, tot = lax.while_loop(
        lambda c: c[0] * ch < total, apply_chunk,
        (jnp.int32(0), state["rec"].reshape((B * S, F * Wr)),
         state["ver"].reshape((B * S,)), state["sum"].reshape((B * S,))))
    return {"rec": rec.reshape(batch + (S, F * Wr)),
            "ver": ver.reshape(batch + (S,)), "sum": tot.reshape(batch + (S,))}


@pytest.mark.parametrize("chunk", [3, 256])
@pytest.mark.parametrize("batch", [(5,), (3, 2)], ids=["lanes", "members"])
def test_the_record_store_s_fold_gives_the_bits_it_gave_before(batch, chunk):
    rng = np.random.default_rng([chunk, len(batch)])
    m = JitRecordKvMachine(records=7, fields=3, field_words=4, seed=SEED)
    m.CHUNK = chunk
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(
            x.reshape(x.shape[:1] + (1,) * (len(batch) - 1) + x.shape[1:]),
            batch + x.shape[1:]), m.jit_init(batch[0]))
    for a in (13, 2):
        cmds = np.zeros(batch + (a, 7), np.int32)
        cmds[..., 0] = rng.integers(0, 3, batch + (a,))
        cmds[..., 1] = rng.integers(-1, 8, batch + (a,))
        cmds[..., 2] = rng.integers(0, 3, batch + (a,))
        cmds[..., 3:] = rng.integers(0, 1 << 31, batch + (a, 4))
        mask = jnp.asarray(rng.random(batch + (a,)) < 0.8)
        now = jax.jit(m.jit_apply_batch)({}, jnp.asarray(cmds), mask, state)
        was = jax.jit(lambda c, k, s: _record_fold_as_it_was(m, c, k, s))(
            jnp.asarray(cmds), mask, state)
        for leaf in ("rec", "ver", "sum"):
            assert np.array_equal(np.asarray(now[leaf]),
                                  np.asarray(was[leaf])), leaf
        state = now
    assert int(np.asarray(state["ver"]).sum()) > 0


# -- stage scopes ------------------------------------------------------------

def _op_names(jaxpr, outer=""):
    """(primitive, op_name) of every operation of a jaxpr and of the
    jaxprs inside it (a loop's body, a branch): the name stack each is
    lowered under, which becomes its op_name."""
    for eqn in jaxpr.eqns:
        here = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _op_names(sub, here)


def test_every_operation_of_the_fold_and_the_read_carries_its_stage():
    m = _machine()
    state = _state(np.random.default_rng(8), (6, 3))
    cmds, mask = _window(np.random.default_rng(9), (6, 3), 18)
    q = np.ones((6, 3, 4, 2), np.int32)

    def fold(c, k, s):
        with jax.named_scope("ra.s5_apply"):
            return m.jit_apply_batch({}, c, k, s)

    def read(qs, s):
        with jax.named_scope("ra.s5c_read"):
            return m.jit_query(qs, s)

    for fn, args, scope in (
            (fold, (cmds, mask, state), "ra.s5_apply"),
            (read, (q, state), "ra.s5c_read")):
        ops = list(_op_names(jax.make_jaxpr(fn)(*args).jaxpr))
        prims = {p for p, _name in ops}
        assert len(ops) > 20 and (scope != "ra.s5_apply"
                                  or {"while", "sort"} <= prims)
        assert [(p, n) for p, n in ops if scope not in n] == [], scope
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert f'"jit({fn.__name__})/{scope}/' in text


# -- the apply stage's roofline reader ---------------------------------------

def _reader():
    return br.load_reader("apply_fold_roofline")


class _Fleet:
    def __init__(self, kinds, acked):
        self.read_kind = np.array([False, True, False])
        self.op_kind = np.asarray(kinds, np.int8)
        self.op_acked = np.asarray(acked, np.float64)
        self.n_ops = len(kinds)


def _ctx(config, kinds, acked, stage_s, width=28, devices=1):
    run = argparse.Namespace(
        fleet=_Fleet(kinds, acked), trace_window=(10.0, 13.0),
        config=config, eng=argparse.Namespace(payload_width=width),
        _step_stages={"dispatches": 4, "total_s": 1.0,
                      "stages": {"ra.s5_apply": stage_s}})
    return argparse.Namespace(run=run, trace={"devices": devices},
                              device_kind="TPU v5 lite")


META = {"reader": "apply_fold_roofline", "stage": "ra.s5_apply"}


def test_the_fold_roofline_counts_acknowledged_writes_in_the_window():
    # appends, a read, a store; one append acknowledged before the trace,
    # one never
    kinds = [0, 0, 1, 2, 0, 0]
    acked = [11.0, 12.5, 11.5, 12.0, 9.0, np.nan]
    cfg = {"members": 3, "fold_write_bytes": 100}
    got = _reader()(_ctx(cfg, kinds, acked, 2e-6), META)
    least = 3 * (4 * 28 + 3 * 100)
    assert got == pytest.approx(100 * least / 819e9 / 2e-6)
    # a configuration without the key: the command's bytes alone
    got = _reader()(_ctx({"members": 3}, kinds, acked, 2e-6, width=64),
                    META)
    assert got == pytest.approx(100 * 3 * 4 * 64 / 819e9 / 2e-6)
    # four chips: the fleet's bytes divided over them
    four = _reader()(_ctx({"members": 3}, kinds, acked, 2e-6, width=64,
                          devices=4), META)
    assert four == pytest.approx(got / 4)


def test_the_fold_roofline_reads_0_with_no_write_and_nothing_with_no_stage():
    assert _reader()(_ctx({"members": 3}, [1, 1], [11.0, 12.0], 1e-3),
                     META) == 0.0
    assert _reader()(_ctx({"members": 3}, [0], [11.0], 0.0), META) is None
    ctx = _ctx({"members": 3}, [0], [11.0], 1e-3)
    ctx.run._step_stages = None         # a run on the CPU: no device step
    assert _reader()(ctx, META) is None


# -- the benchmark's cell, rehearsed -----------------------------------------

CELL = "stream_2k_x3.paced_stream"
MANIFEST = mf.committed()


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    orig = br.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[0] == "configs":
            d.update(clusters=12, retention=400)
        if parts[0] == "cells":
            d.update(warmup_s=0.5, rate_ops_per_s=900)
        if parts[0] == "traffic":
            d.update(warmup_s=0.5, trace_after_s=0.2, trace_s=0.5)
        return d

    monkeypatch.setattr(br, "load_json", load)
    monkeypatch.setattr(br, "RUN_ROOT", str(tmp_path / "bench_run"))


def _run(seed, **faults):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.5,
                              trace=0, override=[])
    rc, res = br.run_cell(args, MANIFEST, require_tpu=False, **faults)
    assert rc == 0
    return res


def _failed(res) -> set:
    return {k for k, v in res["compared"].items() if v["value"] > v["limit"]}


STATE_COUNTS = ("tail_wrong", "base_wrong", "messages_unknown",
                "messages_duplicated", "appends_out_of_order",
                "cursors_outside", "reads_wrong_messages",
                "replica_cells_wrong", "replicas_behind")


@pytest.mark.parametrize("seed", [2**31 + 39, 3])
def test_the_cell_runs_and_is_correct_at_12_partitions(tiny, seed):
    res = _run(seed)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 500 and res["failed"] == 0
    assert set(res["metrics"]) == {"commit_p50_ms", "commit_p95_ms",
                                   "setup_s"}
    assert all(v["limit"] == 0 for v in res["compared"].values())
    for tag in ("live", "reopen"):
        for count in STATE_COUNTS:
            assert res["compared"][f"{tag}_{count}"]["value"] == 0
    for count in ("reads_outside_consistency", "reads_negative_watermark",
                  "ops_never_acked", "acks_above_fsync",
                  "commit_above_fsync"):
        assert res["compared"][count]["value"] == 0


def test_an_altered_message_word_fails_the_held_messages(tiny):
    hit = []

    def tamper(idx, pay):
        app = np.flatnonzero(pay[:, 0] == 1)
        if len(app) and not hit:
            pay[app[0], 9] ^= 1             # a body word, not op id or session
            hit.append(1)

    res = _run(21, tamper=tamper)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert {"live_messages_unknown", "reopen_messages_unknown"} <= failed
    assert res["compared"]["live_messages_unknown"]["value"] == 1
    assert not {"live_tail_wrong", "reads_outside_consistency"} & failed


def test_a_dropped_append_fails_the_tail(tiny):
    hit = []

    def tamper(idx, pay):
        app = np.flatnonzero(pay[:, 0] == 1)
        if len(app) and not hit:
            pay[app[0], 0] = 0              # acknowledged, and a no-op
            hit.append(1)

    res = _run(22, tamper=tamper)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert {"live_tail_wrong", "reopen_tail_wrong"} <= failed
    assert res["compared"]["live_tail_wrong"]["value"] == 1
    assert "live_messages_unknown" not in failed


def test_a_stale_read_fails_the_read_consistency(tiny):
    hit = []

    def tamper_reply(rec):
        ok = np.flatnonzero(rec["status"] <= 1)
        if len(ok) and len(hit) < 3:
            rec["pay"][ok[0], 1] -= 1000    # a tail 1,000 appends back
            hit.append(1)

    res = _run(23, tamper_reply=tamper_reply)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert res["compared"]["reads_outside_consistency"]["value"] == len(hit)
    assert failed <= {"reads_outside_consistency", "live_reads_wrong_messages",
                      "reopen_reads_wrong_messages"}
