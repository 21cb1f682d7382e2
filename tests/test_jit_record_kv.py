"""``JitRecordKvMachine`` (PR 32): the record store against the plain
reference of the YCSB kit on seeded data, at sizes a CPU test holds.

The machine's one-command ``jit_apply``, its batch fold and a numpy loop
written here agree on every window (repeated keys and fields, masked
positions, keys and fields out of range); ``ver`` / ``sum`` are the
reference's fold and every field holds what the reference allows; the
loaded table is the reference's; a read returns the whole record; a read
through the engine's read plane returns an acknowledged update at reply
width 1 + F*W; and such a record round-trips through a ``READ_REPLY``
frame, which refuses a width its header cannot carry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.kits.ycsb_kv import reference
from ra_tpu.engine import LockstepEngine
from ra_tpu.models import JitRecordKvMachine
from ra_tpu.wire import framing

S, F, W = 12, 3, 4          # records, fields, words a field
FW = F * W
SEED = 7


def _machine(**kw):
    return JitRecordKvMachine(records=S, fields=F, field_words=W,
                              seed=SEED, **kw)


def _window(rng, batch, a, *, repeat):
    """Commands [*batch, A, 3+W] and a mask: ops 0..2 (2 is no op of
    the machine's), keys and fields from one short range (``repeat``:
    many positions of a window hit one field) or over the whole store
    and beyond it on both sides."""
    cmds = np.zeros(batch + (a, 3 + W), np.int32)
    cmds[..., 0] = rng.integers(0, 3, batch + (a,))
    if repeat:
        cmds[..., 1] = rng.integers(0, 2, batch + (a,))
        cmds[..., 2] = rng.integers(0, 2, batch + (a,))
    else:
        cmds[..., 1] = rng.integers(-2, S + 2, batch + (a,))
        cmds[..., 2] = rng.integers(-1, F + 1, batch + (a,))
    cmds[..., 3:] = rng.integers(0, 1 << 31, batch + (a, W))
    return cmds, rng.random(batch + (a,)) < 0.7


def _numpy_fold(state, cmds, mask):
    """The window applied in order, one command at a time, in numpy."""
    rec, ver, tot = (np.array(state[k]) for k in ("rec", "ver", "sum"))
    flat_r = rec.reshape((-1, S, FW))
    flat_v, flat_t = ver.reshape((-1, S)), tot.reshape((-1, S))
    c, m = cmds.reshape((-1,) + cmds.shape[-2:]), mask.reshape(
        (-1, mask.shape[-1]))
    for b in range(len(c)):
        for a in range(c.shape[1]):
            op, key, field = (int(x) for x in c[b, a, :3])
            if not m[b, a] or op != 1 or not 0 <= key < S \
                    or not 0 <= field < F:
                continue
            flat_r[b, key, field * W:(field + 1) * W] = c[b, a, 3:]
            flat_v[b, key] += 1
            flat_t[b, key] = reference.wrap32(
                np.int64(flat_t[b, key]) + np.int64(c[b, a, 3]))
    return {"rec": rec, "ver": ver, "sum": tot}


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


@pytest.mark.parametrize("batch", [(5,), (3, 2)], ids=["lanes", "members"])
@pytest.mark.parametrize("repeat", [True, False],
                         ids=["repeated", "out_of_range"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_batch_fold_is_the_one_by_one_apply_is_a_numpy_loop(
        seed, repeat, batch):
    rng = np.random.default_rng([seed, repeat, len(batch)])
    m = _machine()
    init = m.jit_init(batch[0])
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(
            x.reshape(x.shape[:1] + (1,) * (len(batch) - 1) + x.shape[1:]),
            batch + x.shape[1:]), init)
    for a in (9, 1):                    # two windows, the second on the first
        cmds, mask = _window(rng, batch, a, repeat=repeat)
        want = _numpy_fold(state, cmds, mask)
        folded = jax.jit(m.jit_apply_batch)({}, jnp.asarray(cmds),
                                            jnp.asarray(mask), state)
        single = _one_by_one(m, state, cmds, mask)
        for leaf in ("rec", "ver", "sum"):
            assert np.array_equal(np.asarray(folded[leaf]), want[leaf]), leaf
            assert np.array_equal(np.asarray(single[leaf]), want[leaf]), leaf
        state = folded
    assert int(np.asarray(state["ver"]).sum()) > 0


@pytest.mark.parametrize("chunk", [1, 4, 256])
def test_a_window_of_more_updates_than_a_pass_holds_takes_more_passes(chunk):
    rng = np.random.default_rng(chunk)
    m = _machine()
    m.CHUNK = chunk
    state = m.jit_init(4)
    cmds, mask = _window(rng, (4,), 11, repeat=True)
    cmds[..., 0] = 1
    got = jax.jit(m.jit_apply_batch)({}, jnp.asarray(cmds),
                                     jnp.asarray(mask), state)
    want = _numpy_fold(state, cmds, mask)
    assert all(np.array_equal(np.asarray(got[k]), want[k]) for k in want)
    assert int(mask.sum()) > 4 * 4      # more updates than one pass of 4


@pytest.mark.parametrize("seed", [3, 4])
def test_the_fold_agrees_with_the_kit_s_reference(seed):
    """``ver`` and ``sum`` are the reference's fold of the window's
    updates, and every field holds the loaded value or its last writer
    (the updates "sent" and "acknowledged" in the window's order)."""
    rng = np.random.default_rng(seed)
    n, a = 6, 14
    m = _machine()
    lane = np.repeat(np.arange(n), a)
    key = rng.integers(0, S, n * a)
    field = rng.integers(0, F, n * a)
    sess, op_id = lane * 3 + rng.integers(0, 3, n * a), np.arange(n * a) + 1
    salt = rng.integers(0, 1 << 31, n * a)
    cmds = np.zeros((n, a, 3 + W), np.int32)
    cmds[..., 0] = 1
    cmds[..., 1], cmds[..., 2] = key.reshape(n, a), field.reshape(n, a)
    cmds[..., 3:] = reference.value_words(sess, op_id, salt, W) \
        .reshape(n, a, W)
    got = jax.jit(m.jit_apply_batch)(
        {}, jnp.asarray(cmds), jnp.ones((n, a), bool), m.jit_init(n))
    want = reference.fold(n, S, lane=lane, key=key, op_id=op_id)
    assert np.array_equal(np.asarray(got["ver"]), want["ver"])
    assert np.array_equal(np.asarray(got["sum"]), want["sum"])
    t = np.arange(n * a, dtype=np.float64)
    ups = reference.Updates(S, F, W, lane=lane, key=key, field=field,
                            sess=sess, op_id=op_id, salt=salt, sent=t,
                            acked=t + 0.5)
    rec = np.asarray(got["rec"])
    assert reference.field_counts(SEED, ups, rec) == \
        {"fields_unknown": 0, "fields_stale": 0}
    # a field put back to what it was loaded with is stale, one set to
    # anything else unknown
    first = (int(lane[0]), int(key[0]), int(field[0]))
    cols = slice(first[2] * W, (first[2] + 1) * W)
    bad = rec.copy()
    bad[first[0], first[1], cols] = reference.loaded(SEED, n, S, FW)[
        first[0], first[1], cols]
    assert reference.field_counts(SEED, ups, bad) == \
        {"fields_unknown": 0, "fields_stale": 1}
    bad[first[0], first[1], cols] += 1
    assert reference.field_counts(SEED, ups, bad)["fields_unknown"] == 1


@pytest.mark.parametrize("lanes", [1, 5])
def test_the_loaded_table_is_the_reference_s_and_the_same_again(lanes):
    m = _machine()
    a, b = m.jit_init(lanes), m.jit_init(lanes)
    want = reference.loaded(SEED, lanes, S, FW)
    assert np.array_equal(np.asarray(a["rec"]), want)
    assert np.array_equal(np.asarray(b["rec"]), want)
    assert want.min() >= 0 and len(np.unique(want)) > want.size * 0.99
    assert not np.asarray(a["ver"]).any() and not np.asarray(a["sum"]).any()
    other = JitRecordKvMachine(records=S, fields=F, field_words=W,
                               seed=SEED + 1).jit_init(lanes)
    assert not np.array_equal(np.asarray(other["rec"]), want)
    rows = reference.loaded_rows(SEED, np.arange(lanes) % lanes,
                                 np.arange(lanes) % S, FW)
    assert np.array_equal(rows, want[np.arange(lanes), np.arange(lanes) % S])


def test_a_read_returns_the_whole_record_and_nothing_out_of_range():
    m = _machine()
    state = m.jit_init(3)
    q = np.array([[[1, 0], [1, S - 1], [1, S], [1, -1], [0, 2], [2, 2]]] * 3,
                 np.int32)
    got = np.asarray(jax.jit(m.jit_query)(jnp.asarray(q), state))
    assert got.shape == (3, 6, 1 + FW)
    rec = np.asarray(state["rec"])
    assert (got[:, 0, 0] == 1).all() and (got[:, 1, 0] == 1).all()
    assert np.array_equal(got[:, 0, 1:], rec[:, 0])
    assert np.array_equal(got[:, 1, 1:], rec[:, S - 1])
    assert not got[:, 2:].any()
    assert m.query_reply_spec == ("int32", (1 + FW,))
    assert m.command_spec == ("int32", (3 + W,))
    present, words = m.decode_query_reply(got[1, 0])
    assert present == 1 and np.array_equal(words, rec[1, 0])


def test_the_host_protocol_encodes_an_update_and_a_read():
    m = _machine()
    cmd = np.asarray(m.encode_command(("update", 3, 2, [9, 8, 7, 6])))
    assert cmd.tolist() == [1, 3, 2, 9, 8, 7, 6]
    assert not np.asarray(m.encode_command(("update", 3, 2, [1]))).any()
    assert not np.asarray(m.encode_command("nonsense")).any()
    assert np.asarray(m.encode_query(("read", 5))).tolist() == [1, 5]
    state, reply = m.jit_apply({}, jnp.asarray(cmd), jax.tree.map(
        lambda x: x[0], m.jit_init(1)))
    assert m.decode_reply(reply) == (1, 1)
    assert np.asarray(state["rec"])[3, 2 * W:3 * W].tolist() == [9, 8, 7, 6]
    _, reply = m.jit_apply({}, jnp.asarray(cmd).at[1].set(S), state)
    assert m.decode_reply(reply) == (-2, None)


def test_a_read_registered_after_an_acknowledged_update_returns_it():
    """Through ``LockstepEngine``'s read plane: the update commits and
    applies, then a read of its key comes back with the update's words
    in the field and the loaded words in the others, at reply width
    1 + F*W."""
    m = JitRecordKvMachine(records=S, fields=10, field_words=25, seed=SEED)
    eng = LockstepEngine(m, 4, 3, ring_capacity=64, max_step_cmds=4,
                         max_step_reads=2)
    assert eng.query_reply_width == 251 and eng.payload_width == 28
    value = np.arange(100, 125, dtype=np.int32)
    pay = np.zeros((4, 4, 28), np.int32)
    pay[2, 0] = np.asarray(m.encode_command(("update", 5, 7, value)))
    n_new = np.array([0, 0, 1, 0], np.int32)
    eng.step(n_new, pay)
    for _ in range(4):                  # commit and apply on every member
        eng.step(np.zeros(4, np.int32), np.zeros_like(pay))
    assert int(np.asarray(eng.state.total_committed)[2]) >= 1
    lanes = np.array([2, 1])
    replies, wm, ok = eng.read_lanes(
        lanes, np.array([[1, 5], [1, 5]], np.int32))
    assert ok.all() and (wm >= 0).all() and replies.shape == (2, 251)
    loaded = reference.loaded_rows(SEED, lanes, [5, 5], 250)
    assert (replies[:, 0] == 1).all()
    assert np.array_equal(replies[1, 1:], loaded[1])
    assert np.array_equal(replies[0, 1 + 175:1 + 200], value)
    untouched = np.r_[0:175, 200:250]
    assert np.array_equal(replies[0, 1:][untouched], loaded[0][untouched])
    st = eng.state.mac
    assert np.asarray(st["ver"])[2, :, 5].tolist() == [1, 1, 1]
    assert np.asarray(st["sum"])[2, :, 5].tolist() == [100, 100, 100]


def test_a_record_s_reply_round_trips_through_a_read_reply_frame():
    rng = np.random.default_rng(0)
    pay = rng.integers(0, 1 << 31, (5, 251)).astype(np.int32)
    frame = framing.encode_read_reply(
        np.arange(5), np.arange(5) + 10, np.zeros(5, np.uint8),
        np.arange(5) + 100, pay)
    t, body, end = framing.read_frame(frame)
    assert t == framing.T_READ_REPLY and end == len(frame)
    rec = framing.decode_read_reply(body)
    assert rec.dtype == framing.read_reply_dtype(251)
    assert np.array_equal(rec["pay"], pay)
    assert rec["seqno"].tolist() == [10, 11, 12, 13, 14]
    assert rec["wm"].tolist() == [100, 101, 102, 103, 104]


@pytest.mark.parametrize("width", [256, 300])
def test_a_reply_wider_than_the_header_s_byte_is_refused_not_truncated(
        width):
    with pytest.raises(ValueError, match="reply width .* one byte"):
        framing.encode_read_reply([0], [1], [0], [0],
                                  np.zeros((1, width), np.int32))
    # 255 words, the widest the header carries, still frames
    frame = framing.encode_read_reply([0], [1], [0], [0],
                                      np.ones((1, 255), np.int32))
    rec = framing.decode_read_reply(framing.read_frame(frame)[1])
    assert rec["pay"].shape == (1, 255) and rec["pay"].all()
