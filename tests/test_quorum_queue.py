"""``QuorumQueueMachine``: the quorum queue's batch fold against its own
one-command ``jit_apply`` and against the host ``FifoMachine``, the
plain reference, at sizes a CPU test holds (8 lanes x 3 members,
capacity 40, prefetch 4, windows of 16); its lowering; its delivery
read; and the stage scopes its fold and read run under.

The host machine is driven with the same operations, mapped onto its
own vocabulary:

* a publish is ``("enqueue", None, None, body)``, an untracked enqueue
  (no enqueuer dedup: the fleet's sessions dedup on the device's ring);
  the device's ticket is the host's ``msg_in_id``;
* a publish the device refuses (its slot ``ticket % capacity`` still
  holds a message: the queue holds ``capacity`` of them, or the one
  ``capacity`` tickets older is still checked out) is not sent: the host
  queue has no length limit;
* ``settle(c, n)`` and ``return(c, n)`` name the consumer's ``n``
  oldest checked-out messages; the host commands name message ids, so
  they carry the ``n`` smallest ids the host consumer holds;
* the host machine has no delivery limit: ``_DeadLettering`` below
  drops a returned message whose ``delivery_count`` reaches the limit
  where ``_return_entries`` would requeue it (``rabbit_fifo``'s
  dead-lettering), within the same command;
* the loaded messages are enqueued first and the consumers attach in
  turn with ``("checkout", ("auto", prefetch), (c, "consumer"))``.

What is compared after every window, replica by replica: the ready
queue (tickets, in order), each consumer's checked-out messages by
delivery id with their tickets and delivery counts, its credit, the
service queue's order, and every held message's body.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu.core.machine import ApplyMeta
from ra_tpu.models import FifoMachine
from ra_tpu.models.quorum_queue import QuorumQueueMachine

W, Q, LOADED, C, P, LIMIT = 3, 40, 20, 2, 4, 3
SEED = 11
BATCH = (8, 3)
A = 16


def _machine(**kw):
    args = dict(message_words=W, capacity=Q, loaded=LOADED, consumers=C,
                prefetch=P, delivery_limit=LIMIT, seed=SEED)
    args.update(kw)
    return QuorumQueueMachine(**args)


def _init(m, batch=BATCH):
    init = m.jit_init(batch[0])
    return jax.tree.map(
        lambda x: jnp.broadcast_to(
            x.reshape(x.shape[:1] + (1,) * (len(batch) - 1) + x.shape[1:]),
            batch + x.shape[1:]), init)


def _window(rng, batch, a, m):
    """Commands [*batch, a, 3+W] and a mask: publishes (many, so queues
    fill), settles and returns of 0 to prefetch + 1 messages (beyond
    what a consumer holds), bad consumers and ops, noops."""
    shape = batch + (a,)
    op = rng.choice([0, 1, 2, 3, 4], size=shape,
                    p=[0.05, 0.45, 0.2, 0.25, 0.05])
    who = rng.integers(0, m.consumers, shape)
    who = np.where(rng.random(shape) < 0.03, m.consumers, who)
    n = rng.integers(0, m.prefetch + 2, shape)
    n = np.where(rng.random(shape) < 0.02, -1, n)
    body = rng.integers(0, 1 << 31, shape + (m.message_words,))
    cmds = np.concatenate([op[..., None], who[..., None], n[..., None],
                           body], axis=-1).astype(np.int32)
    mask = rng.random(shape) < 0.9
    return jnp.asarray(cmds), jnp.asarray(mask)


def _meta(mask):
    return {"index": jnp.zeros(mask.shape, jnp.int32),
            "term": jnp.zeros(mask.shape[:-1] + (1,), jnp.int32)}


def _equal(got, want):
    assert set(got) == set(want)
    for k in want:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


# -- the host reference ------------------------------------------------------

class _DeadLettering(FifoMachine):
    """``FifoMachine`` with a delivery limit: a returned message whose
    ``delivery_count`` reaches it is dropped, not requeued."""

    def __init__(self, limit: int) -> None:
        super().__init__()
        self.limit = limit

    def _return_entries(self, state, entries):
        keep = []
        for entry in entries:
            _mid, raft_idx, header, _raw = entry
            if header.get("delivery_count", 0) + 1 >= self.limit:
                state.live.discard(raft_idx)
            else:
                keep.append(entry)
        super()._return_entries(state, keep)


class _Host:
    """One replica's host queue, driven by device commands."""

    def __init__(self, m: QuorumQueueMachine, lane: int) -> None:
        self.m = m
        self.fm = _DeadLettering(m.delivery_limit)
        self.st = self.fm.init({})
        self.index = 0
        words = _loaded(m, lane)
        for t in range(m.loaded):
            self._apply(("enqueue", None, None, tuple(words[t])))
        for c in range(m.consumers):
            self._apply(("checkout", ("auto", m.prefetch), self._cid(c)))

    @staticmethod
    def _cid(c):
        return (c, "consumer")

    def _apply(self, cmd):
        self.index += 1
        self.st, _reply, _effects = self.fm.apply(
            ApplyMeta(index=self.index, term=1), cmd, self.st)

    def _held(self):
        out = set(self.st.messages)
        for con in self.st.consumers.values():
            out |= {e[0] for e in con.checked_out.values()}
        return out

    def command(self, row) -> None:
        op, who, n = int(row[0]), int(row[1]), int(row[2])
        if op == 1:
            nxt, q = self.st.next_msg_in_id, self.m.capacity
            if any(t % q == nxt % q for t in self._held()):
                return                          # refused: the slot is taken
            self._apply(("enqueue", None, None, tuple(int(x)
                                                      for x in row[3:])))
        elif op in (2, 3) and 0 <= who < self.m.consumers and n >= 0:
            con = self.st.consumers[self._cid(who)]
            ids = tuple(sorted(con.checked_out)[:n])
            self._apply(("settle" if op == 2 else "return", ids,
                         self._cid(who)))

    def view(self) -> dict:
        """What the device's leaves say, from the host state."""
        st = self.st
        out = {"ready": list(st.messages), "consumers": []}
        for c in range(self.m.consumers):
            con = st.consumers[self._cid(c)]
            out["consumers"].append(
                {d: (e[0], e[2]["delivery_count"])
                 for d, e in con.checked_out.items()})
        out["queue"] = [cid[0] for cid in st.service_queue]
        bodies = dict((mid, raw) for mid, (_i, _h, raw)
                      in st.messages.items())
        for con in st.consumers.values():
            bodies.update((e[0], e[3]) for e in con.checked_out.values())
        out["bodies"] = bodies
        return out


def _loaded(m, lane):
    from ra_tpu.models.jit_kv import loaded_words
    cell = np.arange(m.loaded * m.message_words, dtype=np.uint32)
    return loaded_words(np, m.seed, np.full(cell.shape, lane, np.uint32),
                        cell).reshape(
        (m.loaded, m.message_words))


def _device_view(m, state, idx) -> dict:
    s = {k: np.asarray(v)[idx] for k, v in state.items()}
    head, tail = int(s["head"]), int(s["tail"])
    out = {"ready": list(range(head, tail)), "consumers": []}
    held = list(range(head, tail))
    for c in range(m.consumers):
        lo, nx = int(s["lo"][c]), int(s["next_id"][c])
        assert int(s["credit"][c]) == m.prefetch - (nx - lo)
        ids = {d: (int(s["out_ticket"][c, d % m.prefetch]),
                   int(s["out_count"][c, d % m.prefetch]))
               for d in range(lo, nx)}
        out["consumers"].append(ids)
        held += [t for t, _dc in ids.values()]
    turn = s["turn"]
    out["queue"] = [int(c) for c in np.argsort(turn, kind="stable")
                    if turn[c] >= 0]
    msgs = s["store"].reshape((m.capacity, m.message_words))
    out["bodies"] = {t: tuple(int(x) for x in msgs[t % m.capacity])
                     for t in held}
    return out


# -- the fold ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_batch_fold_is_the_one_command_fold_and_the_host_queue(seed):
    m = _machine()
    rng = np.random.default_rng(seed)
    state = _init(m)
    hosts = {idx: _Host(m, idx[0]) for idx in np.ndindex(*BATCH)}
    for idx, host in hosts.items():
        assert _device_view(m, state, idx) == host.view()
    batch = jax.jit(m.jit_apply_batch)
    one = jax.jit(m.sequential_window_fold)
    stats = np.zeros(5, np.int64)
    for _ in range(6):
        cmds, mask = _window(rng, BATCH, A, m)
        got = batch(_meta(mask), cmds, mask, state)
        want = one(_meta(mask), cmds, mask, state)
        _equal(got, want)
        state = got
        c, k = np.asarray(cmds), np.asarray(mask)
        for idx, host in hosts.items():
            for a in range(A):
                if k[idx][a]:
                    host.command(c[idx][a])
            assert _device_view(m, state, idx) == host.view(), idx
    stats = np.asarray(state["counts"]).sum(axis=(0, 1))
    # the windows reached every case: deliveries, settles, requeues,
    # dead letters and refusals at a full queue
    assert (stats > 0).all(), stats


def test_the_fold_has_no_sequential_branch_and_no_wide_carry():
    m = _machine()
    cmds, mask = _window(np.random.default_rng(3), BATCH, A, m)
    jaxpr = jax.make_jaxpr(m.jit_apply_batch)(_meta(mask), cmds, mask,
                                              _init(m)).jaxpr

    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    prims = [e.primitive.name for e in eqns(jaxpr)]
    assert "cond" not in prims and "while" not in prims
    loops = [e for e in eqns(jaxpr) if e.primitive.name == "scan"]
    assert len(loops) == 1
    for aval in (v.aval for v in loops[0].params["jaxpr"].jaxpr.invars):
        # the carry and the per-command inputs: a few words a replica
        assert aval.size <= np.prod(BATCH) * C * P, aval
        assert m.capacity not in aval.shape, aval
    text = jax.jit(m.jit_apply_batch).lower(
        _meta(mask), cmds, mask, _init(m)).as_text()
    assert "sequential_window_fold" not in text
    # the lowered loop: its carry is the credit table, the counts and
    # the window's op, consumer and n, never the store (a replica's
    # rows of ten messages) nor a body
    loops = [line for line in text.splitlines() if "stablehlo.while" in line]
    assert len(loops) == 1
    types = re.findall(r"tensor<([0-9x]*)x?i\d+>", loops[0].split(" : ")[-1])
    row = m.CHUNK * m.message_words
    for t in types:
        dims = [int(d) for d in t.split("x") if d]
        assert m.capacity not in dims and row not in dims, t
        assert (np.prod(dims) if dims else 1) <= A * 3 * np.prod(BATCH), t


@pytest.mark.parametrize("capacity,window,fits", [
    (20, 11, True), (20, 12, False), (40, 16, True)])
def test_a_store_too_small_for_a_window_s_run_is_refused(capacity, window,
                                                         fits):
    """A window's publishes land as one run of rows; a store of fewer
    rows than that run can touch (it would wrap onto rows it wrote) is
    refused where the fold is built, not written some other way."""
    m = _machine(capacity=capacity, loaded=10)
    state = _init(m, (4,))
    cmds, mask = _window(np.random.default_rng(5), (4,), window, m)
    if not fits:
        with pytest.raises(ValueError, match="capacity must be at least"):
            m.jit_apply_batch(_meta(mask), cmds, mask, state)
        return
    _equal(m.jit_apply_batch(_meta(mask), cmds, mask, state),
           m.sequential_window_fold(_meta(mask), cmds, mask, state))


# -- the delivery read ---------------------------------------------------------

def test_the_delivery_read_is_each_consumer_s_oldest_messages():
    m = _machine(prefetch=12, loaded=30)
    rng = np.random.default_rng(7)
    state = _init(m)
    hosts = {idx: _Host(m, idx[0]) for idx in np.ndindex(*BATCH)}
    for _ in range(3):
        cmds, mask = _window(rng, BATCH, A, m)
        state = m.jit_apply_batch(_meta(mask), cmds, mask, state)
        for idx, host in hosts.items():
            for a in range(A):
                if np.asarray(mask)[idx][a]:
                    host.command(np.asarray(cmds)[idx][a])
    who = rng.integers(-1, C + 1, BATCH + (5,))
    q = np.stack([np.where(rng.random(who.shape) < 0.9, 1, 2), who], -1)
    reply = np.asarray(m.jit_query(jnp.asarray(q, jnp.int32), state))
    assert reply.shape == BATCH + (5, 3 + m.CHUNK * W)
    for idx, host in hosts.items():
        view = host.view()
        for k in range(5):
            r = reply[idx][k]
            op, c = q[idx][k]
            if op != 1 or not 0 <= c < C:
                assert r[1] == -1 and r[0] == 0 and not r[2:].any()
                continue
            ids = sorted(view["consumers"][c])[:m.CHUNK]
            assert r[0] == len(ids)
            assert r[1] == (ids[0] if ids else
                            int(np.asarray(state["lo"])[idx][c]))
            flags = [view["consumers"][c][d][1] > 0 for d in ids]
            assert r[2] == sum(1 << i for i, f in enumerate(flags) if f)
            got = r[3:].reshape((m.CHUNK, W))
            for i, d in enumerate(ids):
                assert tuple(got[i]) == view["bodies"][
                    view["consumers"][c][d][0]]
            assert not got[len(ids):].any()


def test_the_host_protocol_encodes_every_command_and_a_read():
    m = _machine()
    body = list(range(7, 7 + W))
    assert np.asarray(m.encode_command(("publish", body))).tolist() == \
        [1, 0, 0] + body
    assert np.asarray(m.encode_command(("settle", 1, 8))).tolist()[:3] == \
        [2, 1, 8]
    assert np.asarray(m.encode_command(("return", 0, 1))).tolist()[:3] == \
        [3, 0, 1]
    assert not np.asarray(m.encode_command(("nope",))).any()
    assert np.asarray(m.encode_query(("deliveries", 1))).tolist() == [1, 1]
    state = m.init({})
    state, reply = m.apply(ApplyMeta(index=1, term=1), ("publish", body),
                           state)
    assert reply == (1, LOADED)
    state, reply = m.apply(ApplyMeta(index=2, term=1), ("settle", 0, 9),
                           state)
    assert reply == (1, P)
    state, reply = m.apply(ApplyMeta(index=3, term=1), ("return", 5, 1),
                           state)
    assert reply == (-2, None)
    first, flags, msgs = m.decode_query_reply(
        m.jit_query(jnp.asarray([[1, 0]], jnp.int32), state)[0])
    assert first == P and msgs.shape == (P, W) and not flags.any()


# -- through the engine --------------------------------------------------------

def _engine(m, lanes=4, members=3, **kw):
    from ra_tpu.engine import LockstepEngine
    return LockstepEngine(m, lanes, members, ring_capacity=64,
                          max_step_cmds=4, max_step_reads=2, **kw)


def test_publishes_a_settle_and_a_read_through_the_engine():
    """Through ``LockstepEngine``: a lane's publishes and a settle of
    consumer 0's four oldest commit and apply on every member; the read
    returns consumer 0's new oldest messages (delivered from the front
    of the ready queue), ``overview()`` carries the queue's counts and
    no round took a sequential branch."""
    m = _machine(message_words=25)
    eng = _engine(m)
    assert eng.payload_width == 28 and eng.query_reply_width == 253
    pay = np.zeros((4, 4, 28), np.int32)
    bodies = np.arange(50, dtype=np.int32).reshape(2, 25) + 7
    for j in range(2):
        pay[1, j] = np.asarray(m.encode_command(("publish", bodies[j])))
    pay[1, 2] = np.asarray(m.encode_command(("settle", 0, P)))
    eng.step(np.array([0, 3, 0, 0], np.int32), pay)
    for _ in range(4):                  # commit and apply on every member
        eng.step(np.zeros(4, np.int32), np.zeros_like(pay))
    replies, wm, ok = eng.read_lanes(np.array([1, 2]),
                                     np.array([[1, 0], [1, 0]], np.int32))
    assert ok.all() and (wm >= 0).all() and replies.shape == (2, 253)
    # lane 1: consumer 0 settled ids 0..3 and took tickets 8..11
    assert replies[:, :3].tolist() == [[P, P, 0], [P, 0, 0]]
    got = replies[0, 3:].reshape(10, 25)
    assert np.array_equal(got[:P], _loaded(m, 1)[2 * P:3 * P])
    tail = np.asarray(eng.state.mac["tail"])
    assert tail[1].tolist() == [LOADED + 2] * 3
    ov = eng.overview()
    assert ov["queue"] == {"delivered": 4 * 2 * P + P, "settled": P,
                           "requeued": 0, "dead_lettered": 0, "refused": 0}
    assert ov["pipeline"]["apply_fallback_rounds"] == 0


def test_the_fallback_counter_counts_a_demoted_window_and_not_this_fold():
    """``apply_fallback_rounds``: a ``JitFifoMachine`` window holding a
    settle goes to the sequential fold and is counted, an enqueue-only
    one is not; no ``QuorumQueueMachine`` window is, settles and returns
    in every one."""
    from ra_tpu.models import JitFifoMachine
    from ra_tpu.metrics import ENGINE_PIPELINE_FIELDS
    assert "apply_fallback_rounds" in ENGINE_PIPELINE_FIELDS
    fifo = _engine(JitFifoMachine(capacity=16))
    assert fifo.overview()["pipeline"]["apply_fallback_rounds"] == 0
    pay = np.zeros((4, 4, 3), np.int32)
    pay[:, :2, 0] = 1                              # enqueues
    fifo.step(np.full(4, 2, np.int32), pay)
    for _ in range(3):
        fifo.step(np.zeros(4, np.int32), np.zeros_like(pay))
    assert fifo.overview()["pipeline"]["apply_fallback_rounds"] == 0
    pay[2, 0] = [4, 0, 0]                          # a settle, one lane
    fifo.step(np.full(4, 1, np.int32), pay)
    for _ in range(3):
        fifo.step(np.zeros(4, np.int32), np.zeros_like(pay))
    assert fifo.overview()["pipeline"]["apply_fallback_rounds"] >= 1
    assert "queue" not in fifo.overview()

    m = _machine(message_words=25)
    qq = _engine(m)
    rng = np.random.default_rng(4)
    for _ in range(6):
        cmds, _mask = _window(rng, (4,), 4, m)
        qq.superstep(np.full((1, 4), 4, np.int32),
                     np.asarray(cmds)[None])
    for _ in range(3):
        qq.superstep(np.zeros((1, 4), np.int32),
                     np.zeros((1, 4, 4, 28), np.int32))
    pipe = qq.overview()["pipeline"]
    assert pipe["apply_fallback_rounds"] == 0 and pipe["inner_steps"] == 9
    assert qq.overview()["queue"]["settled"] > 0


def _fallback_machines():
    from ra_tpu.models import JitFifoMachine, StreamLogMachine
    from ra_tpu.models.ttl_kv import TtlKvMachine
    return {"fifo": JitFifoMachine(capacity=16), "ttl": TtlKvMachine(),
            "queue": _machine(), "stream_log": StreamLogMachine()}


@pytest.mark.parametrize("name,settle,enqueue", [
    ("fifo", True, False), ("ttl", True, True), ("queue", None, None),
    ("stream_log", None, None)])
def test_jit_fallback_names_the_windows_a_fold_sends_to_its_sequential_branch(
        name, settle, enqueue):
    """``jit_fallback``: ``JitFifoMachine``'s gate (a settle, op 4,
    demotes the window; enqueues do not), the default fold's True on
    every window, None for a fold with no sequential branch."""
    m = _fallback_machines()[name]
    width = m.command_spec[1][0]
    cmds = np.zeros((2, 3, width), np.int32)
    cmds[..., 0] = 1
    mask = np.ones((2, 3), bool)
    for want, op in ((enqueue, 1), (settle, 4)):
        cmds[1, 2, 0] = op
        got = m.jit_fallback(jnp.asarray(cmds), jnp.asarray(mask))
        assert (got is None) if want is None else bool(got) is want


# -- the stage scopes ----------------------------------------------------------

def _op_names(jaxpr, outer=""):
    """(primitive, op_name) of every operation of a jaxpr and of the
    jaxprs inside it (a loop's body, a branch)."""
    for eqn in jaxpr.eqns:
        here = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _op_names(sub, here)


def test_every_operation_of_the_fold_and_the_read_carries_its_stage():
    """The fold (its window scan and the publishes' run of rows) and
    the read."""
    m = _machine(loaded=10)
    state = _init(m, (6, 3))
    cmds, mask = _window(np.random.default_rng(8), (6, 3), A, m)
    q = np.ones((6, 3, 4, 2), np.int32)

    def fold(c, k, s):
        with jax.named_scope("ra.s5_apply"):
            return m.jit_apply_batch(_meta(k), c, k, s)

    def read(qs, s):
        with jax.named_scope("ra.s5c_read"):
            return m.jit_query(qs, s)

    for fn, args, scope in ((fold, (cmds, mask, state), "ra.s5_apply"),
                            (read, (q, state), "ra.s5c_read")):
        ops = list(_op_names(jax.make_jaxpr(fn)(*args).jaxpr))
        assert len(ops) > 20
        assert [(p, n) for p, n in ops if scope not in n] == [], scope
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert f'"jit({fn.__name__})/{scope}/' in text


# -- the benchmark's cell, rehearsed -------------------------------------------

import argparse  # noqa: E402

from benchmarks import manifest as mf  # noqa: E402
from benchmarks import run as br  # noqa: E402

CELL = "qq_5k_x5.paced_qq"


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    orig = br.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[0] == "configs":
            d.update(clusters=12)
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
    rc, res = br.run_cell(args, mf.committed(), require_tpu=False, **faults)
    assert rc == 0
    return res


def _failed(res) -> set:
    return {k for k, v in res["compared"].items() if v["value"] > v["limit"]}


STATE_COUNTS = ("tail_wrong", "messages_unknown", "messages_duplicated",
                "publishes_lost", "publishes_refused", "publisher_order",
                "settled_wrong", "credit_exceeded", "delivery_counts_wrong",
                "reads_stale", "reads_wrong_messages", "reads_out_of_order",
                "replica_cells_wrong", "replicas_behind")


@pytest.mark.parametrize("seed", [2**31 + 42, 5])
def test_the_cell_runs_and_is_correct_at_12_queues(tiny, seed):
    res = _run(seed)
    assert res["correct"] is True, _failed(res)
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


def test_a_dropped_publish_is_a_lost_publish(tiny):
    hit = []

    def tamper(idx, pay):
        pub = np.flatnonzero(pay[:, 0] == 1)
        if len(pub) and not hit:
            pay[pub[0], 0] = 0              # acknowledged, and a no-op
            hit.append(1)

    res = _run(22, tamper=tamper)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert {"live_publishes_lost", "reopen_publishes_lost",
            "live_tail_wrong"} <= failed
    assert res["compared"]["live_publishes_lost"]["value"] == 1
    assert "live_messages_unknown" not in failed


def test_a_message_published_twice_is_a_duplicated_message(tiny):
    seen = {}

    def tamper(idx, pay):
        # a session's second publish carries its first one's message
        for i in np.flatnonzero(pay[:, 0] == 1):
            sess = int(pay[i, 4])
            if sess in seen and "done" not in seen:
                pay[i, 3:] = seen[sess]
                seen["done"] = True
            elif sess not in seen:
                seen[sess] = pay[i, 3:].copy()

    res = _run(23, tamper=tamper)
    assert "done" in seen and res["correct"] is False
    failed = _failed(res)
    assert {"live_messages_duplicated",
            "reopen_messages_duplicated"} <= failed
    assert res["compared"]["live_messages_duplicated"]["value"] == 1


def test_a_read_with_two_messages_swapped_is_out_of_order(tiny):
    hit = []

    def tamper_reply(rec):
        for i in np.flatnonzero(rec["status"] <= 1):
            pay = rec["pay"][i]
            if pay[0] >= 2 and pay[2] & 3 == 0 and not hit:
                a = pay[3:28].copy()
                pay[3:28] = pay[28:53]
                pay[28:53] = a
                hit.append(1)

    res = _run(24, tamper_reply=tamper_reply)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert "live_reads_out_of_order" in failed
    assert res["compared"]["live_reads_out_of_order"]["value"] == 1
    assert not {"live_messages_unknown", "live_publishes_lost"} & failed


@pytest.mark.parametrize("fault", ["no_op", "other_consumer"])
def test_a_settle_that_removes_what_it_did_not_name_is_caught(tiny, fault):
    """One acknowledged settle applied as a no-op, or to the queue's
    other consumer: the queue's counts and held messages stay
    consistent with one another, and the consumers' settled messages
    do not match what the ledger's settles named."""
    hit = []

    def tamper(idx, pay):
        at = np.flatnonzero(pay[:, 0] == 2)
        if len(at) and not hit:
            if fault == "no_op":
                pay[at[0], 0] = 0
            else:
                pay[at[0], 1] = 1 - pay[at[0], 1]
            hit.append(1)

    res = _run(25, tamper=tamper)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert {"live_settled_wrong", "reopen_settled_wrong"} <= failed
    # no_op: the consumer's oldest delivery id and the queue's settle
    # count; other_consumer: both consumers' oldest delivery ids
    assert res["compared"]["live_settled_wrong"]["value"] == 2
    assert not {"live_messages_unknown", "live_publishes_lost",
                "live_messages_duplicated"} & failed


def test_removals_are_exact_only_where_no_clamp_can_bind():
    """``removals_exact`` from the ledger alone: a queue whose loaded
    backlog outlasts every removal its settles and returns could make
    keeps its consumers' prefetch full, so each removes what it names;
    one that may run short, or a settle wider than the prefetch, does
    not."""
    from benchmarks.harness.kits.quorum_queue import reference
    settles = np.array([[10, 12], [60, 57], [0, 0]])
    returns = np.array([[1, 0], [3, 2], [0, 0]])
    kw = dict(loaded=1000, consumers=2, prefetch=32, return_n=1)
    got = reference.removals_exact(settles=settles, returns=returns,
                                   settle_n=8, **kw)
    # 1000 - 8 * 117 - 5 = 59 < 64 in the second queue
    assert got.tolist() == [True, False, True]
    assert not reference.removals_exact(
        settles=settles, returns=returns, settle_n=33, **kw).any()
