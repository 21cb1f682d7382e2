"""A read-carrying dispatch reads back only the replies its pending
reads asked for: the plane hands the driver every ``(lane, row)`` of
every lane with a batch out, the jitted ``ra_read_rows`` gathers those
rows out of the dispatch's ``[K, N, Kr, Wq]`` reply tensor on the
device, and the plane takes a served row's payload by its place in
that list.

Each case checks, row for row, every outcome the plane fans out
against what the asked rows of the WHOLE reply tensor give (copied to
the host here, beside the driver, for the comparison alone): the
payload at the lane's first serving inner step, the watermark there,
and the status.  Small engines of the three machines that answer
reads with records (``JitRecordKvMachine``, ``StreamLogMachine``,
``QuorumQueueMachine``), on one device and on the eight forced host
devices' lane mesh.

The shapes that exercise the bookkeeping: a lane cut from its
followers while writes pile up on its leader (``apply_window=1``), so
its batch registers, waits out the cut and, once the followers are
back, is served at an inner step past 0 of a later dispatch, one that
carries the zero read block; a lane whose leader is down (its reads
SHED at arrival); a lane cut for good (REJECTed at ``read_timeout``);
and blocks of 1, 64, 65 and every one of ``N x Kr`` rows, the edges
of the padded lengths.
"""
import jax
import numpy as np
import pytest

from ra_tpu import devicewatch
from ra_tpu.engine import LockstepEngine
from ra_tpu.engine.lockstep import read_rows_padded
from ra_tpu.ingress import OK, REJECT, SHED, IngressPlane
from ra_tpu.models import JitRecordKvMachine, StreamLogMachine
from ra_tpu.models.quorum_queue import QuorumQueueMachine

P, KR, K = 3, 2, 4
READ_KEYS = ("read_done", "read_replies", "read_watermark")


def _machine(kind):
    if kind == "record_kv":
        return JitRecordKvMachine(records=12, fields=3, field_words=4,
                                  seed=7)
    if kind == "stream_log":
        return StreamLogMachine(message_words=3, chunk=4, retention=12,
                                groups=2, seed=9)
    return QuorumQueueMachine(message_words=3, capacity=40, loaded=20,
                              consumers=2, prefetch=4, delivery_limit=3,
                              seed=11)


def _queries(kind, n, rng):
    """``n`` reads that answer differently by row: a key, a lag or a
    consumer each."""
    q = np.zeros((n, 2), np.int32)
    q[:, 0] = 1
    q[:, 1] = rng.integers(0, {"record_kv": 12, "stream_log": 8,
                               "queue": 2}[kind], n)
    return q


class _Rig:
    """A plane over a small engine of one machine, one session a lane,
    every dispatch observed in the pump that made it, and a check of
    every settlement the plane fans out against the asked rows of the
    dispatch's whole reply tensor."""

    def __init__(self, kind, n_lanes=8, *, mesh=False, read_timeout=40):
        self.kind = kind
        eng = LockstepEngine(_machine(kind), n_lanes, P, ring_capacity=64,
                             max_step_cmds=4, max_step_reads=KR,
                             apply_window=1, lease_ttl=4,
                             read_timeout=read_timeout)
        if mesh:
            from ra_tpu.parallel.mesh import lane_mesh, shard_engine_state
            shard_engine_state(eng, lane_mesh(jax.devices(), member_axis=1))
        self.eng = eng
        self.plane = plane = IngressPlane(eng, superstep_k=K, window_s=0.0)
        drv = plane.driver
        drv._ready = lambda entry: bool(jax.block_until_ready(entry[1:])) \
            or True
        handles = plane.connect_bulk(16 * n_lanes, key="rig")
        lanes = plane.directory.lane[handles]
        self.on = np.asarray([handles[lanes == lane][0]
                              for lane in range(n_lanes)])
        self.rng = np.random.default_rng(43)
        self.next_seqno = 0
        #: the whole read aux of every dispatch, by its ordinal
        self.full = {}
        real = eng.superstep

        def superstep(*a, **kw):
            aux = real(*a, **kw)
            self.full[drv.staged] = {k: np.asarray(aux[k])
                                     for k in READ_KEYS}
            return aux
        eng.superstep = superstep
        #: whether each dispatch, by its ordinal, carried the zero
        #: read block
        self.zero = {}
        pop = plane._pop_read_block

        def pop_block():
            blk = pop()
            self.zero[drv.staged + 1] = blk is plane._zero_read_blk
            return blk
        plane._pop_read_block = pop_block
        self.want = []              # expected fan-outs, in order
        self.seen = {OK: 0, SHED: 0, REJECT: 0}
        #: (lane, inner step, ordinal the batch rode, ordinal it was
        #: served by) of every served lane
        self.served = []
        emit = plane._emit_read_replies

        def expect(lanes, status, obs=None, k_idx=None):
            take = plane._read_take[lanes]
            li, ri = np.nonzero(plane._read_rows(take))
            ln = lanes[li]
            n = len(ln)
            if status == OK:
                full = self.full[obs["ordinal"]]
                # the first inner step that served the lane, from the
                # whole tensor
                k = np.argmax(full["read_done"][:, ln] > 0, axis=0)
                assert (full["read_done"][k, ln] > 0).all()
                wm = full["read_watermark"][k, ln]
                pay = full["read_replies"][k, ln, ri]
                self.served += list(zip(
                    ln.tolist(), k.tolist(),
                    plane._read_ordinal[ln].tolist(),
                    [obs["ordinal"]] * n))
            else:
                wm = np.full(n, -1)
                pay = np.zeros((n, eng.query_reply_width), np.int32)
            if n:
                self.want.append((plane._read_handles[ln, ri].copy(),
                                  plane._read_seqnos[ln, ri].copy(),
                                  np.full(n, status), wm, pay))
            return emit(lanes, status, obs, k_idx)
        plane._emit_read_replies = expect
        plane.on_reads_done = self._done

    def _done(self, handles, seqnos, statuses, wms, pay):
        h, s, st, wm, p = self.want.pop(0)
        np.testing.assert_array_equal(handles, h)
        np.testing.assert_array_equal(seqnos, s)
        np.testing.assert_array_equal(statuses, st)
        np.testing.assert_array_equal(wms, wm)
        np.testing.assert_array_equal(pay, p)
        self.seen[int(st[0])] += len(st)

    def read(self, lanes, n=KR):
        """``n`` reads to each of ``lanes``."""
        h = np.repeat(self.on[lanes], n)
        seq = np.arange(self.next_seqno, self.next_seqno + len(h))
        self.next_seqno += len(h)
        st = self.plane.submit_reads(h, seq,
                                     _queries(self.kind, len(h), self.rng))
        assert (st <= 1).all(), st

    def write(self, lane, n):
        h = np.repeat(self.on[lane:lane + 1], n)
        st = self.plane.submit(h, self.plane.directory.next_seqnos(h),
                               np.zeros((n, self.eng.payload_width),
                                        np.int32))
        assert (st <= 1).all(), st

    def cut(self, lane, *, leader=False):
        lead = int(np.asarray(self.eng.state.leader_slot)[lane])
        slots = [lead] if leader else [s for s in range(P) if s != lead]
        for s in slots:
            self.eng.fail_member(lane, s)
        return slots

    def heal(self, lane, slots):
        for s in slots:
            self.eng.recover_member(lane, s)


KINDS = ["record_kv", "stream_log", "queue"]


def _outcomes(rig):
    """Lane 0 served at an inner step past 0 of a later dispatch than
    its batch rode, one that carried the zero read block; lane 1 shed;
    lane 2 stale; every other lane served under its lease."""
    slow = rig.cut(0)
    rig.cut(1, leader=True)
    rig.cut(2)
    for _ in range(3):                  # a backlog on lane 0's leader
        rig.write(0, 4)
        rig.plane.pump(force=True)
    rig.heal(0, slow)
    rig.read([0, 1, 2])
    rig.plane.pump(force=True)
    # lane 0's next batch is registered past the backlog its leader
    # applies a round at a time
    rig.read([0])
    rig.plane.pump(force=True)
    assert rig.plane._read_pend[[0, 2]].all()
    for _ in range(6):                  # dispatches with the zero block
        if rig.plane._read_pend[0]:
            rig.plane.pump(force=True)
    for i in range(4):
        rig.read(np.arange(3, rig.eng.n_lanes), n=1 + i % KR)
        rig.plane.pump(force=True)
    rig.plane.settle()
    assert not rig.want
    lanes = rig.eng.n_lanes
    assert rig.seen == {OK: 2 * KR + (lanes - 3) * 6, SHED: KR,
                        REJECT: KR}
    late = {(k, rode, by) for ln, k, rode, by in rig.served
            if ln == 0 and k > 0 and by > rode}
    assert late, rig.served
    assert any(rig.zero[by] for _k, _rode, by in late)
    assert int(np.asarray(rig.eng.state.read_shed).sum()) == KR


@pytest.mark.parametrize("kind", KINDS)
def test_the_asked_rows_match_the_whole_tensor(kind):
    _outcomes(_Rig(kind))


@pytest.mark.parametrize("kind", KINDS)
def test_the_asked_rows_match_the_whole_tensor_on_the_lane_mesh(kind):
    rig = _Rig(kind, n_lanes=16, mesh=True)
    assert rig.eng.mesh_shape() == "1x8"
    _outcomes(rig)


@pytest.mark.parametrize("kind", KINDS)
def test_a_dispatch_reads_back_its_padded_rows_and_the_small_leaves(kind):
    """Blocks of 1, 64, 65 and all ``N x Kr`` rows: the driver pads
    each to 64, 64, 128 and ``N x Kr`` (160 here, no power of two),
    counts the rows and the pad, and transfer site ``driver_read``
    counts the gathered ``M x Wq`` int32 plus the [N] and [K, N]
    leaves that ride as before."""
    n = 80
    rig = _Rig(kind, n_lanes=n)
    eng, plane = rig.eng, rig.plane
    wq = eng.query_reply_width
    small = 4 * (3 * n + 2 * K * n)
    by_fun = devicewatch.WATCH.xla_compiles_by_fun
    compiles = by_fun["jit(ra_read_rows)"]
    for rows, lanes, pad in ((1, [5], 64), (64, np.arange(32), 64),
                             (65, np.arange(33), 128),
                             (n * KR, np.arange(n), n * KR)):
        assert read_rows_padded(rows, n * KR) == pad
        if rows == 65:
            rig.read(lanes[:32])
            rig.read(lanes[32:], n=1)
        else:
            rig.read(lanes, n=min(rows, KR))
        pc = dict(eng.pipeline_counters)
        site = dict(devicewatch.WATCH.sites["driver_read"])
        plane.pump(force=True)
        assert eng.pipeline_counters["read_window_rows"] - \
            pc["read_window_rows"] == rows
        assert eng.pipeline_counters["read_window_padded"] - \
            pc["read_window_padded"] == pad - rows
        got = devicewatch.WATCH.sites["driver_read"]
        assert got["d2h_bytes"] - site.get("d2h_bytes", 0) == \
            pad * wq * 4 + small
        assert got["d2h_events"] - site.get("d2h_events", 0) == 6
        assert not plane._read_pend.any()
    assert not rig.want
    assert rig.seen[OK] == 1 + 64 + 65 + n * KR
    # one program a new length (64, 128, 160), under its own name
    assert by_fun["jit(ra_read_rows)"] - compiles == 3
    ov = eng.overview()["pipeline"]
    assert ov["read_window_rows"] == 1 + 64 + 65 + n * KR
    assert ov["read_window_padded"] == 63 + 0 + 63 + 0


def test_no_read_block_no_gather():
    """A dispatch with no read half gathers nothing and reads back the
    three [N] counters alone; a write-only engine reaches none of it."""
    rig = _Rig("queue")
    rig.write(3, 2)
    site = dict(devicewatch.WATCH.sites["driver_read"])
    rig.plane.pump(force=True)
    got = devicewatch.WATCH.sites["driver_read"]
    assert got["d2h_bytes"] - site.get("d2h_bytes", 0) == \
        4 * 3 * rig.eng.n_lanes
    assert rig.eng.pipeline_counters["read_window_rows"] == 0
    from ra_tpu.models import JitFifoMachine
    eng = LockstepEngine(JitFifoMachine(capacity=16), 8, P,
                         ring_capacity=64, max_step_cmds=4)
    assert not eng.reads_enabled
    assert "read_window_rows" in eng.overview()["pipeline"]


def test_the_counters_and_program_are_documented():
    import os

    from ra_tpu import metrics
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")).read()
    for name in ("read_window_rows", "read_window_padded"):
        assert name in metrics.ENGINE_PIPELINE_FIELDS
        assert f"| `{name}` |" in doc
    assert "`ra_read_rows`" in doc
