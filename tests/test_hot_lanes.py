"""Hot clusters on the served path (ISSUE 27): the limits that a few
busy clusters hit and a uniform fleet never does, each driven through
``WireListener.sweep()`` and ``IngressPlane.pump()`` by the benchmark's
own client (256-byte commands, the refusal re-key, the ledger of what
was acknowledged) until drained, and held against a plain fold of what
was acknowledged: counter, body checksum and per-slot watermark on every
replica of every cluster, live and after the WAL is reopened under
another shard layout.  The fold commutes but the machine's dedup does
not: an operation applied behind a later one of its session is dropped,
so equal counters also say that every lane was served in order.
"""
import time

import jax
import numpy as np
import pytest

from benchmarks.harness import reference
from benchmarks.harness.fleet import BenchFleet
from benchmarks.harness.machine import BodyCounterMachine
from ra_tpu.engine import open_engine
from ra_tpu.ingress import IngressPlane
from ra_tpu.wire.framing import data_stride
from ra_tpu.wire.server import WireListener

LANES, MEMBERS, SLOTS, SPC = 64, 3, 32, 3
K, CMDS, RING = 2, 4, 256
WIDTH = K * CMDS                        # rows a block takes from a lane


class Served:
    """Engine, plane, listener and fleet at a size a test can hold."""

    def __init__(self, wal_dir, *, ring=RING, ring_records=1024,
                 rank_cap=1024, **plane_kw) -> None:
        self.wal_dir, self.ring = str(wal_dir), ring
        self.eng = self._open(wal_shards=2)
        self.plane = IngressPlane(self.eng, superstep_k=K, window_s=0.0,
                                  **plane_kw)
        self.lst = WireListener(
            self.plane, port=None, max_conns=LANES + 4,
            ring_bytes=ring_records * data_stride(self.eng.payload_width))
        self.pool = reference.make_pool(7, rows=256)
        self.fleet = BenchFleet(self.lst, LANES, SPC, self.pool,
                                max_ops=1 << 14, rank_cap=rank_cap)
        self.rng = np.random.default_rng(27)

    def _open(self, wal_shards):
        return open_engine(BodyCounterMachine(slots=SLOTS), self.wal_dir,
                           LANES, MEMBERS, wal_shards=wal_shards,
                           ring_capacity=self.ring, max_step_cmds=CMDS)

    def sessions_of(self, lane) -> np.ndarray:
        return np.flatnonzero(self.fleet.lanes == lane)

    def offer(self, sess) -> None:
        n = len(sess)
        self.fleet.new_ops(sess, self.rng.integers(1, 8, n),
                           self.rng.integers(0, len(self.pool), n),
                           self.rng.integers(0, 1 << 30, n),
                           np.full(n, time.perf_counter()))

    def cycle(self) -> None:
        f = self.fleet
        f.send_queued(time.perf_counter())
        self.lst.sweep()
        f.collect(time.perf_counter())
        self.plane.pump(force=True)
        f.collect(time.perf_counter())

    def drain(self, limit=400) -> None:
        f = self.fleet
        for _ in range(limit):
            if not f.outstanding():
                return
            self.cycle()
            if f.idle():
                self.plane.settle()
                f.collect(time.perf_counter())
        raise AssertionError(f"{f.outstanding()} ops never acknowledged")

    def _apply_all(self) -> None:
        eng, lane = self.eng, np.arange(LANES)
        zero_n = np.zeros((K, LANES), np.int32)
        zero_p = np.zeros((K, LANES, CMDS, eng.payload_width), np.int32)
        for _ in range(256):
            st = eng.state
            tail = np.asarray(st.last_index)[lane, np.asarray(st.leader_slot)]
            if (np.asarray(st.applied) >= tail[:, None]).all():
                return
            self.plane.driver.submit(zero_n, zero_p)
            self.plane.driver.drain()
        raise AssertionError("replicas never applied their leader's log")

    def check(self) -> None:
        """Every acknowledged op applied once, on every replica, live
        and after a reopen under another shard layout."""
        f = self.fleet
        n = f.n_ops
        assert not np.isnan(f.op_acked[:n]).any()
        sess = f.op_sess[:n]
        want = reference.fold(
            LANES, SLOTS, self.pool, lane=f.lanes[sess], slot=f.slots[sess],
            op_id=f.op_id[:n], delta=f.op_delta[:n], row=f.op_row[:n],
            salt=f.op_salt[:n])
        self._apply_all()
        self._compare(self.eng, want)
        self.lst.close()
        self.eng.close()
        eng = self._open(wal_shards=1)
        try:
            self._compare(eng, want)
        finally:
            eng.close()

    @staticmethod
    def _compare(eng, want) -> None:
        mac = eng.state.mac
        for key in ("value", "check", "seq"):
            got = np.asarray(mac[key])
            for member in range(MEMBERS):
                np.testing.assert_array_equal(got[:, member], want[key],
                                              err_msg=f"{key}[{member}]")


def zipf_fleet(s: Served) -> None:
    """Sessions drawn by a Zipf law, as ``traffic/paced_zipf.json``
    draws them: the hottest cluster is offered about a block's window
    a cycle, more in some, and the coldest nothing."""
    n_sess = LANES * SPC
    w = 1.0 / np.arange(1, n_sess + 1) ** 0.99
    order = s.rng.permutation(n_sess)
    for _ in range(12):
        s.offer(order[s.rng.choice(n_sess, 48, p=w / w.sum())])
        s.cycle()
    s.drain()
    c = s.plane.counters
    assert c["lane_capped_rows"] > 0 and c["shed_rows"] == 0


def lane_cap(s: Served) -> None:
    """One cluster offered more than ``superstep_k * max_step_cmds``
    rows a cycle: each block takes its window, in the lane's order, the
    rest waits staged and ``lane_capped_rows`` counts it at every pop;
    nothing is shed."""
    sess = s.sessions_of(5)
    s.offer(np.repeat(sess[:1], WIDTH + 5))
    s.cycle()
    c = s.plane.counters
    assert c["block_rows"] == WIDTH and c["lane_capped_rows"] == 5
    s.cycle()
    assert c["block_rows"] == WIDTH + 5 and c["lane_capped_rows"] == 5
    s.drain()
    assert c["shed_rows"] == 0 and s.fleet.refusals == 0


def hot_beside_cold(s: Served) -> None:
    """Several clusters over the window at once beside clusters with a
    row each: a cold cluster's row rides the first block whatever the
    hot ones hold, each hot cluster waits for its own rows only, and
    what waited is counted lane by lane."""
    lanes = np.unique(s.fleet.lanes)
    hot = np.array([s.sessions_of(lane)[0] for lane in lanes[:6]])
    cold = np.array([s.sessions_of(lane)[0] for lane in lanes[6:30]])
    extra = np.arange(1, 7)
    s.offer(np.concatenate([np.repeat(hot, WIDTH + extra), cold]))
    s.cycle()
    c = s.plane.counters
    assert c["block_rows"] == 6 * WIDTH + len(cold)
    assert c["lane_capped_rows"] == extra.sum()
    s.cycle()       # what waited fits the next block's window
    assert c["block_rows"] == 6 * WIDTH + len(cold) + extra.sum()
    assert c["lane_capped_rows"] == extra.sum()
    s.drain()
    assert c["shed_rows"] == 0 and s.fleet.refusals == 0


def staging(s: Served) -> None:
    """A lane stages no less than one session may hold in flight, so
    one session inside its credit is never shed; a burst of several
    sessions beyond the lane's staging depth is shed, told so, and its
    resend applies once: nothing acknowledged is lost to the shed."""
    hard = s.plane.ladder.hard_credit
    assert s.plane.window.capacity == max(2 * WIDTH, hard) == hard
    sess = s.sessions_of(9)
    assert len(sess) >= 2
    s.offer(np.concatenate([np.repeat(sess[0], hard),
                            np.repeat(sess[1], 40)]))
    s.cycle()
    c = s.plane.counters
    assert c["shed_rows"] == 40 and c["rejected"] == 0
    assert s.fleet.refusals == 40
    s.drain()


def credit(s: Served) -> None:
    """A session over its hard credit (the plane's default, upstream's
    pipe of 500 rounded up) is refused and told so; the client gives
    the operation a new id, and it applies once."""
    hard = s.plane.ladder.hard_credit
    assert hard == 512 and s.plane.ladder.soft_credit == 128
    sess = s.sessions_of(3)
    s.offer(np.repeat(sess[:1], hard + 60))
    s.cycle()
    c = s.plane.counters
    assert c["rejected"] == 60 and s.fleet.refusals == 60
    assert c["slow_signals"] == hard - 128
    s.drain()
    assert c["accepted"] == hard + 60


def ring_room(s: Served) -> None:
    """A lane's ring on the device holds fewer entries than its
    sessions' credit allows in flight: the pump pops no more than the
    ring has room for, so the engine clips nothing (a clipped row would
    be lost after its pop, and its block never retire)."""
    assert s.ring == 64
    sess = s.sessions_of(11)
    for _ in range(6):
        s.offer(np.repeat(sess[:1], 60))
        s.cycle()
    s.drain()
    assert s.plane.counters["lane_capped_rows"] > 0
    assert s.plane.counters["shed_rows"] == 0


def _guarded_ring_room(s: Served) -> None:
    """Hold every ``_ring_room()`` against the device: what it admits,
    with the entries the ring holds now, fits the ring.  Every block
    handed to the driver has been dispatched in the call that took it
    (none waits for a later one), so once the device is done the ring
    holds every row the plane has popped."""
    plane, eng, drv = s.plane, s.eng, s.plane.driver
    room = plane._ring_room

    def checked():
        got = room()
        eng.block_until_ready()
        used = np.asarray(eng.watermarks())[1].astype(np.int64)
        assert drv.staged == drv.observed + drv.in_flight()
        assert (got + used <= s.ring - 3).all(), (got.min(), used.max())
        return got

    plane._ring_room = checked


def ring_room_observed_early(s: Served) -> None:
    """The ring-room guard with every watermark observed as early as
    it can be (ISSUE 28): the device finishes before each poll(), so
    ``last_ring_used`` is the newest dispatch's at every pop."""
    drv, poll = s.plane.driver, s.plane.driver.poll

    def early():
        s.eng.block_until_ready()
        jax.block_until_ready([e[1:] for e in drv._handles])
        return poll()

    drv.poll = early
    _guarded_ring_room(s)
    ring_room(s)
    assert s.eng.pipeline_counters["early_observes"] > 0
    assert s.eng.pipeline_counters["window_syncs"] == 0


def ring_room_observed_late(s: Served) -> None:
    """The same with no poll(): a watermark is read only when the
    in-flight cap pushes it out, so the guard counts two dispatches'
    rows on top of an older reading."""
    s.plane.driver.poll = lambda: 0
    _guarded_ring_room(s)
    ring_room(s)
    assert s.eng.pipeline_counters["early_observes"] == 0


CASES = {
    "zipf_fleet": (zipf_fleet, {"capacity": 64}),
    "lane_cap": (lane_cap, {}),
    "hot_beside_cold": (hot_beside_cold, {}),
    "staging": (staging, {}),
    "credit": (credit, {}),
    "ring_room": (ring_room, {"ring": 64, "capacity": 512}),
    "ring_room_observed_early": (ring_room_observed_early,
                                 {"ring": 64, "capacity": 512}),
    "ring_room_observed_late": (ring_room_observed_late,
                                {"ring": 64, "capacity": 512}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_hot_lane_limit(case, tmp_path):
    drive, kw = CASES[case]
    s = Served(tmp_path / "wal", **kw)
    drive(s)
    s.check()


def test_rows_are_released_lane_by_lane_not_block_by_block():
    """A hot lane's last round commits after its neighbours' rows: the
    cold lane's row is released (and its ACK fanned out) when its own
    lane's commit is observed, and the block retires with its last
    row."""
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine
    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0)
    h = plane.connect_bulk(64, key="fleet")
    lane = plane.directory.lane[h]
    cold, hot = h[lane == 1][0], h[lane == 2][0]
    acked = []
    plane.on_block_committed = lambda hs: acked.append(sorted(hs.tolist()))
    st = plane.submit_auto(np.concatenate([[cold], np.full(8, hot)]),
                           np.ones((9, 1), np.int32))
    assert (st == 0).all()
    d, marks = plane.driver, plane._base_committed.copy()
    d.poll = lambda: 0                      # the test observes by hand
    assert plane.pump(force=True)           # pops and dispatches the block

    def observe(lane_counts):
        for n, c in lane_counts.items():
            marks[n] += c
        d.last_committed = marks.copy()
        d.last_ring_used = np.zeros(8, np.int32)
        d.observed += 1
        plane._harvest()

    observe({})                             # dispatched, nothing committed
    assert acked == [] and len(plane._inflight) == 1
    observe({1: 1, 2: 4})                   # the hot lane's first round only
    assert acked == [[cold]] and plane.counters["credits_released"] == 1
    assert len(plane._inflight) == 1
    assert eng.phases.overview()["block_e2e"]["count"] == 0
    observe({2: 4})
    assert acked == [[cold], [hot] * 8]
    assert plane.counters["credits_released"] == 9
    assert not plane._inflight
    assert eng.phases.overview()["block_e2e"]["count"] == 1
