"""Wire plane tests (ISSUE 12): framing round-trips, the machine-level
dedup fold, the zero-per-command listener sweep, the at-least-once
client contract (refusal re-key, ascending-id replay, reconnect-storm
recovery, resolve_suspects), the FifoClient verdict unification, and
the connection-ladder acceptance rung — ≥100k concurrent connections
through a durable engine at ≥10x the classic-TCP baseline, with an
exactly-once-observable oracle (the full C1M rung rides ``-m slow``).
"""
import time

import numpy as np
import pytest

from ra_tpu.blackbox import RECORDER
from ra_tpu.engine import LockstepEngine
from ra_tpu.ingress import IngressPlane
from ra_tpu.wire import (DEFER, DUP, OK, REJECT, SHED, SLOW,
                         DedupCounterMachine, LoopbackFleet, WireClient,
                         WireListener)
from ra_tpu.wire import framing
from ra_tpu.wire.soak import run_wire_soak

#: the classic-TCP 3-member cluster baseline (BENCH_CLASSIC_r05); the
#: ISSUE 12 bar is 10x it, end to end through a durable engine
CLASSIC_TCP_BASELINE = 2934.0


def mk_engine(lanes=32, cmds=8, ring=128, slots=64, **kw):
    kw.setdefault("donate", False)
    return LockstepEngine(DedupCounterMachine(slots=slots), lanes, 3,
                          ring_capacity=ring, max_step_cmds=cmds, **kw)


def mk_plane(eng, **kw):
    kw.setdefault("superstep_k", 2)
    kw.setdefault("window_s", 0.0)
    kw.setdefault("soft_credit", 1 << 20)
    kw.setdefault("hard_credit", 1 << 20)
    return IngressPlane(eng, **kw)


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

def test_framing_round_trips():
    f = framing.encode_hello("acme/alice", 3, tenants=2,
                             payload_width=3)
    t, body, off = framing.read_frame(f)
    assert t == framing.T_HELLO and off == len(f)
    h = framing.decode_hello(body)
    assert h == {"version": framing.WIRE_VERSION, "tenants": 2,
                 "key": "acme/alice", "n_sessions": 3,
                 "payload_width": 3}
    a = framing.encode_hello_ack(7, 1234, slots=[4, 5, 6],
                                 payload_width=3)
    _t, body, _ = framing.read_frame(a)
    d = framing.decode_hello_ack(body)
    assert d["epoch"] == 7 and d["handle_base"] == 1234
    assert d["payload_width"] == 3
    assert d["slots"].tolist() == [4, 5, 6]
    # ERR: the refusal frame round-trips its code + reason
    e = framing.encode_error(framing.E_PAYLOAD_WIDTH, "width 4 != 3")
    _t, body, _ = framing.read_frame(e)
    err = framing.decode_error(body)
    assert err == {"code": framing.E_PAYLOAD_WIDTH,
                   "message": "width 4 != 3"}
    # data: fixed stride, vectorized both ways
    pay = np.arange(6, dtype=np.int32).reshape(2, 3)
    blob = framing.encode_data([0, 1], [10, 11], pay)
    assert len(blob) == 2 * framing.data_stride(3)
    rec = framing.decode_data(blob, 3)
    assert rec["sess"].tolist() == [0, 1]
    assert rec["seqno"].tolist() == [10, 11]
    assert rec["pay"].tolist() == pay.tolist()
    assert (rec["len"] == framing.data_stride(3) - 4).all()
    # credit: ONE encoder for the verdict surface
    c = framing.encode_credit(1, [0, 2], [5, 6], [OK, SHED])
    _t, body, _ = framing.read_frame(c)
    level, crec = framing.decode_credit(body)
    assert level == 1
    assert crec["sess"].tolist() == [0, 2]
    assert crec["status"].tolist() == [OK, SHED]
    k = framing.encode_ack([1], [99])
    _t, body, _ = framing.read_frame(k)
    arec = framing.decode_ack(body)
    assert arec["acked"].tolist() == [99]
    # partial frames: no complete frame -> None
    assert framing.read_frame(c[:3]) is None
    assert framing.read_frame(c[:-1]) is None


# ---------------------------------------------------------------------------
# machine-level dedup
# ---------------------------------------------------------------------------

def test_dedup_machine_batch_fold_matches_sequential():
    """The vectorized window fold must be EXACTLY order-equivalent to
    the sequential masked apply — duplicates, stale replays and
    inversions inside one fused window included."""
    import jax.numpy as jnp
    mac = DedupCounterMachine(slots=8)
    rng = np.random.default_rng(0)
    for _trial in range(8):
        n, a = 4, 12
        state = {"value": jnp.asarray(rng.integers(0, 5, n), jnp.int32),
                 "seq": jnp.asarray(rng.integers(0, 3, (n, 8)),
                                    jnp.int32)}
        cmds = np.zeros((n, a, 3), np.int32)
        cmds[..., 0] = rng.integers(-1, 9, (n, a))    # incl. bad slots
        cmds[..., 1] = rng.integers(0, 6, (n, a))     # dups + stale
        cmds[..., 2] = rng.integers(1, 5, (n, a))
        mask = rng.random((n, a)) < 0.8
        meta = {"index": jnp.zeros((n, a), jnp.int32),
                "term": jnp.zeros((n, 1), jnp.int32)}
        fast = mac.jit_apply_batch(meta, jnp.asarray(cmds),
                                   jnp.asarray(mask), state)
        slow = mac.sequential_window_fold(meta, jnp.asarray(cmds),
                                          jnp.asarray(mask), state)
        np.testing.assert_array_equal(np.asarray(fast["value"]),
                                      np.asarray(slow["value"]))
        np.testing.assert_array_equal(np.asarray(fast["seq"]),
                                      np.asarray(slow["seq"]))


def test_dedup_machine_host_path_dedups():
    mac = DedupCounterMachine(slots=4)
    state = mac.init({})
    from ra_tpu.core.machine import ApplyMeta
    meta = ApplyMeta(index=1, term=1)
    state, r = mac.apply(meta, (0, 1, 10), state)
    assert r == 10
    state, r = mac.apply(meta, (0, 1, 10), state)   # dup: skipped
    assert r == 10
    state, r = mac.apply(meta, (1, 1, 5), state)    # other slot
    assert r == 15
    state, r = mac.apply(meta, (0, 3, 1), state)    # fresh op
    assert r == 16


# ---------------------------------------------------------------------------
# listener: sweep, rings, protocol errors
# ---------------------------------------------------------------------------

def test_sweep_decodes_rings_into_one_ingress_batch():
    eng = mk_engine(lanes=16, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=None, max_conns=32, ring_bytes=2048)
    fleet = LoopbackFleet(lst, 8, sessions_per_conn=4, key="mux",
                          seed=0)
    assert fleet.n_sessions == 32
    fleet.new_ops(np.arange(32), np.ones(32, np.int32))
    fed = fleet.send_queued()
    assert fed == 32
    swept = lst.sweep()
    assert swept == 32
    fleet.collect()
    assert int((fleet.op_state[:32] == 2).sum()) == 32  # all PLACED
    assert plane.counters["accepted"] == 32
    assert lst.counters["credit_ok"] == 32
    assert lst.counters["sweeps"] == 1
    # drive to commit; acks release the replay window
    plane.pump(force=True)
    plane.settle()
    fleet.collect()
    assert fleet.acked_mask().all()
    assert lst.counters["ack_rows"] > 0
    eng.close()


def test_loopback_feed_backpressure_keeps_tail_queued():
    eng = mk_engine(lanes=4, cmds=4)
    plane = mk_plane(eng)
    stride = framing.data_stride(eng.payload_width)
    lst = WireListener(plane, port=None, max_conns=4,
                       ring_bytes=4 * stride)
    fleet = LoopbackFleet(lst, 1, key="tiny", seed=0)
    fleet.new_ops(np.zeros(10, np.int64), np.ones(10, np.int32))
    fed = fleet.send_queued()
    assert fed == 4                      # bounded ring: 4 records max
    assert len(fleet.queued_ops()) == 6  # tail stays queued (no loss)
    lst.sweep()
    fleet.collect()
    fed2 = fleet.send_queued()
    assert fed2 == 4
    eng.close()


def test_sweep_closes_conns_on_protocol_garbage():
    eng = mk_engine(lanes=4, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=None, max_conns=4, ring_bytes=2048)
    fleet = LoopbackFleet(lst, 2, key="bad", seed=0)
    stride = lst.stride
    garbage = bytes(range(stride))       # wrong len/type columns
    lst.loopback_feed(fleet.conns[:1], garbage, np.array([1]))
    base = len([e for e in RECORDER.events("wire")
                if e[1] == "wire.error"])
    swept = lst.sweep()
    assert swept == 0
    assert lst.counters["protocol_errors"] == 1
    assert lst.counters["conns_closed"] == 1
    assert int(lst.cstate[fleet.conns[0]]) == 0    # slot freed
    # garbage rows are protocol errors, NOT shed verdicts — they must
    # not pollute the credit histogram the bench keys derive from
    assert lst.counters["credit_shed"] == 0
    # the freed slot's ring accounting is CLEAN for its next tenant
    # (a negative rfill here would over-size the reused ring)
    assert int(lst.rfill[fleet.conns[0]]) == 0
    assert (lst.rfill >= 0).all()
    errs = [e for e in RECORDER.events("wire") if e[1] == "wire.error"]
    assert len(errs) >= base + 1
    # a fresh connection REUSING the freed slot works end to end
    fleet2 = LoopbackFleet(lst, 1, key="fresh", seed=1)
    assert int(fleet2.conns[0]) == int(fleet.conns[0])  # slot reused
    fleet2.new_ops(np.zeros(1, np.int64), np.full(1, 7, np.int32))
    assert fleet2.send_queued() == 1
    assert lst.sweep() == 1
    fleet2.collect()
    assert (fleet2.op_state[:1] == 2).all()
    eng.close()


def test_slot_reuse_does_not_cross_close_connections():
    """A disconnected client's key binding dies with its slot: after
    the slot is reused, the old key's reconnect must bind a NEW slot,
    not close the unrelated connection now living in the old one."""
    eng = mk_engine(lanes=8, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=0, max_conns=8, ring_bytes=4096)
    a = WireClient(lst.address, key="a")
    a.close()                          # EOF frees A's slot
    deadline = time.monotonic() + 10.0
    while lst.counters["conns_closed"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    b = WireClient(lst.address, key="b")   # LIFO: reuses A's slot
    a2 = WireClient(lst.address, key="a")  # A reconnects
    assert a2.epoch == 2
    # B is still alive and functional end to end
    b.enqueue(5)
    b.flush()
    _drive(lst, plane, b, want_acked=1)
    assert lst.counters["protocol_errors"] == 0
    lst.close()
    a2.close()
    b.close()
    eng.close()


# ---------------------------------------------------------------------------
# the socket path
# ---------------------------------------------------------------------------

def _drive(lst, plane, cli, *, want_acked, timeout=30.0):
    deadline = time.monotonic() + timeout
    while cli.acked_count() < want_acked:
        cli.flush()
        lst.sweep()
        plane.pump(force=True)
        plane.settle()
        cli.poll()
        assert time.monotonic() < deadline, \
            (cli.acked_count(), want_acked)


def test_socket_client_end_to_end_with_mux_and_reconnect():
    eng = mk_engine(lanes=16, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=0, max_conns=16, ring_bytes=4096)
    cli = WireClient(lst.address, key="acme/alice", n_sessions=3)
    assert cli.epoch == 1 and cli.slots is not None
    for i in range(12):
        cli.enqueue(i + 1, sess=i % 3)
    cli.flush()
    _drive(lst, plane, cli, want_acked=12)
    # reconnect: same key, bumped epoch, unacked window replays (empty
    # here), dedup slots stable
    old_slots = cli.slots.copy()
    cli.reconnect()
    assert cli.epoch == 2
    assert cli.slots.tolist() == old_slots.tolist()
    cli.enqueue(100, sess=0)
    cli.flush()
    _drive(lst, plane, cli, want_acked=13)
    total = int(np.asarray(
        eng.consistent_read(np.arange(16))["value"]).sum())
    assert total == sum(range(1, 13)) + 100
    assert lst.counters["hello_reconnects"] == 1
    lst.close()
    cli.close()
    eng.close()


def test_version_mismatch_refuses_connection():
    import socket
    import struct
    eng = mk_engine(lanes=4, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=0, max_conns=4, ring_bytes=2048)
    sock = socket.create_connection(lst.address, timeout=5.0)
    bad = bytearray(framing.encode_hello("v2-client", 1))
    bad[5] = framing.WIRE_VERSION + 1      # version byte inside HELLO
    sock.sendall(bytes(bad))
    sock.settimeout(5.0)
    # the refusal is LOUD: an ERR frame names the reason, then close
    buf, fr = b"", None
    deadline = time.monotonic() + 5.0
    while fr is None:
        assert time.monotonic() < deadline
        chunk = sock.recv(64)
        if not chunk:
            break
        buf += chunk
        fr = framing.read_frame(buf)
    assert fr is not None and fr[0] == framing.T_ERR
    err = framing.decode_error(fr[1])
    assert err["code"] == framing.E_VERSION
    assert sock.recv(64) == b""            # then the server closed it
    while lst.counters["protocol_errors"] == 0:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    sock.close()
    lst.close()
    eng.close()
    _ = struct  # (layout documented by the slice above)


def test_payload_width_mismatch_refused_with_protocol_error():
    """A client declaring a different DATA column count C must be
    refused at HELLO with a protocol error — NOT accepted and misparsed
    at the first data frame (the mixed-machine listener hazard)."""
    eng = mk_engine(lanes=4, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=0, max_conns=4, ring_bytes=2048)
    assert lst.payload_width == 3
    with pytest.raises(ConnectionError, match="payload_width"):
        WireClient(lst.address, key="wide/c1", payload_width=4)
    deadline = time.monotonic() + 5.0
    while lst.counters["protocol_errors"] == 0:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    # a correctly-declared client on the same listener still connects
    ok = WireClient(lst.address, key="wide/c2",
                    payload_width=lst.payload_width)
    assert ok.epoch == 1
    ok.close()
    lst.close()
    eng.close()


def test_refused_op_rekeys_and_is_not_lost():
    """The at-least-once correctness core: a shed op replayed under a
    stale id would be watermark-skipped; the client re-keys it.  Tiny
    coalescer ring forces the shed."""
    eng = mk_engine(lanes=2, cmds=2, ring=64, slots=8)
    plane = mk_plane(eng, superstep_k=1, capacity=2)
    lst = WireListener(plane, port=None, max_conns=4, ring_bytes=4096)
    fleet = LoopbackFleet(lst, 1, key="shed", seed=0)
    # burst far past the per-lane window capacity: most rows shed
    fleet.new_ops(np.zeros(32, np.int64), np.ones(32, np.int32))
    deadline = time.monotonic() + 30.0
    while fleet.unplaced_count() > 0:
        fleet.send_queued()
        lst.sweep()
        fleet.collect()
        plane.pump(force=True)
        fleet.collect()
        assert time.monotonic() < deadline
    plane.settle()
    fleet.collect()
    assert lst.counters["credit_shed"] > 0          # sheds DID happen
    lane = int(plane.directory.lane[fleet.handles[0]])
    val = int(np.asarray(eng.consistent_read([lane])["value"])[0])
    assert val == 32                                # exactly once each
    assert fleet.acked_mask().all()
    eng.close()


def test_crash_reconnect_replays_exactly_once():
    """A client that crashes WITHOUT draining verdicts or acks:
    reconnect bumps the epoch, the server replays the authoritative
    committed watermarks in the handshake, and the unacked window
    replays under its original ids — the machine dedup absorbs every
    duplicate, so each op applies exactly once."""
    eng = mk_engine(lanes=8, cmds=4, slots=8)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=0, max_conns=8, ring_bytes=4096)
    cli = WireClient(lst.address, key="crash/c1")
    for i in range(6):
        cli.enqueue(i + 1)
    cli.flush()
    deadline = time.monotonic() + 30.0
    while lst.counters["swept_rows"] < 6:
        lst.sweep()
        assert time.monotonic() < deadline
        time.sleep(0.005)
    plane.pump(force=True)
    plane.settle()
    # crash: verdicts + acks never read; redial under the same key
    cli._rx = b""
    cli.close(keep_state=True)
    cli._connect()
    assert cli.epoch == 2
    assert len(cli._queued) == 6      # the whole unacked window replays
    cli.poll()                        # handshake watermark replay
    assert int(cli.watermark[0]) == 6
    _drive(lst, plane, cli, want_acked=6)
    total = int(np.asarray(
        eng.consistent_read(np.arange(8))["value"]).sum())
    assert total == sum(range(1, 7))     # dedup'd: exactly once each
    lst.close()
    cli.close()
    eng.close()


def test_lost_verdict_window_replays_gap_free():
    """The one-batch-per-session flush gate: with verdicts LOST, the
    client refuses to layer new ops above the in-flight window — so a
    crash replay under original ids is a send-order suffix, and even
    shed ops inside the lost window apply exactly once."""
    eng = mk_engine(lanes=2, cmds=2, ring=64, slots=8)
    plane = mk_plane(eng, superstep_k=1, capacity=2)
    lst = WireListener(plane, port=0, max_conns=4, ring_bytes=4096)
    cli = WireClient(lst.address, key="lostv/c1")
    # overload the 2-deep lane window in one burst: the tail SHEDS
    for i in range(8):
        cli.enqueue(i + 1)
    assert cli.flush() == 8
    deadline = time.monotonic() + 30.0
    while lst.counters["swept_rows"] < 8:
        lst.sweep()
        assert time.monotonic() < deadline
        time.sleep(0.005)
    assert lst.counters["credit_shed"] > 0
    plane.pump(force=True)
    plane.settle()
    # the verdicts are LOST (never read).  The gate: new ops must NOT
    # be sent past the in-flight window, or a later commit would make
    # the shed ops' old-id replay watermark-skippable
    cli._rx = b""
    cli.enqueue(100)
    assert cli.flush() == 0          # session busy: held, not sent
    assert cli.pending_count() == 9  # 8 in flight + 1 held
    # crash-reconnect: epoch bump replays the WHOLE unacked window
    # under original ids (a gap-free suffix), watermarks replayed in
    # the handshake
    cli.reconnect()
    _drive(lst, plane, cli, want_acked=9)
    total = int(np.asarray(
        eng.consistent_read(np.arange(2))["value"]).sum())
    assert total == sum(range(1, 9)) + 100   # every op exactly once
    lst.close()
    cli.close()
    eng.close()


# ---------------------------------------------------------------------------
# FifoClient unification (one verdict enum, one encoder)
# ---------------------------------------------------------------------------

def test_fifo_client_speaks_the_shared_verdict_enum():
    """ISSUE 12 satellite: FifoClient's ok→slow→StopSending ladder is
    the wire credit protocol — same enum values, same encoder, and
    the pinned ``blocked_since``/``ingress_rejections`` semantics are
    untouched (their behavior pins live in test_fifo_machine)."""
    from ra_tpu.models import StopSending
    from ra_tpu.models.fifo_client import FifoClient
    assert StopSending.VERDICT == REJECT
    cli = FifoClient.__new__(FifoClient)       # no cluster needed
    cli.pending = {}
    cli.next_seqno = 5
    cli.soft_limit = 2
    cli.max_pending = 4
    cli._applied = type("M", (), {"drain": staticmethod(lambda: [])})()
    assert cli.current_verdict() == OK
    cli.pending = {1: "a", 2: "b"}
    assert cli.current_verdict() == SLOW
    cli.pending = {1: "a", 2: "b", 3: "c", 4: "d"}
    assert cli.current_verdict() == REJECT
    # ONE encoder: the client's episode decodes as a wire credit frame
    t, body, _ = framing.read_frame(cli.credit_frame())
    assert t == framing.T_CREDIT
    _level, rec = framing.decode_credit(body)
    assert rec["status"].tolist() == [REJECT]
    assert rec["seqno"].tolist() == [4]
    # enum names are the single source of the documented strings
    assert framing.STATUS_NAMES[OK] == "ok"
    assert framing.STATUS_NAMES[SLOW] == "slow"
    assert framing.STATUS_NAMES[:6] == ("ok", "slow", "defer",
                                        "reject", "dup", "shed")
    assert (OK, SLOW, DEFER, REJECT, DUP, SHED) == (0, 1, 2, 3, 4, 5)


# ---------------------------------------------------------------------------
# reconnect-storm dedup (single-device AND mesh)
# ---------------------------------------------------------------------------

def _storm_scenario(shard_mesh: bool) -> None:
    from ra_tpu.transport.rpc import FaultPlan, FaultSpec
    eng = mk_engine(lanes=32, cmds=8, ring=256, slots=128)
    if shard_mesh:
        import jax

        from ra_tpu.parallel.mesh import shard_engine_state
        if len(jax.devices()) < 2:
            pytest.skip("single-device backend")
        shard_engine_state(eng)
    plane = mk_plane(eng, superstep_k=2)
    lst = WireListener(plane, port=None, max_conns=512,
                       ring_bytes=4096)
    fleet = LoopbackFleet(lst, 400, sessions_per_conn=2, key="storm",
                          tenants=4, seed=3, max_ops=1 << 16)
    plan = FaultPlan(seed=3, default=FaultSpec(drop=0.1))
    rng = np.random.default_rng(3)
    try:
        requeued = None
        for w in range(8):
            fleet.new_ops(rng.integers(0, fleet.n_sessions, 2000),
                          rng.integers(1, 8, 2000).astype(np.int32))
            fleet.send_queued()
            lst.sweep()
            fleet.collect()
            plane.pump(force=True)
            fleet.collect()
            if w == 4:
                # kill 40% of connections MID-FLIGHT: unswept ring
                # bytes lost, epochs bump, unacked window replays
                # under fresh seqnos
                requeued = fleet.storm(0.4)
        assert requeued is not None and len(requeued) > 0
        deadline = time.monotonic() + 60.0
        while fleet.unplaced_count() > 0:
            fleet.send_queued()
            lst.sweep()
            fleet.collect()
            plane.pump(force=True)
            fleet.collect()
            assert time.monotonic() < deadline
        plane.settle()
        fleet.collect()
        # the oracle: no duplicate machine apply, no lost acked op
        expected = fleet.expected_lane_sums(32)
        got = np.asarray(
            eng.consistent_read(np.arange(32))["value"]).astype(np.int64)
        np.testing.assert_array_equal(got, expected)
        ranked = fleet.op_rank[:fleet.n_ops] >= 0
        assert fleet.acked_mask()[ranked].all()
        # duplicates WERE created and absorbed (the storm replayed
        # placed-but-unacked rows)
        assert lst.counters["swept_rows"] > fleet.n_ops
        assert plane.counters["reconnects"] > 0
    finally:
        plan.unregister()
        eng.close()


def test_reconnect_storm_dedup_single_device():
    _storm_scenario(shard_mesh=False)


def test_reconnect_storm_dedup_sharded_mesh():
    _storm_scenario(shard_mesh=True)


# ---------------------------------------------------------------------------
# the ladder acceptance rung (tier-1 twin; full C1M behind -m slow)
# ---------------------------------------------------------------------------

def test_wire_ladder_100k_conns_durable_beats_10x_classic(tmp_path):
    """The ISSUE 12 acceptance bar, tier-1 scaled: ≥100k concurrent
    connections through a DURABLE engine sustaining ≥10x the
    classic-TCP baseline end to end, bounded per-connection buffers,
    shed fairness, reconnect-storm recovery, exactly-once-observable
    oracle.  One retry absorbs shared-CI weather (the bench tests'
    pattern)."""
    bar = 10 * CLASSIC_TCP_BASELINE
    try:
        res = run_wire_soak(0, conns=100_000, lanes=512, waves=6,
                            wave_ops=50_000, cmds=16, superstep_k=4,
                            durable_dir=str(tmp_path / "w"),
                            wal_shards=2, throughput_bar=bar)
    except AssertionError:  # pragma: no cover — CI load
        res = run_wire_soak(0, conns=100_000, lanes=512, waves=6,
                            wave_ops=50_000, cmds=16, superstep_k=4,
                            durable_dir=str(tmp_path / "w2"),
                            wal_shards=2, throughput_bar=bar)
    assert res["conns"] >= 100_000 and res["durable"]
    assert res["wire_cmds_per_s"] >= bar
    assert res["storm_requeued"] > 0
    assert res["wire_reconnect_recovery_s"] >= 0
    if res["wire_shed_fairness"] >= 0:
        assert res["wire_shed_fairness"] < 3.0


def test_wire_soak_cpu_scaled_with_sockets_and_disk_faults(tmp_path):
    """The C10k-shaped rung, CPU-scaled for tier-1: loopback fleet +
    real-socket side-car, durable with a seeded DiskFaultPlan, storm,
    oracle exact (tools/soak.py --wire runs the full ladder)."""
    res = run_wire_soak(1, conns=4_000, lanes=128, waves=6,
                        wave_ops=8_000, cmds=8, superstep_k=2,
                        socket_conns=4, socket_ops=8,
                        durable_dir=str(tmp_path / "w"),
                        disk_faults=True, wal_shards=2)
    assert res["durable"] and res["socket_conns"] == 4
    assert res["dup_rows_absorbed"] >= 0
    assert res["wire_swept_rows"] > res["ops"] > 0


@pytest.mark.slow
def test_wire_ladder_full_c1m(tmp_path):
    """The full C1M rung: a million concurrent wire connections into
    the coalescer, durable, reconnect storm, exactly-once-observable
    (tools/soak.py --wire --c1m runs the same entry)."""
    res = run_wire_soak(0, conns=1_000_000, lanes=1024, waves=12,
                        wave_ops=500_000, cmds=16, superstep_k=4,
                        ring_records=16,
                        durable_dir=str(tmp_path / "w"), wal_shards=2,
                        throughput_bar=10 * CLASSIC_TCP_BASELINE)
    assert res["conns"] == 1_000_000


def test_recovery_reseeds_dedup_slots_across_generations(tmp_path):
    """Machine state is durable, the session/slot directory is not: a
    listener over a RECOVERED engine must skip the dead generation's
    per-lane dedup slots, or a fresh client's early ops would be
    falsely deduped against a dead client's watermark (found by the
    verify probe, not the soak — the soak never reopens)."""
    from ra_tpu.engine import open_engine
    mac = DedupCounterMachine(slots=64)
    d = str(tmp_path / "w")
    eng = open_engine(mac, d, 16, wal_shards=2, ring_capacity=256,
                      max_step_cmds=8, donate=False)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=None, max_conns=64, ring_bytes=2048)
    f = LoopbackFleet(lst, 32, key="gen1", seed=0)
    f.new_ops(np.arange(32), np.full(32, 3, np.int32))
    f.send_queued()
    lst.sweep()
    f.collect()
    plane.pump(force=True)
    plane.settle()
    expected = f.expected_lane_sums(16)
    eng._dur.flush_all()
    lst.close()
    eng.checkpoint()
    eng.close()
    # reopen under a DIFFERENT shard layout: dedup watermarks recover
    eng2 = open_engine(mac, d, 16, wal_shards=4, ring_capacity=256,
                       max_step_cmds=8, donate=False)
    got = np.asarray(
        eng2.consistent_read(np.arange(16))["value"]).astype(np.int64)
    np.testing.assert_array_equal(got, expected)
    plane2 = mk_plane(eng2)
    lst2 = WireListener(plane2, port=None, max_conns=64,
                        ring_bytes=2048)
    assert (lst2._lane_next > 0).any()   # recovered cursor seeded
    f2 = LoopbackFleet(lst2, 32, key="gen2", seed=1)
    for i in range(32):  # no fresh slot collides with a dead watermark
        lane = int(plane2.directory.lane[f2.handles[i]])
        wm = int(np.asarray(eng2.consistent_read([lane])["seq"])
                 [0][int(f2.slots[i])])
        assert wm == 0, (i, wm)
    f2.new_ops(np.arange(32), np.full(32, 5, np.int32))
    f2.send_queued()
    lst2.sweep()
    f2.collect()
    plane2.pump(force=True)
    plane2.settle()
    f2.collect()
    got2 = np.asarray(
        eng2.consistent_read(np.arange(16))["value"]).astype(np.int64)
    np.testing.assert_array_equal(got2,
                                  expected + f2.expected_lane_sums(16))
    assert f2.acked_mask().all()
    lst2.close()
    eng2.close()


# ---------------------------------------------------------------------------
# observability wiring
# ---------------------------------------------------------------------------

def test_wire_fields_ride_the_observatory():
    from ra_tpu.telemetry import Observatory, parse_prometheus
    eng = mk_engine(lanes=16, cmds=4)
    plane = mk_plane(eng)
    lst = WireListener(plane, port=None, max_conns=32, ring_bytes=2048)
    fleet = LoopbackFleet(lst, 8, key="obs", seed=0)
    fleet.new_ops(np.arange(8), np.ones(8, np.int32))
    fleet.send_queued()
    lst.sweep()
    fleet.collect()
    plane.pump(force=True)
    plane.settle()
    obs = Observatory.for_engine(eng)
    lst.attach(obs)
    try:
        snap = obs.snapshot()
        assert snap["wire"]["swept_rows"] == 8
        assert snap["wire"]["conns"] == 8
        flat = parse_prometheus(obs.prometheus())
        assert flat[("ra_tpu_wire_swept_rows", "")] == 8
        assert ("ra_tpu_wire_credit_ok", "") in flat
        obs.snapshot()
        rates = obs.window_rates()
        assert any(k.startswith("wire_") for k in rates)
    finally:
        obs.close()
    eng.close()


def test_ra_top_renders_wire_panel(tmp_path):
    """ra_top shows the wire tier: record rate over the window, conn
    pool, and the credit-level histogram."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = {"conns": 100_000, "socket_conns": 64, "paused_conns": 2,
            "swept_rows": 1_000, "protocol_errors": 1,
            "credit_ok": 900, "credit_slow": 50, "credit_defer": 0,
            "credit_reject": 10, "credit_dup": 20, "credit_shed": 20}
    t0 = time.time()
    snap0 = {"seq": 1, "ts": t0 - 1.0,
             "engine": {"lanes": 16, "members": 3}, "wire": base}
    snap1 = {"seq": 2, "ts": t0,
             "engine": {"lanes": 16, "members": 3},
             "wire": {**base, "swept_rows": 51_000,
                      "credit_ok": 50_000, "credit_shed": 420}}
    path = str(tmp_path / "obs.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(snap0) + "\n")
        f.write(json.dumps(snap1) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ra_top.py"),
         path, "--once"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "wire" in out and "conns=100000" in out
    assert "sock=64" in out and "paused=2" in out
    assert "ok=49100" in out        # window delta, not lifetime total
    assert "shed=400" in out
    assert "errs=1" in out
    assert "rec/s" in out
