"""On-chip bring-up smoke: the served path, once, on the attached TPU.

    python chip_smoke.py          # from the checkout root, no arguments

One process (a chip belongs to one process at a time) drives the main
path through the entry points a user calls — ``open_engine``,
``IngressPlane``, ``WireListener``, ``WireClient``, ``LoopbackFleet``,
``read_lanes`` — at the width BASELINE.json and the README name, and
checks what comes out by the repo's own means: the exactly-once oracle,
replica equality, the fsynced watermark, the recompile sentinel,
recovery from disk.  Each phase prints ``pass``, ``FAIL`` or
``skipped (<reason>)``; any failure or exception exits non-zero.  The
last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``main()`` refuses any platform but ``tpu`` (exit 2, no result line):
JAX falls back to the CPU by itself when it finds no chip, and a
number from that run would be a lie.  The phase functions take their
sizes, so tests/test_chip_smoke.py runs them small on the CPU.

What it prints besides the verdicts is a record, not a metric: wall
time per phase, seconds spent compiling, peak device memory, the WAL's
I/O path and fsync median.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: WAL scratch inside the checkout (git-ignored): the checkout sits on
#: the machine's disk, where /tmp may be a tmpfs that makes fsync free
WAL_ROOT = os.path.join(HERE, ".chip_smoke_wal")

#: 32-bit patterns that exercise both pieces of split16_matmul
#: (ops/exact.py: the low 24 bits and the top byte): all ones, every
#: bit of the low piece, the first bit of the top one, low pieces that
#: round under fewer bf16 passes; then the sign bit, whose top bytes
#: (128..255) are the edge of what the top byte's one bf16 pass holds
EXACT_VALUES = np.asarray(
    (0x7FFFFFFF, 0x00FFFFFF, 0x01000000, 0x0000FFFF, 0x7FFF8001,
     0x00018000, 0xFFFFFFFF, 0x80000000, 0x81000000, 0xFE000001),
    np.uint32).view(np.int32)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), and how many fetches hit — summed over
    the whole process from jax.monitoring's own events."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in self._EVENTS:
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require(ok, msg: str) -> None:
    """The checks are this program's product, so they raise: ``assert``
    is compiled out under ``python -O`` and the smoke would pass empty."""
    if not ok:
        raise AssertionError(msg)


def _fs_type(path: str) -> str:
    """Filesystem type of the mount holding ``path`` (/proc/mounts,
    longest mount-point prefix)."""
    real = os.path.realpath(path)
    best, fstype = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, fstype = mnt, typ
    except OSError:
        pass
    return fstype


def _peak_device_bytes():
    """Device 0's allocator high-water mark since the process started
    (None where the backend keeps no stats, as the CPU's)."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


def _device_stamp() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(wal_dir: str, cache_dir: str) -> dict:
    """What this run stands on: device, versions, compile cache, WAL
    I/O path and the filesystem under the WAL."""
    import importlib.metadata as md

    import jax

    from ra_tpu import native

    def version(pkg: str) -> str:
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "absent"

    os.makedirs(wal_dir, exist_ok=True)
    fstype = _fs_type(wal_dir)
    out = {
        **_device_stamp(),
        "jax": jax.__version__, "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
        "compile_cache_dir": cache_dir,
        "wal_io_path": "native" if native.IO.native else "python",
        "wal_dir": wal_dir, "wal_fs": fstype,
        # where else a WAL could go on this machine, for whoever picks
        # the benchmark's directory
        "tmp_fs": _fs_type(tempfile.gettempdir()),
    }
    if native.BUILD_ERROR:
        out["wal_native_build_error"] = native.BUILD_ERROR
    if fstype in ("tmpfs", "ramfs"):
        out["note"] = "WAL on a memory filesystem: fsync is free here"
    return out


def _cycle(fleet, lst, plane) -> None:
    """One pump of the whole loop: fleet send -> sweep -> credit ->
    dispatch -> ack."""
    fleet.send_queued()
    lst.sweep()
    fleet.collect()
    plane.pump(force=True)
    fleet.collect()


def _check_replicas_equal(eng) -> None:
    """Every ACTIVE member of a lane holds the leader's machine state
    and apply frontier."""
    import jax
    st = eng.state
    active = np.asarray(st.active)
    lane = np.arange(eng.n_lanes)
    lead = np.asarray(st.leader_slot)
    applied = np.asarray(st.applied)
    bad = active & (applied != applied[lane, lead][:, None])
    require(not bad.any(),
            f"{int(bad.sum())} active replicas behind their leader's apply")
    for leaf in jax.tree.leaves(st.mac):
        x = np.asarray(leaf)
        ref = x[lane, lead][:, None]
        m = active.reshape(active.shape + (1,) * (x.ndim - 2))
        diff = m & (x != ref)
        require(not diff.any(),
                f"{int(diff.sum())} replica cells differ from the leader's")


def _check_commit_below_fsync(eng) -> None:
    """No commit reported above the fsynced watermark.  Commit is read
    FIRST: the watermark only grows, so a later reading of it can only
    be more permissive than the one the step saw."""
    st = eng.state
    com = np.asarray(st.commit).max(axis=1)
    confirm = eng._dur.confirm_upto
    over = com > confirm
    require(not over.any(),
            f"{int(over.sum())} lanes committed above the fsynced watermark")


def _check_oracle(eng, expected: np.ndarray) -> None:
    """Exactly-once: every lane's counter equals the sum of every
    acknowledged op's delta, on the leader through a consistent read
    and on every active replica."""
    got = np.asarray(eng.consistent_read(
        np.arange(eng.n_lanes))["value"]).astype(np.int64)
    wrong = np.flatnonzero(got != expected)
    require(not len(wrong),
            f"exactly-once oracle: {len(wrong)} of {eng.n_lanes} lanes "
            f"differ (first: {wrong[:4].tolist()})")
    _check_replicas_equal(eng)


def _serve(eng, out: dict, *, conns: int, waves: int, wave_ops: int,
           chaos_lanes: int, socket_ops: int, superstep_k: int,
           seed: int) -> np.ndarray:
    """Serve ``waves`` waves over ``eng`` with leader failures in the
    middle, check the run, checkpoint, serve one more wave that only
    the WAL holds.  Returns the oracle's per-lane sums; closes the
    clients and the listener, not the engine."""
    from ra_tpu import devicewatch
    from ra_tpu.ingress import IngressPlane
    from ra_tpu.wire.client import LoopbackFleet, WireClient
    from ra_tpu.wire.framing import data_stride
    from ra_tpu.wire.server import WireListener

    rng = np.random.default_rng(seed)
    lanes = eng.n_lanes
    # the credits are out of the way on purpose, so the staging rings
    # are sized here: left to the plane they follow ``hard_credit``
    # (since PR 27), a million rows a lane
    plane = IngressPlane(eng, superstep_k=superstep_k, window_s=0.001,
                         capacity=2 * superstep_k * eng.max_step_cmds,
                         soft_credit=1 << 20, hard_credit=1 << 20)
    lst = WireListener(plane, port=0, max_conns=conns + 16,
                       ring_bytes=32 * data_stride(eng.payload_width))
    cli = None
    try:
        fleet = LoopbackFleet(lst, conns, key="smoke", tenants=16,
                              seed=seed,
                              max_ops=(waves + 3) * wave_ops + 1024)
        require(int(fleet.slots.max()) < eng.machine.slots,
                "dedup slot overflow")
        cli = WireClient(lst.address, key="smoke/sock")
        sock_lane = int(plane.directory.lane[cli.handle_base])

        def wave() -> None:
            fleet.new_ops(rng.integers(0, fleet.n_sessions, wave_ops),
                          rng.integers(1, 8, wave_ops).astype(np.int32))
            _cycle(fleet, lst, plane)
            for _ in range(socket_ops):
                cli.enqueue(int(rng.integers(1, 8)))
            cli.flush()
            cli.poll()  # prompt verdict processing: refusals re-key

        def drain() -> None:
            deadline = time.monotonic() + 120.0
            while fleet.unplaced_count() > 0:
                _cycle(fleet, lst, plane)
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"drain: {fleet.unplaced_count()} ops unplaced")
            plane.settle(timeout=120.0)
            fleet.collect()
            deadline = time.monotonic() + 60.0
            while cli.pending_count() or cli.unacked_count():
                cli.flush()
                lst.sweep()
                plane.pump(force=True)
                plane.settle(timeout=120.0)
                cli.poll()
                if time.monotonic() > deadline:
                    raise TimeoutError("socket client drain")

        def expected() -> np.ndarray:
            sums = fleet.expected_lane_sums(lanes)
            sums[sock_lane] += sum(cli.op_pay)
            return sums

        # warm-up: compile the fused step, the settle block and the
        # single step (zero-delta ops leave the oracle untouched)
        t0 = time.perf_counter()
        n_warm = min(1024, wave_ops)
        fleet.new_ops(rng.integers(0, fleet.n_sessions, n_warm),
                      np.zeros(n_warm, np.int32))
        _cycle(fleet, lst, plane)
        plane.settle(timeout=600.0)
        fleet.collect()
        eng.consistent_read([0])
        out["warmup_s"] = round(time.perf_counter() - t0, 3)
        eng.phases.reset_reservoirs()   # compile time out of the p50s
        recompiles0 = devicewatch.WATCH.counters["recompiles"]

        victims = rng.choice(lanes, size=min(chaos_lanes, lanes),
                             replace=False)
        deposed = None
        t0 = time.perf_counter()
        for w in range(waves):
            wave()
            _check_commit_below_fsync(eng)
            if w == 0:
                # a leader kill Raft-legally truncates a placed but
                # unfsynced tail (docs/INGRESS.md "Delivery
                # guarantees"): settle, then fail
                plane.settle(timeout=120.0)
                fleet.collect()
                deposed = np.asarray(eng.state.leader_slot)[victims]
                for lane, slot in zip(victims.tolist(), deposed.tolist()):
                    eng.fail_member(lane, slot)
                eng.trigger_election(victims)
            elif w == waves - 2:
                plane.settle(timeout=120.0)
                fleet.collect()
                lead = np.asarray(eng.state.leader_slot)[victims]
                require((lead != deposed).all(),
                        "a failed leader kept its lane")
                eng.recover_member(int(victims[0]), int(deposed[0]))
                eng.recover_members(victims[1:], deposed[1:])
        drain()
        out["serve_s"] = round(time.perf_counter() - t0, 3)
        _check_commit_below_fsync(eng)
        _check_oracle(eng, expected())
        ranked = fleet.op_rank[:fleet.n_ops] >= 0
        acked = fleet.acked_mask()
        require(acked[ranked].all(),
                f"{int((~acked[ranked]).sum())} ranked ops never acked")
        require(cli.acked_count() == len(cli.op_state),
                "socket client ops never acked")
        require(int(lst.rfill.max(initial=0)) == 0,
                "listener rings not drained")
        require(np.asarray(eng.state.active).all(),
                "a failed member never came back")
        recompiles = devicewatch.WATCH.counters["recompiles"] - recompiles0
        require(recompiles == 0,
                f"{recompiles} recompiles after warm-up: "
                f"{dict(devicewatch.WATCH.per_fn)}")
        out["ops"] = int(fleet.n_ops) + len(cli.op_state)
        out["dup_rows_absorbed"] = int(
            lst.counters["swept_rows"] - fleet.n_ops - len(cli.op_state))
        wal = eng.overview()["wal"]
        out["wal_io_path"] = wal["io_path"]
        out["fsync_p50_ms"] = [s["fsync_p50_ms"] for s in wal["shards"]]
        out["records_per_fsync"] = [s["records_per_fsync"]
                                    for s in wal["shards"]]
        # host-clock medians of the engine's own phase stamps over the
        # serving window (metrics.PHASE_FIELDS): where S1 starts looking
        out["phase_p50_ms"] = {
            name: ph["p50_ms"] for name, ph in eng.phases.overview().items()
            if isinstance(ph, dict) and ph["count"]}

        eng.checkpoint()
        wave()
        drain()
        return expected()
    finally:
        if cli is not None:
            cli.close()
        lst.close()


def phase_served_path(wal_dir: str, *, lanes: int = 10_000,
                      members: int = 5, conns: int = 20_000,
                      waves: int = 4, wave_ops: int = 50_000,
                      chaos_lanes: int = 32, socket_ops: int = 16,
                      ring_capacity: int = 1024, cmds: int = 16,
                      superstep_k: int = 4, wal_shards: int = 4,
                      reopen_wal_shards: int = 2, seed: int = 0,
                      mesh_devices: int = 0) -> dict:
    """Wire clients -> listener sweep -> ingress -> fused durable
    dispatch -> fsync-gated commit -> ACK, with leader failures in the
    middle; then recovery: a checkpoint, one more acknowledged wave
    that only the WAL holds, and a cold reopen under another shard
    layout — every acknowledged write is read back.  ``ring_io``,
    ``donate`` and ``superstep_donate`` stay at the engine's defaults:
    what ``"auto"`` resolves to on this backend is what runs.
    ``mesh_devices > 0`` shards the state 1 x that many (lanes over
    devices, one WAL shard per device)."""
    import jax

    from ra_tpu.engine import open_engine
    from ra_tpu.wire.dedup import DedupCounterMachine

    machine = DedupCounterMachine(
        slots=4 * max(1, (conns + 1) // lanes) + 64)
    mesh = None
    if mesh_devices:
        from ra_tpu.parallel.mesh import (lane_mesh, per_device_wal_shards,
                                          shard_engine_state)
        mesh = lane_mesh(jax.devices()[:mesh_devices], member_axis=1)
        wal_shards = per_device_wal_shards(mesh)
    engine_kw = dict(sync_mode=1, ring_capacity=ring_capacity,
                     max_step_cmds=cmds)
    eng = open_engine(machine, wal_dir, lanes, members,
                      wal_shards=wal_shards, **engine_kw)
    out: dict = {"ring_io": eng.ring_io, "wal_shards": wal_shards}
    try:
        if mesh is not None:
            shard_engine_state(eng, mesh)
            per_dev = {s.data.shape[0]
                       for s in eng.state.ring.addressable_shards}
            require(per_dev == {lanes // mesh_devices},
                    f"ring shards hold {per_dev} lanes, want "
                    f"{lanes // mesh_devices} each")
            out["mesh"] = eng.mesh_shape()
        expected = _serve(eng, out, conns=conns, waves=waves,
                          wave_ops=wave_ops, chaos_lanes=chaos_lanes,
                          socket_ops=socket_ops, superstep_k=superstep_k,
                          seed=seed)
    finally:
        eng.close()
    t0 = time.perf_counter()
    eng = open_engine(machine, wal_dir, lanes, members,
                      wal_shards=reopen_wal_shards, **engine_kw)
    try:
        _check_oracle(eng, expected)
    finally:
        eng.close()
    out["recover_s"] = round(time.perf_counter() - t0, 3)
    out["reopen_wal_shards"] = reopen_wal_shards
    out["peak_device_bytes"] = _peak_device_bytes()
    return out


def _drain_engine(eng, limit: int = 64) -> None:
    """Empty rounds until every active member has applied its leader's
    whole log."""
    n, k, c = eng.n_lanes, eng.max_step_cmds, eng.payload_width
    zero_n = np.zeros((n,), np.int32)
    zero_p = np.zeros((n, k, c), eng.payload_dtype)
    lane = np.arange(n)
    for _ in range(limit):
        st = eng.state
        tail = np.asarray(st.last_index)[lane, np.asarray(st.leader_slot)]
        behind = np.asarray(st.active) & \
            (np.asarray(st.applied) < tail[:, None])
        if not behind.any():
            return
        eng.step(zero_n, zero_p)
    raise TimeoutError("engine drain did not converge")


def phase_reads_exact(*, lanes: int = 2_000, members: int = 5,
                      n_keys: int = 64, cmds: int = 16,
                      seed: int = 0) -> dict:
    """32-bit values through the default ring I/O and the KV fold, read
    back bit-exact through the read plane; words over the whole int32
    range through the append (read in the ring) and through the window
    read (a counter's sum); then a leader cut from its followers must
    refuse, not serve stale."""
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine, JitKvMachine

    rng = np.random.default_rng(seed)
    eng = LockstepEngine(JitKvMachine(n_keys=n_keys), lanes, members,
                         max_step_cmds=cmds)
    # a KV cell holds a value >= 0 (-1 is "absent"): the stored values
    # stop at 31 bits, and the sign bit rides in the word a put ignores
    values = rng.integers(0, 1 << 31, (lanes, n_keys)).astype(np.int32)
    stored = EXACT_VALUES[EXACT_VALUES >= 0][:n_keys]
    values[:, :len(stored)] = stored
    words = rng.integers(-(1 << 31), 1 << 31,
                         (lanes, n_keys)).astype(np.int32)
    words[:, :min(len(EXACT_VALUES), n_keys)] = EXACT_VALUES[:n_keys]
    n_new = np.full((lanes,), cmds, np.int32)
    for k0 in range(0, n_keys, cmds):
        keys = np.arange(k0, min(k0 + cmds, n_keys))
        pay = np.zeros((lanes, cmds, 4), np.int32)
        pay[:, :len(keys), 0] = 1                       # put
        pay[:, :len(keys), 1] = keys
        pay[:, :len(keys), 2] = values[:, keys]
        pay[:, :len(keys), 3] = words[:, keys]
        n_new[:] = len(keys)
        eng.step(n_new, pay)
    _drain_engine(eng)
    all_lanes = np.arange(lanes)
    mismatches = 0
    for key in range(n_keys):
        q = np.tile(np.asarray([[1, key]], np.int32), (lanes, 1))
        replies, wm, ok = eng.read_lanes(all_lanes, q)
        require(ok.all() and (wm >= 0).all(),
                f"key {key}: {int((~ok).sum())} lanes refused")
        mismatches += int(((replies[:, 0] != 1) |
                           (replies[:, 1] != values[:, key])).sum())
    require(mismatches == 0,
            f"{mismatches} of {lanes * n_keys} values read back wrong "
            f"(ring_io={eng.ring_io}: split16_matmul not exact here?)")
    ring = np.asarray(eng.state.ring)
    put_lane, put_slot = np.nonzero(ring[:, :, 0] == 1)
    puts = ring[put_lane, put_slot]
    require(len(puts) == lanes * n_keys and
            (puts[:, 3] == words[put_lane, puts[:, 1]]).all(),
            "the ring does not hold the appended words bit for bit "
            f"(ring_io={eng.ring_io})")
    counter = LockstepEngine(CounterMachine(), lanes, members,
                             max_step_cmds=cmds)
    for k0 in range(0, n_keys, cmds):
        incs = words[:, k0:k0 + cmds, None]
        counter.step(np.full((lanes,), incs.shape[1], np.int32), incs)
    _drain_engine(counter)
    sums, _wm, ok = counter.read_lanes(all_lanes,
                                       np.zeros((lanes, 1), np.int32))
    require(ok.all() and (sums[:, 0] == words.sum(
        axis=1, dtype=np.int32)).all(),
            "words over the int32 range were not read from the ring "
            f"bit for bit (ring_io={counter.ring_io})")
    # cut lane 0's leader from every follower, burn the lease
    lead = int(np.asarray(eng.state.leader_slot)[0])
    for slot in range(members):
        if slot != lead:
            eng.fail_member(0, slot)
    zero_n = np.zeros((lanes,), np.int32)
    zero_p = np.zeros((lanes, cmds, 4), np.int32)
    for _ in range(3 * eng.lease_ttl):
        eng.step(zero_n, zero_p)
    q = np.tile(np.asarray([[1, 0]], np.int32), (lanes, 1))
    replies, wm, ok = eng.read_lanes(all_lanes, q)
    require(not ok[0] and wm[0] == -1,
            "a leader cut from its majority served past its lease")
    require(ok[1:].all() and (replies[1:, 1] == values[1:, 0]).all(),
            "healthy lanes stopped serving")
    return {"ring_io": eng.ring_io, "values_checked": lanes * n_keys,
            "stale_refusals": int(np.asarray(eng.state.read_stale).sum())}


class Skip(Exception):
    """A phase that does not apply here; the reason is printed."""


def phase_mesh4(wal_dir: str, *, devices: int = 4, **sizes) -> dict:
    """The served path with state sharded 1 x ``devices``.  The engine
    builds the whole state on device 0 before ``device_put`` spreads
    it, so ``peak_device_bytes`` (device 0) is the number to watch."""
    import jax
    n = len(jax.devices())
    if n < devices:
        raise Skip(f"{n} device" + ("" if n == 1 else "s"))
    return phase_served_path(wal_dir, mesh_devices=devices, **sizes)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_phases(phases, clock=None) -> bool:
    """Run ``(name, fn)`` pairs in order; print one verdict line each.
    Returns True iff no phase failed.  A phase that raises FAILS — the
    traceback is printed and the remaining phases still run, so one
    run reports everything it can."""
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        c0 = clock.seconds if clock else 0.0
        try:
            detail = fn() or {}
            verdict = "pass"
        except Skip as skip:
            detail, verdict = {}, f"skipped ({skip})"
        except Exception:  # noqa: BLE001 — the runner's boundary: report, fail, go on
            traceback.print_exc(file=sys.stdout)
            detail, verdict, ok = {}, "FAIL", False
        detail["wall_s"] = round(time.perf_counter() - t0, 3)
        if clock:
            detail["compile_s"] = round(clock.seconds - c0, 3)
        print(f"phase {name}: {verdict} {json.dumps(detail)}", flush=True)
    return ok


def main() -> int:
    from ra_tpu.utils import enable_compile_cache

    stamp = _device_stamp()
    if stamp["platform"] != "tpu":
        print(f"chip_smoke: refusing to run: JAX found platform "
              f"{stamp['platform']!r} ({stamp['kind']}), not a TPU",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    shutil.rmtree(WAL_ROOT, ignore_errors=True)
    t0 = time.perf_counter()
    try:
        ok = run_phases([
            ("device", lambda: phase_device(WAL_ROOT, cache_dir)),
            ("served_path", lambda: phase_served_path(
                os.path.join(WAL_ROOT, "served"))),
            ("reads_exact", phase_reads_exact),
            ("mesh4", lambda: phase_mesh4(
                os.path.join(WAL_ROOT, "mesh4"))),
        ], clock)
    finally:
        shutil.rmtree(WAL_ROOT, ignore_errors=True)
    print(f"total: wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={clock.seconds:.1f} "
          f"compile_cache_hits={clock.cache_hits} "
          f"peak_device_bytes={_peak_device_bytes()}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
