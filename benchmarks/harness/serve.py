"""One run of one cell: build the served path from the configuration's
file, warm it, drive the mix through it for the window, drain, and hand
back the ledger with everything the readers and the check need.

The path is the program's own: loopback wire frames into
``WireListener.sweep()``, ``IngressPlane.pump()`` and the durable engine
from ``open_engine``, cycled as ``placement/host.py`` cycles them
(sweep, then ``pump(force=True)``).  Nothing is measured beside it.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time

import numpy as np

from . import reference, traffic
from .fleet import BenchFleet

#: how long the loop goes without a dispatch before it settles
IDLE_SETTLE_S = 0.25

#: host spans of the benchmark's loop, in the order of one cycle
SPANS = ("gen.mint", "gen.send", "wire.sweep", "client.collect",
         "ingress.pump")


class Spans:
    """Seconds spent inside each of the loop's calls, on the host's
    clock; with a profile running, the same intervals as
    ``TraceAnnotation`` on the profiler's clock."""

    def __init__(self) -> None:
        self.total = {name: 0.0 for name in SPANS}
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.total[name] += time.perf_counter() - t0


def build_machine(config: dict):
    from .machine import WIDTH, BodyCounterMachine
    if int(config["command_words"]) != WIDTH:
        raise ValueError("config: command_words must be 64 (256 bytes)")
    return BodyCounterMachine(slots=int(config["dedup_slots"]))


def engine_kwargs(config: dict) -> dict:
    e = config["engine"]
    return dict(sync_mode=int(e["sync_mode"]),
                ring_capacity=int(e["ring_capacity"]),
                max_step_cmds=int(e["max_step_cmds"]))


def open_served(config: dict, wal_dir: str):
    """Engine, ingress plane and listener as the configuration states
    them.  ``mesh_lanes`` > 1 shards the lanes over that many devices
    with one WAL shard per device."""
    import jax

    from ra_tpu.engine import open_engine
    from ra_tpu.ingress import IngressPlane
    from ra_tpu.wire.framing import data_stride
    from ra_tpu.wire.server import WireListener

    lanes, members = int(config["clusters"]), int(config["members"])
    wal_shards = int(config["engine"]["wal_shards"])
    mesh = None
    n_dev = int(config.get("mesh_lanes", 1))
    if n_dev > 1:
        from ra_tpu.parallel.mesh import (lane_mesh, per_device_wal_shards,
                                          shard_engine_state)
        mesh = lane_mesh(jax.devices()[:n_dev], member_axis=1)
        wal_shards = per_device_wal_shards(mesh)
    eng = open_engine(build_machine(config), wal_dir, lanes, members,
                      wal_shards=wal_shards, **engine_kwargs(config))
    try:
        if mesh is not None:
            shard_engine_state(eng, mesh)
        plane = IngressPlane(
            eng, superstep_k=int(config["ingress"]["superstep_k"]))
        n_conns = lanes
        lst = WireListener(
            plane, port=None, max_conns=n_conns + 16,
            ring_bytes=int(config["wire"]["ring_records"])
            * data_stride(eng.payload_width))
    except BaseException:
        eng.close()
        raise
    return eng, plane, lst


def device_memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def snapshot_state(eng) -> dict:
    """The machine state of every replica, on the host."""
    st = eng.state
    return {"value": np.asarray(st.mac["value"]),
            "check": np.asarray(st.mac["check"]),
            "seq": np.asarray(st.mac["seq"]),
            "active": np.asarray(st.active),
            "leader": np.asarray(st.leader_slot),
            "applied": np.asarray(st.applied),
            "last_index": np.asarray(st.last_index),
            "commit": np.asarray(st.commit)}


class Run:
    """State of one run, filled in as it goes; the readers and the
    check read it."""

    def __init__(self, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, run_dir: str) -> None:
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.run_dir = run_dir
        self.wal_dir = os.path.join(run_dir, "wal")
        self.trace_dir = os.path.join(run_dir, "trace")
        # long enough for the loop to reach its steady state before
        # the window opens: a few commit latencies (a cell whose
        # latency is seconds says so in its own file)
        self.warmup_s = float(cell.get("warmup_s", mix["warmup_s"]))
        self.spans = Spans()
        self.acks_above_fsync = 0
        self.counters0: dict = {}
        self.counters1: dict = {}
        self.trace_window = None        # (start, end) perf_counter
        self.cycles = 0
        #: (start, seconds, seconds by span) of every cycle, for the
        #: result's notes: where a slow cycle spent its time
        self.cycle_log: list = []
        #: test hook: alters payload rows where they are produced
        self.tamper = None

    # -- the loop ----------------------------------------------------------

    def _cycle(self, minting: bool) -> None:
        sp, fleet = self.spans, self.fleet
        self.cycles += 1
        before = (time.perf_counter(), dict(sp.total))
        with sp("gen.mint"):
            self.gen.step(fleet, time.perf_counter(), self._newly, minting)
        with sp("gen.send"):
            fleet.send_queued(time.perf_counter())
        with sp("wire.sweep"):
            self.lst.sweep()
        with sp("client.collect"):
            a = fleet.collect(time.perf_counter())
        with sp("ingress.pump"):
            now = time.perf_counter()
            if self.plane.pump(force=True):
                self._last_dispatch = now
            elif now - self._last_dispatch > IDLE_SETTLE_S \
                    and self.plane.gauges()["inflight_blocks"]:
                # nothing to dispatch for a while, but blocks in flight:
                # the driver observes a commit only when a later
                # dispatch pushes it out of its window, so an idle host
                # has to settle (placement/geo.py's engine child does
                # the same every 64 cycles) or the last ACKs never come.
                # Not at once: settle is a barrier that acknowledges
                # everything in flight together, and a closed loop then
                # circles as one block for ever
                self.plane.settle(timeout=60.0)
                self._last_dispatch = now
            elif fleet.idle():
                time.sleep(0.001)
        with sp("client.collect"):
            b = fleet.collect(time.perf_counter())
            self._newly = np.concatenate([a, b])
            self._note_acks(self._newly)
        self.cycle_log.append(
            (before[0], time.perf_counter() - before[0],
             {k: round(v - before[1][k], 4) for k, v in sp.total.items()}))

    def _note_acks(self, ops: np.ndarray) -> None:
        """A necessary condition of "no commit reported above the
        fsynced watermark", checked without touching the device: a
        cluster with k acknowledged commands has at least k fsynced log
        entries.  The watermark is read after the ACKs were seen, and it
        only grows, so a sound run cannot trip this."""
        if not len(ops):
            return
        lanes = self.fleet.lanes[self.fleet.op_sess[ops]]
        self._acked_per_lane += np.bincount(lanes, minlength=len(
            self._acked_per_lane))
        confirm = np.asarray(self.eng._dur.confirm_upto)
        self.acks_above_fsync += int(
            (self._acked_per_lane > confirm).sum())

    def _counters(self) -> dict:
        from ra_tpu import devicewatch
        eng = self.eng
        return {"t": time.perf_counter(), "cycles": self.cycles,
                "spans": dict(self.spans.total),
                "pipeline": dict(eng.pipeline_counters),
                "device": dict(devicewatch.WATCH.counters),
                "wire": dict(self.lst.counters),
                "ingress": dict(self.plane.counters),
                "acked": int(self.fleet.watermark.sum())}

    def _drain(self, timeout_s: float = 60.0) -> None:
        deadline = time.perf_counter() + timeout_s
        fleet = self.fleet
        while fleet.outstanding() and time.perf_counter() < deadline:
            self._cycle(minting=False)
            if fleet.idle():
                self.plane.settle(timeout=timeout_s)
                self._note_acks(fleet.collect(time.perf_counter()))

    # -- phases ------------------------------------------------------------

    def set_up(self) -> None:
        cfg = self.config
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.wal_dir)
        self.eng, self.plane, self.lst = open_served(cfg, self.wal_dir)
        self.pool = reference.make_pool(self.seed)
        n_conns = int(cfg["clusters"])
        spc = int(cfg["sessions_per_cluster"])
        warm_s = self.warmup_s
        self.gen = traffic.make(self.mix, self.cell, self.seed,
                                n_conns * spc, len(self.pool),
                                warm_s + self.seconds)
        pipe = int(self.mix.get("pipe", 0))
        self.fleet = BenchFleet(
            self.lst, n_conns, spc, self.pool,
            max_ops=max(1 << 20, 4 * pipe * n_conns * spc),
            rank_cap=max(256, 2 * pipe))
        if int(self.fleet.slots.max()) >= int(cfg["dedup_slots"]):
            raise RuntimeError("config: dedup_slots too few for the "
                               "sessions hashed onto one cluster")
        self._acked_per_lane = np.zeros(int(cfg["clusters"]), np.int64)
        self._newly = np.zeros(0, np.int64)
        self._last_dispatch = time.perf_counter()
        self.fleet.tamper = self.tamper
        # compile and warm every program the window drives (the fused
        # durable dispatch and the empty dispatch of ``settle``), with
        # one op on one session of every cluster connection
        first = np.arange(n_conns) * spc
        d, r, s = traffic.OpContent(self.seed ^ 0x5EED, n_conns * spc,
                                    len(self.pool),
                                    self.mix["delta"]).draw(first)
        self.fleet.new_ops(first, d, r, s,
                           np.full(n_conns, time.perf_counter()))
        self._drain(timeout_s=900.0)    # the generator mints nothing yet
        if self.fleet.outstanding():
            raise RuntimeError("warm-up: ops never acknowledged")
        self.eng.phases.reset_reservoirs()

    def measure(self) -> None:
        warm_s = self.warmup_s
        t_start = time.perf_counter()
        self.gen.start(self.fleet, t_start)
        self.t0 = t_start + warm_s
        self.t1 = self.t0 + self.seconds
        while time.perf_counter() < self.t0:
            self._cycle(minting=True)
        self.eng.phases.reset_reservoirs()
        self.counters0 = self._counters()
        tracing = False
        t_trace0 = self.t0 + float(self.mix["trace_after_s"])
        t_trace1 = t_trace0 + float(self.cell.get("trace_s",
                                                  self.mix["trace_s"]))
        while True:
            now = time.perf_counter()
            if now >= self.t1:
                break
            if self.trace and not tracing and self.trace_window is None \
                    and now >= t_trace0:
                self._start_trace()
                tracing = True
            if tracing and now >= t_trace1:
                self._stop_trace()
                tracing = False
            self._cycle(minting=True)
        if tracing:
            self._stop_trace()
        self.counters1 = self._counters()
        self.phases = self.eng.phases.overview()
        self.wal = self.eng.overview()["wal"]
        self._drain()
        self.memory_peak = device_memory_peak()

    def _start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = int(self.mix.get("host_tracer_level", 2))
        self.spans.annotate = True
        self._trace_t0 = time.perf_counter()
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()
        self.spans.annotate = False
        self.trace_window = (self._trace_t0, time.perf_counter())

    def _apply_all(self, limit: int = 256) -> None:
        """Empty fused dispatches until every active replica has applied
        its leader's whole log.  An ACK says committed and fsynced; a
        replica applies at most its apply window a round, so a cluster
        that took a burst is still applying when its last ACK is seen
        (``consistent_read`` drives empty rounds for the same reason)."""
        eng, plane = self.eng, self.plane
        k = int(self.config["ingress"]["superstep_k"])
        zero_n = np.zeros((k, eng.n_lanes), np.int32)
        zero_p = np.zeros((k, eng.n_lanes, eng.max_step_cmds,
                           eng.payload_width), np.dtype(eng.payload_dtype))
        lane = np.arange(eng.n_lanes)
        for _ in range(limit):
            st = eng.state
            tail = np.asarray(st.last_index)[lane, np.asarray(st.leader_slot)]
            behind = np.asarray(st.active) & \
                (np.asarray(st.applied) < tail[:, None])
            if not behind.any():
                return
            plane.driver.submit(zero_n, zero_p)
            plane.driver.drain()

    def finish(self) -> dict:
        """Read the program's state, free it, then reopen the WAL and
        read what recovery rebuilds.  Returns both snapshots."""
        cfg = self.config
        self._apply_all()
        live = snapshot_state(self.eng)
        live["confirm"] = np.asarray(self.eng._dur.confirm_upto).copy()
        self.lst.close()
        self.eng.close()
        self.eng = self.plane = self.lst = None
        self.fleet.listener = None
        gc.collect()
        from ra_tpu.engine import open_engine
        t0 = time.perf_counter()
        eng = open_engine(build_machine(cfg), self.wal_dir,
                          int(cfg["clusters"]), int(cfg["members"]),
                          wal_shards=int(cfg["engine"]["reopen_wal_shards"]),
                          **engine_kwargs(cfg))
        try:
            reopened = snapshot_state(eng)
        finally:
            eng.close()
        self.reopen_s = time.perf_counter() - t0
        return {"live": live, "reopened": reopened}

    def close(self) -> None:
        """Stop what is still open and remove what the run wrote."""
        if getattr(self, "lst", None) is not None:
            self.lst.close()
        if getattr(self, "eng", None) is not None:
            self.eng.close()
        self.eng = self.plane = self.lst = None
        shutil.rmtree(self.run_dir, ignore_errors=True)
