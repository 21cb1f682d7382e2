"""Deterministic in-process simulation harness for core conformance tests.

Plays the role the mocked log + scripted events play in the reference's
ra_server_SUITE (/root/reference/test/ra_server_SUITE.erl): drives pure
RaServer cores directly, routing effect data between them with no real
timers, threads, or I/O, so every interleaving is scriptable and
assertions are data-in/data-out.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, Optional

from ra_tpu.core.machine import SimpleMachine
from ra_tpu.core.server import RaServer
from ra_tpu.core.types import (
    CancelElectionTimeout,
    Checkpoint,
    CommandEvent,
    ConsistentQueryEvent,
    ElectionTimeout,
    InstallSnapshotRpc,
    NextEvent,
    Notify,
    PromoteCheckpoint,
    ReleaseCursor,
    Reply,
    SendRpc,
    SendSnapshot,
    SendVoteRequests,
    ServerConfig,
    ServerId,
    StartElectionTimeout,
    TransferLeadershipEvent,
    UserCommand,
)
from ra_tpu.log.memory import MemoryLog


def mk_ids(n: int) -> list:
    return [ServerId(f"s{i+1}", f"node{i+1}") for i in range(n)]


class SimCluster:
    """Synchronous router between N RaServer cores."""

    def __init__(self, n: int = 3, machine_factory: Optional[Callable] = None,
                 auto_written: bool = True,
                 snapshot_chunk_size: int = 64,
                 log_factory: Optional[Callable] = None,
                 initial_count: Optional[int] = None) -> None:
        """``log_factory(cfg) -> log`` swaps the in-memory mock for a
        real log (e.g. RaSystem.log_factory) so core scenarios can run
        against durable storage; default stays MemoryLog.
        ``initial_count`` starts only the first K ids as cluster members
        — the rest run as standby servers awaiting a '$ra_join' (the
        start_server-then-add_member flow)."""
        self.ids = mk_ids(n)
        if machine_factory is None:
            machine_factory = lambda: SimpleMachine(  # noqa: E731
                lambda cmd, st: st + cmd, 0)
        self.servers: dict[ServerId, RaServer] = {}
        self.queues: dict[ServerId, deque] = {sid: deque() for sid in self.ids}
        self.replies: list = []         # (server_id, Reply)
        self.notifies: list = []        # (server_id, Notify)
        self.timer_kinds: dict[ServerId, Optional[str]] = {}
        self.dropped: set = set()       # partitioned links (src, dst)
        self.snapshot_chunk_size = snapshot_chunk_size
        self._log_factory = log_factory
        initial = tuple(self.ids[:initial_count]
                        if initial_count else self.ids)
        for sid in self.ids:
            cfg = ServerConfig(server_id=sid, uid=f"uid_{sid.name}",
                               cluster_name="simcluster",
                               initial_members=initial,
                               machine=machine_factory())
            log = (self._log_factory(cfg) if self._log_factory
                   else MemoryLog(auto_written=auto_written))
            srv = RaServer(cfg, log)
            srv.recover()
            self.servers[sid] = srv
            self.timer_kinds[sid] = None

    # -- driving -----------------------------------------------------------

    def handle(self, sid: ServerId, event: Any) -> None:
        """Feed one event to a server and process its effects."""
        srv = self.servers[sid]
        effects = srv.handle(event)
        self._process_effects(sid, effects)
        self._drain_log_events(sid)

    def _drain_log_events(self, sid: ServerId) -> None:
        srv = self.servers[sid]
        for evt in srv.log.take_events():
            effects = srv.handle(evt)
            self._process_effects(sid, effects)

    def _process_effects(self, sid: ServerId, effects: list) -> None:
        srv = self.servers[sid]
        for eff in effects:
            if isinstance(eff, SendRpc):
                self._send(sid, eff.to, eff.msg)
            elif isinstance(eff, SendVoteRequests):
                for to, msg in eff.requests:
                    self._send(sid, to, msg)
            elif isinstance(eff, NextEvent):
                inner = srv.handle(eff.event)
                self._process_effects(sid, inner)
            elif isinstance(eff, Reply):
                self.replies.append((sid, eff))
            elif isinstance(eff, Notify):
                self.notifies.append((sid, eff))
            elif isinstance(eff, StartElectionTimeout):
                self.timer_kinds[sid] = eff.kind
            elif isinstance(eff, CancelElectionTimeout):
                self.timer_kinds[sid] = None
            elif isinstance(eff, (ReleaseCursor, Checkpoint,
                                  PromoteCheckpoint)):
                self._process_effects(sid, srv.handle_machine_effect(eff))
            elif isinstance(eff, SendSnapshot):
                self._send_snapshot(sid, eff)
            # other effects (aux, metrics, monitors...) are inert here

    def _send(self, src: ServerId, dst: ServerId, msg: Any) -> None:
        if (src, dst) in self.dropped:
            return
        self.queues[dst].append(msg)

    def _send_snapshot(self, src: ServerId, eff: SendSnapshot) -> None:
        """Chunked snapshot transfer, modeled synchronously."""
        srv = self.servers[src]
        snap = srv.log.snapshot()
        if snap is None:
            return
        meta, data = snap
        leader_id, term = eff.id_term
        chunks = list(srv.log.snapshot_module.chunks(
            data, self.snapshot_chunk_size)) or [b""]
        for i, chunk in enumerate(chunks):
            flag = "last" if i == len(chunks) - 1 else "next"
            self._send(src, eff.to,
                       InstallSnapshotRpc(term=term, leader_id=leader_id,
                                          meta=meta, chunk_number=i + 1,
                                          chunk_flag=flag, data=chunk,
                                          token=eff.token))

    def step(self) -> bool:
        """Deliver one pending message (round-robin across servers)."""
        for sid in self.ids:
            if self.queues[sid]:
                msg = self.queues[sid].popleft()
                self.handle(sid, msg)
                return True
        return False

    def run(self, max_steps: int = 10_000) -> int:
        n = 0
        while self.step():
            n += 1
            if n >= max_steps:
                raise RuntimeError("simulation did not quiesce")
        return n

    # -- convenience -------------------------------------------------------

    def elect(self, sid: ServerId) -> None:
        """Trigger an election timeout at sid and run to quiescence."""
        self.handle(sid, ElectionTimeout())
        self.run()

    def leader(self) -> Optional[ServerId]:
        for sid, srv in self.servers.items():
            if srv.raft_state.value == "leader":
                return sid
        return None

    def command(self, sid: ServerId, data: Any, from_: Any = None,
                **kw: Any) -> None:
        self.handle(sid, CommandEvent(UserCommand(data, **kw), from_=from_))
        self.run()

    def consistent_query(self, sid: ServerId, fn: Callable,
                         from_: Any = "qclient") -> None:
        self.handle(sid, ConsistentQueryEvent(fn, from_=from_))
        self.run()

    def transfer_leadership(self, sid: ServerId, target: ServerId,
                            from_: Any = "tclient") -> None:
        self.handle(sid, TransferLeadershipEvent(target, from_=from_))
        self.run()

    def partition(self, a: ServerId, b: ServerId) -> None:
        self.dropped.add((a, b))
        self.dropped.add((b, a))

    def heal(self) -> None:
        self.dropped.clear()

    def isolate(self, sid: ServerId) -> None:
        for other in self.ids:
            if other != sid:
                self.partition(sid, other)

    def machine_states(self) -> dict:
        return {sid: srv.machine_state for sid, srv in self.servers.items()}

    def states(self) -> dict:
        return {sid: srv.raft_state.value
                for sid, srv in self.servers.items()}


class _GatedReadback:
    """One dispatch's watermark readback behind a :class:`ReadbackGate`:
    the engine's real array, ``is_ready()`` a flag the test holds."""

    def __init__(self, real, seq: int, gate: "ReadbackGate") -> None:
        self.real, self.seq, self.gate = real, seq, gate
        self.ready = gate.arrived
        self.nbytes = real.nbytes

    def copy_to_host_async(self) -> None:
        self.real.copy_to_host_async()

    def is_ready(self) -> bool:
        return self.ready

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        self.gate.log.append(self.seq)
        return np.asarray(self.real)


class ReadbackGate:
    """Lets a test decide when a lane engine's watermark readbacks
    arrive: routes ``eng.watermarks()`` (what ``DispatchAheadDriver``
    reads a dispatch by) through handles whose ``ready`` the test sets.
    ``made`` holds them in dispatch order, ``log`` the dispatch numbers
    in the order the driver converted them (who was observed, in what
    order, how often); ``arrived`` is what a new handle starts as."""

    def __init__(self, eng, arrived: bool = False) -> None:
        self.arrived, self.made, self.log = arrived, [], []
        real = eng.watermarks

        def watermarks():
            self.made.append(_GatedReadback(real(), len(self.made), self))
            return self.made[-1]

        eng.watermarks = watermarks

    def release(self) -> None:
        """Every readback made so far, and every later one, has
        arrived."""
        self.arrived = True
        for h in self.made:
            h.ready = True


class Dispatched:
    """Records what each of a lane engine's ``superstep`` calls was
    given, in dispatch order: ``blocks`` holds ``(n_new_blk,
    payloads_blk, n_read_blk)`` a dispatch, the device arrays the
    driver staged for it."""

    def __init__(self, eng) -> None:
        self.blocks = []
        real = eng.superstep

        def superstep(n_new_blk, payloads_blk, **kw):
            self.blocks.append((n_new_blk, payloads_blk,
                                kw.get("n_read_blk")))
            return real(n_new_blk, payloads_blk, **kw)

        eng.superstep = superstep



# -- the served path under a profiler session ---------------------------------

#: traced cycles of :func:`served_run`
SERVED_PUMPS = 6


def superstep_args(eng, k: int = 2) -> tuple:
    """All-zero arguments of a lane engine's fused step, ``k`` rounds."""
    import jax.numpy as jnp
    n, c = eng.n_lanes, eng.max_step_cmds
    return (eng.state, jnp.zeros((k, n), jnp.int32),
            jnp.zeros((k, n, c, eng.payload_width), jnp.int32),
            eng._zero_fail, jnp.zeros((k, n), bool), eng._zero_confirm,
            jnp.zeros((k, n), bool), jnp.zeros((k, n), jnp.int32),
            jnp.broadcast_to(eng._zero_readq,
                             (k,) + eng._zero_readq.shape))


def step_args(eng) -> list:
    """All-zero arguments of a lane engine's single step: one round of
    ``superstep_args``."""
    return [a[0] if i in (1, 2, 4, 6, 7, 8) else a
            for i, a in enumerate(superstep_args(eng, k=1))]


def _read_threads(trace_dir) -> list:
    """[[(name, start_ns, end_ns, args)] per thread] of the ``ra.*``
    events of a profile."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)) for e in line.events
                  if e.name.startswith("ra.")]
            if ev:
                threads.append(ev)
    return threads


def served_run(tmp) -> dict:
    """A small durable engine behind a listener, pumped SERVED_PUMPS
    times under a CPU profiler session (the first retire needs a few
    blocks in flight), then settled.  Returns the threads' events, the
    pumps' phase counts, the compile counter's delta over the warm
    pumps, the counters the benchmark's harness snapshots and the
    lowered text of the engine's fused step (tests/conftest.py keeps
    one a module as the ``served`` fixture)."""
    import types

    import jax
    import numpy as np

    from benchmarks.harness import serve
    from ra_tpu import devicewatch
    from ra_tpu.engine import open_engine
    from ra_tpu.ingress import IngressPlane
    from ra_tpu.wire import DedupCounterMachine, LoopbackFleet, WireListener

    eng = open_engine(DedupCounterMachine(slots=64), str(tmp / "wal"), 16,
                      wal_shards=2, ring_capacity=256, max_step_cmds=8,
                      donate=False)
    plane = IngressPlane(eng, superstep_k=2, window_s=0.0,
                         soft_credit=1 << 20, hard_credit=1 << 20)
    lst = WireListener(plane, port=None, max_conns=64, ring_bytes=4096)
    fleet = LoopbackFleet(lst, 32, key="spans", seed=0)

    def cycle():
        fleet.new_ops(np.arange(32), np.full(32, 3, np.int32))
        fleet.send_queued()
        lst.sweep()
        fleet.collect()
        assert plane.pump(force=True)
        fleet.collect()

    try:
        for _ in range(4):           # compile and warm every program
            cycle()
        plane.settle()
        eng._dur.flush_all()         # no WAL work half inside the trace
        counts0 = {p: v["count"]
                   for p, v in eng.phases.overview().items()
                   if isinstance(v, dict)}
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp / "trace"),
                                 profiler_options=opts)
        try:
            # the slices of the WAL workers' readback compile a program
            # per new row count: the same rows a cycle, so a warm loop
            compiles0 = devicewatch.WATCH.counters["xla_compiles"]
            for _ in range(SERVED_PUMPS):
                cycle()
            eng._dur.drain_all()
            compiles = devicewatch.WATCH.counters["xla_compiles"] \
                - compiles0
            plane.settle()
            eng._dur.flush_all()
        finally:
            jax.profiler.stop_trace()
        counts = {p: v["count"] - counts0[p]
                  for p, v in eng.phases.overview().items()
                  if isinstance(v, dict)}
        # the groups as the benchmark's own Run._counters takes them
        counters = serve.Run._counters(types.SimpleNamespace(
            eng=eng, plane=plane, lst=lst, cycles=0, spans=serve.Spans(),
            fleet=types.SimpleNamespace(watermark=np.zeros(1))))
        lowered = eng._sstep.lower(*superstep_args(eng)).as_text(
            debug_info=True)
    finally:
        lst.close()
        eng.close()
    return {"threads": _read_threads(str(tmp / "trace")),
            "phase_counts": counts, "warm_compiles": compiles,
            "counters": counters, "lowered": lowered}
