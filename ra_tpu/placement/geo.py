"""The geo-distributed survival soak (ISSUE 19 acceptance).

Everything PR 17 did in one process, stretched across REAL processes
and a CD-Raft latency geometry:

* the parent ("ctl") hosts one placement-table member, the
  :class:`~ra_tpu.placement.supervisor.EngineSupervisor` (probing over
  the reliable RPC tier via :class:`~ra_tpu.placement.fabric
  .RpcEngineProbe`), and the wire clients;
* a control child ("far") hosts the other two table members behind an
  80-150 ms latency-domain matrix — every control commit pays at least
  one cross-domain round trip for quorum (the CD-Raft shape);
* two engine children each run a :class:`~ra_tpu.placement.host
  .LaneEngineHost` serving a REAL TCP wire listener, fronted by a
  :class:`~ra_tpu.placement.fabric.HostAgent` (the host_* control
  verbs over reliable RPC); the engine tier is local — the delay
  matrix does not touch it.

One run (:func:`run_geo_soak`):

1. live wire traffic against both engine children;
2. a **delay-only episode**: the parent's matrix temporarily stretches
   the control→engine domain crossing by the same 80-150 ms — probes
   slow down but keep completing (RTT reads as age), and the run
   asserts ZERO migrations and zero down verdicts: geography is not
   death;
3. **SIGKILL** of one engine child mid-traffic: probes go silent, the
   verdict ladder escalates through the hysteresis window, the
   supervisor commits ``engine_down`` + generation-gated ``migrate``
   through the cross-domain table, the survivor adopts the victim's
   durable directory over ``host_adopt``, the committed placement is
   pushed to the survivor's serving cache (``host_placement``), the
   victim's wire client re-homes over ``host_rehome`` +
   :meth:`WireClient.rehome_to` (old dedup slots claimed, unacked
   window replayed);
4. the exactly-once oracle closes over BOTH engines' machine state
   read back over ``host_lane_sums``: zero lost-acked, zero
   double-applied.

The JSON tail stamps ``geo_failover_recovery_s`` (SIGKILL → first
commit on the new home) and ``geo_false_migrations`` (must be 0).
``tools/soak.py --geo SEED [SEED...]`` drives it
standalone; this module is also its own child-process entrypoint
(``python -m ra_tpu.placement.geo --child ...``).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from ..blackbox import record
from ..trace import new_trace_ctx

#: the latency geometry: control followers are far, engines are local
_DELAY_MS = (80.0, 150.0)


def _geo_members() -> dict:
    return {"ctl": ["ctl"], "far": ["gf1", "gf2"],
            "eng": ["n_engA", "n_engB"]}


def _geo_plan(local: str, seed: int, *, eng_delay: bool = False):
    """The latency-domain FaultPlan one process of the geo topology
    installs: geography as a named-domain matrix, compiled onto the
    per-(peer, class, direction) fault streams (docs/INTERNALS.md
    §20).  ``eng_delay`` adds the control→engine crossing — the
    delay-only episode's knob."""
    from ..transport.rpc import FaultPlan
    matrix: dict = {("ctl", "far"): {"delay_ms": _DELAY_MS}}
    if eng_delay:
        matrix[("ctl", "eng")] = {"delay_ms": _DELAY_MS}
    return FaultPlan(seed=seed, domains={
        "local": local, "members": _geo_members(), "matrix": matrix})


def _tune_detector(router) -> None:
    """Transport-level detector thresholds that tolerate the matrix:
    a 150 ms one-way stretch must never flap a peer suspect (reliable
    RPC refuses suspect peers — flapping would starve the commit
    path)."""
    router.suspect_after = 2.0
    router.down_after = 6.0
    router.detector_hysteresis = 0.5


def _await(what: str, timeout_s: float, fn: Callable[[], bool], *,
           tick: Optional[Callable[[], None]] = None,
           sleep_s: float = 0.01) -> int:
    """Deadline-bounded progress wait (the one retry shape RA16
    allows): polls ``fn`` — optionally driving ``tick`` between polls
    — and emits the registered give-up event on exhaustion."""
    deadline = time.monotonic() + timeout_s
    attempts = 0
    while time.monotonic() < deadline:
        attempts += 1
        if tick is not None:
            tick()
        if fn():
            return attempts
        time.sleep(sleep_s)
    record("placement.giveup", what=what, attempts=attempts)
    raise TimeoutError(f"geo soak: {what} not reached in {timeout_s}s")


def _machine_slots(sessions: int, lanes: int) -> int:
    """Dedup-slot budget per lane (parent and children must agree —
    the machine is built in the child, the client asserts against it
    in the parent)."""
    return 4 * max(1, sessions // lanes) + 64


def _write_ready(path: str, payload: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)  # atomic: the parent never reads a torn file


# ----------------------------------------------------------------------
# child entrypoints (one OS process each)
# ----------------------------------------------------------------------


def _engine_child(args) -> None:
    """One lane-engine host process: TcpRouter + RaNode + LaneEngineHost
    (real TCP wire listener) + HostAgent, pumping until stopped,
    killed, or the run deadline."""
    from ..node import RaNode
    from ..transport.tcp import TcpRouter
    from ..wire.dedup import DedupCounterMachine
    from .fabric import HostAgent
    from .host import LaneEngineHost
    eid = args.eid
    router = TcpRouter(("127.0.0.1", 0),
                       {"ctl": (args.parent_host, args.parent_port)})
    router.set_fault_plan(_geo_plan("eng", args.seed))
    _tune_detector(router)
    node = RaNode(f"n_{eid}", router=router)
    slots = _machine_slots(args.sessions, args.lanes)
    host = LaneEngineHost(
        eid, args.data_dir,
        machine_factory=lambda: DedupCounterMachine(slots=slots),
        lanes=args.lanes, wal_shards=args.wal_shards, max_conns=16,
        port=0)
    agent = HostAgent(host, node, placement_rid=f"{eid}/lanes")
    _write_ready(args.ready, {
        "router": list(router.listen_addr),
        "wire": list(host.listener.address),
        "node": node.name, "pid": os.getpid()})
    deadline = time.monotonic() + args.max_run_s
    n = 0
    while time.monotonic() < deadline and not agent.stopped.is_set():
        agent.pump()
        host.cycle()
        n += 1
        if n % 64 == 0:
            # drive the async committed-watermark readbacks so ACK
            # watermarks stay live between the parent's waves
            try:
                host.settle(timeout=2.0)
            except (TimeoutError, RuntimeError):
                pass
        time.sleep(0.002)
    if not agent.stopped.is_set():
        record("placement.giveup", what="geo_engine_child_deadline",
               attempts=n)
    host.close()
    node.stop()
    router.stop()


def _control_child(args) -> None:
    """The far latency domain: one TcpRouter hosting BOTH remote
    placement-table nodes (gf1, gf2) — local to each other, 80-150 ms
    from the parent's domain.  The table members themselves are
    started REMOTELY by the parent over the control plane
    (start_cluster's config-snapshot path)."""
    import threading
    from ..node import RaNode
    from ..transport.tcp import TcpRouter
    from . import table as _table  # registers the machine spec  # noqa: F401
    router = TcpRouter(("127.0.0.1", 0),
                       {"ctl": (args.parent_host, args.parent_port)})
    router.set_fault_plan(_geo_plan("far", args.seed))
    _tune_detector(router)
    stop = threading.Event()
    nodes = [RaNode("gf1", router=router), RaNode("gf2", router=router)]
    nodes[0].control_ops["geo_stop"] = \
        lambda a: (stop.set(), "stopping")[1]
    _write_ready(args.ready, {
        "router": list(router.listen_addr),
        "node": "gf1,gf2", "pid": os.getpid()})
    deadline = time.monotonic() + args.max_run_s
    waited = 0
    while time.monotonic() < deadline and not stop.is_set():
        time.sleep(0.05)
        waited += 1
    if not stop.is_set():
        record("placement.giveup", what="geo_control_child_deadline",
               attempts=waited)
    for n in nodes:
        n.stop()
    router.stop()


def _spawn_child(role: str, ready: str, parent_addr: tuple,
                 seed: int, max_run_s: float, **kw) -> subprocess.Popen:
    argv = [sys.executable, "-m", "ra_tpu.placement.geo",
            "--child", role, "--ready", ready,
            "--parent-host", parent_addr[0],
            "--parent-port", str(parent_addr[1]),
            "--seed", str(seed), "--max-run-s", str(max_run_s)]
    for k, v in kw.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    # a correctness harness: several engine processes on one host, and
    # a chip belongs to one process — every child is held to the CPU
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(argv, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.STDOUT)


def _read_ready(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ----------------------------------------------------------------------
# the parent orchestration
# ----------------------------------------------------------------------


def run_geo_soak(seed: int, *, sessions: int = 24, lanes: int = 16,
                 waves: int = 5, wave_ops: int = 300,
                 wal_shards: int = 2,
                 delay_episode_s: float = 2.5,
                 data_dir: Optional[str] = None,
                 max_run_s: float = 300.0,
                 recovery_bar: Optional[float] = None) -> dict:
    """One geo run; returns its tail row.  See the
    module docstring for the scenario."""
    from ..api import process_command, start_cluster
    from ..core.types import ErrorResult, ServerId
    from ..node import RaNode
    from ..transport.tcp import TcpRouter
    from ..wire.client import WireClient
    from .fabric import (RpcEngineProbe, push_placement, remote_adopt,
                         remote_lane_sums, remote_rehome)
    from .supervisor import EngineSupervisor
    from .table import placement_spec
    rng = np.random.default_rng(seed)
    tmp = None
    if data_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="geo-soak-")
        data_dir = tmp.name
    dirs = {e: os.path.join(data_dir, e) for e in ("engA", "engB")}
    base_plan = _geo_plan("ctl", seed)
    router = TcpRouter(("127.0.0.1", 0), {})
    router.set_fault_plan(base_plan)
    _tune_detector(router)
    ctl = RaNode("ctl", router=router)
    procs: dict = {}
    clients: dict = {}
    row: dict = {}
    try:
        # -- topology: one far control child + two engine children ---
        ready = {r: os.path.join(data_dir, f"{r}.ready")
                 for r in ("far", "engA", "engB")}
        procs["far"] = _spawn_child("control", ready["far"],
                                    router.listen_addr, seed, max_run_s)
        for eid in ("engA", "engB"):
            procs[eid] = _spawn_child(
                "engine", ready[eid], router.listen_addr, seed,
                max_run_s, eid=eid, data_dir=dirs[eid], lanes=lanes,
                sessions=sessions, wal_shards=wal_shards)
        _await("geo_children_ready", 120.0,
               lambda: all(os.path.exists(p) for p in ready.values()),
               sleep_s=0.05)
        info = {r: _read_ready(p) for r, p in ready.items()}
        for n in ("gf1", "gf2"):
            router.address_book[n] = tuple(info["far"]["router"])
        for eid in ("engA", "engB"):
            router.address_book[f"n_{eid}"] = \
                tuple(info[eid]["router"])
        node_of = {eid: f"n_{eid}" for eid in ("engA", "engB")}
        wire_of = {eid: tuple(info[eid]["wire"])
                   for eid in ("engA", "engB")}

        # -- control plane: the table quorum spans the delay matrix --
        sids = [ServerId("pt1", "ctl"), ServerId("pt2", "gf1"),
                ServerId("pt3", "gf2")]
        start_cluster("geo_pt", placement_spec(), sids, router=router,
                      election_timeout_ms=800, tick_interval_ms=200)
        sup = EngineSupervisor(
            sids[0], router, suspect_after=0.75, down_after=2.5,
            hysteresis=0.5, commit_timeout=10.0)
        probes = {}
        for eid in ("engA", "engB"):
            p = RpcEngineProbe(router, node_of[eid], eid, timeout=1.5,
                               min_interval=0.05)
            sup.watch(eid, p)
            p.bind(sup)
            probes[eid] = p
        adopted_addr: dict = {}

        def _on_migrate(victim, survivor, placements, trace_ctx):
            adopted_addr[victim] = remote_adopt(
                router, node_of[survivor], victim, dirs[victim],
                survivor=survivor, rid=f"{victim}/lanes",
                timeout=90.0, trace_ctx=trace_ctx)
        sup.on_migrate = _on_migrate
        for cmd in (("register_engine", "engA"),
                    ("register_engine", "engB"),
                    ("assign", "engA/lanes", "engA", 0, lanes),
                    ("assign", "engB/lanes", "engB", 0, lanes)):
            res = sup._commit(lambda c=cmd: process_command(
                sids[0], c, router, timeout=15.0), what="geo_setup")
            assert not isinstance(res, ErrorResult)
        state0 = sup.table_state()
        for eid in ("engA", "engB"):
            push_placement(router, node_of[eid], state0, timeout=15.0)

        # -- live wire traffic over real TCP -------------------------
        mslots = _machine_slots(sessions, lanes)
        for eid in ("engA", "engB"):
            c = WireClient(wire_of[eid], f"geo{seed}/{eid}",
                           n_sessions=sessions, tenants=2,
                           timeout=20.0)
            assert int(np.max(c.slots)) < mslots, "dedup slot overflow"
            clients[eid] = c
        victim, survivor = "engA", "engB"
        killed = False
        handled: set = set()  # engines whose down verdict was acted on

        def _live() -> list:
            return [e for e in ("engA", "engB")
                    if not (e == victim and killed
                            and e not in handled)]

        def _failover(eid: str) -> None:
            surv = "engB" if eid == "engA" else "engA"
            ctx = new_trace_ctx("geo-failover")
            record("placement.refuse", trace=ctx, engine=eid,
                   unplaced=int(_unplaced(eid)))
            sup.failover(eid, surv, trace_ctx=ctx)  # on_migrate adopts
            # cache-invalidation-on-commit: the survivor's serving view
            # learns the committed move BEFORE the client is re-pointed
            # — its placement mask then routes the re-homed sessions
            # instead of REHOME-refusing them
            push_placement(router, node_of[surv], sup.table_state(),
                           timeout=15.0)
            durable = remote_rehome(router, node_of[surv], eid,
                                    clients[eid], timeout=60.0,
                                    trace_ctx=ctx)
            clients[eid].rehome_to(adopted_addr[eid], durable)

        def _pump() -> None:
            # the nemesis reaction lives HERE: a down verdict — never a
            # mere delay — is the one migration trigger, so the delay
            # episode's zero-migration assert is a real property
            for eid in sup.tick():
                if eid not in handled:
                    handled.add(eid)
                    _failover(eid)
            for e in _live():
                try:
                    clients[e].flush()
                    clients[e].poll()
                except OSError:
                    pass

        def _unplaced(e: str) -> int:
            c = clients[e]
            return sum(1 for s in c.op_state if s != 2)

        def _undrained(e: str) -> int:
            # placed is a SWEEP verdict; the oracle reads committed
            # machine state, so drain until every RANKED op is acked
            # (acks ride the committed watermark — fsync-gated).
            # DUP-placed replays never rank: their delta is already in
            # the recovered state, nothing of theirs is in flight.
            c = clients[e]
            ranked_unacked = sum(
                1 for i in range(len(c.op_state))
                if c.op_rank[i] >= 0 and not c._acked(i))
            return _unplaced(e) + ranked_unacked

        def _wave() -> None:
            for e in _live():
                c = clients[e]
                for _ in range(wave_ops):
                    c.enqueue(int(rng.integers(1, 8)),
                              sess=int(rng.integers(0, sessions)))
            _await("geo_wave_placed", 60.0,
                   lambda: all(_unplaced(e) == 0 for e in _live()),
                   tick=_pump)

        t0 = time.perf_counter()
        _wave()  # warm both serving paths end to end

        # -- episode 1: delay is not death ---------------------------
        downs0 = sup.counters["downs"]
        mig0 = sup.counters["migrations"]
        router.set_fault_plan(_geo_plan("ctl", seed, eng_delay=True))
        ep_end = time.monotonic() + delay_episode_s
        _wave()
        _await("geo_delay_episode", delay_episode_s + 30.0,
               lambda: time.monotonic() >= ep_end, tick=_pump)
        router.set_fault_plan(base_plan)
        false_migrations = sup.counters["migrations"] - mig0
        assert sup.counters["downs"] == downs0, \
            "delay-only episode produced a down verdict"
        assert false_migrations == 0, \
            "delay-only episode migrated an engine (geography as death)"

        # -- episode 2: SIGKILL one engine host ----------------------
        for w in range(waves):
            if w == waves // 2 and not killed:
                os.kill(info[victim]["pid"], signal.SIGKILL)
                t_kill = time.perf_counter()
                killed = True
                wm = int(clients[victim].watermark.sum())
                _await("geo_detect_and_migrate", 60.0,
                       lambda: victim in handled, tick=_pump)
                _await("geo_recovery_commit", 90.0,
                       lambda: int(clients[victim].watermark.sum())
                       > wm, tick=_pump)
                recovery_s = time.perf_counter() - t_kill
            _wave()
        assert killed and victim in handled, "kill wave never ran"
        _await("geo_drain", 120.0,
               lambda: all(_undrained(e) == 0
                           for e in ("engA", "engB")), tick=_pump)
        elapsed = time.perf_counter() - t0

        # -- the exactly-once oracle over both engines ---------------
        got = {
            victim: remote_lane_sums(router, node_of[survivor],
                                     victim, timeout=30.0),
            survivor: remote_lane_sums(router, node_of[survivor],
                                       survivor, timeout=30.0),
        }
        lost = double = 0
        for eid in ("engA", "engB"):
            expected = _expected_lane_sums(clients[eid], lanes,
                                           f"geo{seed}/{eid}")
            lost += int(np.maximum(expected - got[eid], 0).sum())
            double += int(np.maximum(got[eid] - expected, 0).sum())
            np.testing.assert_array_equal(got[eid], expected)
        assert sup.counters["downs"] - downs0 == 1
        assert sup.counters["migrations"] >= 1
        if recovery_bar is not None:
            assert recovery_s <= recovery_bar, \
                f"recovery {recovery_s:.3f}s > bar {recovery_bar}s"
        row = {
            "value": recovery_s,
            "geo_failover_recovery_s": recovery_s,
            "geo_false_migrations": int(false_migrations),
            "geo_lost_acked": lost,
            "geo_double_applied": double,
            "seed": seed, "sessions": 2 * sessions, "lanes": lanes,
            "ops": int(sum(len(clients[e].op_state)
                           for e in clients)),
            "migrations": int(sup.counters["migrations"]),
            "stale_probe_drops":
                int(sup.counters["stale_probe_drops"]),
            "rehome_follows":
                int(sum(clients[e].rehome_follows for e in clients)),
            "probe_replies":
                {e: int(probes[e].replies) for e in probes},
            "detector": {k: int(sup.counters[k]) for k in
                         ("heartbeats", "suspects", "downs",
                          "recoveries")},
            "domain_matrix": base_plan.overview().get("domain_matrix"),
            "elapsed_s": elapsed, "wal_shards": wal_shards,
            "host": _host_envelope(),
        }
        return row
    finally:
        _teardown(router, ctl, procs, clients, node_of={
            "engA": "n_engA", "engB": "n_engB"})
        if tmp is not None:
            tmp.cleanup()


def _expected_lane_sums(client, lanes: int, key: str) -> np.ndarray:
    """The oracle's truth, reconstructed parent-side: the key→lane
    hashing is deterministic per (seed, key), so a shadow directory
    re-derives exactly the lane placement the engine child's
    directory handed the client's sessions."""
    from ..ingress.sessions import SessionDirectory
    d = SessionDirectory(lanes, seed=0)
    h = d.connect_bulk(client.n_sessions, key=f"wire/{key}",
                       tenants=client.tenants)
    lane = d.lane[h]
    out = np.zeros(lanes, np.int64)
    for i in range(len(client.op_state)):  # control-plane scale
        out[lane[client.op_sess[i]]] += int(client.op_pay[i])
    return out


def _teardown(router, ctl, procs: dict, clients: dict,
              node_of: dict) -> None:
    from ..transport.rpc import reliable_node_call
    for c in clients.values():
        try:
            c.close()
        except OSError:
            pass
    for eid, node in node_of.items():
        try:
            reliable_node_call(router, node, "host_stop", {},
                               timeout=2.0)
        except (RuntimeError, TimeoutError):
            pass
    try:
        reliable_node_call(router, "gf1", "geo_stop", {}, timeout=2.0)
    except (RuntimeError, TimeoutError):
        pass
    for p in procs.values():
        try:
            p.terminate()
            p.wait(timeout=10.0)
        except (subprocess.TimeoutExpired, OSError):
            p.kill()
    ctl.stop()
    router.stop()


def _host_envelope() -> dict:
    from ..utils import host_envelope
    return host_envelope()


def geo_main(seeds, **kw) -> list:
    """tools/soak.py --geo: one run per seed, JSON tail per run."""
    rows = []
    for seed in seeds:
        res = run_geo_soak(int(seed), **kw)
        print(f"geo seed={seed}: "
              f"recovery={res['geo_failover_recovery_s']:.2f}s "
              f"false_migrations={res['geo_false_migrations']} "
              f"lost_acked={res['geo_lost_acked']} "
              f"migrations={res['migrations']}")
        print(json.dumps(res))
        rows.append(res)
    return rows


def _child_main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(prog="ra_tpu.placement.geo")
    ap.add_argument("--child", required=True,
                    choices=("engine", "control"))
    ap.add_argument("--ready", required=True)
    ap.add_argument("--parent-host", required=True)
    ap.add_argument("--parent-port", type=int, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-run-s", type=float, default=300.0)
    ap.add_argument("--eid", default="")
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--lanes", type=int, default=16)
    ap.add_argument("--sessions", type=int, default=24)
    ap.add_argument("--wal-shards", type=int, default=2)
    args = ap.parse_args(argv)
    if args.child == "engine":
        _engine_child(args)
    else:
        _control_child(args)


if __name__ == "__main__":
    _child_main()
