"""Causal tracing plane + crash flight recorder (ISSUE 7).

Covers: per-subsystem ring discipline and the RA06 runtime mirror;
trace-context propagation client→submit→append→WAL→commit→apply on the
classic path; trace ids riding reliable-RPC frames under a seeded
transport FaultPlan (duplicate deliveries VISIBLE as ``rpc.dup`` under
one id while execution stays at-most-once); post-mortem bundle dumps
on WAL kill / poison-streak escalation with the active DiskFaultPlan
named inside; recovery stamping a join-able report; ra_trace timeline
reconstruction + --explain; the RPC_FIELDS→Observatory round trip; the
ra_top incident footer; and the <3% recorder overhead pin on the bench
dispatch path.

``run_blackbox_chaos`` is the seeded chaos family ``tools/soak.py
--blackbox`` drives: kill-9 a WAL under an active DiskFaultPlan and
prove the bundle explains a faulted command end to end.
"""
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ra_tpu.api as A
from ra_tpu import trace
from ra_tpu.blackbox import EVENT_REGISTRY, FlightRecorder, RECORDER, \
    load_bundle
from ra_tpu.core.machine import SimpleMachine
from ra_tpu.core.types import ServerId
from ra_tpu.engine import LockstepEngine
from ra_tpu.log import faults
from ra_tpu.models import CounterMachine
from ra_tpu.node import LocalRouter, RaNode
from ra_tpu.system import RaSystem
from ra_tpu.telemetry import parse_prometheus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import ra_trace  # noqa: E402


ADD = SimpleMachine(lambda c, s: s + c, 0)

#: the complete classic-path lifecycle ra_trace must reconstruct
CORE_HOPS = {"cmd.ingress", "cmd.submit", "cmd.append", "wal.write",
             "wal.confirm", "cmd.commit", "cmd.apply"}


@pytest.fixture(autouse=True)
def _fresh_recorder():
    RECORDER.clear()
    yield
    RECORDER.clear()
    faults.clear_plan()


def _mk_cluster(root, router, n=3, prefix="bx"):
    sys_ = RaSystem(str(root), wal_supervise=False)
    node = RaNode(f"{prefix}-n1", router=router, system=sys_)
    sids = [ServerId(f"{prefix}-s{i}", f"{prefix}-n1") for i in range(n)]
    A.start_cluster(f"{prefix}-c", lambda: ADD, sids, router=router)
    return sys_, node, sids


# ---------------------------------------------------------------------------
# recorder unit behaviour
# ---------------------------------------------------------------------------

def test_rings_are_per_subsystem_and_bounded():
    r = FlightRecorder(ring_capacity=8)
    for i in range(50):
        r.record("wal.fsync", ms=i)
    r.record("sup.giveup", plane="wal")
    assert len(r.events("wal")) == 8          # bounded
    assert len(r.events("sup")) == 1          # noisy plane can't evict
    assert [e[2]["ms"] for e in r.events("wal")] == list(range(42, 50))
    merged = r.events()
    assert merged == sorted(merged, key=lambda e: e[0])


def test_unregistered_event_counted_not_lost():
    """The RA06 runtime mirror: a typo'd type is still recorded
    (evidence beats purity at a crash site) but self-counted."""
    r = FlightRecorder()
    r.record("zz.not_a_real_event", x=1)
    assert r.counters["unregistered_events"] == 1
    assert len(r.events("zz")) == 1
    r.record("wal.fsync", ms=1)
    assert r.counters["unregistered_events"] == 1


def test_disabled_recorder_records_nothing():
    r = FlightRecorder()
    r.enabled = False
    r.record("wal.fsync", ms=1)
    assert r.events() == [] and r.counters["events"] == 0


def test_dump_isolates_failing_sources(tmp_path):
    r = FlightRecorder()
    r.add_source("good", lambda: {"x": 1})
    r.add_source("bad", lambda: 1 / 0)
    r.record("bb.dump", reason="seed")  # some ring content
    path = r.dump("unit_test", what="w", where="here",
                  data_dir=str(tmp_path))
    doc = load_bundle(path)
    assert doc["sources"]["good"] == {"x": 1}
    assert "error" in doc["sources"]["bad"]
    assert doc["reason"] == "unit_test"
    assert r.last_incident()["path"] == path
    # a second dump lists the first as a prior incident
    path2 = r.dump("unit_test_2", data_dir=str(tmp_path))
    assert load_bundle(path2)["incidents"][-1]["reason"] == "unit_test"


def test_every_registry_key_has_a_doc_line():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    missing = [k for k in EVENT_REGISTRY if f"`{k}`" not in doc]
    assert not missing, missing


# ---------------------------------------------------------------------------
# trace-context propagation: classic path
# ---------------------------------------------------------------------------

def test_classic_command_full_lifecycle_traced(tmp_path):
    router = LocalRouter()
    trace.set_trace_origin("tlc")
    sys_, node, sids = _mk_cluster(tmp_path, router)
    try:
        res = A.process_command(sids[0], 7, router=router, timeout=10)
        assert res.reply == 7
        evs = RECORDER.events()
        mine = [e for e in evs if e[2].get("trace") == "tlc-1"]
        kinds = [e[1] for e in mine]
        assert kinds[0] == "cmd.ingress"
        assert "cmd.submit" in kinds and "cmd.append" in kinds
        assert kinds.count("cmd.apply") == 3  # every member applies
        # the idx-keyed WAL/commit joins complete the timeline
        traces = ra_trace.index_traces(
            [(*e, "local") for e in evs])
        tl = traces["tlc-1"]
        assert CORE_HOPS <= {e[1] for e in tl["hops"]}, \
            sorted({e[1] for e in tl["hops"]})
        text = ra_trace.explain("tlc-1", tl)
        assert "breakdown:" in text and "wal write+fsync wait" in text
    finally:
        node.stop()
        sys_.close()


def test_pipeline_command_and_fifo_seqno_ctx(tmp_path):
    """pipeline_command mints a ctx too, and FifoClient's derived
    ``<mailbox>/<seqno>`` id is stable across resends by design."""
    from ra_tpu.models.fifo_client import FifoClient
    from ra_tpu.models.fifo import FifoMachine

    router = LocalRouter()
    sys_ = RaSystem(str(tmp_path), wal_supervise=False)
    node = RaNode("fx-n1", router=router, system=sys_)
    sids = [ServerId(f"fx-s{i}", "fx-n1") for i in range(3)]
    A.start_cluster("fx-c", FifoMachine, sids, router=router)
    try:
        cli = FifoClient(sids, router=router, tag="fxc")
        cli.enqueue(b"one")
        cli.flush()
        want = f"{cli.mailbox.name}/1"
        evs = [e for e in RECORDER.events("cmd")
               if e[2].get("trace") == want]
        assert any(e[1] == "cmd.ingress" for e in evs)
        assert any(e[1] == "cmd.append" for e in evs)
        # a resend reuses the SAME id: one timeline, not two
        cli.pending[1] = b"one"
        cli.resend()
        ing = [e for e in RECORDER.events("cmd")
               if e[1] == "cmd.ingress" and e[2].get("trace") == want]
        # >= 2: flush() may add its own stall-driven resend, also
        # under the same id — still one timeline
        assert len(ing) >= 2
    finally:
        node.stop()
        sys_.close()


# ---------------------------------------------------------------------------
# trace context over reliable RPC under a transport FaultPlan
# ---------------------------------------------------------------------------

def test_rpc_trace_ctx_survives_duplicates_and_partition():
    """Satellite: duplicate/partition frames keep at-most-once
    execution while the dup delivery is VISIBLE in the trace under the
    same id (fixed seed, asserted timeline shape)."""
    from ra_tpu.transport.rpc import FaultPlan, FaultSpec, Unreachable
    from ra_tpu.transport.tcp import TcpRouter

    server = TcpRouter(("127.0.0.1", 0), {})
    node = RaNode("bz1", router=server)
    client = TcpRouter(("127.0.0.1", 0), {"bz1": server.listen_addr})
    try:
        plan = FaultPlan(11, by_class={
            "rpc_req": FaultSpec(duplicate=1.0, limit=3)})
        client.set_fault_plan(plan)
        trace.set_trace_origin("rpx")
        assert A.node_call("bz1", "ping", {}, router=client,
                           timeout=20) == ("pong", "bz1")
        evs = RECORDER.events("rpc")
        sends = [e for e in evs if e[1] == "rpc.send"]
        assert sends, "sender never recorded rpc.send"
        ctx = sends[0][2]["trace"]
        assert ctx.startswith("rpx-")
        recvs = [e for e in evs
                 if e[1] == "rpc.recv" and e[2]["trace"] == ctx]
        dups = [e for e in evs
                if e[1] == "rpc.dup" and e[2]["trace"] == ctx]
        # at-most-once: executed exactly once; every duplicate dedup'd
        # under the SAME trace id
        assert len(recvs) == 1
        assert len(dups) >= 1
        assert server.rpc_counters["rpc_dedup_hits"] >= 1
        # the injected duplicates themselves are events too
        assert any(e[1] == "net.fault"
                   and e[2]["kind"] == "duplicate"
                   for e in RECORDER.events("net"))
        # reorder: frames shuffle behind the batch; the rid+ctx keep
        # execution at-most-once and the call still completes
        plan2 = FaultPlan(12, by_class={
            "rpc_req": FaultSpec(reorder=1.0, limit=2)})
        client.set_fault_plan(plan2)
        executed0 = server.rpc_counters["rpc_requests_executed"]
        assert A.node_call("bz1", "ping", {}, router=client,
                           timeout=20) == ("pong", "bz1")
        assert server.rpc_counters["rpc_requests_executed"] \
            - executed0 == 1
        # partition: unreachable surfaces, with the partition visible
        plan2.partition("bz1")
        with pytest.raises(Unreachable):
            A.node_call("bz1", "ping", {}, router=client, timeout=2)
        assert any(e[2]["kind"] == "partition"
                   for e in RECORDER.events("net"))
        plan2.heal()
    finally:
        node.stop()
        client.stop()
        server.stop()


# ---------------------------------------------------------------------------
# dump triggers + recovery stamp
# ---------------------------------------------------------------------------

def test_wal_kill_dumps_bundle_with_active_plan_named(tmp_path):
    router = LocalRouter()
    sys_, node, sids = _mk_cluster(tmp_path, router, prefix="bk")
    try:
        A.process_command(sids[0], 1, router=router, timeout=10)
        faults.install_plan(faults.DiskFaultPlan(5, by_class={
            "wal": faults.DiskFaultSpec(fsync_eio=1.0, limit=1)}))
        A.process_command(sids[0], 2, router=router, timeout=10)
        sys_.wal.kill()
        bundles = glob.glob(str(tmp_path / "blackbox" / "bundle-*"))
        assert len(bundles) == 1
        doc = load_bundle(bundles[0])
        assert doc["reason"] == "wal_kill"
        plan_src = doc["sources"]["disk_fault_plan"]
        assert plan_src["plan"] is not None
        assert "fsync_eio" in json.dumps(plan_src["plan"])
        kinds = {e[1] for evs in doc["events"].values() for e in evs}
        assert {"wal.kill", "wal.poison", "disk.fault"} <= kinds
    finally:
        faults.clear_plan()
        node.stop()
        sys_.close()


def test_poison_streak_escalation_dumps_bundle(tmp_path):
    """MAX_POISON_STREAK consecutive faulted batches -> thread death is
    a dump trigger (the ladder giving up is exactly when you want the
    black box)."""
    from ra_tpu.log.wal import MAX_POISON_STREAK, Wal

    wal = Wal(str(tmp_path))
    try:
        wal.register("u1", lambda *a: None)
        faults.install_plan(faults.DiskFaultPlan(1, by_class={
            "wal": faults.DiskFaultSpec(fsync_eio=1.0)}))
        # the no-op notify never resends, so drive a fresh faulted
        # batch per write until the streak escalates
        deadline = time.monotonic() + 10
        idx = 0
        while wal.alive and time.monotonic() < deadline:
            idx += 1
            try:
                wal.write("u1", idx, 1, b"x")
            except Exception:  # noqa: BLE001 — WalDown once it dies
                break
            time.sleep(0.05)
        assert not wal.alive
        bundles = glob.glob(str(tmp_path / "blackbox" / "bundle-*"))
        assert bundles, "escalation did not dump"
        doc = load_bundle(bundles[0])
        assert doc["reason"] == "wal_escalation"
        esc = [e for e in doc["events"]["wal"]
               if e[1] == "wal.escalate"]
        assert esc and esc[0][2]["streak"] == MAX_POISON_STREAK
    finally:
        faults.clear_plan()
        wal.close()


def test_recovery_stamp_joins_newest_bundle(tmp_path):
    router = LocalRouter()
    sys_, node, sids = _mk_cluster(tmp_path, router, prefix="br")
    A.process_command(sids[0], 3, router=router, timeout=10)
    sys_.wal.kill()          # bundle
    node.stop()
    sys_.close()
    RECORDER.clear()
    sys2 = RaSystem(str(tmp_path), wal_supervise=False)  # reopen
    try:
        recs = sorted(glob.glob(str(tmp_path / "blackbox"
                                    / "recovery-*")))
        assert recs, "reopen did not stamp a recovery report"
        with open(recs[-1]) as f:
            rep = json.load(f)
        assert rep["plane"] == "classic_wal"
        assert rep["joins"] and rep["joins"].startswith("bundle-")
        assert any(e[1] == "bb.recover"
                   for e in RECORDER.events("bb"))
    finally:
        sys2.close()


# ---------------------------------------------------------------------------
# RPC_FIELDS -> Observatory exposition/ring (satellite, round-trip)
# ---------------------------------------------------------------------------

def test_rpc_counters_reach_exposition_and_ring(tmp_path):
    class _Router:
        rpc_counters = {"rpc_calls": 3, "rpc_retries": 1,
                        "rpc_dedup_hits": 2}

    sys_ = RaSystem(str(tmp_path), wal_supervise=False)
    try:
        obs = sys_.observatory(router=_Router())
        text = obs.prometheus()
        parsed = parse_prometheus(text)
        assert parsed[("ra_tpu_rpc_rpc_calls", "")] == 3.0
        assert parsed[("ra_tpu_rpc_rpc_dedup_hits", "")] == 2.0
        # and the time-series ring rates them like any counter
        _Router.rpc_counters["rpc_calls"] = 13
        obs.snapshot()
        rates = obs.window_rates()
        assert rates.get("rpc_rpc_calls", 0) > 0
    finally:
        sys_.close()


def test_observatory_embeds_blackbox_incident(tmp_path):
    sys_ = RaSystem(str(tmp_path), wal_supervise=False)
    try:
        obs = sys_.observatory()
        RECORDER.dump("unit_incident", what="w", where="x",
                      data_dir=str(tmp_path))
        snap = obs.snapshot()
        inc = snap["blackbox"]["last_incident"]
        assert inc["reason"] == "unit_incident"
        # bundles embed a fresh Observatory snapshot while it is wired
        path = RECORDER.dump("unit_incident_2", data_dir=str(tmp_path))
        assert "observatory" in load_bundle(path)["sources"]
        # close() unhooks the bundle source (identity-guarded: a NEWER
        # observatory's registration would survive a stale close)
        obs.close()
        path = RECORDER.dump("unit_incident_3", data_dir=str(tmp_path))
        assert "observatory" not in load_bundle(path)["sources"]
    finally:
        sys_.close()


def test_ra_top_once_renders_incident_footer(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    snap = {"seq": 1, "ts": time.time(),
            "engine": {"lanes": 4, "members": 3},
            "blackbox": {"last_incident": {
                "ts": time.time() - 5, "reason": "wal_escalation",
                "what": "poison streak 3 -> thread death",
                "where": "/x/00000001.wal",
                "path": "/x/blackbox/bundle-1-2-003-wal_escalation"
                        ".json"}}}
    with open(path, "w") as f:
        f.write(json.dumps(snap) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ra_top.py"),
         path, "--once"], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "incident wal_escalation" in r.stdout
    assert "bundle-1-2-003-wal_escalation.json" in r.stdout


# ---------------------------------------------------------------------------
# overhead: recorder enabled vs disabled on the bench dispatch path
# ---------------------------------------------------------------------------

def test_volatile_dispatch_path_emits_no_recorder_events():
    """Structural half of the overhead pin: the volatile engine
    dispatch path emits ZERO per-dispatch recorder events (boundary
    events exist only on the durable submit path and on rare host
    transitions)."""
    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    # a compile is a ``device.compile`` event (ISSUE 37): warm first
    eng.uniform_step(2)
    eng.block_until_ready()
    base = RECORDER.counters["events"]
    for _ in range(20):
        eng.uniform_step(2)
    eng.block_until_ready()
    assert RECORDER.counters["events"] == base


def test_recorder_overhead_under_3pct_on_bench_path(monkeypatch):
    """What the recorder and the span sites add to the bench dispatch
    pattern with tracing off, pinned as counts of what a dispatch does
    and not as a ratio of two wall windows (which 0.25 s windows on a
    shared box cannot resolve to 3%): no recorder call a dispatch, one
    span site a dispatch and that the shared no-op; an emit is one ring
    append; with the master switch off an emit touches nothing."""
    import collections
    import threading

    eng = LockstepEngine(CounterMachine(), 64, 3, ring_capacity=64,
                         max_step_cmds=8, donate=False)
    n_new = np.full((64,), 8, np.int32)
    pay = np.ones((64, 8, 1), np.int32)
    for _ in range(10):
        eng.step(n_new, pay)
    # a compile is a ``device.compile`` event (ISSUE 37): warm the
    # readback the loop below takes too
    np.asarray(eng.committed_lanes_async())
    eng.block_until_ready()

    me = threading.get_ident()
    emits, spans = [], []
    real_record, real_span = RECORDER.record, trace.span

    def counting_record(etype, **fields):
        if threading.get_ident() == me:     # other tests' threads leak
            emits.append(etype)
        real_record(etype, **fields)

    def counting_span(name, cat="ra", **args):
        sp = real_span(name, cat, **args)
        if threading.get_ident() == me:
            spans.append((name, sp))
        return sp

    monkeypatch.setattr(RECORDER, "record", counting_record)
    monkeypatch.setattr(trace, "span", counting_span)

    def loop(n):
        rb: collections.deque = collections.deque()
        for _ in range(n):         # the bench dispatch pattern
            eng.step(n_new, pay)
            rb.append(eng.committed_lanes_async())
            while len(rb) > 8:
                np.asarray(rb.popleft())
        eng.block_until_ready()

    assert RECORDER.enabled and not trace.active()
    loop(200)
    assert emits == []
    assert [n for n, _sp in spans] == ["ra.engine.step"] * 200
    assert all(sp is trace.NULL for _n, sp in spans)
    # an emit: one ring append and one count, no more
    monkeypatch.undo()
    ring = RECORDER._rings.setdefault(
        "engine", collections.deque(maxlen=RECORDER.ring_capacity))
    n0, c0 = len(ring), dict(RECORDER.counters)
    if n0 == ring.maxlen:
        ring.popleft()
        n0 -= 1
    RECORDER.record("engine.elect", lane=0)
    assert len(ring) == n0 + 1
    c1 = dict(RECORDER.counters)
    assert c1["events"] >= c0["events"] + 1
    assert c1["unregistered_events"] == c0["unregistered_events"]
    RECORDER.enabled = False
    try:
        last = ring[-1]
        for _ in range(50):
            RECORDER.record("engine.elect", lane=0)
        assert ring[-1] is last
    finally:
        RECORDER.enabled = True


# ---------------------------------------------------------------------------
# the seeded chaos family (tools/soak.py --blackbox)
# ---------------------------------------------------------------------------

def run_blackbox_chaos(seed: int, root: str) -> dict:
    """One episode: classic durable cluster, traced traffic through a
    seeded DiskFaultPlan, then kill-9 the WAL under the ACTIVE plan.
    Asserts the bundle exists, parses, names the injected fault, and
    that ra_trace reconstructs a complete faulted-command lifecycle.
    Returns summary facts for the soak driver."""
    import random

    rng = random.Random(seed)
    RECORDER.clear()
    trace.set_trace_origin(f"bb{seed}")
    router = LocalRouter()
    # supervised, like production: a fault schedule that happens to
    # kill the batch thread mid-rollover (a torn write hitting the
    # fresh file's magic) must heal via restart+resend, not stall the
    # episode — the let-it-crash shape PR 4 pinned
    sys_ = RaSystem(os.path.join(root, "sys"), wal_supervise=True)
    node = RaNode("cb-n1", router=router, system=sys_)
    sids = [ServerId(f"cb-s{i}", "cb-n1") for i in range(3)]
    A.start_cluster("cb-c", lambda: ADD, sids, router=router)
    kind = rng.choice(["fsync_eio", "short_write"])
    try:
        for i in range(rng.randint(2, 5)):
            A.process_command(sids[0], i, router=router, timeout=10)
        spec = faults.DiskFaultSpec(**{kind: 1.0},
                                    limit=rng.randint(1, 2))
        faults.install_plan(faults.DiskFaultPlan(
            seed, by_class={"wal": spec}))
        # traced traffic THROUGH the fault: poison -> rollover ->
        # resend -> confirm, so the faulted command still completes
        # its lifecycle (that is the point: explain a command the
        # fault delayed, not one it killed)
        for i in range(4):
            A.process_command(sids[0], 100 + i, router=router,
                              timeout=15)
        sys_.wal.kill()      # kill-9 under the active plan
        bdir = os.path.join(sys_.data_dir, "blackbox")
        bundles = sorted(glob.glob(os.path.join(bdir, "bundle-*")))
        assert bundles, "wal kill did not dump a bundle"
        doc = load_bundle(bundles[-1])          # parses
        plan_named = doc["sources"]["disk_fault_plan"]["plan"]
        assert plan_named is not None and kind in json.dumps(plan_named)
        kinds = {e[1] for evs in doc["events"].values() for e in evs}
        assert "disk.fault" in kinds, "injected fault not in rings"
        # -- reconstruction through the public tool surface ------------
        events = ra_trace.load_events([bundles[-1]])
        traces = ra_trace.index_traces(events)
        auto = ra_trace.pick_auto(traces)
        tl = traces[auto]
        hops = {e[1] for e in tl["hops"]}
        assert CORE_HOPS <= hops, (auto, sorted(hops))
        assert tl["faults"], "picked trace has no fault in window"
        text = ra_trace.explain(auto, tl)
        assert "FAULT" in text and "breakdown:" in text
        return {"bundle": bundles[-1], "trace": auto, "kind": kind,
                "n_traces": len(traces),
                "fault_events": sum(1 for e in events
                                    if e[1] == "disk.fault")}
    finally:
        faults.clear_plan()
        node.stop()
        sys_.close()
        RECORDER.clear()


def test_blackbox_chaos_family_seed0(tmp_path):
    res = run_blackbox_chaos(0, str(tmp_path))
    assert res["n_traces"] >= 4 and res["fault_events"] >= 1
    # the acceptance surface is the CLI: a bundle + --explain auto
    # prints the full lifecycle with the injected fault inline
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ra_trace.py"),
         res["bundle"], "--explain", "auto"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    for frag in ("cmd.ingress", "cmd.submit", "cmd.append",
                 "wal.confirm", "cmd.commit", "cmd.apply",
                 "FAULT", "breakdown:"):
        assert frag in r.stdout, (frag, r.stdout)


def test_blackbox_chaos_family_seed3(tmp_path):
    res = run_blackbox_chaos(3, str(tmp_path))
    assert res["kind"] in ("fsync_eio", "short_write")


def test_chrome_export_is_loadable(tmp_path):
    router = LocalRouter()
    trace.set_trace_origin("ce")
    sys_, node, sids = _mk_cluster(tmp_path, router, prefix="ce")
    try:
        A.process_command(sids[0], 1, router=router, timeout=10)
        events = [(*e, "procA") for e in RECORDER.events()]
        traces = ra_trace.index_traces(events)
        out = str(tmp_path / "trace.json")
        ra_trace.to_chrome(events, traces, out)
        with open(out) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert any(e["ph"] == "X" for e in evs)      # hop spans
        assert any(e.get("name") == "process_name" for e in evs)
        assert all("ts" in e for e in evs if e["ph"] != "M")
    finally:
        node.stop()
        sys_.close()
