"""A write's commit split where it happens (ISSUE 37): ``durable_wait``,
``confirm_carry`` and ``commit_observe`` sum to every block's
``block_e2e``; a confirm that misses the next dispatch is counted in
``confirm_late_blocks``; a slow ``pump()`` is stamped with tracing off
(``slow_pumps``, ``pump.slow``); medians are over the window; every
compile names its program.

All counts, sums of the same stamps, or structure: no assertion is a
ratio of wall times.
"""
import collections
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu import devicewatch, ingress, metrics, trace
from ra_tpu.autotune import NON_BUDGET_PHASES
from ra_tpu.blackbox import EVENT_REGISTRY, RECORDER
from ra_tpu.engine import open_engine
from ra_tpu.ingress import IngressPlane
from ra_tpu.log import faults
from ra_tpu.telemetry import PhaseStats
from ra_tpu.wire import DedupCounterMachine, LoopbackFleet, WireListener

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPLIT = ("durable_wait", "confirm_carry", "commit_observe")


class _Served:
    """A small durable engine behind a listener, with every phase
    sample kept in the order it was noted."""

    def __init__(self, d, wal_shards=2, lanes=16):
        self.eng = open_engine(DedupCounterMachine(slots=64), str(d), lanes,
                               wal_shards=wal_shards, ring_capacity=256,
                               max_step_cmds=8, donate=False)
        self.plane = IngressPlane(self.eng, superstep_k=2, window_s=0.0,
                                  soft_credit=1 << 20, hard_credit=1 << 20)
        self.lst = WireListener(self.plane, port=None, max_conns=64,
                                ring_bytes=4096)
        self.fleet = LoopbackFleet(self.lst, 2 * lanes, key="split", seed=0)
        self.samples = collections.defaultdict(list)
        real = self.eng.phases.note

        def note(phase, dt_s):
            self.samples[phase].append(dt_s)
            real(phase, dt_s)

        self.eng.phases.note = note

    def cycle(self, wait_confirm=False):
        n = self.fleet.n_conns
        self.fleet.new_ops(np.arange(n), np.full(n, 3, np.int32))
        self.fleet.send_queued()
        self.lst.sweep()
        self.fleet.collect()
        assert self.plane.pump(force=True)
        self.fleet.collect()
        if wait_confirm:
            dur = self.eng._dur
            deadline = time.monotonic() + 30
            while dur.confirmed_step < dur.step_seq:
                assert time.monotonic() < deadline, "WAL never confirmed"
                time.sleep(0.001)

    def close(self):
        self.plane.settle()
        self.eng._dur.flush_all()
        self.lst.close()
        self.eng.close()


@pytest.mark.parametrize("wal_shards", [1, 2])
def test_the_three_phases_sum_to_block_e2e(tmp_path, wal_shards):
    s = _Served(tmp_path, wal_shards=wal_shards)
    try:
        for _ in range(8):
            s.cycle()
    finally:
        s.close()
    b = s.samples["block_e2e"]
    parts = [s.samples[p] for p in SPLIT]
    assert b and all(len(p) == len(b) for p in parts)
    # noted together at the retire, in one order: block by block
    for i, whole in enumerate(b):
        assert all(p[i] >= 0 for p in parts), i
        assert sum(p[i] for p in parts) == pytest.approx(whole, abs=1e-9)
    ph = s.eng.phases.overview()
    assert {ph[p]["count"] for p in SPLIT} == {ph["block_e2e"]["count"]}
    total = sum(sum(p) for p in parts) * 1e3
    assert total == pytest.approx(sum(b) * 1e3, rel=1e-6)
    assert total == pytest.approx(
        sum(ph[p]["total_ms"] for p in SPLIT), abs=5e-3)


def test_a_confirm_in_time_is_carried_by_the_next_dispatch(tmp_path):
    """Each pump waits for the WAL to confirm what it dispatched: the
    next dispatch's sample covers the block, no block is late."""
    s = _Served(tmp_path)
    try:
        for _ in range(6):
            s.cycle(wait_confirm=True)
    finally:
        s.close()
    assert s.samples["block_e2e"]
    assert s.eng.pipeline_counters["confirm_late_blocks"] == 0
    assert s.eng.overview()["pipeline"]["confirm_late_blocks"] == 0


def test_a_confirm_held_past_the_next_dispatch_is_late(tmp_path):
    """WAL I/O held 150 ms a call: the next dispatch samples before the
    block's rows are durable, so its carrier comes later."""
    s = _Served(tmp_path, wal_shards=1)
    try:
        for _ in range(2):                  # compile and warm
            s.cycle(wait_confirm=True)
        late0 = s.eng.pipeline_counters["confirm_late_blocks"]
        faults.install_plan(faults.DiskFaultPlan(by_class={
            "wal": faults.DiskFaultSpec(slow=1.0, slow_ms=(150.0, 150.0))}))
        try:
            for _ in range(4):
                s.cycle()
            s.plane.settle()
        finally:
            faults.clear_plan()
    finally:
        s.close()
    assert late0 == 0
    assert s.eng.pipeline_counters["confirm_late_blocks"] > 0
    assert min(s.samples["confirm_carry"]) >= 0
    assert min(s.samples["durable_wait"]) >= 0


def test_a_dispatch_names_the_steps_it_carries_only_while_tracing(
        tmp_path, monkeypatch):
    """``carries=<first>-<last>`` on ``ra.driver.dispatch``: the merged
    confirm's new steps, each range after the last, set only while
    something records."""
    calls = []
    real = trace._PhaseSpan.set_metadata

    def spy(self, **args):
        calls.append(args)
        real(self, **args)

    monkeypatch.setattr(trace._PhaseSpan, "set_metadata", spy)
    s = _Served(tmp_path / "off")
    try:
        for _ in range(4):
            s.cycle(wait_confirm=True)
    finally:
        s.close()
    assert calls == []
    t = trace.Tracer()
    trace.set_tracer(t)
    try:
        s = _Served(tmp_path / "on")
        try:
            for _ in range(4):
                s.cycle(wait_confirm=True)
        finally:
            s.close()
    finally:
        trace.set_tracer(None)
    ranges = [tuple(map(int, e["args"]["carries"].split("-")))
              for e in t.events() if e["name"] == "ra.driver.dispatch"
              and "carries" in e.get("args", {})]
    assert ranges and len(ranges) == len(calls)
    for (a0, a1), (b0, _b1) in zip(ranges, ranges[1:]):
        assert a0 <= a1 < b0


def test_a_slow_pump_is_counted_and_recorded_with_its_split(
        tmp_path, monkeypatch):
    s = _Served(tmp_path)
    try:
        for _ in range(3):
            s.cycle()
        assert s.plane.counters["slow_pumps"] == 0
        monkeypatch.setattr(ingress, "PUMP_SLOW_S", 0.1)
        dur = s.eng._dur
        real = dur.backpressure
        monkeypatch.setattr(dur, "backpressure",
                            lambda *a: (time.sleep(0.3), real(*a)))
        before_ns = time.time_ns()
        s.cycle()
        monkeypatch.setattr(dur, "backpressure", real)
        after_ns = time.time_ns()
        events = [f for _t, e, f in RECORDER.events("pump")
                  if e == "pump.slow" and f["start_ns"] >= before_ns]
    finally:
        s.close()
    assert s.plane.counters["slow_pumps"] == 1
    (ev,) = events
    assert set(ev["split"]) == set(s.eng.pump_split)
    assert ev["split"]["backpressure"] >= 300
    assert sum(ev["split"].values()) == pytest.approx(ev["ms"], rel=0.05)
    assert before_ns <= ev["start_ns"] <= after_ns - ev["ms"] * 1e6 + 1e6
    assert ev["xla_compiles"] >= 0 and ev["window_syncs"] >= 0
    assert len(ev["gc_collections"]) == len(gc.get_stats())
    assert s.plane.overview()["slow_pumps"] == 1


def test_the_pump_is_a_phase_a_span_and_out_of_the_tuners_budget(tmp_path):
    s = _Served(tmp_path)
    try:
        for _ in range(3):
            s.cycle()
        pumps = s.plane.counters["blocks_built"]
    finally:
        s.close()
    assert len(s.samples["pump"]) >= pumps == 3
    assert set(SPLIT) | {"pump"} <= set(NON_BUDGET_PHASES)


def test_a_median_is_over_every_sample_of_the_window():
    rng = np.random.default_rng(37)
    vals = rng.exponential(5.0, 2000)
    ph = PhaseStats()
    for v in vals:
        ph.note("durable_wait", v / 1e3)
    ov = ph.overview()["durable_wait"]
    assert ov["samples"] == ov["count"] == 2000
    ordered = np.sort(vals)
    assert ov["p50_ms"] == pytest.approx(ordered[1000], abs=1e-3)
    assert ov["max_ms"] == pytest.approx(ordered[-1], abs=1e-3)
    ph.reset_reservoirs()
    after = ph.overview()["durable_wait"]
    assert after["samples"] == 0 and after["count"] == 2000


def test_a_full_reservoir_keeps_the_newest():
    ph = PhaseStats(reservoir=4)
    for ms in (100, 100, 100, 100, 1, 2, 3):
        ph.note("pump", ms / 1e3)
    ov = ph.overview()["pump"]
    assert ov["samples"] == 4 and ov["count"] == 7
    assert ov["max_ms"] == 100.0 and ov["p50_ms"] == 3.0


def test_a_compile_names_its_program():
    def ra_test_split_named_program(x):
        return x * 37 + 1

    before = devicewatch.WATCH.xla_compiles_by_fun[
        "jit(ra_test_split_named_program)"]
    jax.jit(ra_test_split_named_program)(jnp.arange(5.0)).block_until_ready()
    by_fun = devicewatch.WATCH.overview()["xla_compiles_by_fun"]
    assert by_fun["jit(ra_test_split_named_program)"] == before + 1
    evs = [f for _t, e, f in RECORDER.events("device")
           if e == "device.compile"
           and f["fun"] == "jit(ra_test_split_named_program)"]
    assert evs and evs[-1]["s"] >= 0 and evs[-1]["thread"]
    assert devicewatch.WATCH.compiled_names(1) == [
        "jit(ra_test_split_named_program)"]


@pytest.mark.parametrize("name, fields", [
    ("durable_wait", "PHASE_FIELDS"), ("confirm_carry", "PHASE_FIELDS"),
    ("commit_observe", "PHASE_FIELDS"), ("pump", "PHASE_FIELDS"),
    ("confirm_late_blocks", "ENGINE_PIPELINE_FIELDS"),
    ("slow_pumps", "INGRESS_FIELDS"),
])
def test_new_field_is_registered_and_documented(name, fields):
    assert name in getattr(metrics, fields)
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read()
    assert f"| `{name}` |" in doc


@pytest.mark.parametrize("event", ["pump.slow", "device.compile"])
def test_new_event_is_registered_and_documented(event):
    assert event in EVENT_REGISTRY
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md")).read()
    assert f"| `{event}` |" in doc
