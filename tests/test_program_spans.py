"""Program spans on the profiler's clock (ISSUE 25): every span of the
served path in the xplane of a CPU profiler session, children inside
parents, the WAL's spans on threads of their own, one ``block=`` id
from pop to retire; ``PhaseStats`` and the annotation fed by one site;
the stages of the fused step named in its lowering and the computation
unchanged by the names; the process-wide compile counter.

All counts or structure: no assertion is a ratio of wall times.
"""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ra_tpu import devicewatch, metrics, trace
from ra_tpu.blackbox import EVENT_REGISTRY
from ra_tpu.engine import LockstepEngine, lockstep, open_engine
from ra_tpu.ingress import IngressPlane
from ra_tpu.models import CounterMachine
from ra_tpu.telemetry import PhaseStats
from ra_tpu.wire import DedupCounterMachine, LoopbackFleet, WireListener

from harness import SERVED_PUMPS as N_PUMPS, step_args, superstep_args

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span -> the span that must contain it (None: a root of its thread);
#: settle() drains the driver itself, so it too holds stages and dispatches
PARENT = {
    "ra.sweep": None,
    "ra.sweep.receive": "ra.sweep",
    "ra.sweep.decode": "ra.sweep",
    "ra.sweep.submit": "ra.sweep",
    "ra.sweep.credit": "ra.sweep",
    "ra.pump": None,
    "ra.pump.retire": "ra.pump.harvest",
    "ra.pump.pop_block": "ra.pump",
    "ra.pump.harvest": ("ra.pump", "ra.settle"),
    "ra.pump.reads_pop": ("ra.pump", "ra.settle"),
    "ra.pump.reads_harvest": "ra.pump.harvest",
    "ra.driver.stage": ("ra.pump", "ra.settle"),
    "ra.driver.dispatch": ("ra.pump", "ra.settle"),
    "ra.engine.backpressure": "ra.driver.dispatch",
    "ra.engine.superstep": "ra.driver.dispatch",
    "ra.engine.wal_submit": "ra.driver.dispatch",
    "ra.settle": None,
    "ra.wal.encode": None,
    "ra.wal.readback": "ra.wal.encode",
    "ra.wal.encode_block": "ra.wal.encode",
    "ra.wal.batch": None,
    "ra.wal.write": "ra.wal.batch",
    "ra.wal.fsync": "ra.wal.batch",
    "ra.wal.confirm_publish": "ra.wal.batch",
}
#: once per wait at the in-flight cap: a small engine on the CPU may
#: never wait, so its own test below forces one
WINDOW_SYNC = "ra.driver.window_sync"
CONFIRM_ONLY = "ra.pump.confirm_only"
#: spans that only some traffic draws: a wait at the cap; rows of a
#: block released ahead of it because another lane's still wait
#: (tests/test_hot_lanes.py drives that); read outcomes framed for
#: their connections (a machine with a query kernel and a client that
#: reads: tests/test_ycsb_rehearsal.py drives that); a confirm that
#: missed its dispatch carried at the pump's tail
#: (tests/test_confirm_only.py drives that)
SOMETIMES = {WINDOW_SYNC, "ra.pump.release", "ra.sweep.read_reply",
             CONFIRM_ONLY}
#: what one steady pump() emits, exactly (a retire per block the
#: watermark covers and a window_sync per wait come on top)
PER_PUMP = {"ra.pump": 1, "ra.pump.harvest": 2, "ra.pump.pop_block": 1,
            "ra.pump.reads_pop": 1, "ra.pump.reads_harvest": 2,
            "ra.driver.stage": 1, "ra.driver.dispatch": 1,
            "ra.engine.backpressure": 1, "ra.engine.superstep": 1,
            "ra.engine.wal_submit": 1}
STAGES = ("ra.s0_elect", "ra.s1_append", "ra.s2_replicate",
          "ra.s3_confirm", "ra.s4_quorum", "ra.s4a_lease", "ra.s4b_query",
          "ra.s5_apply", "ra.s5b_telemetry", "ra.s5c_read",
          "ra.durable_compact")
NEW_PHASES = ("pop_block", "wal_submit", "wal_readback", "sweep_decode",
              "staged_wait", "block_e2e")


def _all(served, name):
    return [(i, e) for i, th in enumerate(served["threads"])
            for e in th if e[0] == name]


# -- A. the spans ------------------------------------------------------------

@pytest.mark.parametrize("span", sorted(PARENT))
def test_span_is_in_the_xplane_inside_its_parent(served, span):
    found = _all(served, span)
    assert found, f"{span} not in the profile"
    parent = PARENT[span]
    if parent is None:
        return
    parents = (parent,) if isinstance(parent, str) else parent
    for thread, (_n, s, e, _a) in found:
        assert any(ps <= s and e <= pe
                   for n, ps, pe, _pa in served["threads"][thread]
                   if n in parents), f"{span} outside every {parent}"


def test_every_span_site_is_in_the_table_and_the_registry(served):
    seen = {e[0] for th in served["threads"] for e in th}
    assert seen <= set(PARENT) | SOMETIMES, seen - set(PARENT)
    assert set(PARENT) | SOMETIMES <= set(EVENT_REGISTRY)


def test_wal_spans_run_on_threads_other_than_the_serve_thread(served):
    serve = {t for t, _e in _all(served, "ra.pump")}
    assert len(serve) == 1
    assert serve == {t for t, _e in _all(served, "ra.sweep")}
    encode = {t for t, _e in _all(served, "ra.wal.encode")}
    batch = {t for t, _e in _all(served, "ra.wal.batch")}
    # two shards: a worker and a writer thread each
    assert len(encode) == 2 and len(batch) == 2
    assert not (encode | batch) & serve and not encode & batch


def test_one_block_id_from_pop_to_retire_and_steps_join_the_wal(served):
    by_block = {}
    for name in ("ra.pump.pop_block", "ra.driver.stage",
                 "ra.driver.dispatch", "ra.pump.retire"):
        for _t, (_n, s, _e, args) in _all(served, name):
            by_block.setdefault(args["block"], {})[name] = (s, args)
    whole = {b: v for b, v in by_block.items() if len(v) == 4}
    assert whole, by_block
    wal_steps = {a["step"] for _t, (_n, _s, _e, a)
                 in _all(served, "ra.wal.encode")}
    for b, v in whole.items():
        order = [v[n][0] for n in ("ra.pump.pop_block", "ra.driver.stage",
                                   "ra.driver.dispatch", "ra.pump.retire")]
        assert order == sorted(order), (b, order)
        # dispatch-ahead: a block is dispatched by the pump after its own
        first, last = map(int, v["ra.driver.dispatch"][1]["step"]
                          .split("-"))
        assert last - first + 1 == 2            # superstep_k rounds
        assert set(range(first, last + 1)) <= wal_steps
    # a batch of writes names its index range; a flush marker has none
    batches = {a["step"] for _t, (_n, _s, _e, a)
               in _all(served, "ra.wal.batch")} - {"None"}
    assert batches and all(
        int(s.split("-")[0]) <= int(s.split("-")[1]) for s in batches)


def test_spans_per_pump_are_a_fixed_count(served):
    pumps = [e for _t, e in _all(served, "ra.pump")]
    thread = served["threads"][_all(served, "ra.pump")[0][0]]
    settle = [(s, e) for n, s, e, _a in thread if n == "ra.settle"]
    steady = [p for p in pumps
              if not any(s <= p[1] and p[2] <= e for s, e in settle)]
    assert len(steady) == N_PUMPS
    # the first pump after a settle only stages: nothing is staged yet
    for _n, s, e, _a in sorted(steady, key=lambda p: p[1])[1:]:
        inside = [n for n, cs, ce, _ca in thread if s <= cs and ce <= e]
        for name, count in PER_PUMP.items():
            assert inside.count(name) == count, (name, inside)
        extra = set(inside) - set(PER_PUMP)
        assert extra <= {"ra.pump.retire", "ra.pump.release", WINDOW_SYNC,
                         CONFIRM_ONLY}
        assert inside.count(WINDOW_SYNC) <= 1
        assert inside.count(CONFIRM_ONLY) <= 1


@pytest.mark.parametrize("phase, span", [
    ("pop_block", "ra.pump.pop_block"),
    ("host_staging", "ra.driver.stage"),
    ("wal_submit", "ra.engine.wal_submit"),
    ("sweep_decode", "ra.sweep.decode"),
    ("wal_encode", "ra.wal.encode"),
    ("wal_readback", "ra.wal.readback"),
    ("encode", "ra.wal.encode_block"),
    ("fsync_wait", "ra.wal.fsync"),
    ("confirm_publish", "ra.wal.confirm_publish"),
    ("pump", "ra.pump"),
])
def test_a_phase_has_one_sample_a_span(served, phase, span):
    """One site stamps both: as many phase samples as spans."""
    assert served["phase_counts"][phase] == len(_all(served, span)) > 0


class _NeverReady:
    """A watermark readback that is not ready when the driver pops it."""
    nbytes = 4

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        return np.zeros((2, 4), np.int32)


def test_window_sync_is_a_span_once_per_wait_and_only_for_a_wait():
    eng = LockstepEngine(CounterMachine(), 4, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    driver = lockstep.DispatchAheadDriver(eng, max_in_flight=1)
    blk = (np.zeros((2, 4), np.int32), np.zeros((2, 4, 4, 1), np.int32))
    t = trace.Tracer()
    trace.set_tracer(t)
    try:
        for _ in range(3):
            driver.submit(*blk)
        driver.drain()               # every readback awaited: none waits
        ready_waits = t.summary().get(WINDOW_SYNC, {}).get("count", 0)
        syncs = eng.pipeline_counters["window_syncs"]
        assert ready_waits == syncs
        # a readback that is not ready at the cap: one wait, one span
        eng.watermarks = _NeverReady
        for _ in range(3):           # the first fits under the cap
            driver.submit(*blk)
    finally:
        trace.set_tracer(None)
    waits = eng.pipeline_counters["window_syncs"] - syncs
    assert waits == 2                # cap 1: each later dispatch waited
    assert t.summary()[WINDOW_SYNC]["count"] == ready_waits + waits


def test_the_two_waits_are_noted_once_a_block(served):
    counts = served["phase_counts"]
    assert counts["staged_wait"] == len(_all(served, "ra.driver.dispatch"))
    assert counts["block_e2e"] == len(_all(served, "ra.pump.retire"))


def test_phase_span_feeds_the_phase_and_the_tracer_from_one_interval():
    stats, t = PhaseStats(), trace.Tracer()
    trace.set_tracer(t)
    try:
        with trace.phase_span("ra.pump.pop_block", stats, "pop_block",
                              block=3) as sp:
            pass
    finally:
        trace.set_tracer(None)
    ph = stats.overview()["pop_block"]
    assert ph["count"] == 1
    assert ph["total_ms"] == pytest.approx(sp.dt_s * 1e3, abs=1e-3)
    (ev,) = t.events()
    assert ev["name"] == "ra.pump.pop_block" and ev["args"] == {"block": 3}
    # no accumulator wired: the span alone
    with trace.phase_span("ra.wal.fsync", None, "fsync_wait"):
        pass


def test_with_no_session_a_span_is_the_shared_noop(tmp_path):
    """"On" is "a profiler session runs or a Tracer is installed":
    with neither, a span site builds nothing, and a site that would
    have to compute its span's arguments asks ``active()`` first."""
    assert trace.get_tracer() is None
    assert not trace.active()
    assert trace.span("ra.pump", block=1) is trace.NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.active()
        assert trace.span("ra.pump", block=1) is not trace.NULL
    finally:
        jax.profiler.stop_trace()
    assert not trace.active()
    trace.set_tracer(trace.Tracer())
    try:
        assert trace.active()
    finally:
        trace.set_tracer(None)


def test_a_span_in_a_process_without_jax_imports_none():
    """The classic host WAL's batch thread runs spans too: a process
    that never imported jax has no session, and a span there is the
    no-op without pulling jax in."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from ra_tpu import trace\n"
            "assert trace.span('ra.wal.batch', n=1) is trace.NULL\n"
            "assert not trace.active()\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=60)


@pytest.mark.parametrize("site", ["wal_batch", "driver_dispatch"])
def test_step_ranges_are_computed_only_while_something_records(
        site, tmp_path, monkeypatch):
    """The ``step=<first>-<last>`` join costs a walk of the batch (the
    WAL) or a format (the driver): neither runs with tracing off."""
    seen = []
    real = trace.span

    def spy(name, cat="ra", **args):
        if "step" in args:
            seen.append((name, args["step"]))
        return real(name, cat, **args)

    monkeypatch.setattr(trace, "span", spy)
    name = {"wal_batch": "ra.wal.batch",
            "driver_dispatch": "ra.driver.dispatch"}[site]

    def pumps(d):
        eng = open_engine(DedupCounterMachine(slots=64), str(d), 16,
                          wal_shards=2, ring_capacity=256,
                          max_step_cmds=8, donate=False)
        plane = IngressPlane(eng, superstep_k=2, window_s=0.0,
                             soft_credit=1 << 20, hard_credit=1 << 20)
        lst = WireListener(plane, port=None, max_conns=64,
                           ring_bytes=4096)
        fleet = LoopbackFleet(lst, 32, key="spans", seed=0)
        try:
            for _ in range(3):
                fleet.new_ops(np.arange(32), np.full(32, 3, np.int32))
                fleet.send_queued()
                lst.sweep()
                assert plane.pump(force=True)
                fleet.collect()
            plane.settle()
            eng._dur.flush_all()
        finally:
            lst.close()
            eng.close()

    pumps(tmp_path / "off")
    assert [s for n, s in seen if n == name and s is not None] == []
    seen.clear()
    trace.set_tracer(trace.Tracer())
    try:
        pumps(tmp_path / "on")
    finally:
        trace.set_tracer(None)
    assert [s for n, s in seen if n == name and s is not None]


def test_the_tracer_stamps_on_the_profilers_clock():
    import time
    t = trace.Tracer()
    before = time.time_ns() / 1e3
    with t.span("mark"):
        pass
    assert before <= t.events()[0]["ts"] <= time.time_ns() / 1e3


@pytest.mark.parametrize("name", NEW_PHASES + ("xla_compiles",
                                               "xla_compile_ms"))
def test_new_field_is_registered_and_documented(name):
    fields = metrics.PHASE_FIELDS if name in NEW_PHASES \
        else metrics.DEVICE_FIELDS
    assert name in fields
    assert fields in metrics.FIELD_REGISTRY.values()
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        assert f"`{name}`" in f.read()


# -- B. names on the device --------------------------------------------------

@pytest.fixture(scope="module")
def lowered():
    eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                         max_step_cmds=4, donate=False)
    eng._compile_step(durable=True)
    low = eng._sstep.lower(*superstep_args(eng))
    return eng, low, low.as_text(debug_info=True)


@pytest.mark.parametrize("stage", STAGES)
def test_stage_scope_is_in_the_lowered_superstep(lowered, stage):
    assert f'loc("{stage}/' in lowered[2]


def test_the_jitted_functions_carry_names(lowered):
    eng, _low, text = lowered
    assert "module @jit_ra_superstep" in text
    step = eng._step.lower(*step_args(eng))
    assert "module @jit_ra_step" in step.as_text()


def _seeded_rounds(eng, rounds=3):
    """Outputs of a few seeded fused dispatches: the state's leaves and
    the last dispatch's aux, as host arrays."""
    rng = np.random.default_rng(7)
    n, c, k = eng.n_lanes, eng.max_step_cmds, 2
    for _ in range(rounds):
        aux = eng.superstep(
            rng.integers(0, c + 1, (k, n)).astype(np.int32),
            rng.integers(1, 9, (k, n, c, eng.payload_width))
            .astype(np.int32))
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (eng.state, aux))]


def test_scopes_are_metadata_only(lowered, monkeypatch):
    """The compiled computation is the same with the scopes and without
    them: flops and bytes of the fused step by the compiler's count,
    and the outputs of seeded dispatches."""
    import contextlib
    eng, low, _text = lowered
    named = low.compile().cost_analysis()
    named_out = _seeded_rounds(LockstepEngine(
        CounterMachine(), 8, 3, ring_capacity=64, max_step_cmds=4,
        donate=False))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(lockstep, "_STEP_JIT_CACHE", {})
    bare_eng = LockstepEngine(CounterMachine(), 8, 3, ring_capacity=64,
                              max_step_cmds=4, donate=False)
    bare_eng._compile_step(durable=True)
    bare_low = bare_eng._sstep.lower(*superstep_args(bare_eng))
    assert "ra.s5_apply" not in bare_low.as_text(debug_info=True)
    bare = bare_low.compile().cost_analysis()
    assert named["flops"] == bare["flops"] > 0
    assert named["bytes accessed"] == bare["bytes accessed"] > 0
    bare_out = _seeded_rounds(LockstepEngine(
        CounterMachine(), 8, 3, ring_capacity=64, max_step_cmds=4,
        donate=False))
    assert len(named_out) == len(bare_out) > 10
    for a, b in zip(named_out, bare_out):
        np.testing.assert_array_equal(a, b)
    assert any(a.any() for a in named_out)


# -- C. the compile counter --------------------------------------------------

def test_xla_compiles_counts_a_new_shape_on_a_worker_thread_once():
    x = jnp.arange(4099)
    c = devicewatch.WATCH.counters

    def pull(n):
        np.asarray(x[7:7 + n])

    def on_thread(n):
        before = c["xla_compiles"]
        th = threading.Thread(target=pull, args=(n,))
        th.start()
        th.join()
        return c["xla_compiles"] - before

    ms = c["xla_compile_ms"]
    assert on_thread(1031) == 1          # a slice size never seen
    assert c["xla_compile_ms"] > ms
    assert on_thread(1031) == 0          # warm: nothing compiles
    assert on_thread(1033) == 1


def test_xla_compiles_is_zero_over_a_warm_steady_loop(served):
    assert served["warm_compiles"] == 0
