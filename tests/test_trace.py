"""Tracing/profiling hooks (SURVEY §5 tracing row): span recording,
Chrome trace-event export, summary rollups, the swappable process-wide
tracer, and the instrumentation sites in the engine and WAL.
"""
import json
import threading
import time

import jax.numpy as jnp

from ra_tpu import trace
from ra_tpu.trace import Tracer


def test_span_recorded():
    t = Tracer()
    with t.span("op", "cat", k=1):
        time.sleep(0.002)
    evts = t.events()
    assert {e["ph"] for e in evts} == {"X"}
    sp = evts[0]
    assert sp["name"] == "op" and sp["dur"] >= 1000  # >= 1ms in us
    assert sp["args"] == {"k": 1}


def test_dump_chrome_trace_is_loadable_json(tmp_path):
    t = Tracer()
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    path = t.dump_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == 5
    assert all("ts" in e and "pid" in e for e in doc["traceEvents"])


def test_ring_capacity_keeps_newest():
    t = Tracer(capacity=10)
    for i in range(25):
        with t.span(f"s{i}"):
            pass
    evts = t.events()
    assert len(evts) == 10
    names = [e["name"] for e in evts]
    assert names == [f"s{i}" for i in range(15, 25)]


def test_summary_rollup():
    t = Tracer()
    for _ in range(3):
        with t.span("hot"):
            pass
    with t.span("cold"):
        pass
    s = t.summary()
    assert s["hot"]["count"] == 3
    assert s["cold"]["count"] == 1
    assert s["hot"]["total_us"] >= s["hot"]["max_us"]


def test_global_tracer_disabled_by_default():
    assert trace.get_tracer() is None
    with trace.span("noop"):
        pass  # must not raise, must not record anywhere


def test_threads_get_distinct_tids():
    t = Tracer()

    def work():
        with t.span("w"):
            pass

    th = threading.Thread(target=work)
    th.start()
    th.join()
    with t.span("main"):
        pass
    tids = {e["tid"] for e in t.events()}
    assert len(tids) == 2


def test_engine_step_instrumented():
    from ra_tpu.engine import LockstepEngine
    from ra_tpu.models import CounterMachine

    t = Tracer()
    trace.set_tracer(t)
    try:
        eng = LockstepEngine(CounterMachine(), 4, 3, ring_capacity=64,
                             max_step_cmds=4, donate=False)
        for _ in range(3):
            eng.step(jnp.full((4,), 2, jnp.int32),
                     jnp.ones((4, 4, 1), jnp.int32))
        eng.block_until_ready()
    finally:
        trace.set_tracer(None)
    s = t.summary()
    assert s.get("ra.engine.step", {}).get("count") == 3


def test_wal_batch_instrumented(tmp_path):
    from ra_tpu.core.types import Entry, UserCommand

    from test_durable_log import drain, mk_log, mk_system

    t = Tracer()
    trace.set_tracer(t)
    try:
        sys_ = mk_system(tmp_path)
        log = mk_log(sys_)
        for i in range(1, 21):
            log.append(Entry(i, 1, UserCommand(i)))
        drain(log)
        sys_.close()
    finally:
        trace.set_tracer(None)
    s = t.summary()
    assert s.get("ra.wal.batch", {}).get("count", 0) >= 1


def test_ring_wrap_preserves_order_and_reports_drops():
    """Satellite (ISSUE 6): after the ring wraps, events() stays in
    oldest->newest order across the wrap seam and the tracer reports
    how many events were overwritten — a truncated trace must not be
    mistaken for a complete one."""
    t = Tracer(capacity=8)
    assert not t.wrapped and t.dropped_events == 0
    for i in range(20):
        with t.span(f"e{i}"):
            pass
    evts = t.events()
    assert [e["name"] for e in evts] == [f"e{i}" for i in range(12, 20)]
    ts = [e["ts"] for e in evts]
    assert ts == sorted(ts)  # monotone across the seam
    assert t.wrapped and t.dropped_events == 12
    # keep recording after the wrap: the ring keeps sliding
    with t.span("late"):
        pass
    assert t.events()[-1]["name"] == "late"
    assert t.dropped_events == 13


def test_summary_carries_wrapped_indicator():
    t = Tracer(capacity=4)
    for i in range(3):
        with t.span("a"):
            pass
    s = t.summary()
    assert s["_meta"] == {"wrapped": False, "dropped_events": 0}
    assert s["a"]["count"] == 3
    for _ in range(6):
        with t.span("b"):
            pass
    s = t.summary()
    assert s["_meta"]["wrapped"] is True
    assert s["_meta"]["dropped_events"] == 5
    # post-wrap counts cover only the surviving window — the indicator
    # is what stops them being read as totals
    assert s["b"]["count"] == 4 and "a" not in s


# -- causal trace context (ISSUE 7) -----------------------------------------

def test_trace_ctx_is_deterministic_under_set_origin():
    trace.set_trace_origin("seeded")
    a = [trace.new_trace_ctx() for _ in range(3)]
    trace.set_trace_origin("seeded")
    b = [trace.new_trace_ctx() for _ in range(3)]
    assert a == b == ["seeded-1", "seeded-2", "seeded-3"]
    assert trace.new_trace_ctx("other") == "other-4"


def test_trace_ctx_default_origin_is_process_scoped():
    import os

    trace.set_trace_origin(f"p{os.getpid()}")
    ctx = trace.new_trace_ctx()
    assert ctx.startswith(f"p{os.getpid()}-")
