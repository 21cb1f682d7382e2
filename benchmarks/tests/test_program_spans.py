"""Self time, stage time and gap attribution, checked without a chip:
on small hand-made traces where the answer is known, and on a trace
recorded on the chip with the program's spans and scopes (PR 25, one
v5e, ``ra_bench_1k_x3.paced``, 1 s traced).  And the manifest's diff is
the appends that ISSUE 25 names."""
import os
import subprocess
import sys

import pytest

from benchmarks import manifest as mf
from benchmarks import run as br
from benchmarks.harness import program_spans as ps
from benchmarks.harness import serve, trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(HERE, "fixtures", "spans_v5e_1k_x3_paced.json.gz")
STAGES = ["ra.s0_elect", "ra.s1_append", "ra.s2_replicate",
          "ra.s3_confirm", "ra.s4_quorum", "ra.s4a_lease", "ra.s4b_query",
          "ra.s5_apply", "ra.s5b_telemetry", "ra.s5c_read",
          "ra.durable_compact"]


@pytest.fixture(scope="module")
def recorded():
    return ps.load_fixture(FIXTURE)


def _host(*events):
    return {"host": [{"line": 1, "events": [list(e) for e in events]}],
            "devices": {}}


# -- self time ---------------------------------------------------------------

def test_self_time_is_the_span_less_what_its_children_cover():
    loaded = _host(("ra.pump", 0, 100, {}),
                   ("ra.pump.harvest", 10, 20, {}),
                   ("ra.pump.retire", 12, 5, {}),      # inside harvest
                   ("ra.driver.dispatch", 50, 30, {}),
                   ("ingress.pump", 0, 100, {}),       # the benchmark's own
                   ("ra.pump", 200, 100, {}))          # no child at all
    # 100 - (20 + 30) uncovered of the first, 100 of the second
    assert ps.self_pct(loaded, "ra.pump") == pytest.approx(
        100.0 * 150 / 200)
    assert ps.self_pct(loaded, "ra.sweep") is None


def test_a_child_that_overhangs_its_parent_counts_only_inside_it():
    loaded = _host(("ra.sweep", 0, 100, {}),
                   ("ra.sweep.decode", 90, 50, {}))
    assert ps.self_pct(loaded, "ra.sweep") == pytest.approx(90.0)


def test_self_time_of_the_recorded_trace(recorded):
    assert ps.self_pct(recorded, "ra.pump") == pytest.approx(
        0.4147807825166954, rel=1e-9)
    assert ps.self_pct(recorded, "ra.sweep") == pytest.approx(
        0.19842201866156323, rel=1e-9)


def test_wal_spans_of_the_recorded_trace_are_on_threads_of_their_own(
        recorded):
    serve_line = [ln["line"] for ln in recorded["host"]
                  if any(e[0] == "ra.pump" for e in ln["events"])]
    wal = {ln["line"] for ln in recorded["host"]
           if any(e[0].startswith("ra.wal.") for e in ln["events"])}
    # four shards: a worker and a writer thread each
    assert len(serve_line) == 1 and len(wal) == 8
    assert serve_line[0] not in wal
    blocks = {}
    for ln in recorded["host"]:
        for name, _s, _d, args in ln["events"]:
            if "block" in args:
                blocks.setdefault(args["block"], set()).add(name)
    assert {"ra.pump.pop_block", "ra.driver.stage", "ra.driver.dispatch",
            "ra.pump.retire"} in blocks.values()


# -- stages ------------------------------------------------------------------

@pytest.mark.parametrize("scope, stage", [
    ("jit(ra_superstep)/while/body/closed_call/ra.s5_apply/jit(_where)/"
     "select_n", "ra.s5_apply"),
    ("jit(ra_superstep)/while/body/ra.s4a_lease/add", "ra.s4a_lease"),
    ("jit(ra_superstep)/while/body/ra.durable_compact/gather:",
     "ra.durable_compact"),
    ("ra.s1_append", "ra.s1_append"),
    ("jit(ra_superstep)/while:", ""),
    ("jit(dynamic_slice)/dynamic_slice:", ""),
    ("jit(f)/extra.s5_apply/mul", ""),
])
def test_stage_of_an_op_name(scope, stage):
    assert ps.stage_of(scope) == stage


def _device(ops, modules):
    return {"host": [], "devices": {"/device:TPU:0": {
        "modules": modules, "ops": ops}}}


def test_a_loop_holds_its_body_and_each_time_counts_once():
    step = "7"
    loaded = _device(
        ops=[["%while", step, "jit(ra_superstep)/while:", 0, 100],
             ["%fusion.1", step, "jit(ra_superstep)/while/body/ra.s1_append"
              "/scatter", 10, 30],
             ["%fusion.2", step, "jit(ra_superstep)/while/body/ra.s5_apply"
              "/gather", 40, 50],
             ["%copy", step, "", 100, 10],
             ["%slice", "9", "jit(dynamic_slice)/dynamic_slice:", 200, 40]],
        modules=[["jit_ra_superstep(7)", step, 0, 110],
                 ["jit_dynamic_slice(9)", "9", 200, 40]])
    got = ps.step_stages(loaded)
    assert got["dispatches"] == 1
    assert got["total_s"] == pytest.approx(110e-9)
    assert got["stages"] == {"ra.s1_append": pytest.approx(30e-9),
                             "ra.s5_apply": pytest.approx(50e-9)}
    # the loop's own 20 ns and the copy: under no stage
    assert dict(got["unnamed"]) == {"%while": pytest.approx(20e-9),
                                    "%copy": pytest.approx(10e-9)}


def test_no_module_of_the_steps_name_reads_nothing():
    loaded = _device(ops=[["%fusion", "3", "", 0, 10]],
                     modules=[["jit__unknown(3)", "3", 0, 10]])
    assert ps.step_stages(loaded) is None


def test_stage_times_are_the_mean_over_the_device_planes():
    def plane(ns):
        return {"modules": [["jit_ra_superstep(1)", "1", 0, ns]],
                "ops": [["%f", "1", "x/ra.s5_apply/y", 0, ns]]}
    loaded = {"host": [], "devices": {"/device:TPU:0": plane(100),
                                      "/device:TPU:1": plane(300)}}
    got = ps.step_stages(loaded)
    assert got["stages"]["ra.s5_apply"] == pytest.approx(200e-9)
    assert got["total_s"] == pytest.approx(200e-9)


def test_stages_of_the_recorded_trace(recorded):
    got = ps.step_stages(recorded)
    assert got["dispatches"] == 6
    assert got["total_s"] == pytest.approx(0.091071337, rel=1e-9)
    assert set(got["stages"]) <= set(STAGES)
    assert got["stages"]["ra.s1_append"] == pytest.approx(0.037927,
                                                          rel=1e-3)
    # 0.031356 s under its own op_name and the machine's batched
    # scatter (%fusion.230, 0.009809 s), which the compiler rewrites
    # without its op_name: the instructions fused into it name the stage
    assert recorded["fused_stages"] == {"6523500218371049585": {
        "fusion.230": "ra.s5_apply", "and_reduce_fusion.2": "ra.s5_apply",
        "multiply_reduce_fusion.5": "ra.s5_apply",
        "add_bitcast_fusion.8": "ra.durable_compact",
        "broadcast_select_fusion.19": "ra.s0_elect"}}
    assert got["stages"]["ra.s5_apply"] == pytest.approx(0.041165,
                                                         rel=1e-3)
    named = sum(got["stages"].values())
    assert 100 * named / got["total_s"] == pytest.approx(97.2817, rel=1e-5)
    # what is left under no stage: the compiler's own layout copies
    assert got["unnamed"][0][0].startswith("%copy.375 = s32[1000,3,64]")
    without = ps.step_stages({**recorded, "fused_stages": {}})
    assert 100 * sum(without["stages"].values()) / without["total_s"] \
        == pytest.approx(86.4407, rel=1e-5)
    assert without["unnamed"][0][0].startswith("%fusion.230 = s32[192000]")
    # the same operations through the accepted reduction: same module time
    assert sum(d for n, _p, _s, d in
               recorded["devices"]["/device:TPU:0"]["modules"]
               if ps.STEP_MODULE in n) / 1e9 >= got["total_s"]


def _hlo(*computations):
    """An HloModule of (id, [(name, opcode, op_name, called ids)])."""
    mod = ps._messages()["HloProto"]().hlo_module
    for cid, instructions in computations:
        comp = mod.computations.add(id=cid)
        for name, opcode, op_name, called in instructions:
            ins = comp.instructions.add(name=name.encode(),
                                        opcode=opcode.encode())
            ins.metadata.op_name = op_name.encode()
            ins.called_computation_ids.extend(called)
    return mod


def test_a_fusion_without_a_stage_takes_the_one_its_instructions_name():
    body = "jit(ra_superstep)/while/body/"
    mod = _hlo(
        (1, [("scatter.1", "scatter", "", []),
             ("add.1", "add", body + "ra.s5_apply/add", []),
             ("mul.1", "multiply", body + "ra.s5_apply/mul", []),
             ("sub.1", "subtract", body + "ra.s0_elect/sub", [])]),
        (2, [("copy.1", "copy", "", [])]),
        (3, [("p.1", "parameter", body + "ra.s1_append/x", [])]),
        (9, [("fusion.7", "fusion", body + "closed_call", [1]),
             ("fusion.8", "fusion", "", [2]),       # names none: none
             ("fusion.9", "fusion", body + "ra.s4_quorum/max", [3]),
             ("while.1", "while", "jit(ra_superstep)/while", [1])]))
    # its own op_name wins (fusion.9); only fusions are looked into
    assert ps.fused_stages(mod) == {"fusion.7": "ra.s5_apply"}


def test_an_operation_under_no_stage_counts_under_its_fused_stage():
    step = "7"
    loaded = _device(
        ops=[["%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p)", step,
              "jit(ra_superstep)/while/body/closed_call", 0, 30],
             ["%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p)", "9", "", 50, 5],
             ["%copy.1 = s32[8]{0} copy(s32[8]{0} %q)", step, "", 30, 10]],
        modules=[["jit_ra_superstep(7)", step, 0, 40],
                 ["jit_ra_superstep(9)", "9", 50, 5]])
    loaded["fused_stages"] = {step: {"fusion.7": "ra.s5_apply"}}
    got = ps.step_stages(loaded)
    # the other program's operation of the same name stays under none
    assert got["stages"] == {"ra.s5_apply": pytest.approx(30e-9)}
    assert dict(got["unnamed"]) == {
        "%copy.1 = s32[8]{0} copy(s32[8]{0} %q)": pytest.approx(10e-9),
        "%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p)": pytest.approx(5e-9)}


# -- gaps --------------------------------------------------------------------

def test_segments_put_each_instant_under_its_innermost_span():
    events = [("a", 0, 100, {}), ("b", 10, 30, {}), ("c", 20, 10, {}),
              ("d", 60, 20, {}), ("e", 150, 10, {})]
    assert ps.innermost_segments(events) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 60, "a"), (60, 80, "d"), (80, 100, "a"), (150, 160, "e")]


def test_idle_time_goes_to_the_span_the_serve_thread_was_in():
    loaded = {
        "host": [{"line": 1, "events": [
            ["ingress.pump", 0, 100, {}], ["ra.pump", 0, 100, {}],
            ["ra.pump.pop_block", 20, 40, {}]]},
            {"line": 2, "events": [["ra.wal.encode", 30, 20, {}]]}],
        "devices": {"/device:TPU:0": {"modules": [], "ops": [
            ["%a", "1", "", 0, 10], ["%b", "1", "", 70, 50]]}}}
    gaps = ps.idle_gaps(loaded)
    assert gaps["window_s"] == pytest.approx(120e-9)
    assert gaps["idle_s"] == pytest.approx(60e-9)         # 10 .. 70
    assert gaps["by_span"] == {"ra.pump.pop_block": pytest.approx(40e-9),
                               "ra.pump": pytest.approx(20e-9)}
    assert gaps["meanwhile"]["ra.pump.pop_block"] == {
        "ra.wal.encode": pytest.approx(20e-9)}


def test_gaps_of_the_recorded_trace_sum_to_the_idle_time(recorded):
    gaps = ps.idle_gaps(recorded)
    assert sum(gaps["by_span"].values()) == pytest.approx(gaps["idle_s"],
                                                          rel=1e-9)
    assert 0 < gaps["idle_s"] < gaps["window_s"]
    top = list(gaps["by_span"])[:4]
    assert "ra.driver.dispatch" in top and "ra.sweep.decode" in top
    # while the serve thread sat in its dispatch, the WAL workers pulled
    assert next(iter(gaps["meanwhile"]["ra.driver.dispatch"])) == \
        "ra.wal.readback"


def test_gaps_tool_prints_the_tables():
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "gaps.py"), FIXTURE],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "ra.driver.dispatch" in r.stdout and "meanwhile" in r.stdout
    assert "ra.s5_apply" in r.stdout and "(no stage)" in r.stdout


# -- the readers, against a program that lacks what they read ----------------

class _Run:
    trace = True
    phases: dict = {}
    config = {"ingress": {"superstep_k": 4}}

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir


NEW_PHASES = ("pop_block", "wal_submit", "wal_readback", "sweep_decode",
              "staged_wait", "block_e2e")


def _reads_what_pr25_added(metric_file: str) -> bool:
    meta = br.load_json("metrics", metric_file)
    return meta["reader"] in (
        "counter_delta", "stage_named_pct", "span_self_pct",
        "stage_ms_per_round") or meta.get("phase") in NEW_PHASES


@pytest.mark.parametrize("metric", sorted(
    f[:-5] for f in os.listdir(os.path.join(HERE, "metrics"))
    if f.endswith(".paced.json") and _reads_what_pr25_added(f)))
def test_reader_reads_nothing_and_does_not_raise_on_an_older_program(
        metric, tmp_path):
    """The parent of the PR that added a span, phase or counter has
    none of them: the reader returns nothing, and the line leaves the
    metric out."""
    meta = br.load_json("metrics", metric + ".json")

    class Window:
        def delta(self, group, key):
            return {"device": {"compiles": 1}, "pipeline": {}}[group][key]

    ctx = br.Ctx(_Run(str(tmp_path)), Window(), {}, "cpu")
    assert br.load_reader(meta["reader"])(ctx, meta) is None


def test_a_counter_metric_reads_what_the_counter_rose_by():
    """A count, so 0 is a reading (no compile in the window) and not a
    missing one."""
    meta = br.load_json("metrics", "xla.compiles_in_window.paced.json")
    assert (meta["group"], meta["key"]) == ("device", "xla_compiles")

    class Window:
        rose = 0

        def delta(self, g, k):
            assert (g, k) == ("device", "xla_compiles")
            return self.rose

    read = br.load_reader(meta["reader"])
    win = Window()
    assert read(br.Ctx(_Run(""), win, {}, "cpu"), meta) == 0
    win.rose = 7
    assert read(br.Ctx(_Run(""), win, {}, "cpu"), meta) == 7


# -- the manifest ------------------------------------------------------------

#: ISSUE 25's metrics, in the order ``manifest.derive()`` lists them:
#: every name sorts after PR 23's last, so ``--write`` appended
ISSUE_25_METRICS = [
    "wire.sweep_decode_p50_ms.paced", "wire.sweep_self_pct.paced",
    "write.block_commit_p50_ms.paced", "write.observe_p50_ms.paced",
    "write.pop_block_p50_ms.paced", "write.pump_self_pct.paced",
    "write.staged_wait_p50_ms.paced",
    "write.submit_to_confirm_p50_ms.paced",
    "write.wal_queue_wait_p50_ms.paced", "write.wal_readback_p50_ms.paced",
    "write.wal_submit_p50_ms.paced", "xla.compiles_in_window.paced",
    "xla.stage_append_ms_per_round.paced",
    "xla.stage_apply_ms_per_round.paced",
    "xla.stage_compact_ms_per_round.paced", "xla.stage_named_pct.paced"]


def test_the_manifest_appends_what_issue_25_names():
    """``manifest.py --write`` put the new entries last in their lists
    (the byte comparison is ``test_manifest.py``'s): PR 23's 12 metrics
    first, then these 16; the new cell and its configuration last, with
    the issue's parameters, and on every per-layer list."""
    committed = mf.committed()
    names = [m["name"] for m in committed["per_layer"]]
    assert names[12:] == ISSUE_25_METRICS and len(names) == 28
    assert names[11] == "wire.sweep_busy_pct.paced"
    assert committed["workloads"][-1]["name"] == \
        "ra_bench_20k_x5_mesh4.paced"
    assert committed["configs"][-1]["name"] == "ra_bench_20k_x5_mesh4"
    assert all(m["workloads"][-1] == "ra_bench_20k_x5_mesh4.paced"
               and m["moves"] == "commit_p95_ms"
               for m in committed["per_layer"])
    cell = br.load_json("cells", "ra_bench_20k_x5_mesh4.paced.json")
    assert (cell["chips"], cell["rate_ops_per_s"], cell["warmup_s"],
            cell["trace_s"], cell["traffic"]) == (4, 8000, 8, 3, "paced")


def test_the_loader_reads_a_profile_of_this_installation(tmp_path):
    """A CPU profile with one annotated thread: the loader's own
    declaration of the trace's messages reads names, arguments and one
    line a thread, and keeps the benchmark's spans only when asked."""
    import threading

    import jax
    from jax.profiler import TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def worker():
        with TraceAnnotation("ra.wal.encode", step=3, shard=1):
            pass
    try:
        th = threading.Thread(target=worker)
        th.start()
        th.join()
        with TraceAnnotation("ingress.pump"):
            with TraceAnnotation("ra.pump.pop_block", block=7):
                pass
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    loaded = ps.load(path, serve.SPANS)
    assert loaded["devices"] == {}
    by_line = {frozenset(e[0] for e in ln["events"])
               for ln in loaded["host"]}
    assert by_line == {frozenset({"ra.wal.encode"}),
                       frozenset({"ingress.pump", "ra.pump.pop_block"})}
    args = {e[0]: e[3] for ln in loaded["host"] for e in ln["events"]}
    assert args["ra.wal.encode"] == {"step": 3, "shard": 1}
    assert args["ra.pump.pop_block"] == {"block": 7}
    assert all(e[0] != "ingress.pump" for ln in ps.load(path)["host"]
               for e in ln["events"])
