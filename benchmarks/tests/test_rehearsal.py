"""``run.py`` rehearsed for every cell at a tiny size on the CPU (four
forced host devices for the sharded cell): the path, ``correct`` against
the plain reference, the faults that have to fail it, and no number
under a device metric's name."""
import argparse
import copy
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import manifest as mf
from benchmarks import run as br
from benchmarks.harness import reference, traffic

DEVICE_SOURCES = ("device_trace",)
MANIFEST = mf.committed()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Every file as committed, cut to a size a test can hold."""
    extra_cells: dict = {}
    orig = br.load_json

    def load(*parts):
        name = parts[1][:-5]
        if parts[0] == "cells" and name in extra_cells:
            d = dict(extra_cells[name])
        else:
            d = orig(*parts)
        if parts[0] == "configs":
            d["clusters"] = 48
        if parts[0] == "cells":
            d["warmup_s"] = 0.5
            if "rate_ops_per_s" in d:
                d["rate_ops_per_s"] = 1500
        if parts[0] == "traffic":
            d.update(warmup_s=0.5, trace_after_s=0.2, trace_s=0.5)
            if d["loop"] == "closed":
                d.update(ramp_s=0.2, think_s=[0.0, 0.05])
        return d

    monkeypatch.setattr(br, "load_json", load)
    monkeypatch.setattr(br, "RUN_ROOT", str(tmp_path / "bench_run"))
    return extra_cells


def _args(workload, seed, trace=0, seconds=1.5):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, override=[])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct_at_a_tiny_size(tiny, cell, trace):
    rc, res = br.run_cell(_args(cell, 2**31 + 11, trace), MANIFEST,
                          require_tpu=False)
    assert rc == 0
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 100 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert all(v["limit"] == 0 for v in res["compared"].values())
    if trace == 0:
        want = {m["name"] for m in MANIFEST["end_to_end"]
                if cell in m.get("workloads", CELLS)}
        assert set(res["metrics"]) == want
        assert res["metrics"]["commit_p95_ms"]["value"] >= \
            res["metrics"]["commit_p50_ms"]["value"] > 0
        assert res["metrics"]["setup_s"]["value"] > 0
    else:
        listed = {m["name"]: m for m in MANIFEST["per_layer"]
                  if cell in m["workloads"]}
        assert set(res["metrics"]) <= set(listed)
        # a CPU run puts no number under a device metric's name
        for name in res["metrics"]:
            assert listed[name]["source"] not in DEVICE_SOURCES, name
        not_device = {n for n, m in listed.items()
                      if m["source"] not in DEVICE_SOURCES}
        assert set(res["metrics"]) == not_device
        assert "busy_s" not in res["device"]
    assert res["device"]["platform"] == "cpu"


def test_closed_loop_mix_runs_and_is_correct(tiny):
    """The ``pipe`` mix has no cell yet (PERF.md, Open questions); its
    generator is rehearsed all the same."""
    tiny["ra_bench_1k_x3.pipe"] = {
        "config": "ra_bench_1k_x3", "traffic": "pipe", "chips": 1,
        "why": "test"}
    m = copy.deepcopy(MANIFEST)
    m["workloads"].append({"name": "ra_bench_1k_x3.pipe",
                           "config": "ra_bench_1k_x3", "traffic": "pipe",
                           "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "committed_ops_per_s", "unit": "ops/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["ra_bench_1k_x3.pipe"]})
    rc, res = br.run_cell(_args("ra_bench_1k_x3.pipe", 7), m,
                          require_tpu=False)
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"committed_ops_per_s", "setup_s"}
    assert res["metrics"]["committed_ops_per_s"]["value"] > 0


def test_sharded_deployment_runs_and_is_correct_on_four_forced_devices(tiny):
    """``ra_bench_20k_x5_mesh4`` has no cell yet (PERF.md, Open
    questions): its configuration is rehearsed with the cell a later PR
    would add, lanes sharded 1 x 4 over forced host devices."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    name = "ra_bench_20k_x5_mesh4.paced"
    tiny[name] = {"config": "ra_bench_20k_x5_mesh4", "traffic": "paced",
                  "chips": 4, "rate_ops_per_s": 8000, "why": "test"}
    m = copy.deepcopy(MANIFEST)
    m["workloads"].append({"name": name, "config": "ra_bench_20k_x5_mesh4",
                           "traffic": "paced", "chips": 4, "why": "test"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append(name)
    for trace in (0, 1):
        rc, res = br.run_cell(_args(name, 13, trace), m, require_tpu=False)
        assert rc == 0 and res["correct"] is True, res
        assert res["device"]["count"] >= 4
    assert "wire.sweep_busy_pct.paced" in res["metrics"]


def _once(fn):
    done = []

    def tamper(idx, pay):
        if not done and len(idx):
            done.append(1)
            fn(pay)
    return tamper


@pytest.mark.parametrize("fault, fails", [
    # an answer altered where it is produced: one body word of one op
    (lambda pay: pay.__setitem__((0, 40), pay[0, 40] ^ 0x10000),
     "live_check_wrong"),
    # an acknowledged op dropped: sent as the engine's no-op (op id 0),
    # so the program acknowledges it and never applies it
    (lambda pay: pay.__setitem__((0, 1), 0), "live_value_wrong"),
], ids=["flipped-body-word", "dropped-acked-op"])
def test_a_fault_under_the_timed_path_comes_out_not_correct(tiny, fault,
                                                            fails):
    rc, res = br.run_cell(_args("ra_bench_1k_x3.paced", 5), MANIFEST,
                          require_tpu=False,
                          tamper=_once(fault))
    assert rc == 0
    assert res["correct"] is False
    assert res["compared"][fails]["value"] >= 1
    assert res["compared"]["reopen" + fails[4:]]["value"] >= 1


def test_no_tpu_no_result(tiny):
    rc, res = br.run_cell(_args(CELLS[0], 1), MANIFEST)
    assert rc == 2 and res is None


def test_only_the_benchmark_in_a_directory_is_refused(tmp_path):
    shutil.copytree(os.path.join(br.ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(br.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_reference_folds_each_op_once_with_wrapping_sums():
    pool = reference.make_pool(3, rows=8)
    lane = np.array([0, 0, 1]); slot = np.array([2, 2, 0])
    row = np.array([1, 5, 1], np.int32)
    salt = np.array([7, 9, 7], np.int32)
    out = reference.fold(2, 4, pool, lane=lane, slot=slot,
                         op_id=np.array([1, 2, 1]),
                         delta=np.array([3, 4, 5]), row=row, salt=salt)
    assert out["value"].tolist() == [7, 5]
    assert out["seq"].tolist() == [[0, 0, 2, 0], [1, 0, 0, 0]]
    words = reference.body_words(pool, row, salt).astype(object)
    w = [int(x) for x in reference.WEIGHTS]
    sums = [sum(int(a) * b for a, b in zip(ws, w)) for ws in words]
    want = [(sums[0] + sums[1]) % 2**32, sums[2] % 2**32]
    got = [int(x) % 2**32 for x in out["check"]]
    assert got == want
    assert (words >= 0).all() and (words < 2**31).all()
    assert reference.addresses_distinct([0, 0, 1], [1, 2, 1], 4)
    assert not reference.addresses_distinct([0, 0], [1, 1], 4)


def test_the_same_seed_gives_the_same_inputs_whatever_the_order():
    a = traffic.OpContent(2**31 + 5, 10, 64, [1, 7])
    b = traffic.OpContent(2**31 + 5, 10, 64, [1, 7])
    one = a.draw(np.array([3, 3, 4]))
    b.draw(np.array([4]))
    two = b.draw(np.array([3, 3]))
    for x, y in zip(one, two):
        assert x[:2].tolist() == y.tolist()
    assert 1 <= one[0].min() and one[0].max() <= 7


@pytest.mark.parametrize("mix", [
    {"loop": "open", "arrivals": "poisson", "session": "uniform"},
    {"loop": "open", "arrivals": "burst", "burst_on_s": 0.5,
     "burst_off_s": 0.5, "session": "uniform"},
    {"loop": "open", "arrivals": "poisson", "session": "zipf",
     "zipf_s": 0.99},
], ids=["poisson", "burst", "zipf"])
def test_open_loop_schedules_keep_the_mean_rate(mix):
    mix = dict(mix, delta=[1, 7])
    gen = traffic.make(mix, {"rate_ops_per_s": 2000}, 9, 50, 64, 20.0)
    assert abs(len(gen.due) - 40000) < 2000
    assert (np.diff(gen.due) >= 0).all() and gen.due[-1] < 20.0
    assert gen.sess.min() >= 0 and gen.sess.max() < 50
