"""The seam the ledger is read through: what the benchmark's files name
of the program is there.

``BENCHMARK.json`` lists per-layer metrics; each has a file under
``benchmarks/metrics/`` that names a reader and, for most, a phase,
counter, span or stage scope of the program's.  A reader that finds
nothing returns ``None`` and the run leaves the metric out, after which
every ``benchmark`` PR is refused: so a rename in the program has to
fail here first.  The same for a cell's configuration file, whose
``engine`` and ``ingress`` keys are passed to the program as keywords.

Reads the benchmark's files and writes none; one tiny served run a
module (``served``, tests/conftest.py).
"""
import inspect
import json
import os

import pytest

from benchmarks.harness import program_spans
from ra_tpu import metrics
from ra_tpu.engine import LockstepEngine, open_engine
from ra_tpu.ingress import IngressPlane
from ra_tpu.models import CounterMachine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

#: readers whose metric file names nothing: the counter each divides by
#: or sums, as its source spells it
READS_COUNTER = {"ops_per_round": ("pipeline", "inner_steps"),
                 "d2h_bytes_per_op": ("device", "d2h_bytes")}
#: readers of the step program's device time, found by the jit's name
READS_STEP_MODULE = {"step_ms_per_round", "step_roofline",
                     "stage_named_pct", "stage_ms_per_round"}
#: readers of the client's ledger, of the device's own operations or of
#: a span the benchmark draws itself: nothing of the program's to find
NOT_THE_PROGRAMS = {"client_commit_p99_ms", "client_commit_p50_ms",
                    "gen_late_p95_ms", "device_idle_pct",
                    "sweep_busy_pct", "hot_commit_p50_ms"}
#: readers of one phase that answer 0 where the mix sends no such
#: operation (the served run sends none): the phase has to exist
READS_PHASE = {"read_e2e_p50_ms"}
#: readers of one stage's device time and of the engine's widths
READS_STAGE = {"stage_ms_per_round", "read_serve_roofline",
               "apply_fold_roofline"}


def _load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = _load(REPO, "BENCHMARK.json")


def _metric_files() -> list:
    listed = [m["name"] for m in MANIFEST["per_layer"]]
    metas = [_load(BENCH, "metrics", name + ".json") for name in listed]
    readers = {m["reader"] for m in metas}
    known = set(READS_COUNTER) | READS_STEP_MODULE | NOT_THE_PROGRAMS \
        | READS_PHASE | READS_STAGE \
        | {"phase_p50", "counter_delta", "span_self_pct", "span_own_pct"}
    assert readers <= known, f"a reader this file has no case for: " \
                             f"{readers - known}"
    return [(name, meta) for name, meta in zip(listed, metas)
            if meta["reader"] not in NOT_THE_PROGRAMS]


def _reader_source(name: str) -> str:
    with open(os.path.join(BENCH, "readers", name + ".py")) as f:
        return f.read()


@pytest.mark.parametrize("meta", [
    pytest.param(meta, id=name) for name, meta in _metric_files()])
def test_a_listed_metric_reads_something_the_program_has(served, meta):
    reader = meta["reader"]
    if reader == "phase_p50":
        assert meta["phase"] in metrics.PHASE_FIELDS
        assert served["phase_counts"][meta["phase"]] > 0
    elif reader == "counter_delta":
        assert meta["key"] in served["counters"][meta["group"]]
    elif reader in READS_COUNTER:
        group, key = READS_COUNTER[reader]
        assert f'"{group}", "{key}"' in _reader_source(reader)
        assert served["counters"][group][key] > 0
    elif reader in ("span_self_pct", "span_own_pct"):
        assert meta["span"].startswith(program_spans.PROGRAM_PREFIX)
        assert meta["span"] in {e[0] for th in served["threads"]
                                for e in th}
    elif reader in READS_PHASE:
        assert meta["phase"] in metrics.PHASE_FIELDS
        assert meta["phase"] in served["phase_counts"]
    if reader in READS_STAGE:
        assert program_spans.stage_of(meta["stage"]) == meta["stage"]
        assert f'loc("{meta["stage"]}/' in served["lowered"]
    if reader == "read_serve_roofline":
        src = _reader_source(reader)
        assert "eng.query_width" in src and "eng.query_reply_width" in src
        assert {"query_width", "query_reply_width"} <= set(
            vars(LockstepEngine(CounterMachine(), 2, 3, ring_capacity=16,
                                max_step_cmds=2)))
    if reader == "apply_fold_roofline":
        assert "eng.payload_width" in _reader_source(reader)
        assert "payload_width" in vars(LockstepEngine(
            CounterMachine(), 2, 3, ring_capacity=16, max_step_cmds=2))
    if reader in READS_STEP_MODULE | READS_STAGE:
        assert f"module @jit_{program_spans.STEP_MODULE}" \
            in served["lowered"]


def _config_files() -> list:
    return [os.path.basename(c["file"]) for c in MANIFEST["configs"]]


def _keywords(fn) -> set:
    return {n for n, p in inspect.signature(fn).parameters.items()
            if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)}


@pytest.mark.parametrize("config", _config_files())
def test_a_cell_s_configuration_names_only_options_the_program_takes(
        config):
    cfg = _load(BENCH, "configs", config)
    # open_engine hands what it does not name to LockstepEngine
    assert "engine_kwargs" in inspect.signature(open_engine).parameters
    engine = _keywords(open_engine) | _keywords(LockstepEngine.__init__)
    # the harness's own: the layout its check reopens the WAL under
    asked = set(cfg["engine"]) - {"reopen_wal_shards"}
    assert asked and asked <= engine, asked - engine
    asked = set(cfg["ingress"])
    assert asked and asked <= _keywords(IngressPlane.__init__), asked


# -- the seam a deployment comes in through, counted by tier-1 ---------------
#
# ``benchmarks/tests/test_seam.py`` (PR 31, a ``benchmark`` PR, which
# may write nothing outside ``benchmarks/``) holds the cases; tier-1
# runs ``tests/`` only, so they are taken in here by name and run as
# this module's (PERF.md Open question 20(a)).  One is written again:
# the original states how many configurations, cells and metrics the
# committed manifest has (4, 4, 33) and is wrong since the first
# deployment that came in through the seam.

from benchmarks.tests import test_seam as _seam  # noqa: E402

test_a_configuration_s_kit_has_the_whole_contract_and_builds = \
    _seam.test_a_configuration_s_kit_has_the_whole_contract_and_builds
test_a_module_short_of_the_contract_is_named_with_what_it_lacks = \
    _seam.test_a_module_short_of_the_contract_is_named_with_what_it_lacks
test_seed_31_gives_the_bytes_the_parent_gave = \
    _seam.test_seed_31_gives_the_bytes_the_parent_gave
test_a_mix_without_ops_never_draws_a_kind_and_one_with_draws_its_shares = \
    _seam.test_a_mix_without_ops_never_draws_a_kind_and_one_with_draws_its_shares
test_a_kit_s_reference_shares_no_code_with_its_machine = \
    _seam.test_a_kit_s_reference_shares_no_code_with_its_machine
test_the_harness_holds_nothing_of_any_machine = \
    _seam.test_the_harness_holds_nothing_of_any_machine
test_derive_of_the_tree_is_the_committed_manifest_byte_for_byte = \
    _seam.test_derive_of_the_tree_is_the_committed_manifest_byte_for_byte
test_a_read_goes_out_at_the_writes_stride_and_a_refused_one_again = \
    _seam.test_a_read_goes_out_at_the_writes_stride_and_a_refused_one_again
test_the_probe_s_bounds_on_a_ledger_made_by_hand = \
    _seam.test_the_probe_s_bounds_on_a_ledger_made_by_hand


def test_derive_keeps_every_committed_entry_at_its_index(tmp_path):
    """A cell, a configuration, a mix and a metric under names that sort
    before every committed one: each committed entry keeps its index in
    every list, the new ones come last, however many are committed."""
    import shutil

    from benchmarks import manifest as mf
    here = tmp_path / "benchmarks"
    for sub in ("cells", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), here / sub)
    cfg = _load(BENCH, "configs", "ra_bench_1k_x3.json")
    (here / "configs" / "000_first.json").write_text(
        json.dumps(dict(cfg, name="000_first")))
    (here / "cells" / "000_first.paced.json").write_text(json.dumps(
        {"config": "000_first", "traffic": "paced", "chips": 1,
         "rate_ops_per_s": 10, "why": "sorts first"}))
    (here / "cells" / "000_first.pipe.json").write_text(json.dumps(
        {"config": "000_first", "traffic": "pipe", "chips": 1,
         "why": "sorts first, and reports what no committed cell does"}))
    meta = _load(BENCH, "metrics", "device.idle_pct.paced.json")
    (here / "metrics" / "000.first_metric.paced.json").write_text(
        json.dumps(meta))
    committed = mf.committed()
    derived = mf.derive(str(here), committed)
    mf.validate(derived)
    n = {key: len(committed[key]) for key in
         ("configs", "workloads", "end_to_end", "per_layer")}
    for key in n:
        was = [e["name"] for e in committed[key]]
        assert [e["name"] for e in derived[key]][:n[key]] == was, key
    assert [c["name"] for c in derived["configs"]][n["configs"]:] == \
        ["000_first"]
    assert [w["name"] for w in derived["workloads"]][n["workloads"]:] == \
        ["000_first.paced", "000_first.pipe"]
    # the new end-to-end metric follows setup_s, though its file has it
    # before; the .pipe metric files follow the new .paced one by name
    assert [m["name"] for m in derived["end_to_end"]][n["end_to_end"]:] \
        == ["committed_ops_per_s"]
    new = [m["name"] for m in derived["per_layer"]][n["per_layer"]:]
    assert new[0] == "000.first_metric.paced" and new[1:] == sorted(new[1:])
    assert len(new) > 1 and all(x.endswith(".pipe") for x in new[1:])
    old = {m["name"]: m for m in committed["per_layer"]}
    for m in derived["per_layer"]:
        if m["name"] in old:
            assert m["workloads"] == \
                old[m["name"]]["workloads"] + ["000_first.paced"]
            assert {k: v for k, v in m.items() if k != "workloads"} == \
                {k: v for k, v in old[m["name"]].items()
                 if k != "workloads"}
        elif m["name"].endswith(".pipe"):
            assert m["workloads"] == ["000_first.pipe"]
    # with no committed manifest to append to, name order
    fresh = mf.derive(str(here), {})
    assert [w["name"] for w in fresh["workloads"]][:2] == \
        ["000_first.paced", "000_first.pipe"]
    assert fresh["per_layer"][0]["name"] == "000.first_metric.paced"


def test_the_quorum_queue_kit_compares_every_leaf_of_its_machine():
    """The quorum queue's kit (``kits/quorum_queue/``) names every leaf
    of its machine's state for the check, so that no leaf goes
    uncompared across replicas; its configuration builds the machine
    at the deployment's sizes; and the counter its cell's new metric
    reads is the engine's from construction."""
    from benchmarks.harness import kits
    cfg = _load(BENCH, "configs", "qq_5k_x5.json")
    kit = kits.load(cfg)
    machine = kit.build_machine(dict(cfg, clusters=2))
    assert (machine.capacity, machine.loaded, machine.consumers,
            machine.prefetch, machine.delivery_limit) == (
        cfg["capacity"], cfg["loaded"], cfg["consumers"], cfg["prefetch"],
        cfg["delivery_limit"])
    state = machine.jit_init(2)
    assert set(kit.leaves(state)) == set(state)
    meta = _load(BENCH, "metrics", "apply.fallback_rounds.paced.json")
    assert meta["key"] in metrics.ENGINE_PIPELINE_FIELDS
    eng = LockstepEngine(CounterMachine(), 2, 3, ring_capacity=16,
                         max_step_cmds=2)
    assert eng.pipeline_counters[meta["key"]] == 0
