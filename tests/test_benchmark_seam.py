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


def _load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = _load(REPO, "BENCHMARK.json")


def _metric_files() -> list:
    listed = [m["name"] for m in MANIFEST["per_layer"]]
    metas = [_load(BENCH, "metrics", name + ".json") for name in listed]
    readers = {m["reader"] for m in metas}
    known = set(READS_COUNTER) | READS_STEP_MODULE | NOT_THE_PROGRAMS \
        | {"phase_p50", "counter_delta", "span_self_pct"}
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
    elif reader == "span_self_pct":
        assert meta["span"].startswith(program_spans.PROGRAM_PREFIX)
        assert meta["span"] in {e[0] for th in served["threads"]
                                for e in th}
    elif reader == "stage_ms_per_round":
        assert program_spans.stage_of(meta["stage"]) == meta["stage"]
        assert f'loc("{meta["stage"]}/' in served["lowered"]
    if reader in READS_STEP_MODULE:
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
