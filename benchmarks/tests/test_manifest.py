"""BENCHMARK.json is what the files derive, and the rules that refuse a
manifest refuse the cases they are for."""
import copy
import json
import os

import pytest

from benchmarks import manifest as mf

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def test_committed_manifest_is_the_derived_one_and_valid():
    derived = mf.derive()
    mf.validate(derived)
    assert mf.committed() == derived
    assert set(derived) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}


def test_every_per_layer_metric_lists_only_cells_that_report_what_it_moves():
    m = mf.committed()
    for metric in m["per_layer"]:
        assert metric["workloads"], metric["name"]
        assert set(metric["workloads"]) <= \
            mf.cells_reporting(m, metric["moves"]), metric["name"]


def test_the_manifest_pr22_was_refused_for_is_refused_here():
    with open(os.path.join(FIXTURES, "pr22_manifest.json")) as f:
        bad = json.load(f)
    bad.pop("note")
    with pytest.raises(mf.ManifestError, match=(
            "gen.late_p99_ms is reported on workload "
            "ra_bench_1k_x3.saturate, where commit_p99_ms, which it "
            "should move, is not")):
        mf.validate(bad)


def _break(manifest, how):
    m = copy.deepcopy(manifest)
    how(m)
    return m


def _five_end_to_end(m):
    for i in range(5):
        m["end_to_end"].append(dict(m["end_to_end"][0], name=f"extra_{i}"))


def _most_cells_on_four_chips(m):
    for w in m["workloads"]:
        w["chips"] = 4


def _config_without_cell(m):
    m["configs"].append(dict(m["configs"][0], name="nobody_runs_this"))


@pytest.mark.parametrize("how, message", [
    (lambda m: m["workloads"][0].update(name="has space"), "name"),
    (lambda m: m["per_layer"][0].update(name="x" * 65), "name"),
    (lambda m: m["per_layer"][0].update(name="a/b"), "name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(unit="x" * 17), "unit"),
    (lambda m: m["per_layer"][0].update(moves="no_such_metric"), "moves"),
    (lambda m: m["per_layer"][0].pop("workloads"), "no workloads list"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: m["end_to_end"][0].update(source="program_span"), "source"),
    (_five_end_to_end, "more than four"),
    (_most_cells_on_four_chips, "four chips"),
    (_config_without_cell, "has no cell"),
], ids=["name-space", "name-long", "name-slash", "unit-space", "unit-long",
        "moves-unknown", "no-list", "bound-over-cap", "e2e-source",
        "five-e2e", "four-chip-share", "config-no-cell"])
def test_a_broken_manifest_is_refused(how, message):
    with pytest.raises(mf.ManifestError, match=message):
        mf.validate(_break(mf.committed(), how))
