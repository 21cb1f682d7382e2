"""The skewed cell (``ra_bench_zipf_10k_x5.paced_zipf``) rehearsed tiny
on the CPU, its reader against a ledger made by hand, and the manifest
as an append to PR 25's."""
import argparse
import types

import numpy as np
import pytest

from benchmarks import manifest as mf
from benchmarks import run as br
from benchmarks.harness import traffic

CELL = "ra_bench_zipf_10k_x5.paced_zipf"
SIBLING = "ra_bench_10k_x5"
MANIFEST = mf.committed()
XSKEW = {m["name"] for m in MANIFEST["per_layer"]
         if m["name"].startswith("xskew.")}


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """The cell's files as committed, cut to a size a test can hold: 48
    clusters, so the hot 1% is one cluster."""
    orig = br.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[0] == "configs":
            d["clusters"] = 48
        if parts[0] == "cells":
            d.update(warmup_s=0.5, rate_ops_per_s=1500)
        if parts[0] == "traffic":
            d.update(warmup_s=0.5, trace_after_s=0.2, trace_s=0.5)
        return d

    monkeypatch.setattr(br, "load_json", load)
    monkeypatch.setattr(br, "RUN_ROOT", str(tmp_path / "bench_run"))


def test_the_files_are_the_siblings_but_for_the_skew():
    cfg = br.load_json("configs", "ra_bench_zipf_10k_x5.json")
    sib = br.load_json("configs", SIBLING + ".json")
    for key in ("clusters", "members", "sessions_per_cluster",
                "command_bytes", "command_words", "machine", "dedup_slots",
                "engine", "ingress", "wire", "guarantees", "upstream"):
        assert cfg[key] == sib[key], key
    assert "ra_bench.erl:18-19,54-69" in cfg["source"]
    assert "zipfian" in cfg["source"] and "0.99" in cfg["source"]
    assert len(cfg["source"]) < 200 and len(cfg["why"]) < 200
    assert "target_rate" in cfg["reduced"]
    assert "popularity" in cfg["assumed"]
    mix = br.load_json("traffic", "paced_zipf.json")
    paced = br.load_json("traffic", "paced.json")
    differs = {k for k in set(mix) | set(paced) if mix.get(k) != paced.get(k)}
    assert differs == {"session", "zipf_s", "pipe", "note"}
    assert mix["session"] == "zipf" and mix["zipf_s"] == 0.99
    # the client's rank ring covers the program's hard credit
    from ra_tpu.ingress.backpressure import CreditLadder
    hard = CreditLadder.__init__.__kwdefaults__["hard_credit"]
    assert max(256, 2 * mix["pipe"]) >= hard > 2 * (mix["pipe"] - 1)
    cell = br.load_json("cells", CELL + ".json")
    assert cell["config"] == "ra_bench_zipf_10k_x5" and cell["chips"] == 1
    assert cell["warmup_s"] == 8 and cell["rate_ops_per_s"] % 500 == 0


def test_the_mix_draws_what_the_issue_computed():
    """Zipf 0.99 over 50,000 sessions: the hottest takes 8.33% of the
    arrivals, the top three 15.3%, the top ten 24.6%."""
    mix = br.load_json("traffic", "paced_zipf.json")
    rng = np.random.default_rng(5)
    sess = traffic._sessions(rng, mix, 240_000, 50_000)
    share = np.sort(np.bincount(sess, minlength=50_000))[::-1] / 240_000
    assert abs(share[0] - 0.0833) < 0.004
    assert abs(share[:3].sum() - 0.153) < 0.006
    assert abs(share[:10].sum() - 0.246) < 0.008


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_is_correct_at_a_tiny_size(tiny, trace):
    args = argparse.Namespace(workload=CELL, seed=2**31 + 27, seconds=1.5,
                              trace=trace, override=[])
    rc, res = br.run_cell(args, MANIFEST, require_tpu=False)
    assert rc == 0 and res["correct"] is True, res
    assert res["attempted"] > 100 and res["failed"] == 0
    if trace == 0:
        assert set(res["metrics"]) == {"setup_s", "commit_p50_ms",
                                       "commit_p95_ms"}
        return
    # every new metric reads something, on a CPU too
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert len(XSKEW) == 5 and XSKEW <= set(m)
    assert m["xskew.shed_rows.paced"] == 0
    # one cluster of 48 holds the hottest session: far over its 2%
    assert m["xskew.hot_ops_share_pct.paced"] > 8
    assert m["xskew.hot_commit_p50_ms.paced"] > 0


def _ledger(lanes_of_sess, sess, due, acked):
    fleet = types.SimpleNamespace(
        lanes=np.asarray(lanes_of_sess), op_sess=np.asarray(sess),
        op_due=np.asarray(due, float), op_acked=np.asarray(acked, float),
        n_ops=len(sess))
    window = types.SimpleNamespace(
        in_window=(fleet.op_due >= 10.0) & (fleet.op_due < 20.0))
    run = types.SimpleNamespace(fleet=fleet, config={"clusters": 200})
    return types.SimpleNamespace(run=run, window=window)


def test_hot_readers_against_a_ledger_made_by_hand():
    read = br.load_reader("hot_commit_p50_ms")
    # 200 clusters, so the hot 1% is two: cluster 7 (sessions 0 and 1;
    # five ops in the window) and cluster 3 (session 2; three); cluster
    # 9 (session 3) is sent two, and one before the window opens
    lanes = [7, 7, 3, 9]
    sess = [0, 1, 0, 0, 1, 2, 2, 2, 3, 3, 3]
    due = [10, 11, 12, 13, 14, 10, 15, 19, 5, 16, 17]
    lat = [.1, .2, .3, .4, .5, 1., 2., np.nan, 9., .01, .02]
    ctx = _ledger(lanes, sess, due, np.add(due, lat))
    meta = {"hot_share": 0.01, "reads": "ops_share_pct"}
    assert read(ctx, meta) == pytest.approx(100.0 * 8 / 10)
    meta["reads"] = "commit_p50_ms"
    # the eight hot ops: 100..500 ms, 1,000, 2,000, and one never
    # acknowledged (60,000): nearest-rank median the fourth
    assert read(ctx, meta) == pytest.approx(400.0)
    # an empty window reads nothing
    ctx.window.in_window[:] = False
    assert read(ctx, meta) is None


def test_the_manifest_is_pr_25s_with_this_prs_entries_appended():
    """What ``test_program_spans.py`` pins of PR 25's manifest, in a
    form an append leaves true: PR 23's 12 metrics, PR 25's 16, then
    this PR's five; this PR's configuration and cell after the
    four-chip ones; the new cell on every per-layer list, last."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[11] == "wire.sweep_busy_pct.paced"
    assert (names[12], names[27]) == ("wire.sweep_decode_p50_ms.paced",
                                      "xla.stage_named_pct.paced")
    assert names[:28] == sorted(names[:28])
    assert names[28:] == sorted(XSKEW) and len(names) == 33
    assert [w["name"] for w in MANIFEST["workloads"]][-2:] == \
        ["ra_bench_20k_x5_mesh4.paced", CELL]
    assert [c["name"] for c in MANIFEST["configs"]][-2:] == \
        ["ra_bench_20k_x5_mesh4", "ra_bench_zipf_10k_x5"]
    for m in MANIFEST["per_layer"]:
        assert m["workloads"][-2:] == ["ra_bench_20k_x5_mesh4.paced", CELL]
        assert m["moves"] == "commit_p95_ms"
