"""The benchmark's YCSB cell (``ycsb_kv_2k_x3.paced_ycsb_b``, PR 32)
rehearsed on the CPU at a few stores and records, as
``benchmarks/tests/test_rehearsal.py`` rehearses every cell: the open
loop at 95 / 5 through the listener, the read plane and the WAL comes
out ``correct`` against the kit's plain reference, and the two faults
the fleet can plant each fail a count of their own.

Shapes are the source's (10 fields of 100 bytes, whole records in a
reply); only the scale is a test's.
"""
import argparse

import numpy as np
import pytest

from benchmarks import manifest as mf
from benchmarks import run as br

CELL = "ycsb_kv_2k_x3.paced_ycsb_b"
MANIFEST = mf.committed()


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    orig = br.load_json

    def load(*parts):
        d = orig(*parts)
        if parts[0] == "configs":
            d.update(clusters=12, records=40)
        if parts[0] == "cells":
            d.update(warmup_s=0.5, rate_ops_per_s=900)
        if parts[0] == "traffic":
            d.update(warmup_s=0.5, trace_after_s=0.2, trace_s=0.5)
        return d

    monkeypatch.setattr(br, "load_json", load)
    monkeypatch.setattr(br, "RUN_ROOT", str(tmp_path / "bench_run"))


def _run(seed, **faults):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.5,
                              trace=0, override=[])
    rc, res = br.run_cell(args, MANIFEST, require_tpu=False, **faults)
    assert rc == 0
    return res


def _failed(res) -> set:
    return {k for k, v in res["compared"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("seed", [2**31 + 32, 5])
def test_the_cell_runs_and_is_correct_at_a_tiny_size(tiny, seed):
    res = _run(seed)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 500 and res["failed"] == 0
    assert set(res["metrics"]) == {"commit_p50_ms", "commit_p95_ms",
                                   "setup_s"}
    assert all(v["limit"] == 0 for v in res["compared"].values())
    for tag in ("live", "reopen"):
        for count in ("ver_wrong", "sum_wrong", "fields_unknown",
                      "fields_stale", "replica_cells_wrong",
                      "replicas_behind"):
            assert res["compared"][f"{tag}_{count}"]["value"] == 0
    for count in ("reads_outside_consistency", "reads_not_present",
                  "reads_negative_watermark", "ops_never_acked",
                  "acks_above_fsync", "commit_above_fsync"):
        assert res["compared"][count]["value"] == 0


def test_a_tampered_reply_fails_the_read_counts_alone(tiny):
    hit = []

    def tamper_reply(rec):
        ok = np.flatnonzero(rec["status"] <= 1)
        if len(ok) and len(hit) < 3:
            rec["pay"][ok[0], 7] += 1       # one word of one field
            hit.append(1)

    res = _run(11, tamper_reply=tamper_reply)
    assert hit and res["correct"] is False
    assert _failed(res) == {"reads_outside_consistency"}
    assert res["compared"]["reads_outside_consistency"]["value"] == len(hit)


def test_a_tampered_update_fails_the_state_counts(tiny):
    hit = []

    def tamper(idx, pay):
        if len(idx) and not hit:
            pay[0, 5] ^= 1                  # a value word, not the op id
            hit.append(int(idx[0]))

    res = _run(12, tamper=tamper)
    assert hit and res["correct"] is False
    failed = _failed(res)
    assert {"live_fields_unknown", "reopen_fields_unknown"} <= failed
    assert not {"live_ver_wrong", "live_sum_wrong",
                "live_replica_cells_wrong"} & failed
    # a read of that record may have seen the altered field too; no
    # other count moves
    assert failed <= {"live_fields_unknown", "reopen_fields_unknown",
                      "reads_outside_consistency"}


def test_an_update_with_another_op_id_fails_the_sums(tiny):
    hit = []

    def tamper(idx, pay):
        if len(idx) and not hit:
            pay[0, 3] += 1                  # the op id the record sums
            hit.append(1)

    failed = _failed(_run(13, tamper=tamper))
    assert {"live_sum_wrong", "reopen_sum_wrong"} <= failed
    assert "live_ver_wrong" not in failed
