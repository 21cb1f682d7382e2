"""The reduction from a trace to busy share, step time, top operations
and gaps, checked against a trace recorded on the chip in PR 23
(one v5e, ra_bench_10k_x5.paced at 14,000 ops/s, 2.7 s traced)."""
import os

import pytest

from benchmarks.harness import peaks, roofline, trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures",
    "trace_v5e_10k_x5_paced.json.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load_fixture(FIXTURE))


def test_busy_window_and_step_time_of_the_recorded_trace(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(2.662449793, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.448238114, rel=1e-9)
    # two fused dispatches of four rounds ran in the traced window
    assert reduced["step_dispatches"] == 2
    assert reduced["step_s"] == pytest.approx(0.445806116, rel=1e-9)
    assert reduced["step_module"].startswith("jit__unknown")
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_busy_is_a_union_not_a_sum(reduced):
    planes = trace_reduce.load_fixture(FIXTURE)
    ops = planes["/device:TPU:0"][trace_reduce.OPS_LINE]
    # the while op and the fusions inside it overlap on the op line
    assert sum(d for _, _, d in ops) / 1e9 > 1.5 * reduced["busy_s"]


def test_top_operations_and_gaps_by_span(reduced):
    assert len(reduced["device_ops"]) == 10
    assert reduced["device_ops"][0][0].startswith("%while")
    gaps = dict(reduced["idle_gaps"])
    # the host was inside the listener's sweep for most of the idle time
    assert max(gaps, key=gaps.get) == "wire.sweep"
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_no_device_plane_reduces_to_nothing():
    assert trace_reduce.reduce({"/host:CPU": {"python3": [("x", 0, 5)]}}) == {}


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks.peaks_for("TPU v5 lite")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_roofline_counts_the_work_not_the_implementation():
    b = roofline.step_min_bytes(ops=1000, rounds=4, lanes=10, members=5)
    assert b == 1000 * 3 * 256 + 4 * 2 * 4 * (17 * 10 + 9 * 50)
    pct = roofline.step_roofline_pct(
        ops=1000, rounds=4, lanes=10, members=5, step_device_s=1e-6,
        peak_bytes_per_s=819e9)
    assert pct == pytest.approx(100 * b / 819e9 / 1e-6)
