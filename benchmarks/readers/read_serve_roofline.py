"""The read stage's share of its roofline over the traced part of the
window: the reads answered in it times the least bytes one read has to
move (its query in, the record read, the reply written:
``4 x (query_width + 2 x query_reply_width)``, widths from the engine,
so the same work is counted whatever implements it) at the chip's peak
bytes/s, over the device time of the stage the metric's file names.

0 where no read was answered (a cell whose mix sends none).  No
reading where the trace holds no device's step at all (a run on the
CPU), nor where reads were answered but the stage has no device time
of its own (the compiler fused it into another stage's operations): a
share of nothing is not 0."""
from benchmarks.harness import peaks, program_spans


def read(ctx, metric):
    run = ctx.run
    stages = program_spans.stages_of_run(run)
    if not stages:                  # no device in the trace, or no trace
        return None
    fleet = run.fleet
    if not fleet.has_reads:
        return 0.0
    n = fleet.n_ops
    t0, t1 = run.trace_window
    acked = fleet.op_acked[:n]
    served = int((fleet.read_kind[fleet.op_kind[:n]]
                  & (acked >= t0) & (acked < t1)).sum())
    if not served:
        return 0.0
    stage_s = stages["stages"].get(metric["stage"], 0.0)
    if stage_s <= 0:
        return None
    eng = run.eng
    least = served * 4.0 * (eng.query_width + 2 * eng.query_reply_width)
    peak = peaks.peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / stage_s
