"""The apply stage's share of its roofline over the traced part of the
window: the writes acknowledged in it times the least bytes one write
has to move at the chip's peak bytes/s, over the device time of the
stage the metric's file names.

A write's least bytes, so that the same work counts whatever implements
it: its command read once a lane from the ring (``4 x`` the engine's
command width), and what it writes into each member's state (the
configuration's ``fold_write_bytes`` a member; a configuration without
the key writes nothing the count can name, 0).  The bytes of a fleet
sharded over several chips are divided over them, as the stage's time
is the mean over their planes.

0 where no write was acknowledged in the traced window.  No reading
where the trace holds no device's step at all (a run on the CPU), nor
where writes were acknowledged but the stage has no device time of its
own: a share of nothing is not 0."""
from benchmarks.harness import peaks, program_spans


def least_bytes(writes: int, command_width: int, members: int,
                fold_write_bytes: float) -> float:
    return writes * (4.0 * command_width + members * fold_write_bytes)


def read(ctx, metric):
    run = ctx.run
    stages = program_spans.stages_of_run(run)
    if not stages:                  # no device in the trace, or no trace
        return None
    fleet = run.fleet
    n = fleet.n_ops
    t0, t1 = run.trace_window
    acked = fleet.op_acked[:n]
    writes = int((~fleet.read_kind[fleet.op_kind[:n]]
                  & (acked >= t0) & (acked < t1)).sum())
    if not writes:
        return 0.0
    stage_s = stages["stages"].get(metric["stage"], 0.0)
    if stage_s <= 0:
        return None
    cfg = run.config
    least = least_bytes(writes, run.eng.payload_width, int(cfg["members"]),
                        float(cfg.get("fold_write_bytes", 0)))
    chips = int((ctx.trace or {}).get("devices", 1)) or 1
    peak = peaks.peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / chips / peak) / stage_s
