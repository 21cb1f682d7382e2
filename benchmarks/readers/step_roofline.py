"""The step's share of its roofline over the traced part of the window:
the least time the traced work needs at the chip's peak bytes/s
(``harness/roofline.py``) over the step program's device time."""
from benchmarks.harness import peaks, roofline


def read(ctx, metric):
    tr = ctx.trace
    if not tr or not tr.get("step_dispatches") or not tr.get("step_s"):
        return None
    cfg = ctx.run.config
    t0, t1 = ctx.run.trace_window
    ops = ctx.window.acked_between(t0, t1)
    if ops <= 0:
        return None
    return roofline.step_roofline_pct(
        ops=ops,
        rounds=tr["step_dispatches"] * int(cfg["ingress"]["superstep_k"]),
        lanes=int(cfg["clusters"]), members=int(cfg["members"]),
        step_device_s=tr["step_s"], chips=tr["devices"],
        peak_bytes_per_s=peaks.peaks_for(ctx.device_kind)["hbm_bytes_per_s"])
