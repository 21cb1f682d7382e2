"""Device time of the step program over the rounds it ran, both from
the profiler's trace of the traced part of the window."""


def read(ctx, metric):
    tr = ctx.trace
    if not tr or not tr.get("step_dispatches") or not tr.get("step_s"):
        return None
    rounds = tr["step_dispatches"] * int(
        ctx.run.config["ingress"]["superstep_k"])
    return 1000.0 * tr["step_s"] / rounds
