"""Device time of one stage of the fused step over the rounds it ran in
the traced window, the mean over the chips used; the metric's file
names the stage.  A stage whose operations the compiler fused into
another stage's reads 0."""
from benchmarks.harness import program_spans


def read(ctx, metric):
    stages = program_spans.stages_of_run(ctx.run)
    if not stages or not stages["dispatches"]:
        return None
    rounds = stages["dispatches"] * int(
        ctx.run.config["ingress"]["superstep_k"])
    return 1000.0 * stages["stages"].get(metric["stage"], 0.0) / rounds
