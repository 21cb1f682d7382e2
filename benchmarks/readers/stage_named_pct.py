"""Share of the fused step's device time whose operation carries one of
the program's stage scopes in its metadata, the mean over the chips
used.  A fusion counts under the scope of its root; one whose root the
compiler left without a scope, under the scope the instructions fused
into it name (from the step's HLO, which the trace holds)."""
from benchmarks.harness import program_spans


def read(ctx, metric):
    stages = program_spans.stages_of_run(ctx.run)
    if not stages or stages["total_s"] <= 0:
        return None
    return 100.0 * sum(stages["stages"].values()) / stages["total_s"]
