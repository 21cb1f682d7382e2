"""A program span's self time in the traced window as a share of the
span: its time less what its child spans on the same thread cover.  The
metric's file names the span."""
from benchmarks.harness import program_spans


def read(ctx, metric):
    loaded = program_spans.of_run(ctx.run)
    if not loaded:
        return None
    return program_spans.self_pct(loaded, metric["span"])
