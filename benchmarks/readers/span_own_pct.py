"""A program span's own time in the traced window as a share of the
span: its time less what the program spans nested inside it cover, over
every occurrence.  For a span that is not a root of its thread:
``span_self_pct`` subtracts every other program span on the thread, a
span's parents among them, and reads 0 for any nested span.  The
metric's file names the span."""
from benchmarks.harness import program_spans, trace_reduce


def read(ctx, metric):
    loaded = program_spans.of_run(ctx.run)
    if not loaded:
        return None
    events = program_spans.line_of(loaded, metric["span"])
    if not events:
        return None
    span = metric["span"]
    others = [(s, s + d) for n, s, d, _a in events
              if n != span and n.startswith(program_spans.PROGRAM_PREFIX)]
    total = covered = 0
    for lo, hi in ((s, s + d) for n, s, d, _a in events if n == span):
        total += hi - lo
        covered += sum(e - s for s, e in trace_reduce._union(
            (s, e) for s, e in others if lo <= s and e <= hi))
    if total <= 0:
        return None
    return 100.0 * (total - covered) / total
