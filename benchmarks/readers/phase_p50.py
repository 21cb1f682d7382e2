"""Median of one of the program's own phase stamps (``PhaseStats``,
host clock) over the window; the metric's file names the phase."""


def read(ctx, metric):
    ph = ctx.run.phases.get(metric["phase"])
    if not ph or ph["p50_ms"] < 0:
        return None
    return ph["p50_ms"]
