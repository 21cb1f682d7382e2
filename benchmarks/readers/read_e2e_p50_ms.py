"""Median of the program's ``read_e2e`` phase (read block submitted to
its serve outcome observed on the host) over the window.  0 where the
cell's mix sends no read: no read waited.  No reading where reads were
sent and the program stamped none."""


def read(ctx, metric):
    ph = ctx.run.phases.get(metric["phase"])
    if ph and ph["p50_ms"] >= 0:
        return ph["p50_ms"]
    return None if ctx.run.fleet.has_reads else 0.0
