"""99th percentile of the samples the end-to-end tail is taken from
(ACK seen minus due); recorded, not judged."""
from benchmarks.harness.metrics import percentile


def read(ctx, metric):
    lat = ctx.window.commit_ms()
    return percentile(lat, 0.99) if len(lat) else None
