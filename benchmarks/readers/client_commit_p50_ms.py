"""Median of ACK seen minus first fed to the transport, under the
closed loop; recorded, not judged."""
from benchmarks.harness.metrics import percentile


def read(ctx, metric):
    lat = ctx.window.rtt_ms()
    return percentile(lat, 0.50) if len(lat) else None
