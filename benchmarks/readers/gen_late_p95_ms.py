"""How late the generator ran: 95th percentile of (first fed to the
transport) minus (due) over the ops due in the window."""
from benchmarks.harness.metrics import percentile


def read(ctx, metric):
    late = ctx.window.late_ms()
    return percentile(late, 0.95) if len(late) else None
