"""The hot clusters' side of the ledger: of the operations due in the
window, those sent to the ``hot_share`` (1%) of clusters that were sent
the most.  The metric's file says what is read of them (``reads``):
``commit_p50_ms``, the median of ACK seen minus due (an operation never
acknowledged timed at the drain's patience, as end to end), or
``ops_share_pct``, their share of the window's operations: the check
that the traffic is as skewed as the cell says."""
import numpy as np

from benchmarks.harness.metrics import NEVER_MS, percentile


def hot_ops(fleet, in_window, clusters: int, hot_share: float):
    """(ops due in the window, mask of those on a hot cluster)."""
    ops = np.flatnonzero(in_window)
    lanes = fleet.lanes[fleet.op_sess[ops]]
    sent = np.bincount(lanes, minlength=clusters)
    k = max(1, int(np.ceil(hot_share * clusters)))
    hot = np.zeros(len(sent), bool)
    hot[np.argsort(sent, kind="stable")[-k:]] = True
    return ops, hot[lanes]


def read(ctx, metric):
    fleet = ctx.run.fleet
    ops, on_hot = hot_ops(fleet, ctx.window.in_window,
                          int(ctx.run.config["clusters"]),
                          float(metric["hot_share"]))
    if not on_hot.any():
        return None
    if metric["reads"] == "ops_share_pct":
        return 100.0 * on_hot.sum() / len(ops)
    ops = ops[on_hot]
    lat = (fleet.op_acked[ops] - fleet.op_due[ops]) * 1000.0
    return percentile(np.sort(np.where(np.isnan(lat), NEVER_MS, lat)), 0.50)
