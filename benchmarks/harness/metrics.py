"""From the ledger to the end-to-end metrics, and what the per-layer
readers share.  Every number is over all the work and all the time of
the window: a rate is every operation acknowledged in the window over
the window's seconds, a tail is the tail of every operation due in it.
"""
from __future__ import annotations

import numpy as np

#: what an operation that was never acknowledged is timed at, so that it
#: misses any latency limit: the drain's whole patience
NEVER_MS = 60_000.0


def percentile(sorted_vals: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of an ascending array."""
    n = len(sorted_vals)
    return float(sorted_vals[min(n - 1, int(np.ceil(q * n)) - 1)])


class Window:
    """The ledger's view of the measured window [t0, t1)."""

    def __init__(self, run) -> None:
        f = run.fleet
        n = f.n_ops
        self.run = run
        self.t0, self.t1 = run.t0, run.t1
        self.seconds = run.t1 - run.t0
        due, acked = f.op_due[:n], f.op_acked[:n]
        self.in_window = (due >= self.t0) & (due < self.t1)
        self.attempted = int(self.in_window.sum())
        self.failed = int((self.in_window & np.isnan(acked)).sum())
        self.acked_in_window = int(((acked >= self.t0)
                                    & (acked < self.t1)).sum())

    def _lat_ms(self, since: np.ndarray) -> np.ndarray:
        f = self.run.fleet
        n = f.n_ops
        lat = (f.op_acked[:n] - since[:n]) * 1000.0
        lat = np.where(np.isnan(lat), NEVER_MS, lat)[self.in_window]
        return np.sort(lat)

    def commit_ms(self) -> np.ndarray:
        """ACK seen minus due, ascending, of every op due in the window."""
        return self._lat_ms(self.run.fleet.op_due)

    def rtt_ms(self) -> np.ndarray:
        """ACK seen minus first fed to the transport."""
        return self._lat_ms(self.run.fleet.op_sent)

    def late_ms(self) -> np.ndarray:
        f = self.run.fleet
        n = f.n_ops
        late = (f.op_sent[:n] - f.op_due[:n]) * 1000.0
        return np.sort(np.where(np.isnan(late), NEVER_MS,
                                late)[self.in_window])

    def acked_between(self, a: float, b: float) -> int:
        f = self.run.fleet
        acked = f.op_acked[:f.n_ops]
        return int(((acked >= a) & (acked < b)).sum())

    def backlog(self, t: float) -> int:
        """Ops due by ``t`` and not acknowledged by ``t``."""
        f = self.run.fleet
        n = f.n_ops
        return int((f.op_due[:n] <= t).sum()
                   - (f.op_acked[:n] <= t).sum())

    def delta(self, group: str, key: str):
        r = self.run
        return r.counters1[group][key] - r.counters0[group][key]


def end_to_end(run, window: Window, setup_s: float) -> dict:
    """Every end-to-end metric this run's mix reports, and setup_s."""
    out = {"setup_s": setup_s}
    reports = run.mix["reports"]
    if "commit_p50_ms" in reports or "commit_p95_ms" in reports:
        lat = window.commit_ms()
        out["commit_p50_ms"] = percentile(lat, 0.50)
        out["commit_p95_ms"] = percentile(lat, 0.95)
    if "committed_ops_per_s" in reports:
        out["committed_ops_per_s"] = window.acked_in_window / window.seconds
    return {k: v for k, v in out.items() if k == "setup_s" or k in reports}
