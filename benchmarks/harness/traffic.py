"""One general traffic generator, driven by a mix's data file.

A mix file (``benchmarks/traffic/<name>.json``) holds parameters only:

    loop      "open"  arrivals on a schedule drawn from the seed, sent
                      when due whether or not earlier ones returned
              "closed" every session keeps ``pipe`` operations
                      unacknowledged and sends one for each ACK
    arrivals  "poisson" | "burst" (open loop): exponential gaps at the
              cell's ``rate_ops_per_s``; "burst" switches the rate on
              for ``burst_on_s`` and off for ``burst_off_s`` at the same
              mean rate
    session   "uniform" | "zipf" (open loop): how an arrival picks its
              session; "zipf" takes ``zipf_s``
    delta     [lo, hi] inclusive
    pipe      (closed loop) operations a session keeps in flight
    ramp_s    (closed loop) sessions start at times drawn uniformly from
              the seed over this long, inside the warm-up
    think_s   (closed loop) [lo, hi]: a session sends its next op this
              long after the ACK, drawn uniformly from the seed.  With
              none, every session answers in the cycle its ACK came in,
              the whole fleet circles as one block, and a window counts
              whole blocks of 50,000
    reports   the end-to-end metrics cells of this mix report

A cell's file adds what belongs to the pairing: ``rate_ops_per_s``.

What an op carries is a pure function of (seed, session, the session's
ordinal for that op), so the same seed gives the same inputs whatever
the timing of ACKs.
"""
from __future__ import annotations

import numpy as np

from .fleet import batch_rank


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 arrays (wrapping)."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class OpContent:
    """delta, pool row and salt of an op from (seed, session, ordinal)."""

    def __init__(self, seed: int, n_sessions: int, pool_rows: int,
                 delta) -> None:
        self.seed = np.uint64(int(seed) & 0xFFFFFFFFFFFF)
        self.ordinal = np.zeros(n_sessions, np.int64)
        self.pool_rows = int(pool_rows)
        self.lo, self.hi = int(delta[0]), int(delta[1])

    def draw(self, sess: np.ndarray):
        sess = np.asarray(sess, np.int64)
        ordinal = self.ordinal[sess] + batch_rank(sess)
        np.add.at(self.ordinal, sess, 1)
        with np.errstate(over="ignore"):
            h = _mix64(self.seed * np.uint64(0x9E3779B97F4A7C15)
                       + sess.astype(np.uint64) * np.uint64(0xD1B54A32D192ED03)
                       + ordinal.astype(np.uint64))
        span = np.uint64(self.hi - self.lo + 1)
        delta = (h % span).astype(np.int32) + np.int32(self.lo)
        rows = ((h >> np.uint64(8)) % np.uint64(self.pool_rows)) \
            .astype(np.int32)
        salts = ((h >> np.uint64(24)) & np.uint64(0x7FFFFFFF)) \
            .astype(np.int32)
        return delta, rows, salts


def _arrival_times(rng, mix: dict, rate: float, horizon_s: float):
    n = int(rate * horizon_s * 1.1) + 64
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        t = np.cumsum(rng.exponential(1.0 / rate, n))
    elif kind == "burst":
        on, off = float(mix["burst_on_s"]), float(mix["burst_off_s"])
        t_on = np.cumsum(rng.exponential(on / (rate * (on + off)), n))
        t = t_on + np.floor(t_on / on) * off
    else:
        raise ValueError(f"traffic: unknown arrivals {kind!r}")
    return t[t < horizon_s]


def _sessions(rng, mix: dict, n: int, n_sessions: int) -> np.ndarray:
    kind = mix.get("session", "uniform")
    if kind == "uniform":
        return rng.integers(0, n_sessions, n)
    if kind == "zipf":
        w = 1.0 / np.arange(1, n_sessions + 1) ** float(mix["zipf_s"])
        order = rng.permutation(n_sessions)
        return order[rng.choice(n_sessions, n, p=w / w.sum())]
    raise ValueError(f"traffic: unknown session choice {kind!r}")


class OpenLoop:
    """The whole schedule is drawn before the first op is due."""

    def __init__(self, mix: dict, cell: dict, seed: int, n_sessions: int,
                 pool_rows: int, horizon_s: float) -> None:
        rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self.rate = float(cell["rate_ops_per_s"])
        self.due = _arrival_times(rng, mix, self.rate, horizon_s)
        self.sess = _sessions(rng, mix, len(self.due), n_sessions)
        self.content = OpContent(seed, n_sessions, pool_rows, mix["delta"])
        self._next = 0

    def start(self, fleet, t0: float) -> None:
        self.t0 = t0

    def step(self, fleet, now: float, newly_acked, minting: bool) -> None:
        """Mint every op whose due time has come."""
        if not minting:
            return
        hi = int(np.searchsorted(self.due, now - self.t0, side="right"))
        if hi > self._next:
            sl = slice(self._next, hi)
            delta, rows, salts = self.content.draw(self.sess[sl])
            fleet.new_ops(self.sess[sl], delta, rows, salts,
                          self.t0 + self.due[sl])
            self._next = hi


class ClosedLoop:
    """``pipe`` ops a session, one more for each ACK."""

    def __init__(self, mix: dict, cell: dict, seed: int, n_sessions: int,
                 pool_rows: int, horizon_s: float) -> None:
        self.pipe = int(mix["pipe"])
        self.content = OpContent(seed, n_sessions, pool_rows, mix["delta"])
        rng = np.random.default_rng([int(seed), 0xC105ED])
        starts = rng.uniform(0.0, float(mix["ramp_s"]), n_sessions)
        order = np.argsort(starts, kind="stable")
        # sessions waiting to send, by the time they will: each entry
        # is one op of one session
        self._sess = np.repeat(order, self.pipe)
        self._when = np.repeat(starts[order], self.pipe)
        self.think = [float(x) for x in mix.get("think_s", (0.0, 0.0))]

    def start(self, fleet, t0: float) -> None:
        self.t0 = t0

    def _mint(self, fleet, sess, due) -> None:
        delta, rows, salts = self.content.draw(sess)
        fleet.new_ops(sess, delta, rows, salts, due)

    def step(self, fleet, now: float, newly_acked, minting: bool) -> None:
        if not minting:
            return
        if len(newly_acked):
            # the think time of an op is drawn with the op before it
            sess = fleet.op_sess[newly_acked]
            lo, hi = self.think
            wait = lo + (hi - lo) * self._unit_of(fleet, newly_acked)
            self._sess = np.concatenate([self._sess, sess])
            self._when = np.concatenate([self._when, now - self.t0 + wait])
        ready = self._when <= now - self.t0
        if ready.any():
            self._mint(fleet, self._sess[ready], self.t0 + self._when[ready])
            self._sess, self._when = self._sess[~ready], self._when[~ready]

    def _unit_of(self, fleet, ops) -> np.ndarray:
        """A number in [0, 1) from the seed, the op's session and id."""
        with np.errstate(over="ignore"):
            h = _mix64(self.content.seed
                       + fleet.op_sess[ops].astype(np.uint64)
                       * np.uint64(0x9E3779B97F4A7C15)
                       + fleet.op_id[ops].astype(np.uint64))
        return (h >> np.uint64(40)).astype(np.float64) / float(1 << 24)


def make(mix: dict, cell: dict, seed: int, n_sessions: int, pool_rows: int,
         horizon_s: float):
    loop = mix["loop"]
    if loop == "open":
        return OpenLoop(mix, cell, seed, n_sessions, pool_rows, horizon_s)
    if loop == "closed":
        return ClosedLoop(mix, cell, seed, n_sessions, pool_rows, horizon_s)
    raise ValueError(f"traffic: unknown loop {loop!r}")
