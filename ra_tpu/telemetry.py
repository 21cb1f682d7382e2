"""Device-resident per-lane telemetry plane + the unified Observatory.

The reference answers "how is my cluster doing" with ra_counters
(seshat atomics, sampled off the event loop) and ra:key_metrics; this
module is the lane-engine equivalent at 100k-lane scale (ISSUE 6):

* :class:`TelemetrySampler` — drains the engine's ``LaneTelemetry``
  accumulators (the ``[lanes]`` int32 pytree that rides inside
  ``LaneState`` through every jitted step) on a step cadence.  The
  aggregation to a fixed-size snapshot (scalar rollups, log2 commit-lag
  histogram, ``lax.top_k`` offenders) happens ON DEVICE
  (``lockstep._telemetry_summary``); the sampler only starts an ASYNC
  host copy of the few-hundred-byte result and harvests it on a later
  tick once ready.  The dispatch loop never blocks: the same readback
  discipline as the dispatch-ahead driver (lint rule RA04 gates this
  file's tick path, see tools/lint.py).
* :class:`Observatory` — the host-side unification: one merged snapshot
  of engine telemetry + dispatch-pipeline counters + WAL/disk-fault
  stats + :class:`~ra_tpu.metrics.Counters` groups, with (a) Prometheus
  text exposition, (b) a bounded time-series ring yielding per-window
  rates and percentiles (the substrate a future SLO autotuner reads),
  and (c) JSONL-ring export for ``tools/ra_top.py``.

Nothing here is on the step's critical path: a sampler at the default
cadence adds one tiny extra XLA dispatch per ``cadence_steps`` engine
rounds and zero blocking syncs (pinned by tests/test_telemetry.py).
"""
from __future__ import annotations

import collections
import json
import logging
import os
import re
import threading
import time
from array import array
from typing import Any, Callable, Optional

import numpy as np

from . import devicewatch

logger = logging.getLogger("ra_tpu.telemetry")

#: default sampling cadence in ENGINE ROUNDS (inner steps, not
#: dispatches): one on-device aggregation + async readback per window.
#: At 64 rounds the sampler's extra dispatch is amortized to <0.2% of
#: dispatch count even on the single-step path.
DEFAULT_CADENCE_STEPS = 64

#: a lane is flagged STALLED once it has sat this many consecutive
#: rounds with a commit backlog and zero commit progress
DEFAULT_STALL_THRESHOLD = 8

#: minimum seconds between device-memory censuses on the harvest tick
#: (ISSUE 16): jax.live_arrays() is O(live buffers), so in a
#: buffer-heavy process an every-harvest walk would tax the loop the
#: watermarks exist to observe — 4 Hz bounds the walk while staying
#: far inside any human-scale observation window.  A sampler's FIRST
#: harvest censuses eagerly so short runs and tests always get one.
CENSUS_MIN_INTERVAL_S = 0.25

#: log2 millisecond buckets for phase histograms: bucket 0 = <1ms,
#: bucket b = < 2^b ms, last bucket absorbs the tail (~9 hours)
PHASE_HIST_BUCKETS = 16


class PhaseStats:
    """Phase-resolved latency attribution (ISSUE 9): where did a
    window's latency budget go — host staging, device dispatch, WAL
    encode, queue wait, fsync wait, confirm publish?

    One accumulator per engine (the durable bridge and the attached
    engine share one; each WAL shard feeds the same object).  A sample
    is a pair of ``time.monotonic()`` stamps taken at HOST-side edges
    of the dispatch/durability path — never a device sync, so the
    attribution is always-on at zero pipeline cost (lint rule RA04
    gates the stamp path like the sampler tick).

    Per phase (``metrics.PHASE_FIELDS``): a latency reservoir
    (p50/p99/max) that holds every sample since the last
    :meth:`reset_reservoirs`, up to ``reservoir`` a phase and the
    newest after that, so a median is the window's (ISSUE 37); a
    log2-ms histogram, a sample count, and a MONOTONE cumulative
    ``total_ms``.  Differentiating ``total_ms`` over the
    Observatory time-series ring yields each phase's per-window share
    of the budget — the SLO engine's and autotuner's triggering-phase
    input."""

    def __init__(self, *, reservoir: int = 65536) -> None:
        from .metrics import PHASE_FIELDS
        self._fields = PHASE_FIELDS
        self._lock = threading.Lock()
        self._cap = int(reservoir)
        self._res = {p: array("d") for p in PHASE_FIELDS}
        #: where the next sample of a full reservoir goes (its oldest)
        self._head = {p: 0 for p in PHASE_FIELDS}
        self._hist = {p: [0] * PHASE_HIST_BUCKETS for p in PHASE_FIELDS}
        self._count = {p: 0 for p in PHASE_FIELDS}
        self._total_ms = {p: 0.0 for p in PHASE_FIELDS}
        #: samples addressed to an unknown phase (registry mismatch —
        #: the telemetry_dropped discipline applied to phases)
        self.dropped = 0

    def note(self, phase: str, dt_s: float) -> None:
        """Record one phase sample of ``dt_s`` seconds.  Called from
        the dispatch thread, WAL batch threads and encode workers —
        one lock + an array append + int/float adds, nothing that can
        block on the device (rule RA04)."""
        if phase not in self._count:
            self.dropped += 1
            return
        ms = dt_s * 1000.0
        b = min(PHASE_HIST_BUCKETS - 1,
                max(0, int(ms).bit_length()))
        with self._lock:
            res = self._res[phase]
            if len(res) < self._cap:
                res.append(ms)
            else:
                h = self._head[phase]
                res[h] = ms
                self._head[phase] = (h + 1) % self._cap
            self._hist[phase][b] += 1
            self._count[phase] += 1
            self._total_ms[phase] += ms

    def overview(self) -> dict:
        """Per-phase ``{count, total_ms, samples, p50_ms, p99_ms,
        max_ms, hist}`` — what the Observatory engine source embeds
        (the scalars flatten into the exposition/ring; the hist
        renders as a labelled Prometheus bucket family).
        ``samples`` is how many samples the percentiles were taken
        over."""
        out: dict = {}
        with self._lock:
            for p in self._fields:
                lats = np.array(self._res[p])
                n = len(lats)
                ph = {"count": self._count[p],
                      "total_ms": round(self._total_ms[p], 3),
                      "samples": n,
                      "p50_ms": -1.0, "p99_ms": -1.0, "max_ms": -1.0,
                      "hist": list(self._hist[p])}
                if n:
                    ks = (n // 2, min(n - 1, int(n * 0.99)), n - 1)
                    lats.partition(ks)
                    ph["p50_ms"], ph["p99_ms"], ph["max_ms"] = (
                        round(float(lats[k]), 3) for k in ks)
                out[p] = ph
        out["dropped"] = self.dropped
        return out

    def reset_reservoirs(self) -> None:
        """Clear the percentile reservoirs (p50/p99/max) while keeping
        the MONOTONE fields (count, total_ms, hist) monotone — a
        measurement-phase boundary for bench harnesses (ISSUE 20):
        warmup/compile samples must not sit in a measured phase's p99
        tail, but the Observatory ring's rate differentiation over
        ``total_ms``/``count`` must never see a counter reset.  A
        barrier-side call, never the hot path."""
        with self._lock:
            for p in self._fields:
                self._res[p] = array("d")
                self._head[p] = 0

    def encode_share_pct(self) -> float:
        """Codec encode time as a percentage of ALL phase time this
        accumulator has seen (ISSUE 18), lower-better: encode-once
        should drive it toward zero as shipped images replace
        per-entry object encode.  -1.0 until any phase sample lands
        (sentinel)."""
        with self._lock:
            tot = sum(self._total_ms.values())
            enc = self._total_ms.get("encode", 0.0)
        return round(100.0 * enc / tot, 2) if tot > 0 else -1.0


def _host_scalar(x) -> Any:
    """Device/np scalar -> python int/float; small vectors -> lists.
    Callers pass only READY arrays (the harvest path is is_ready-gated,
    drain is an explicit barrier), so the conversions cannot block."""
    arr = np.asarray(x)  # ra04-ok: callers gate on is_ready (or drain)
    if arr.ndim == 0:
        v = arr.item()  # ra04-ok: host np scalar, already off device
        return round(v, 4) if isinstance(v, float) else v
    return arr.tolist()


class TelemetrySampler:
    """Async drain of a :class:`LockstepEngine`'s telemetry plane.

    Attach one per engine (construction attaches, like
    ``DispatchAheadDriver``); the engine calls :meth:`tick` after every
    dispatch.  Every ``cadence_steps`` engine rounds the sampler
    dispatches the jitted on-device summary over the CURRENT state and
    starts an async device->host copy; ready copies are harvested on
    later ticks (never blocking — an unready sample simply waits, and
    if more than ``max_pending`` samples are in flight the oldest is
    dropped, counted in ``samples_dropped``).  ``last`` always holds
    the newest harvested snapshot as plain host data."""

    def __init__(self, engine, *, cadence_steps: int = DEFAULT_CADENCE_STEPS,
                 top_k: int = 8, hist_buckets: int = 16,
                 stall_threshold: int = DEFAULT_STALL_THRESHOLD,
                 max_pending: int = 4) -> None:
        from .engine.lockstep import telemetry_summary_fn
        self.engine = engine
        self.cadence_steps = max(1, int(cadence_steps))
        self.top_k = min(int(top_k), engine.n_lanes)
        self.hist_buckets = int(hist_buckets)
        self.stall_threshold = int(stall_threshold)
        self.max_pending = max(1, int(max_pending))
        self._fn = telemetry_summary_fn(self.top_k, self.hist_buckets,
                                        self.stall_threshold)
        self._pending: collections.deque = collections.deque()
        self._steps_since = 0
        #: first harvest censuses device memory eagerly (ISSUE 16)
        self._censused = False
        #: newest harvested snapshot (plain dict), or None
        self.last: Optional[dict] = None
        #: sampler health (host ints): ``samples_started`` device
        #: aggregations dispatched, ``samples_harvested`` snapshots
        #: landed, ``samples_dropped`` in-flight overflow evictions,
        #: ``blocking_waits`` forced waits — stays 0 on the tick path
        #: (only :meth:`drain` blocks; the RA04 gauge twin)
        self.counters = {"samples_started": 0, "samples_harvested": 0,
                         "samples_dropped": 0, "blocking_waits": 0,
                         "observer_errors": 0}
        self._observers: list = []
        engine._telemetry = self

    # -- dispatch-loop path (called by the engine; must never block) ------

    def tick(self, k: int = 1) -> None:
        """Advance the cadence by ``k`` engine rounds (the engine calls
        this after each dispatch: k=1 single step, k=K superstep) and
        harvest any READY samples.  No host sync happens here."""
        self._steps_since += k
        if self._steps_since >= self.cadence_steps:
            # keep the overshoot: a superstep whose K does not divide
            # the cadence would otherwise stretch the effective window
            # (48-round ticks at cadence 64 -> samples every 96), and
            # the stall-detection "within one window" bound with it
            self._steps_since %= self.cadence_steps
            self._start_sample()
        self._harvest(block=False)

    def _start_sample(self) -> None:
        st = self.engine.state
        out = self._fn(st.telem, st.total_committed,
                       (st.read_served, st.read_shed, st.read_stale,
                        st.read_leased))
        for v in out.values():
            try:
                v.copy_to_host_async()
            except AttributeError:  # pragma: no cover — older jax arrays
                pass
        # transfer ledger (ISSUE 16): the telemetry harvest IS the
        # steady-state loop's other d2h budget line — one async copy
        # per summary value, counted at copy start (.nbytes = host
        # metadata, no sync; rule RA04 gates this path)
        devicewatch.record_d2h(
            "sampler_harvest",
            sum(getattr(v, "nbytes", 0) for v in out.values()),
            events=len(out))
        self.counters["samples_started"] += 1
        self._pending.append((time.time(),
                              self.engine.pipeline_counters["inner_steps"],
                              out))
        while len(self._pending) > self.max_pending:
            # never block on a slow readback: evict the oldest sample
            # instead (the snapshot is a health gauge, not a ledger)
            self._pending.popleft()
            self.counters["samples_dropped"] += 1

    def _is_ready(self, out: dict) -> bool:
        for v in out.values():
            try:
                if not v.is_ready():
                    return False
            except AttributeError:  # pragma: no cover — older jax arrays
                pass
        return True

    def _harvest(self, block: bool) -> None:
        while self._pending:
            ts, steps, out = self._pending[0]
            if not self._is_ready(out):
                if not block:
                    return
                self.counters["blocking_waits"] += 1
            self._pending.popleft()
            snap = {k: _host_scalar(v) for k, v in out.items()}  # is_ready-gated (or an explicit drain barrier); the syncs live in _host_scalar
            snap["ts"] = ts
            snap["inner_steps_at_sample"] = steps
            snap["stall_threshold"] = self.stall_threshold
            self.last = snap
            self.counters["samples_harvested"] += 1
            # device-memory watermarks ride THIS tick (ISSUE 16): the
            # harvest cadence is the one host-side rhythm the dispatch
            # loop already pays for, and the census is pure metadata
            # (jax.live_arrays + .nbytes) — zero new syncs, see
            # docs/INTERNALS.md.  Eager on the sampler's first
            # harvest, then throttled: the walk is O(live buffers)
            if devicewatch.sample_watermarks(
                    0.0 if not self._censused
                    else CENSUS_MIN_INTERVAL_S):
                self._censused = True
            for fn in self._observers:
                # observability must never crash the plane it observes:
                # the harvest path rides the engine's dispatch loop, so
                # a failing export (ENOSPC on a JSONL ring, a vanished
                # directory) is counted and logged, never raised
                try:
                    fn(snap)
                except Exception:  # noqa: BLE001 — observer fault isolation
                    self.counters["observer_errors"] += 1
                    logger.exception("telemetry observer failed; "
                                     "snapshot dropped from this export")

    # -- out-of-loop API ---------------------------------------------------

    def add_observer(self, fn: Callable[[dict], None]) -> None:
        """Call ``fn(snapshot)`` for every harvested sample (the
        Observatory ring and the soak JSONL export ride this).

        Observers run SYNCHRONOUSLY on the harvest path, which the
        engine's dispatch loop drives via :meth:`tick` — keep them
        cheap: host dict work or a single buffered
        append (``append_jsonl_ring`` is O(1) writes by design; no
        fsync, no readbacks).  Anything slower belongs on its own
        thread fed from a queue, or the sampler's no-stall contract
        quietly becomes the observer's problem."""
        self._observers.append(fn)

    def drain(self) -> Optional[dict]:
        """Force a sample of the CURRENT state and block until it (and
        any older in-flight samples) land.  A window-boundary/run-end
        operation — never call from a dispatch loop."""
        self._steps_since = 0
        self._start_sample()
        self._harvest(block=True)
        return self.last


# ---------------------------------------------------------------------------
# Observatory: the merged host-side surface
# ---------------------------------------------------------------------------

class Observatory:
    """One merged snapshot of everything observable, plus derived
    per-window series.

    Sources are named zero-arg callables returning plain dicts of HOST
    data (no device syncs — the engine source reads the sampler's last
    harvested snapshot and host-side counter dicts only, so periodic
    snapshots are safe next to a running dispatch loop).  Snapshots
    land in a bounded ring; :meth:`window_rates` differentiates
    monotone counters into per-second rates between the last two ring
    entries and :meth:`percentile` reads a distribution over the ring
    — the substrate the SLO autotuner (ROADMAP item 4) will read."""

    def __init__(self, *, ring_capacity: int = 256) -> None:
        self._sources: dict[str, Callable[[], dict]] = {}
        self._ring: collections.deque = collections.deque(
            maxlen=max(2, ring_capacity))
        self._seq = 0
        # post-mortem bundles embed a fresh Observatory snapshot (the
        # flight recorder fault-isolates a failing source, so a
        # half-closed engine degrades to an ``error`` entry, not a
        # failed dump); newest-constructed Observatory wins the name,
        # and close() unhooks it — the stored bound-method ref is what
        # makes the identity-guarded removal work (a fresh
        # ``self.snapshot`` access is a NEW object every time)
        from .blackbox import RECORDER
        self._bb_src = self.snapshot
        RECORDER.add_source("observatory", self._bb_src)

    # -- wiring ------------------------------------------------------------

    def add_source(self, name: str, fn: Callable[[], dict]) -> "Observatory":
        self._sources[name] = fn
        return self

    def close(self) -> None:
        """Unhook this Observatory's flight-recorder bundle source (the
        mirror of EngineDurability.close's source removal).  Call when
        the observed engine/system is being torn down in a long-lived
        process — otherwise the source closure pins the closed engine
        (and its device buffers) for the rest of the process and every
        later bundle embeds an ``error`` entry instead of live state."""
        from .blackbox import RECORDER
        RECORDER.remove_source("observatory", self._bb_src)

    @classmethod
    def for_engine(cls, engine, *, sampler: Optional[TelemetrySampler] = None,
                   system=None, counters=None, router=None,
                   ring_capacity: int = 256) -> "Observatory":
        """The standard wiring: engine telemetry + pipeline + WAL plane,
        optionally a RaSystem's node-wide counters, a Counters registry
        (a node's per-server groups + the telemetry_dropped
        self-metric), and a router carrying the reliable-RPC counters."""
        obs = cls(ring_capacity=ring_capacity)
        sampler = sampler or getattr(engine, "_telemetry", None)

        def engine_src() -> dict:
            out: dict = {"lanes": engine.n_lanes,
                         "members": engine.n_members}
            # the autotuner-tunable knobs are stamped NEXT TO the rates
            # they move (rule RA07: no silent knob turns — every knob
            # the controller may touch is in this overview, so a ring
            # window always shows knob value + its effect together)
            dur = engine._dur
            out["pipeline"] = {
                "superstep_k": engine._superstep_k_last,
                "cmds_per_step": engine.max_step_cmds,
                "mesh_shape": engine.mesh_shape(),
                "wal_max_batch_interval_ms": (
                    dur.batch_interval_ms() if dur is not None else -1.0),
                "dispatches_in_flight": (engine._driver.in_flight()
                                         if engine._driver is not None
                                         else 0),
                **engine.pipeline_counters,
            }
            phases = getattr(engine, "phases", None)
            if phases is not None:
                out["phases"] = phases.overview()
            s = sampler or getattr(engine, "_telemetry", None)
            if s is not None:
                out["sampler"] = dict(s.counters)
                if s.last is not None:
                    out["telemetry"] = s.last
            if dur is not None:
                out["wal"] = dur.wal_overview()
            return out

        obs.add_source("engine", engine_src)
        ing = getattr(engine, "_ingress", None)
        if ing is not None:
            # the session tier (ISSUE 10): INGRESS_FIELDS counters +
            # flow gauges as their own source, so ring keys read
            # ``ingress_<field>`` (the SLO namespace)
            obs.add_source("ingress", ing.overview)
            if getattr(ing, "reads_enabled", False):
                # the read lane (ISSUE 20): READ_FIELDS counters +
                # lease coverage as ring keys ``read_<field>`` (the
                # ra_top read panel's namespace)
                obs.add_source("read", ing.read_overview)
        # the device plane (ISSUE 16): recompile sentinel + transfer
        # ledger + memory watermarks as their own source — ring keys
        # read ``device_<field>`` (DEVICE_FIELDS; the namespace the
        # ``steady_state_recompiles`` SLO objective resolves against).  Process-wide on
        # purpose: compiles and live buffers are process facts, not
        # per-engine ones.
        obs.add_source("device", devicewatch.WATCH.overview)
        cls._wire_host_sources(obs, system, counters, router)
        return obs

    @classmethod
    def for_system(cls, system, *, counters=None, router=None,
                   ring_capacity: int = 256) -> "Observatory":
        """Classic-plane wiring (no lane engine): system counters +
        an optional node Counters registry and reliable-RPC router."""
        obs = cls(ring_capacity=ring_capacity)
        cls._wire_host_sources(obs, system, counters, router)
        return obs

    @staticmethod
    def _wire_host_sources(obs: "Observatory", system, counters,
                           router=None) -> None:
        """The system/counters source wiring shared by both factories —
        one definition keeps the engine-path and classic-path snapshots
        field-for-field comparable."""
        if system is not None:
            obs.add_source("system", lambda: {
                "counters": system.counters(),
                "engine_pipeline": {
                    "superstep_k": system.superstep_k,
                    "dispatch_ahead": system.dispatch_ahead,
                    "wal_max_batch_interval_ms": getattr(
                        system, "wal_max_batch_interval_ms", -1.0),
                },
            })
        if counters is not None:
            obs.add_source("counters", lambda: {
                **counters.overview(), "self": counters.self_metrics()})
        if router is not None and \
                getattr(router, "rpc_counters", None) is not None:
            # the reliable control plane's RPC_FIELDS (retry/dedup/
            # unreachable...) flow through _flatten_numeric into the
            # Prometheus exposition and the time-series ring exactly
            # like the per-shard WAL stats (ISSUE 7 satellite; the
            # round-trip is test-pinned)
            obs.add_source("rpc", lambda: dict(router.rpc_counters))
        from .blackbox import RECORDER
        # the flight recorder's health + last incident ride every
        # snapshot so a stalled soak is explainable from the live view
        # (ra_top's incident footer reads this)
        obs.add_source("blackbox", RECORDER.overview)

    # -- snapshots ---------------------------------------------------------

    def snapshot(self) -> dict:
        """Merge every source into one dict, append the numeric
        flattening to the time-series ring, and return the snapshot.
        A failing source contributes an ``error`` entry instead of
        killing the export (observability must not crash the plane it
        observes)."""
        self._seq += 1
        snap: dict = {"seq": self._seq, "ts": time.time()}
        for name, fn in self._sources.items():
            try:
                snap[name] = fn()
            except Exception as exc:  # noqa: BLE001 — degrade, don't die
                snap[name] = {"error": repr(exc)[:200]}
        self._ring.append((snap["ts"], _flatten_numeric(snap)))
        return snap

    def ring(self) -> list:
        """The (ts, flat-numeric-dict) time series, oldest first."""
        return list(self._ring)

    #: flat-key patterns whose values are MONOTONE counters: a negative
    #: window delta on one of these is a counter reset (engine restart,
    #: a fresh bridge adopting the Observatory's source names) and must
    #: yield an OMITTED rate, never a negative one — a burn-rate
    #: evaluator fed a huge negative "rate" across a restart window
    #: would mis-verdict every objective that reads it.  Suffix-
    #: anchored where a looser match would swallow a gauge: plain
    #: substring "dispatches" also matches the dispatches_in_flight
    #: DEPTH gauge, whose negative drift (pipeline draining) is real
    #: signal a consumer must keep seeing.
    _MONOTONE_SUFFIXES = (
        "committed_total", "dispatches", "inner_steps", "_writes",
        "batches", "_syncs", "events", "_count", "total_ms",
        "blocks_staged", "seq", "telemetry_steps", "wal_files",
        "window_syncs", "early_observes", "apply_member_rounds",
        "apply_fallback_rounds", "leader_changes",
        "bytes_written",
        # ingress plane counters (ISSUE 10) — suffix-anchored so the
        # ingress_queue_rows / ingress_level DEPTH gauges keep their
        # negative drift (the dispatches_in_flight lesson)
        "submitted", "_accepted", "dup_dropped", "slow_signals",
        "_deferred", "_rejected", "shed_rows", "blocks_built",
        "block_rows", "reconnects", "credits_released",
        # device plane (ISSUE 16) — "compiles" also anchors
        # device_recompiles (the steady_state_recompiles SLO rate).
        # device_live_buffers stays an un-hinted gauge; live_bytes is
        # swallowed by the "bytes" infix, which only omits its
        # negative drift from rates — the gauge VALUE in snapshots is
        # untouched (rates of a census gauge are not a signal anyway)
        "compiles", "compile_ms", "_freed", "_samples",
    )
    _MONOTONE_INFIXES = (
        "bytes", "samples_", "encoded_", "readback_", "rpc_",
        "faults_", "elections_",
    )

    @classmethod
    def _is_monotone_key(cls, key: str) -> bool:
        return any(key.endswith(s) for s in cls._MONOTONE_SUFFIXES) \
            or any(h in key for h in cls._MONOTONE_INFIXES)

    def window_rates(self, span: int = 1, end: int = -1,
                     keys=None) -> dict:
        """Per-second deltas of every numeric key between ring entries
        ``span`` windows apart (default: the last two snapshots).
        Monotone counters (committed_total, dispatches, wal writes...)
        read as true rates; gauges read as drift — callers pick their
        keys from the field registry (docs/OBSERVABILITY.md).

        ``span`` > 1 rates over a wider window (``ring[end-span]`` ->
        ``ring[end]``) — the SLO engine's multi-window burn-rate input;
        ``end`` indexes the newer entry (negative from the newest).

        Counter-reset guard: a key the monotone-hint list recognises
        whose delta went NEGATIVE (an engine restart zeroed its
        counters mid-ring) is omitted — absent beats a bogus negative
        rate, same contract as the stale-sample omission below.

        ``engine_telemetry_*`` keys rate over the SAMPLER's own sample
        window (the embedded sample's ``ts``): snapshots taken faster
        than the harvest cadence re-embed the same sample, and the
        snapshot-ts delta would read a running engine as 0 cmds/s.
        With no fresh sample between the two snapshots those keys are
        omitted entirely — absent beats misleadingly zero.

        ``keys`` restricts the computation to an iterable of flat keys
        — the SLO engine's per-objective evaluation sweeps many ring
        windows per verdict, and differentiating every key of every
        window would put O(windows x keys) dict work on the snapshot
        path for the handful it reads."""
        span = max(1, int(span))
        n = len(self._ring)
        if end < 0:
            end = n + end
        lo = end - span
        if lo < 0 or end >= n or n < 2:
            return {}
        (t0, a), (t1, b) = self._ring[lo], self._ring[end]
        dt = max(t1 - t0, 1e-9)
        ts_key = "engine_telemetry_ts"
        tdt = (b[ts_key] - a[ts_key]
               if ts_key in a and ts_key in b else 0.0)
        out: dict = {}
        for k in (b if keys is None else keys):
            if k not in a or k not in b:
                continue
            delta = b[k] - a[k]
            if delta < 0 and self._is_monotone_key(k):
                continue  # counter reset across an engine restart
            if k.startswith("engine_telemetry_"):
                if tdt > 1e-9 and k != ts_key:
                    out[k] = round(delta / tdt, 4)
                continue
            out[k] = round(delta / dt, 4)
        return out

    def series(self, key: str) -> list:
        return [v[key] for _t, v in self._ring if key in v]

    def percentile(self, key: str, q: float) -> Optional[float]:
        """q in [0,1] percentile of ``key`` over the ring window."""
        s = sorted(self.series(key))
        if not s:
            return None
        return s[min(len(s) - 1, int(len(s) * q))]

    # -- exports -----------------------------------------------------------

    def prometheus(self, snap: Optional[dict] = None) -> str:
        """Prometheus text exposition of a snapshot (fresh one by
        default): scalars flatten to ``ra_tpu_<path>``, the commit-lag
        histogram becomes a cumulative ``_bucket{le=...}`` family, and
        the top-K offender arrays become lane-labelled gauges.
        Round-trip pinned by tests/test_telemetry.py via
        :func:`parse_prometheus`."""
        snap = snap if snap is not None else self.snapshot()
        lines = ["# ra-tpu Observatory exposition",
                 f"# seq {snap.get('seq', 0)}"]
        flat = _flatten_numeric(snap)
        for key in sorted(flat):
            lines.append(f"ra_tpu_{key} {_fmt_num(flat[key])}")
        tel = snap.get("engine", {}).get("telemetry")
        if tel:
            hist = tel.get("commit_lag_hist")
            if hist:
                # log2 buckets: bucket 0 = lag 0, bucket b = lag <
                # 2^b; cumulative counts per the exposition format
                cum = 0
                for b, count in enumerate(hist):
                    cum += count
                    le = "0" if b == 0 else (
                        "+Inf" if b == len(hist) - 1 else str(2 ** b - 1))
                    lines.append(
                        'ra_tpu_engine_commit_lag_bucket{le="%s"} %d'
                        % (le, cum))
                lines.append(f"ra_tpu_engine_commit_lag_count {cum}")
            lanes = tel.get("top_lanes") or []
            for rank, lane in enumerate(lanes):
                for field in ("top_commit_lag", "top_apply_lag",
                              "top_stall_steps"):
                    vals = tel.get(field) or []
                    if rank < len(vals):
                        lines.append(
                            'ra_tpu_engine_%s{lane="%d",rank="%d"} %s'
                            % (field, lane, rank, _fmt_num(vals[rank])))
        phases = snap.get("engine", {}).get("phases") or {}
        for pname in sorted(phases):
            ph = phases[pname]
            if not isinstance(ph, dict):
                continue
            hist = ph.get("hist")
            if not hist:
                continue
            # log2-ms buckets: bucket 0 = <1ms, bucket b = <2^b ms
            cum = 0
            for b, count in enumerate(hist):
                cum += count
                le = "+Inf" if b == len(hist) - 1 else str(2 ** b)
                lines.append(
                    'ra_tpu_engine_phase_ms_bucket{phase="%s",le="%s"}'
                    ' %d' % (pname, le, cum))
        return "\n".join(lines) + "\n"

    def to_jsonl(self, path: str, *, max_lines: int = 512) -> dict:
        """Append a fresh snapshot to a bounded JSONL ring at ``path``
        (compacted back to ``max_lines`` once it doubles) — what
        ``tools/soak.py --obs`` writes and ``tools/ra_top.py`` follows."""
        snap = self.snapshot()
        append_jsonl_ring(path, snap, max_lines=max_lines)
        return snap


# ---------------------------------------------------------------------------
# helpers: flattening, exposition formatting, parsing, JSONL ring
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _flatten_numeric(obj: Any, prefix: str = "") -> dict:
    """Nested dicts -> {'a_b_c': float} for scalar numeric leaves.
    Lists of dicts flatten with their index (``wal_shards_0_...`` —
    the per-shard fsync stats must reach the exposition and the ring);
    lists of scalars and strings are skipped (histograms and top-K
    arrays get their own labelled exposition families)."""
    out: dict = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            key = _NAME_RE.sub("_", str(k))
            out.update(_flatten_numeric(v, f"{prefix}{key}_"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            if isinstance(v, dict):
                out.update(_flatten_numeric(v, f"{prefix}{i}_"))
    elif isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    return out


def _fmt_num(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


#: exposition line: name{labels} value — the value token is validated
#: by float() below, which accepts every form the format allows
#: (negative exponents like 5e-05, +Inf, NaN) without a lookalike
#: character-class regex drifting out of sync with it
_PROM_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_prometheus(text: str) -> dict:
    """Parse Prometheus text exposition into {(name, labels): float}.
    Raises ValueError on any malformed non-comment line — the
    round-trip test runs every Observatory export through this."""
    out: dict = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m is None:
            raise ValueError(f"unparsable exposition line: {raw!r}")
        name, labels, val = m.group(1), m.group(2) or "", m.group(3)
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            raise ValueError(
                f"unparsable exposition value: {raw!r}") from None
    return out


#: per-path line-count cache so the steady-state append is ONE
#: buffered write — re-reading the whole ring per append would put
#: O(file) disk reads on the harvest path that observers (and through
#: them the dispatch loop) ride
_RING_LINES: dict = {}


def append_jsonl_ring(path: str, obj: dict, *, max_lines: int = 512) -> None:
    """Append one JSON line; once the file exceeds ``2*max_lines``
    lines, atomically compact it down to the newest ``max_lines`` (a
    bounded ring that tail-followers can read mid-compaction).  The
    line count is tracked in memory per path: the common call is one
    buffered append (no fsync, no re-read); the file is only read back
    at first touch of an existing ring and at compaction."""
    line = json.dumps(obj, separators=(",", ":"))
    count = _RING_LINES.get(path)
    if count is None:
        try:
            with open(path) as f:
                count = sum(1 for _ in f)
        except OSError:
            count = 0
    with open(path, "a") as f:
        f.write(line + "\n")
    count += 1
    if count > 2 * max_lines:
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            _RING_LINES[path] = count
            return
        tmp = path + ".compact"
        with open(tmp, "w") as f:
            f.writelines(lines[-max_lines:])
        os.replace(tmp, path)
        count = min(len(lines), max_lines)
    _RING_LINES[path] = count


def read_jsonl_tail(path: str, n: int = 1) -> list:
    """Newest ``n`` parsable snapshots from a JSONL ring (oldest first
    within the result); tolerant of a torn last line mid-append."""
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return []
    out = []
    for raw in lines[-(n + 1):]:
        try:
            out.append(json.loads(raw))
        except ValueError:
            continue
    return out[-n:]
