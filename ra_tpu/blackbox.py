"""Black-box flight recorder + the causal-event registry (ISSUE 7).

PR 6's Observatory answers *what* is slow (aggregate counters,
histograms, top-K offenders); this module answers *why a specific
command took 191ms* and *what the system was doing when it died*:

* :class:`FlightRecorder` — an always-on, bounded, per-subsystem
  structured-event ring.  Every plane (engine dispatch, WAL shards,
  reliable RPC, supervisors, fault plans, nemesis) emits typed events
  through :func:`record`; the emit path is one dict lookup + one deque
  append (no locks, no host syncs — lint rule RA04 gates it like the
  telemetry sampler's tick path).  The ring is the aircraft black box:
  it records continuously and is only *read* when something crashes.
* **Post-mortem bundles** — on supervisor escalation, poisoned-WAL
  rollover, ``MAX_POISON_STREAK`` thread death, a nemesis kill, or an
  unhandled server crash, :meth:`FlightRecorder.dump` writes one JSON
  bundle: the recent event rings + every registered state source
  (Observatory snapshot, per-shard WAL watermarks, active FaultPlan /
  DiskFaultPlan state, durability config).  Recovery later stamps a
  join-able report next to the bundle (:func:`stamp_recovery`), so a
  crash and the recovery that answered it read as one incident.
* :data:`EVENT_REGISTRY` — the central event-type registry.  Lint rule
  RA06 (tools/lint.py) statically requires every event type emitted
  anywhere (``record(...)``/``trace.span(...)``/``trace.phase_span(...)``)
  to be a key here and documented in docs/OBSERVABILITY.md — the
  RA05 field-registry discipline applied to events; the runtime mirror
  is the ``unregistered_events`` self-counter (MUST stay 0).

Trace-context joins: host-side events carry either an explicit
``trace`` id (classic commands: the context rides the command object
and the RPC frames) or a join key — ``(uid, idx)`` for the WAL plane,
``(lane, submit_index)``/``step`` for the engine plane, where commands
are never tagged inside jit (the dispatch loop stays host-sync-free;
see docs/INTERNALS.md §10 for the step-stamp join).
``tools/ra_trace.py`` reconstructs per-command timelines from bundles.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import tempfile
import threading
import time
from typing import Any, Callable, Optional

logger = logging.getLogger("ra_tpu.blackbox")

#: every event type the tracing/flight-recorder plane may emit, with a
#: one-line meaning (the machine-checked registry; RA06 gates emit
#: sites against the KEYS, docs/OBSERVABILITY.md documents them).
#: Span names recorded through ra_tpu.trace at module level are events
#: too — a Chrome trace and a post-mortem bundle must speak one
#: vocabulary.
EVENT_REGISTRY = {
    # -- command lifecycle (classic path; `trace` = propagated ctx) ----
    "cmd.ingress": "client created a trace context at the API boundary",
    "cmd.submit": "traced command handed to a member (one per attempt; "
                  "redirects show as extra submits)",
    "cmd.append": "leader appended the command at (uid, idx, term)",
    "cmd.commit": "a server's commit index advanced to idx (uid-keyed)",
    "cmd.apply": "a traced command was applied on a member",
    # -- classic replication batching (ISSUE 13) -----------------------
    "rpc.batch": "leader built one multi-entry AppendEntries batch "
                 "(entry count + payload bytes; ONE event per batch, "
                 "never per entry)",
    # -- reliable control-plane RPC (transport/rpc.py) -----------------
    "rpc.send": "reliable-RPC attempt left the sender (rid stable "
                "across retries)",
    "rpc.recv": "receiver started executing a request id",
    "rpc.dup": "receiver dedup hit — duplicate delivery of a seen rid "
               "under the same trace context",
    "rpc.expired": "request arrived past its propagated deadline",
    # -- transport fault plan ------------------------------------------
    "net.fault": "transport FaultPlan injected a fault (kind, peer, "
                 "frame class)",
    "rpc.domain_delay": "latency-domain matrix stretched a frame "
                        "crossing (src -> dst) domains (ISSUE 19: "
                        "geography, not chaos — rides the same "
                        "per-(peer, class, direction) streams)",
    # -- WAL plane (per shard) -----------------------------------------
    "wal.write": "one group-commit batch reached the file (per-uid "
                 "index ranges ride along)",
    "wal.fsync": "durability syscall latency (ms)",
    "wal.confirm": "per-writer durable range notify (uid, lo..hi)",
    "wal.resend": "out-of-sequence write gap -> resend_from signal",
    "wal.poison": "batch I/O error poisoned the current WAL file",
    "wal.escalate": "poison streak exhausted -> thread death "
                    "(supervisor restart)",
    "wal.kill": "injected WAL crash (nemesis / kill hook)",
    "wal.restart": "supervised restart of a dead WAL incarnation",
    # -- engine durability bridge (keyed by step = submit_index) -------
    "engine.submit": "dispatch queued steps [step_lo, step_hi] to "
                     "every WAL shard",
    "engine.confirm": "a shard's durable step horizon advanced",
    "engine.crash": "a shard encode worker died on an exception",
    "engine.elect": "host requested elections for a lane set",
    "engine.fail": "host failure detector marked a member down",
    "engine.recover": "host revived a member via snapshot install",
    "engine.member": "host membership change (add/promote/remove)",
    # -- program spans (trace.span / trace.phase_span: profiler
    # annotations, recorded while a jax.profiler session runs; a phase
    # in brackets is the PhaseStats phase the same `with` feeds) ---------
    "ra.sweep": "span: one WireListener.sweep() on the serve thread",
    "ra.sweep.receive": "span: snapshot of the connections' ring fill "
                        "under the listener lock",
    "ra.sweep.decode": "span [sweep_decode]: ring bytes gathered, "
                       "decoded and validated; rings advanced (conns=)",
    "ra.sweep.submit": "span: the sweep's rows offered to the ingress "
                       "plane (rows=)",
    "ra.sweep.credit": "span: verdicts folded and CREDIT frames fanned "
                       "out",
    "ra.sweep.read_reply": "span: read outcomes framed as READ_REPLY "
                           "records a connection, under the pump's "
                           "read harvest (rows=)",
    "ra.pump": "span [pump]: one IngressPlane.pump() on the serve "
               "thread",
    "ra.pump.reads_pop": "span: the read half of a dispatch popped "
                         "from the read window (or the zero block "
                         "that keeps a pending batch's replies coming)",
    "ra.pump.reads_harvest": "span: the read block in flight settled "
                             "against the observed dispatches' read "
                             "aux, replies fanned out",
    "ra.pump.harvest": "span: credit release for blocks the committed "
                       "watermark covers (twice a pump)",
    "ra.pump.retire": "span: one block retired, its last rows' "
                      "credit released, ACK fan-out hook (block=)",
    "ra.pump.release": "span: rows of a block released ahead of it: "
                       "their own lanes have committed, another "
                       "lane's rows still wait (block=)",
    "ra.pump.confirm_only": "span: at a pump's tail, a WAL confirm "
                            "that missed its dispatch carried by the "
                            "confirm-only program, and the wait for "
                            "the committed count it returns",
    "ra.pump.pop_block": "span [pop_block]: the coalescer built one "
                         "dense block (block=)",
    "ra.driver.stage": "span [host_staging]: host encode + async H2D "
                       "of one block (block=)",
    "ra.driver.dispatch": "span: the staged block's dispatch (block=, "
                          "step=first-last of the WAL steps it "
                          "submits, carries=first-last of the WAL "
                          "steps whose confirm it is the first to "
                          "sample)",
    "ra.driver.window_sync": "span: blocked on the oldest watermark "
                             "readback at the in-flight cap, once per "
                             "wait",
    "ra.engine.step": "span: one single-step XLA dispatch",
    "ra.engine.superstep": "span: the jitted fused K-round step's call",
    "ra.engine.backpressure": "span: dispatch thread waiting on the "
                              "unconfirmed-step window",
    "ra.engine.wal_submit": "span [wal_submit]: handing a dispatch's "
                            "aux to the WAL shards",
    "ra.settle": "span: IngressPlane.settle(), the flush barrier",
    "ra.wal.encode": "span [wal_encode]: shard worker pulled + encoded "
                     "one step's WAL block (shard=, step=)",
    "ra.wal.readback": "span [wal_readback]: the device-to-host pulls "
                       "of one step's block (where a new slice shape "
                       "compiles)",
    "ra.wal.encode_block": "span [encode]: block encode + CRC",
    "ra.wal.batch": "span: one group-commit batch (n=, step=lowest-"
                    "highest index written)",
    "ra.wal.write": "span: the batch's write(2) (bytes=)",
    "ra.wal.fsync": "span [fsync_wait]: the durability syscall",
    "ra.wal.confirm_publish": "span [confirm_publish]: durable range "
                              "notified to every writer",
    # -- storage fault plan --------------------------------------------
    "disk.fault": "DiskFaultPlan injected a fault (kind, path class, "
                  "op, path)",
    # -- supervision / crashes -----------------------------------------
    "sup.restart": "a supervisor restarted a dead component",
    "sup.giveup": "restart intensity exceeded; supervisor backing off",
    "srv.crash": "a server shell crashed out of the node event loop",
    # -- nemesis -------------------------------------------------------
    "nemesis.op": "chaos schedule executed one op",
    # -- SLO autotuner (ra_tpu/autotune.py, ISSUE 9) -------------------
    "tune.decision": "autotuner changed a knob (knob, old->new, "
                     "triggering phase + objective) — RA07: no silent "
                     "knob turns",
    "tune.freeze": "autotuner entered a freeze (active FaultPlan/"
                   "DiskFaultPlan or a fresh incident): decisions "
                   "suspended",
    # -- ingress plane (ra_tpu/ingress/, ISSUE 10) ---------------------
    "ingress.connect": "session (re)connected: epoch bump under a "
                       "stable (tenant, lane, shard) placement",
    "ingress.level": "backpressure ladder level transition "
                     "(SLO-verdict-driven; open/tight/fair)",
    "ingress.shed": "coalescer ring overflow began shedding rows "
                    "(transition into a shed episode, not per row)",
    "pump.slow": "one IngressPlane.pump() took over PUMP_SLOW_S (ms, "
                 "start_ns on the profiler's clock, split= ms a child, "
                 "xla_compiles / compiled / window_syncs / "
                 "gc_collections that rose while it ran; ISSUE 37)",
    "device.compile": "one backend compile (fun= the program jax "
                      "names, s= seconds, thread= the compiling "
                      "thread's name)",
    # -- read lane (ra_tpu/ingress/, ISSUE 20) -------------------------
    "read.shed": "ladder bias began shedding read waves at admission "
                 "(any tightened level refuses reads BEFORE writes "
                 "are delayed; transition, not per row)",
    "read.stale": "the device refused pending reads rather than serve "
                  "past lease/quorum cover (stale-refusal episode "
                  "transition — the linearizable-read oracle pins "
                  "stale SERVES, refusals are the safe outcome)",
    # -- wire plane (ra_tpu/wire/, ISSUE 12) ---------------------------
    "wire.conn": "connection lifecycle: accept/close/bulk-connect/"
                 "reconnect-storm (loopback fleets emit ONE event, "
                 "never one per connection)",
    "wire.credit": "the credit-frame ladder level changed between "
                   "sweeps (transition only, never per row)",
    "wire.shed": "a sweep began answering shed verdicts (transition "
                 "into a wire shed episode)",
    "wire.error": "protocol error (bad hello/version/record) closed "
                  "a connection",
    # -- device plane (ra_tpu/devicewatch.py, ISSUE 16) ----------------
    "device.recompile": "recompile sentinel caught a steady-state "
                        "retrace of a wrapped jit entry point (fn tag "
                        "+ which argument's shape/dtype/sharding "
                        "drifted + compile wall ms)",
    "profile.captured": "a jax_profile() capture finished; the profile "
                        "dir rides along so the capture shows up in "
                        "ra_trace timelines instead of being a side "
                        "file nobody finds",
    # -- engine failure detector (supervisor tier, ISSUE 17) -----------
    "detector.suspect": "failure detector escalated a peer/engine to "
                        "suspect (silent beyond suspect_after; age = "
                        "seconds since last heard)",
    "detector.down": "failure detector confirmed a peer/engine down "
                     "(silent beyond down_after AND suspect for the "
                     "full hysteresis window; age rides along)",
    # -- placement failover (ISSUE 17; `trace` = migrated-cmd ctx) -----
    "placement.refuse": "a lane range's old home refused/was "
                        "unreachable for a session (the client-visible "
                        "start of a failover incident)",
    "placement.migrate": "control plane committed a lane-range "
                         "re-placement through the placement table "
                         "(rid, victim -> survivor, new generation)",
    "placement.adopt": "survivor restored a victim engine's durable "
                       "lane state (checkpoint + WAL-shard merge, "
                       "gated at the fsynced watermark)",
    "placement.rehome": "sessions re-bound to the new home: epoch "
                        "bump, dedup slots claimed, ack watermarks "
                        "re-seeded",
    "placement.giveup": "a bounded placement retry loop exhausted its "
                        "deadline/attempts and gave up (RA16: no "
                        "silent infinite retry in the control plane)",
    # -- cross-host placement serving path (ISSUE 19) ------------------
    "placement.rehome_hint": "listener refused a frame routed on a "
                             "stale placement revision with a typed "
                             "REHOME hint (engine, generation, rev) — "
                             "never a silent misroute into a dead "
                             "engine's lanes",
    "placement.adopt_rpc": "a survivor host committed an adoption "
                           "requested over the reliable control-plane "
                           "RPC tier (host_adopt — retried, "
                           "deduplicated, deadline-bounded)",
    "placement.stale_probe": "supervisor discarded a probe reply from "
                             "a superseded engine generation (a stale "
                             "reply must not reset the new incumbent's "
                             "suspect streak)",
    # -- recorder meta -------------------------------------------------
    "bb.dump": "post-mortem bundle written",
    "bb.recover": "recovery stamped a join-able recovery report",
}


def _json_safe(obj: Any) -> Any:
    """Best-effort conversion for bundle serialization — events may
    carry exceptions, ServerIds, numpy scalars; a bundle write must
    never fail on a field repr."""
    return repr(obj)


class FlightRecorder:
    """Bounded per-subsystem structured-event rings + bundle dumps.

    The subsystem is the event type's dotted prefix (``wal.fsync`` ->
    ring ``wal``), so one noisy plane can never evict another plane's
    history — the property that makes the recorder useful at the crash
    site (the engine's kHz dispatch events do not wash out the three
    supervisor events that explain the death)."""

    DEFAULT_RING = 4096

    def __init__(self, ring_capacity: int = DEFAULT_RING) -> None:
        self.ring_capacity = int(ring_capacity)
        self._rings: dict[str, collections.deque] = {}
        #: named zero-arg state callables merged into every bundle
        #: (Observatory snapshot, WAL watermarks, fault-plan state...)
        self._sources: dict[str, Callable[[], Any]] = {}
        #: newest-first incident log (what/where/when + bundle path)
        self.incidents: collections.deque = collections.deque(maxlen=32)
        #: master switch: False turns record() into one attr read + a
        #: bool test (the A/B knob the overhead pin flips)
        self.enabled = True
        #: where dump() writes when the trigger site has no data_dir;
        #: None -> $RA_TPU_BLACKBOX_DIR -> <tmp>/ra_tpu_blackbox
        self.dump_dir: Optional[str] = None
        self.origin = f"pid{os.getpid()}"
        self.counters = {"events": 0, "unregistered_events": 0,
                         "dumps": 0, "dump_errors": 0, "recoveries": 0}
        self._dump_lock = threading.Lock()
        self._dump_seq = 0

    # -- emit path (rides dispatch loops and WAL threads: stay cheap) --

    def record(self, etype: str, **fields: Any) -> None:
        """Append one structured event to its subsystem ring.  One dict
        lookup + one deque append; never blocks, never raises, never
        touches a device array (rule RA06/RA04-gated)."""
        if not self.enabled:
            return
        sub = etype.partition(".")[0]
        ring = self._rings.get(sub)
        if ring is None:
            ring = self._rings.setdefault(
                sub, collections.deque(maxlen=self.ring_capacity))
        if etype not in EVENT_REGISTRY:
            # the runtime mirror of lint rule RA06: a typo'd event type
            # is still recorded (evidence beats purity at a crash
            # site) but counted so tests can pin the mismatch to 0
            self.counters["unregistered_events"] += 1
        ring.append((time.time(), etype, fields))
        self.counters["events"] += 1

    # -- wiring --------------------------------------------------------

    def add_source(self, name: str, fn: Callable[[], Any]) -> None:
        """Register a state source merged into every bundle.  Sources
        are fault-isolated at dump time (a failing one contributes an
        ``error`` entry, the dump still lands)."""
        self._sources[name] = fn

    def remove_source(self, name: str, fn: Optional[Callable] = None) -> None:
        """Drop a source; with ``fn`` given, only when it is still the
        registered one (a closed engine must not unhook its
        successor's source under the shared name)."""
        if fn is None or self._sources.get(name) is fn:
            self._sources.pop(name, None)

    def clear(self, *, sources: bool = False) -> None:
        """Drop every ring and incident (test isolation).  Sources are
        KEPT by default — module-level wiring (fault-plan registries)
        registers once per process and must survive a ring wipe."""
        self._rings.clear()
        self.incidents.clear()
        if sources:
            self._sources.clear()
        for k in self.counters:
            self.counters[k] = 0

    # -- readout -------------------------------------------------------

    def events(self, subsystem: Optional[str] = None) -> list:
        """Recorded events as [(ts, etype, fields)], oldest first —
        one subsystem's ring, or every ring merged and time-sorted."""
        rings = ([self._rings.get(subsystem, ())] if subsystem
                 else list(self._rings.values()))
        out: list = []
        for ring in rings:
            got: list = []
            for _ in range(3):
                # deque iteration can race a concurrent append
                # ("deque mutated during iteration"); retry into a
                # FRESH list so a failed attempt's partial copy never
                # duplicates events — readers are rare, appends must
                # never wait on them
                try:
                    got = list(ring)
                    break
                except RuntimeError:  # pragma: no cover — append race
                    got = []
                    continue
            out.extend(got)
        out.sort(key=lambda e: e[0])
        return out

    def last_incident(self) -> Optional[dict]:
        return self.incidents[-1] if self.incidents else None

    def overview(self) -> dict:
        """Host-side health summary (what the Observatory embeds)."""
        return {"counters": dict(self.counters),
                "rings": {k: len(v) for k, v in self._rings.items()},
                "last_incident": self.last_incident()}

    # -- post-mortem bundles -------------------------------------------

    def _resolve_dir(self, data_dir: Optional[str]) -> str:
        if data_dir:
            return os.path.join(data_dir, "blackbox")
        if self.dump_dir:
            return self.dump_dir
        env = os.environ.get("RA_TPU_BLACKBOX_DIR")
        if env:
            return env
        return os.path.join(tempfile.gettempdir(), "ra_tpu_blackbox")

    def dump(self, reason: str, *, what: str = "", where: str = "",
             data_dir: Optional[str] = None,
             extra: Optional[dict] = None) -> Optional[str]:
        """Write a post-mortem bundle and log the incident.  Returns
        the bundle path, or None when the write itself failed (an
        ENOSPC'd disk must not add a crash to the crash — counted in
        ``dump_errors``).  Trigger sites pass their ``data_dir`` so
        bundles land next to the data they explain."""
        ts = time.time()
        with self._dump_lock:
            self._dump_seq += 1
            seq = self._dump_seq
        # the whole build+write is guarded: dump() is called from crash
        # handlers, so ANY escape (a ring dict resized by a concurrent
        # first-event record, a non-string dict key json refuses, a
        # full disk) must degrade to a counted dump_error — a failing
        # dump must never add a crash to the crash (doc'd contract)
        try:
            bundle = {
                "format": "ra-tpu-blackbox-1",
                "reason": reason,
                "what": what,
                "where": where,
                "ts": ts,
                "origin": self.origin,
                "pid": os.getpid(),
                "counters": dict(self.counters),
                "incidents": list(self.incidents),
                "events": {sub: self.events(sub)
                           for sub in list(self._rings)},
                "sources": {},
                "extra": extra or {},
            }
            for name, fn in list(self._sources.items()):
                try:
                    bundle["sources"][name] = fn()
                except Exception as exc:  # noqa: BLE001 — degrade
                    bundle["sources"][name] = {"error": repr(exc)[:200]}
            out_dir = self._resolve_dir(data_dir)
            path = os.path.join(
                out_dir, f"bundle-{int(ts)}-{os.getpid()}-{seq:03d}-"
                f"{reason[:40]}.json")
            os.makedirs(out_dir, exist_ok=True)
            tmp = path + ".partial"
            with open(tmp, "w") as f:
                json.dump(bundle, f, default=_json_safe,
                          separators=(",", ":"), skipkeys=True)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 — never raise from a dump
            self.counters["dump_errors"] += 1
            logger.exception("flight recorder: bundle dump failed "
                             "(%s)", reason)
            return None
        incident = {"ts": ts, "reason": reason, "what": what,
                    "where": where, "path": path}
        self.incidents.append(incident)
        self.counters["dumps"] += 1
        self.record("bb.dump", reason=reason, what=what, where=where,
                    path=path)
        logger.warning("flight recorder: post-mortem bundle %s (%s)",
                       path, reason)
        return path

    def stamp_recovery(self, info: dict,
                       data_dir: Optional[str] = None) -> Optional[str]:
        """Write a recovery report that joins the newest bundle in the
        same blackbox dir (``joins`` names it, or None for a clean
        boot) — crash and recovery read as one incident."""
        ts = time.time()
        out_dir = self._resolve_dir(data_dir)
        joins = None
        try:
            names = sorted(n for n in os.listdir(out_dir)
                           if n.startswith("bundle-")
                           and n.endswith(".json"))
            joins = names[-1] if names else None
        except OSError:
            pass
        report = {"format": "ra-tpu-recovery-1", "ts": ts,
                  "origin": self.origin, "joins": joins, **info}
        path = os.path.join(out_dir,
                            f"recovery-{int(ts)}-{os.getpid()}.json")
        try:
            os.makedirs(out_dir, exist_ok=True)
            tmp = path + ".partial"
            with open(tmp, "w") as f:
                json.dump(report, f, default=_json_safe, skipkeys=True)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 — recovery must not fail on this
            self.counters["dump_errors"] += 1
            logger.exception("flight recorder: recovery stamp failed")
            return None
        self.counters["recoveries"] += 1
        self.record("bb.recover", joins=joins, path=path,
                    plane=info.get("plane", "?"))
        return path


#: the process-wide recorder.  Always on (the black-box contract); the
#: rings are bounded, so "on" costs memory O(subsystems * capacity)
#: and one deque append per event.
RECORDER = FlightRecorder()


def record(etype: str, **fields: Any) -> None:
    """Emit one flight-recorder event (module-level convenience — the
    instrumented call sites all route through here; RA06 gates the
    event types statically)."""
    RECORDER.record(etype, **fields)


def stamp_recovery(info: dict, data_dir: Optional[str] = None):
    return RECORDER.stamp_recovery(info, data_dir=data_dir)


def load_bundle(path: str) -> dict:
    """Parse a post-mortem bundle (the ra_trace input contract)."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("format") != "ra-tpu-blackbox-1":
        raise ValueError(f"not a ra-tpu blackbox bundle: {path}")
    return doc
