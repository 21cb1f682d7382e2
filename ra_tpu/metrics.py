"""Counters + leaderboard — the observability surface.

The reference keeps ~47 flat atomic counter fields per server behind
seshat (ra_counters.erl, field specs ra.hrl:236-390) plus lock-free ETS
tables for leader lookup (ra_leaderboard.erl).  Here: a Counters registry
of plain int dicts (GIL-atomic increments), sampled without touching the
server event loop — the same contract as ra:key_metrics (ra.erl:1229).
On the lane engine, the equivalent metrics live *on device* as the
total_committed / term / commit arrays and are sampled via readback.
"""
from __future__ import annotations

import threading
from typing import Optional

#: per-server LOG subsystem counter fields (RA_LOG_COUNTER_FIELDS,
#: ra.hrl:236-268 — same names).  Owned by the log facade (DurableLog /
#: MemoryLog keep a plain dict) and merged into key_metrics.
#: Deliberate N/A vs the reference: ``reserved_1`` (a placeholder), and
#: ``read_open_mem_tbl``/``read_closed_mem_tbl`` — the reference's
#: open/closed WAL ETS tables are merged into the DurableLog memtable
#: here (wal.py:15-21), so those reads all count as ``read_cache``.
LOG_FIELDS = (
    "write_ops", "write_resends", "read_ops", "read_cache",
    "read_segment", "fetch_term", "snapshots_written",
    "snapshot_installed", "snapshot_bytes_written", "open_segments",
    "checkpoints_written", "checkpoint_bytes_written",
    "checkpoints_promoted",
)

#: per-server raft/process counter fields (RA_SRV_COUNTER_FIELDS,
#: ra.hrl:311-357 — same names).  ``reserved_2`` omitted (placeholder);
#: ``invalid_reply_mode_commands`` stays 0 by construction — reply modes
#: are a typed enum here, so an invalid one cannot be submitted.
#: ``msgs_processed`` is ours (no reference equivalent): total events
#: through the shell, useful for busy-loop diagnostics.
SERVER_FIELDS = (
    "aer_received_follower", "aer_replies_success", "aer_replies_fail",
    "commands", "command_flushes", "aux_commands", "consistent_queries",
    "rpcs_sent", "msgs_sent", "dropped_sends", "send_msg_effects_sent",
    "pre_vote_elections", "elections", "forced_gcs", "snapshots_sent",
    "release_cursors", "aer_received_follower_empty",
    "term_and_voted_for_updates", "local_queries",
    "invalid_reply_mode_commands", "checkpoints", "msgs_processed",
)

#: per-server gauge fields (RA_SRV_METRICS_COUNTER_FIELDS,
#: ra.hrl:359-383): sampled live from server state at key_metrics time
#: rather than double-written into counters on every event.
METRIC_FIELDS = (
    "last_applied", "commit_index", "snapshot_index", "last_index",
    "last_written_index", "commit_latency", "term", "checkpoint_index",
    "effective_machine_version",
)

#: router-level reliable-RPC counter fields (transport/rpc.py): the
#: control plane's at-most-once observability.  Sender side: calls,
#: retries and the typed failure triad; receiver side: executions,
#: dedup hits (a retry mapped onto an already-seen request id — the
#: proof no lifecycle verb ran twice), responses re-sent from the
#: cache, and requests that arrived past their propagated deadline.
#: No reference equivalent: rpc:call rides Erlang distribution there.
RPC_FIELDS = (
    "rpc_calls", "rpc_retries", "rpc_timeouts", "rpc_unreachable",
    "rpc_remote_errors", "rpc_dedup_hits", "rpc_requests_executed",
    "rpc_responses_resent", "rpc_expired",
)

#: node-wide WAL counter fields (ra_log_wal.erl:32-43 — same names,
#: plus ``syncs``: fsync count, the number the reference exposes through
#: ra_file_handle instead, and ``sync_time_us``: cumulative durability-
#: syscall wall time, the wal_sync_time gauge role).  Each WAL *shard*
#: owns one counter dict (the sharded engine bridge runs S of them);
#: ``Wal.stats()`` adds the derived fsync latency p50/p99 and
#: records-per-fsync from a bounded latency reservoir.
WAL_FIELDS = ("wal_files", "batches", "writes", "bytes_written", "syncs",
              "sync_time_us")

#: engine durability-bridge counter fields (ra_tpu/engine/durable.py),
#: mirroring the RPC_FIELDS pattern: plain int dict, merged into the
#: engine overview.  ``readback_bytes`` is what the compacted device->
#: host readback actually moved for WAL encode; ``readback_bytes_full``
#: is what the pre-compaction full-ring readback would have moved on the
#: same steps (the ratio is the compaction win).  The overview adds
#: ``confirm_lag_steps`` — dispatched-but-unconfirmed steps on the
#: laggiest shard — as a DERIVED gauge sampled at overview time, not a
#: counter field.
ENGINE_WAL_FIELDS = ("readback_bytes", "readback_bytes_full",
                     "encoded_blocks", "encoded_bytes")

#: engine dispatch-pipeline counter fields (ra_tpu/engine/lockstep.py),
#: host-side ints stamped into ``engine.overview()["pipeline"]`` and the
#: bench JSON (ISSUE 5).  ``dispatches`` counts XLA dispatches (single
#: steps AND fused supersteps each count 1); ``inner_steps`` counts
#: engine rounds (a superstep of K adds K — dividing the two gives the
#: realized fusion factor); ``superstep_dispatches`` the fused subset;
#: ``blocks_staged`` host->device staging transfers started by the
#: dispatch-ahead driver; ``window_syncs`` the driver's in-flight-cap
#: waits — the ONLY host blocking points in a dispatch-ahead loop, so
#: window_syncs << dispatches is the proof the pipeline actually ran
#: ahead (the gauge twin of lint rule RA04's static guarantee);
#: ``early_observes`` the dispatches observed by the driver's
#: non-blocking ``poll()`` because their watermark had arrived, as
#: against the in-flight cap's pops (ISSUE 28);
#: ``apply_member_rounds`` the rounds (of ``inner_steps``) whose apply
#: stage folded the committed window once a MEMBER: every round of an
#: engine whose machine state is larger than its ring (a table is never
#: handed over), and on the lane path the rounds in which some active
#: member did not share its lane's interval (ISSUE 34).  Counted from a
#: flag in the step's aux as its copy arrives, so it trails by the
#: dispatches in flight.  ``confirm_late_blocks`` (ISSUE 37) the
#: retired write blocks whose carrier, the first dispatch whose sample
#: of the WAL's confirm horizon covered the block's rows, was not the
#: dispatch directly after the block's own: the confirm arrived after
#: the next dispatch had sampled.  Counted by ``IngressPlane`` at the
#: retire.  ``confirm_only_runs`` the ``ra_confirm``
#: programs run at a pump's tail to carry such a confirm without a
#: dispatch (not dispatches: ``dispatches`` does not count them);
#: ``confirm_only_blocks`` the retired blocks whose carrier was one.
#: ``apply_fallback_rounds`` the rounds in which some lane's window took
#: its machine's sequential branch (``JitMachine.sequential_window_fold``
#: behind ``window_fold_dispatch``, or the default ``jit_apply_batch``):
#: the demotion cliff, counted from a flag in the step's aux as
#: ``apply_member_rounds`` is.
ENGINE_PIPELINE_FIELDS = ("dispatches", "inner_steps",
                          "superstep_dispatches", "blocks_staged",
                          "window_syncs", "early_observes",
                          "apply_member_rounds", "confirm_late_blocks",
                          "confirm_only_runs", "confirm_only_blocks",
                          "apply_fallback_rounds")

#: node-wide segment-writer counter fields (ra_log_segment_writer.erl:
#: 37-52 — same names)
SEGMENT_WRITER_FIELDS = ("mem_tables", "segments", "entries",
                         "bytes_written")

#: storage-plane fault observability (ra_tpu/log/faults.py): one
#: node-wide dict, the disk twin of RPC_FIELDS.  Plan-side:
#: ``faults_injected`` counts DiskFaultPlan decisions that injected a
#: fault (per-kind detail lives on the plan's own counters).  Policy
#: side: ``faults_hit`` is every I/O error the log layer *handled*
#: (poison/rollover/retry/skip — not thread death), ``crc_catches``
#: read-side corruption caught by a crc check, ``poisoned_files`` WAL
#: files poisoned by a failed durability syscall (fsyncgate: the fd is
#: never fsynced again), ``fault_rollovers`` the rollovers that poison
#: forced, ``wal_escalations`` consecutive-poison cap overflows that
#: escalate to thread death (supervisor restart), ``flush_retries``/
#: ``flush_escalations`` the segment-flush backoff ladder,
#: ``snapshot_write_failures`` failed container writes (pending-dir
#: discipline: the old snapshot stays), ``swallowed_oserrors`` the
#: audited allow-listed swallow sites (each carries a why-safe
#: comment), and ``fsync_retries_after_failure`` fsyncgate-discipline
#: violations — an fsync re-issued on a failed fd with no intervening
#: rewrite of its data; MUST stay 0.
DISK_FAULT_FIELDS = (
    "faults_injected", "faults_hit", "crc_catches", "poisoned_files",
    "fault_rollovers", "wal_escalations", "flush_retries",
    "flush_escalations", "snapshot_write_failures",
    "swallowed_oserrors", "fsync_retries_after_failure",
)

#: device-resident per-lane telemetry accumulators (ISSUE 6): the
#: ``[lanes]``-shaped int32 pytree carried through the jitted step
#: (ra_tpu/engine/lockstep.py LaneTelemetry — field parity is pinned by
#: tests).  Counters: ``elections_requested`` host-requested election
#: rounds, ``elections_won`` vote rounds that seated a leader,
#: ``leader_changes`` the subset that moved the leader to a different
#: slot (churn), ``steps`` engine rounds observed.  Gauges (rewritten
#: every step): ``leader_age`` steps since the lane's leader last
#: changed (stability), ``commit_lag`` leader tail minus leader commit
#: in entries, ``apply_lag`` leader commit minus the lane apply
#: frontier, ``stall_steps`` consecutive rounds with a nonempty commit
#: backlog and zero commit progress — a lane is flagged STALLED when it
#: crosses the sampler's ``stall_threshold``.
TELEMETRY_FIELDS = (
    "elections_requested", "elections_won", "leader_changes",
    "leader_age", "commit_lag", "apply_lag", "stall_steps", "steps",
)

#: phase-resolved latency attribution (ISSUE 9): the host-side edges of
#: the lane-engine path, each a monotonic-stamp latency sample fed into
#: a ``telemetry.PhaseStats`` accumulator (bounded reservoir + log2-ms
#: histogram + cumulative ``total_ms`` per phase).  ``total_ms`` is
#: MONOTONE, so differentiating it over the Observatory ring yields the
#: per-window budget share of each phase — "where did this window's
#: latency go" — which is exactly the autotuner's triggering-phase
#: input.  Phases: ``host_staging`` host->device block staging in the
#: dispatch-ahead driver, ``device_dispatch`` dispatch-submit to
#: async-watermark-readback-observed (PR 5's step stamps; no new host
#: syncs), ``queue_wait`` a submitted step waiting for its shard encode
#: worker, ``wal_encode`` the off-thread readback+encode+CRC of one WAL
#: block, ``fsync_wait`` the durability syscall, ``confirm_publish``
#: fsync-to-confirm-notify fan-out, ``commit_e2e`` the full
#: submit->all-shards-confirmed edge (the continuous commit-latency
#: signal the `commit_p99_ms` SLO reads), ``encode`` time spent
#: producing codec payload images (ISSUE 18) — fed by BOTH planes: the
#: classic leader/follower encode sites in DurableLog and the
#: lane-engine WAL workers' block encode; its share of total phase time
#: is the `encode_share_pct` key bench tails carry (lower is better —
#: encode-once should drive it toward zero).
PHASE_FIELDS = (
    "host_staging", "device_dispatch", "queue_wait", "wal_encode",
    "fsync_wait", "confirm_publish", "commit_e2e", "encode",
    # ``read_e2e`` (ISSUE 20): read-block submit -> serve outcome
    # observed at the driver's existing window-boundary pops — the
    # continuous read-latency signal the `read_p99_ms` SLO objective
    # evaluates (flat ring key engine_phases_read_e2e_p99_ms)
    "read_e2e",
    # beneath pump() and sweep() (ISSUE 25), each stamped by the one
    # ``with`` that is also the profiler span in brackets: ``pop_block``
    # the coalescer building one dense block (``ra.pump.pop_block``),
    # ``wal_submit`` the serve thread handing a dispatch's aux to the
    # WAL shards (``ra.engine.wal_submit``), ``wal_readback`` a shard
    # worker's device-to-host pulls of one step (``ra.wal.readback``),
    # ``sweep_decode`` the listener's ring-byte gather and decode
    # (``ra.sweep.decode``, noted into the engine's accumulator) — and
    # two waits of a block, stamped with note(): ``staged_wait`` end of
    # staging to the start of the block's dispatch (both in one
    # submit), ``block_e2e`` pop to the harvest that retires it
    "pop_block", "wal_submit", "wal_readback", "sweep_decode",
    "staged_wait", "block_e2e",
    # ``read_staged_wait`` (ISSUE 35): a read staged by
    # ``submit_reads`` to the read lane's pop that takes it, one
    # sample a pop (the mean over the pop's rows), stamped with note()
    "read_staged_wait",
    # a write block's ``block_e2e`` split where it happens (ISSUE 37),
    # one sample of each a retired block, stamped with note() at the
    # retiring harvest so the three sum to its ``block_e2e``:
    # ``durable_wait`` pop to the instant the WAL's confirm horizon
    # covered the block's rows (the clock read of the bridge's confirm
    # stamp), ``confirm_carry`` that instant to the carrier's sample of
    # the horizon (the first dispatch whose sample covers the rows),
    # ``commit_observe`` that sample to the retire; ``pump`` one
    # ``IngressPlane.pump()`` (span ``ra.pump``)
    "durable_wait", "confirm_carry", "commit_observe", "pump",
)

#: ingress-plane counter fields (ra_tpu/ingress/, ISSUE 10): one dict
#: per IngressPlane, merged into the Observatory as the ``ingress``
#: source (flat ring keys ``ingress_<field>``).  ``submitted`` is every
#: row offered to submit(); ``accepted`` the subset that reached the
#: coalescer (placed — these and only these advance the at-most-once
#: seqno watermark); ``dup_dropped`` rows rejected by the per-session
#: dedup (a resend of an already-placed (session, seqno) — the proof
#: resends are at-most-once end-to-end); ``slow_signals`` admissions
#: past the soft credit (the generalized FifoClient "slow" verdict);
#: ``deferred`` rows parked by tenant-fairness admission at ladder
#: level >= 2; ``rejected`` rows refused at the hard credit (the
#: StopSending analogue); ``shed_rows`` rows dropped by coalescer ring
#: overflow (bounded queues shed, they never grow); ``blocks_built``
#: superstep blocks dispatched and ``block_rows`` the rows they
#: carried (rows/blocks = realized coalescing factor);
#: ``reconnects`` session epoch bumps; ``credits_released`` per-row
#: credit returns at block-commit granularity; ``flat_blocks`` the
#: blocks that left the host as the rows they carry (ISSUE 26; the
#: rest of ``blocks_built`` went dense) and ``flat_rows_padded`` the
#: padded rows put to the device for them (``block_rows`` over it is
#: the fill share of the flat path's buckets).  Hot lanes (ISSUE 27):
#: ``lane_capped_rows`` rows that stayed staged at a pop because their
#: lane had reached what it may put into one dispatch (the block's
#: window of ``superstep_k * max_step_cmds`` rows, the room in the
#: lane's ring; 0 on a uniform fleet).  The read lane's outcomes in
#: this group too (PR 32), for a reader of one snapshot: ``read_blocks``
#: read blocks popped, ``read_served_rows`` reads answered at a
#: certified watermark, ``read_refused_rows`` reads settled as refused
#: (shed at arrival on the device, or past the lease's and the
#: quorum's cover), ``read_zero_blocks`` dispatches that carried the
#: zero read block (ISSUE 35: some lane's batch out and no free lane
#: with a read staged); READ_FIELDS keeps the lane's full ledger.
#: ``slow_pumps`` (ISSUE 37) pumps that took over ``ingress.PUMP_SLOW_S``,
#: each also a ``pump.slow`` event.
INGRESS_FIELDS = (
    "submitted", "accepted", "dup_dropped", "slow_signals", "deferred",
    "rejected", "shed_rows", "blocks_built", "block_rows", "reconnects",
    "credits_released", "flat_blocks", "flat_rows_padded",
    "lane_capped_rows", "read_blocks", "read_served_rows",
    "read_refused_rows", "read_zero_blocks", "slow_pumps",
)

#: wire-plane counter fields (ra_tpu/wire/, ISSUE 12): one dict per
#: WireListener, the Observatory ``wire`` source (flat ring keys
#: ``wire_<field>``).  Pool lifecycle: ``conns_opened``/
#: ``conns_closed`` connection slots bound/released (socket accepts
#: AND loopback bulk connects), ``hello_reconnects`` re-binds of a
#: known connection key (the epoch-bump trigger).  Data plane:
#: ``bytes_recv`` raw bytes landed in the rings, ``sweeps`` vectorized
#: sweep passes, ``swept_rows`` DATA records decoded and submitted
#: (the wire twin of ingress ``submitted``), ``protocol_errors``
#: malformed frames/records (each closes its connection).  Feedback
#: plane: ``credit_rows``/``ack_rows`` verdict and watermark records
#: serialized back; ``credit_ok``/``credit_slow``/``credit_defer``/
#: ``credit_reject``/``credit_dup``/``credit_shed`` the credit-level
#: histogram — per-status verdict counts (ra_top renders these as the
#: wire panel's credit histogram).
WIRE_FIELDS = (
    "conns_opened", "conns_closed", "hello_reconnects", "bytes_recv",
    "sweeps", "swept_rows", "protocol_errors", "credit_rows",
    "ack_rows", "credit_ok", "credit_slow", "credit_defer",
    "credit_reject", "credit_dup", "credit_shed",
    # read plane (ISSUE 20): ``read_rows`` READ records decoded and
    # submitted by the vectorized sweep (the read twin of swept_rows),
    # ``read_reply_rows`` READ_REPLY records fanned back with their
    # certified watermark
    "read_rows", "read_reply_rows",
)

#: ingress read-lane counter fields (ra_tpu/ingress/, ISSUE 20): one
#: dict per IngressPlane read lane, the Observatory ``read`` source
#: (flat ring keys ``read_<field>``).  Admission: ``submitted`` read
#: rows offered, ``accepted`` the subset placed into the read
#: coalescer, ``shed`` rows shed by overload (the CreditLadder sheds
#: reads BEFORE it delays writes — any ladder level above green sheds),
#: ``rejected`` rows refused by coalescer ring overflow.  Dispatch:
#: ``blocks_built`` read superstep blocks dispatched and
#: ``block_rows`` the rows they carried.  Settlement (from the
#: device's cumulative serve/refuse watermarks): ``served`` reads
#: answered at a certified watermark, ``stale_refused`` reads the
#: device refused rather than serve stale (lease expired / quorum
#: lost / timeout — the oracle pins consistent reads to 0 stale
#: SERVES; refusals are the safe outcome), ``lease_served`` the
#: served-under-lease subset (lease coverage), ``replies_sent``
#: READ_REPLY rows fanned back to clients.
READ_FIELDS = (
    "submitted", "accepted", "shed", "rejected", "blocks_built",
    "block_rows", "served", "stale_refused", "lease_served",
    "replies_sent",
)

#: the on-device aggregation of TELEMETRY_FIELDS (lockstep's jitted
#: telemetry summary): scalar rollups plus the fixed-size lag histogram
#: and the lax.top_k offender slots.  ``stalled_lanes`` lanes at or
#: past the stall threshold; ``commit_lag_hist`` log2-bucket counts of
#: per-lane commit lag; ``top_lanes`` the K worst lane ids by
#: (stall, lag) offender score with their ``top_commit_lag``/
#: ``top_apply_lag``/``top_stall_steps`` gauges; ``committed_total``
#: cumulative committed commands (float32 — the per-window rate
#: substrate the Observatory ring derives throughput from).
TELEMETRY_SUMMARY_FIELDS = (
    "steps", "elections_requested", "elections_won", "leader_changes",
    "stalled_lanes", "commit_lag_max", "commit_lag_mean",
    "apply_lag_max", "apply_lag_mean", "leader_age_min",
    "commit_lag_hist", "top_lanes", "top_commit_lag", "top_apply_lag",
    "top_stall_steps", "committed_total",
    "read_served_total", "read_shed_total", "read_stale_total",
    "read_leased_total",
)

#: classic replication-batching health (ISSUE 13): the shape of
#: ``RaNode.classic_stats()`` — wired into the leader system's
#: Observatory as the ``classic`` source.  ``aer_batches_sent`` counts multi-entry
#: AppendEntries frames built by leaders hosted on the node and
#: ``aer_batch_entries`` the entries they carried (their ratio is the
#: realized AER batching factor); ``entries_per_batch_p50``/
#: ``entries_per_batch_p99``/``entries_per_batch_mean`` come from the
#: cores' bounded batch-size reservoirs; ``records_per_fsync`` — the
#: group-commit fan-in half of the pair — is Wal.stats()'s
#: amortization factor, stamped next to the AER numbers by the
#: embedding bench so one doc answers "how batched was replication,
#: end to end".
CLASSIC_FIELDS = (
    "aer_batches_sent", "aer_batch_entries", "entries_per_batch_p50",
    "entries_per_batch_p99", "entries_per_batch_mean",
    "records_per_fsync",
)

#: device-plane runtime observatory (ra_tpu/devicewatch.py, ISSUE 16):
#: one process-wide dict behind the ``WATCH`` singleton, the runtime
#: mirror of the jit-plane static gates (RA04/RA13/RA14 are proof-only
#: — these fields are the measurement).  Recompile sentinel:
#: ``compiles`` counts XLA compiles observed across every wrapped jit
#: entry point (lockstep step/superstep, telemetry summary — warm-up
#: compiles land here), ``recompiles`` the subset BEYOND the first per
#: wrapped callable (a retrace: steady-state MUST stay 0, the runtime
#: twin of RA13, and the ``steady_state_recompiles`` SLO objective),
#: ``compile_ms`` cumulative wall time of compiling calls.  Transfer
#: ledger (the measured number behind RA04's lint promise):
#: ``h2d_events``/``h2d_bytes`` host->device transfers (driver block
#: staging, mesh state sharding), ``d2h_events``/``d2h_bytes``
#: device->host readbacks (driver window readbacks, telemetry
#: harvests, WAL encode readbacks).  Memory watermarks (sampled on the
#: TelemetrySampler harvest tick — zero new syncs): ``live_buffers``/
#: ``live_bytes`` gauges of live device buffers at the last sample,
#: ``peak_live_bytes`` the high-water mark, ``buffers_freed``
#: cumulative buffer releases observed between samples (donation
#: effectiveness, the runtime twin of RA14), ``watermark_samples``
#: samples taken.
DEVICE_FIELDS = (
    "compiles", "recompiles", "compile_ms", "h2d_events", "h2d_bytes",
    "d2h_events", "d2h_bytes", "live_buffers", "live_bytes",
    "peak_live_bytes", "buffers_freed", "watermark_samples",
    # every backend compile of the process, whichever thread and
    # program (ISSUE 25): ``xla_compiles`` / ``xla_compile_ms`` from
    # jax.monitoring's backend-compile duration event.  The sentinel's
    # ``compiles`` above sees only the wrapped step callables
    "xla_compiles", "xla_compile_ms",
)

#: placement failover control plane (ra_tpu/placement/, ISSUE 17):
#: the EngineSupervisor's counter group.  Detector tier:
#: ``heartbeats`` probe responses heard (delayed arrivals count when
#: they land), ``suspects``/``downs`` verdict escalations (a suspect
#: that recovers inside the hysteresis window never becomes a down —
#: the slow-fsync guard), ``recoveries`` suspect→up de-escalations.
#: Re-placement tier: ``migrations`` lane-range re-placements
#: committed through the placement table, ``migrate_retries`` extra
#: attempts the bounded commit loop needed beyond the first,
#: ``giveups`` bounded loops that exhausted their deadline (each also
#: emits ``placement.giveup``), ``adopts`` victim engines restored
#: into a survivor's lane space, ``rehomed_sessions`` sessions
#: re-bound to a new home (epoch bump + slot claim).  Cross-host tier
#: (ISSUE 19): ``stale_probe_drops`` probe replies discarded because
#: the slot was re-provisioned to a newer generation while the probe
#: was in flight (each also emits ``placement.stale_probe``), and
#: ``rehome_hints`` frames refused by a serving listener with a typed
#: REHOME hint because the lane's home moved (each refusal batch also
#: emits ``placement.rehome_hint``).
PLACEMENT_FIELDS = (
    "heartbeats", "suspects", "downs", "recoveries", "migrations",
    "migrate_retries", "giveups", "adopts", "rehomed_sessions",
    "stale_probe_drops", "rehome_hints",
)

#: the complete field-group registry (rule RA05): every counter-field
#: tuple in this module MUST be listed here, covered by the registry
#: parity test (tests/test_telemetry.py) and documented in
#: docs/OBSERVABILITY.md — tools/lint.py statically enforces both.
#: Its event-plane sibling is ra_tpu/blackbox.py's EVENT_REGISTRY
#: (rule RA06): counters answer "how many", flight-recorder events
#: answer "which one, when" — one registry discipline for both.
FIELD_REGISTRY = {
    "log": LOG_FIELDS,
    "server": SERVER_FIELDS,
    "metric": METRIC_FIELDS,
    "rpc": RPC_FIELDS,
    "wal": WAL_FIELDS,
    "engine_wal": ENGINE_WAL_FIELDS,
    "engine_pipeline": ENGINE_PIPELINE_FIELDS,
    "segment_writer": SEGMENT_WRITER_FIELDS,
    "disk_faults": DISK_FAULT_FIELDS,
    "telemetry": TELEMETRY_FIELDS,
    "telemetry_summary": TELEMETRY_SUMMARY_FIELDS,
    "phase": PHASE_FIELDS,
    "ingress": INGRESS_FIELDS,
    "read": READ_FIELDS,
    "wire": WIRE_FIELDS,
    "classic": CLASSIC_FIELDS,
    "device": DEVICE_FIELDS,
    "placement": PLACEMENT_FIELDS,
}


class Counters:
    """Named counter groups (the seshat role)."""

    def __init__(self) -> None:
        self._groups: dict[str, dict] = {}
        self._lock = threading.Lock()
        #: increments addressed to an unknown group or field.  The old
        #: behaviour silently dropped them — a typo'd field name lost
        #: its events with no trace; now every drop is itself counted
        #: (the seshat-style self-metric; asserted 0 under the normal
        #: workloads in tests).
        self.dropped = 0

    def new(self, name: str, fields=SERVER_FIELDS) -> dict:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                g = {f: 0 for f in fields}
                self._groups[name] = g
            return g

    def incr(self, name: str, field: str, n: int = 1) -> None:
        g = self._groups.get(name)
        if g is None or field not in g:
            self.dropped += 1
            return
        g[field] += n

    def self_metrics(self) -> dict:
        """The registry's own health: ``telemetry_dropped`` counts
        increments lost to unknown group/field names (MUST stay 0 — a
        nonzero value means an instrumentation site and the field
        registry disagree)."""
        return {"telemetry_dropped": self.dropped}

    def fetch(self, name: str) -> Optional[dict]:
        g = self._groups.get(name)
        return dict(g) if g is not None else None

    def delete(self, name: str) -> None:
        with self._lock:
            self._groups.pop(name, None)

    def overview(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._groups.items()}


class Leaderboard:
    """cluster name -> (leader, members); written on leader change, read
    lock-free by clients (ra_leaderboard.erl:23-34)."""

    def __init__(self) -> None:
        self._tab: dict[str, tuple] = {}

    def record(self, cluster_name: str, leader, members) -> None:
        self._tab[cluster_name] = (leader, tuple(members))

    def lookup_leader(self, cluster_name: str):
        got = self._tab.get(cluster_name)
        return got[0] if got else None

    def lookup_members(self, cluster_name: str):
        got = self._tab.get(cluster_name)
        return got[1] if got else None

    def overview(self) -> dict:
        return dict(self._tab)
