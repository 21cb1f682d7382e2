"""Durability bridge between the lockstep lane engine and the WAL plane.

This closes the loop the engine docstring describes: in durable mode a
step's accepted entries are compacted ON DEVICE to a dense row buffer
(a prefix-sum gather over the per-lane accept counts — the readback
carries only bytes that will hit disk), pulled off-device by per-shard
encode workers (double-buffered: the readback of step N overlaps the
dispatch of step N+1), encoded as one WAL record per step per shard,
and fed through S independent :class:`ra_tpu.log.wal.Wal` shards — each
with its own file, writer thread and fsync, running the adaptive
group-commit policy (one fdatasync per group).  Every shard's fsync
confirm comes back as a slice of the ``confirm_upto`` input of a later
step, so ``last_written`` — and therefore the commit quorum — advances
only over entries that are really on disk.  This is the engine-scale
version of the reference's written-event protocol: an entry only counts
toward the commit median after write(2)+fsync
(/root/reference/src/ra_log_wal.erl:753-800), with the single fan-in
writer multiplied across cores — the fan-in batching axis of SURVEY.md
§2.4 extended the way partitioned-serialization-point Raft variants
split their log pipeline.

Record format (one WAL payload per step per shard, uid ``__engine__``):

  RTB1:  magic(4) | n_lanes:u32 | C:u32 | dtype:8s | n_flat:u32
  RTB2:  magic(4) | n_lanes:u32 | C:u32 | dtype:8s | n_flat:u32 | lane_lo:u32
  hi:    i32[N]   leader tail after the step (per lane of the slice)
  n_app: i32[N]   entries appended this step (accepted cmds + noop)
  n_acc: i32[N]   how many of those came from the host batch
  flat:  [n_flat, C] the accepted host rows, lane-major

RTB1 is the lane_lo=0 form — byte-identical to the pre-sharding format,
which is what ``wal_shards=1`` emits (the default-compatible path).
Shards at a nonzero lane offset emit RTB2; blocks therefore fully
self-describe their lane slice and recovery can merge ANY mix of shard
layouts found on disk (a shard-count change needs no migration step).

``hi - n_app`` is the step's append base; a base below the running tail
records an election truncation (a deposed leader's unconfirmed suffix),
exactly the overwrite-invalidates-higher-indexes rule of WAL recovery
(/root/reference/src/ra_log_wal.erl:871-955) at step granularity.
Entries between ``n_acc`` and ``n_app`` are the term-opening noop
(all-zero payload, the machine-noop encoding).

Recovery (:func:`open_engine`) restores the latest checkpoint, scans
every surviving WAL shard (plus foreign-layout leftovers), stitches the
per-slice pieces into full-lane step blocks — lanes whose shard crashed
before recording a step carry their tail forward, which is safe because
the merged per-lane confirm rule means nothing beyond a shard's last
record was ever reported committed — resolves truncations, and replays
through the jitted step.  A crash (kill -9) therefore loses nothing
that was ever reported committed.

Checkpointing (:meth:`EngineDurability.checkpoint`) quiesces all
shards, snapshots the full lane state via ``engine.save`` (atomic
.npz), and prunes WAL files whose records the checkpoint covers — the
release_cursor/snapshot-truncation role of ra_snapshot.erl at the
engine scale.
"""
from __future__ import annotations

import bisect
import collections
import json
import logging
import os
import struct
import threading
import time
from typing import Optional

import numpy as np

from .. import devicewatch, trace
from ..blackbox import RECORDER, record, stamp_recovery
from ..log import faults
from ..log.wal import Wal, WalDown, scan_wal_file
from ..metrics import ENGINE_WAL_FIELDS

UID = "__engine__"

#: shard-WAL supervisor restart intensity: (max restarts, window s) —
#: the engine twin of system.WAL_RESTART_INTENSITY; beyond it the
#: supervisor backs off for the window instead of hot-looping against a
#: dead disk
SHARD_RESTART_INTENSITY = (10, 5.0)
MAGIC = b"RTB1"
MAGIC2 = b"RTB2"          # RTB1 + lane_lo:u32 (sharded lane slice)
_BLK = struct.Struct("<4sII8sI")
_BLK2 = struct.Struct("<4sII8sII")


def _is_multidevice(arr) -> bool:
    """True when a step-aux leaf lives sharded across >1 device (the
    engine state was placed on a mesh).  Host numpy (recovery replay)
    and single-device jax arrays read False.  Pure metadata — no
    device sync."""
    try:
        return len(arr.sharding.device_set) > 1
    except AttributeError:
        return False


def encode_block_flat(hi: np.ndarray, n_app: np.ndarray, n_acc: np.ndarray,
                      flat: np.ndarray, lane_lo: int = 0) -> bytes:
    """Encode one step's append outcome for a lane slice from the
    already-compacted accepted rows (lane-major).  ``lane_lo == 0``
    emits the legacy RTB1 bytes; a sharded slice carries its offset."""
    n = hi.shape[0]
    flat = np.ascontiguousarray(flat)
    if flat.ndim != 2:
        flat = flat.reshape(flat.shape[0], -1)
    c = flat.shape[1]
    dt = np.dtype(flat.dtype).str.encode().ljust(8, b"\x00")
    if lane_lo:
        head = _BLK2.pack(MAGIC2, n, c, dt, flat.shape[0], lane_lo)
    else:
        head = _BLK.pack(MAGIC, n, c, dt, flat.shape[0])
    return b"".join((head,
                     np.ascontiguousarray(hi, "<i4").tobytes(),
                     np.ascontiguousarray(n_app, "<i4").tobytes(),
                     np.ascontiguousarray(n_acc, "<i4").tobytes(),
                     flat.tobytes()))


def encode_block(hi: np.ndarray, n_app: np.ndarray, n_acc: np.ndarray,
                 payload_host: np.ndarray) -> bytes:
    """Legacy host-side path: mask the accepted rows out of the full
    [N, K, C] batch, then encode.  Byte-identical to what the device
    compaction path produces — kept for tests and offline tooling."""
    _N, K, _C = payload_host.shape
    mask = np.arange(K)[None, :] < n_acc[:, None]
    return encode_block_flat(hi, n_app, n_acc, payload_host[mask])


_ROWS_WINDOW = None


def _rows_window(rows, start: int, size: int):
    """``rows[start:start + size]`` of a device array, ``start`` an
    argument and ``size`` static: the jitted ``ra_rows_window``."""
    global _ROWS_WINDOW
    if _ROWS_WINDOW is None:
        import jax

        def ra_rows_window(rows, start, size):
            return jax.lax.dynamic_slice_in_dim(rows, start, size)  # ra12-ok: the single-device readback only, in place of the eager slice it had; over a mesh _process pulls the aux whole and slices numpy
        _ROWS_WINDOW = jax.jit(ra_rows_window, static_argnums=2)  # ra12-ok: as the line above: never reached with a sharded array
    return _ROWS_WINDOW(rows, start, size)


def _pull_rows(rows, r0: int, r1: int) -> np.ndarray:
    """Rows ``[r0, r1)`` of a device array, on the host, pulled through
    the least power-of-two window that holds them (moved back where it
    would run past the end) and trimmed here."""
    size = min(rows.shape[0], 1 << max(r1 - r0 - 1, 0).bit_length())
    start = min(r0, rows.shape[0] - size)
    return np.asarray(_rows_window(rows, start, size))[
        r0 - start:r1 - start]


def _ragged_arange(counts: np.ndarray) -> np.ndarray:
    """[0..c0) ++ [0..c1) ++ ... as one array."""
    starts = np.cumsum(counts) - counts
    return np.arange(int(counts.sum())) - np.repeat(starts, counts)


class _PieceRows:
    """A decoded block's accepted rows as the WAL holds them: lane
    by lane, ``n_acc`` a lane, a view of the record's own bytes.
    ``shape`` is that of the dense ``[N, Kmax, C]`` form, which
    :meth:`take` builds for the lanes asked for and no others: a WAL
    written under skew has a few lanes a step that are ``Kmax`` deep
    and thousands that are empty, and recovery holds every step's
    pieces at once."""

    def __init__(self, n_app: np.ndarray, n_acc: np.ndarray,
                 flat: np.ndarray) -> None:
        self.n_acc, self.flat = n_acc.astype(np.int64), flat
        self.shape = (len(n_app), int(n_app.max()) if len(n_app) else 0,
                      flat.shape[1])
        self.dtype = flat.dtype

    def take(self, lanes: np.ndarray) -> np.ndarray:
        """Dense ``[len(lanes), Kmax, C]`` rows of the piece's lanes
        ``lanes`` (indexes into the piece), noop rows zero-filled."""
        out = np.zeros((len(lanes),) + self.shape[1:], self.dtype)
        counts = self.n_acc[lanes]
        if counts.any():
            first = (np.cumsum(self.n_acc) - self.n_acc)[lanes]
            within = _ragged_arange(counts)
            out[np.repeat(np.arange(len(lanes)), counts), within] = \
                self.flat[np.repeat(first, counts) + within]
        return out


def decode_block(data: bytes, dense: bool = True):
    """Inverse of the encoders -> (lane_lo, hi, n_app, n_acc, rows)
    where rows is [N, Kmax, C] for the block's lane slice with noop
    rows already zero-filled; with ``dense=False`` the
    :class:`_PieceRows` that builds it on demand."""
    magic = data[:4]
    if magic == MAGIC2:
        _m, n, c, dt, n_flat, lane_lo = _BLK2.unpack_from(data, 0)
        off = _BLK2.size
    elif magic == MAGIC:
        _m, n, c, dt, n_flat = _BLK.unpack_from(data, 0)
        lane_lo = 0
        off = _BLK.size
    else:
        raise ValueError("bad engine block magic")
    dtype = np.dtype(dt.rstrip(b"\x00").decode())
    hi = np.frombuffer(data, "<i4", n, off).astype(np.int32)
    off += 4 * n
    n_app = np.frombuffer(data, "<i4", n, off).astype(np.int32)
    off += 4 * n
    n_acc = np.frombuffer(data, "<i4", n, off).astype(np.int32)
    off += 4 * n
    flat = np.frombuffer(data, dtype, n_flat * c, off).reshape(n_flat, c)
    rows = _PieceRows(n_app, n_acc, flat)
    if dense:
        rows = rows.take(np.arange(n))
    return lane_lo, hi, n_app, n_acc, rows


class _WalFileRetirer:
    """Duck-typed segment_writer for an engine WAL shard: instead of
    flushing per-server memtables to segments, rolled WAL files are kept
    until a checkpoint covers their step range, then unlinked — the
    engine's .npz checkpoint plays the segment role (the WAL-file
    deletion barrier of ra_log_segment_writer.erl:129-201)."""

    def __init__(self) -> None:
        self._files: list = []  # (hi_step, path)
        self._lock = threading.Lock()

    def accept_ranges(self, ranges: dict, wal_path: str) -> None:
        hi = max(r[1] for r in ranges.values())
        with self._lock:
            self._files.append((hi, wal_path))

    def retire(self, uids: list, wal_files: list) -> None:
        # recovered files: every record in them predates any future
        # checkpoint, so hi=0 (pruned by the first checkpoint taken)
        with self._lock:
            for path in wal_files:
                self._files.append((0, path))

    def mark_deleted(self, uid: str) -> None:  # pragma: no cover
        pass

    def prune(self, ckpt_step: int) -> None:
        with self._lock:
            keep = []
            for hi, path in self._files:
                if hi <= ckpt_step:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                else:
                    keep.append((hi, path))
            self._files = keep


class _WalShard:
    """One WAL shard: a contiguous lane slice [lo, hi) with its own
    file, writer thread and fsync, plus an encode worker that pulls the
    device-compacted aux of queued steps to the host, encodes the WAL
    block (CRC included) off the engine dispatch thread, and hands it to
    this shard's fan-in Wal — so step N+1's XLA dispatch overlaps step
    N's encode+write+fsync end to end."""

    def __init__(self, bridge, idx: int, lo: int, hi: int,
                 shard_dir: str, wal_kwargs: dict) -> None:
        self.idx = idx
        self.lo = lo
        self.hi = hi
        self.bridge = bridge  # ra-type: EngineDurability
        self.error: Optional[BaseException] = None
        self.retirer = _WalFileRetirer()
        self.wal = Wal(shard_dir, segment_writer=self.retirer,
                       **wal_kwargs)
        self.confirmed_step = 0
        self.confirm_upto = np.zeros((hi - lo,), np.int32)
        self._appended: dict = {}   # step -> hi np[N_s] (until confirmed)
        self._blocks: dict = {}     # step -> bytes      (until confirmed)
        self._bases: dict = {}      # step -> base np[N_s]
        self._jobs: collections.deque = collections.deque()
        self.unprocessed = 0
        self._resend_above: Optional[int] = None
        self._generation = self.wal.generation
        self._stop = False
        self.wal.register(UID, self._notify)
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"ra-engine-wal-s{idx}")
        self._thread.start()

    # -- WAL confirm path (called from this shard's WAL batch thread) ------

    def _notify(self, uid: str, lo: Optional[int], hi: int,
                term: int) -> None:
        cond = self.bridge._cond
        with cond:
            if lo is None:
                # out-of-sequence signal: resend everything above hi on
                # the encode worker (ra_log_wal.erl:457-481)
                self._resend_above = hi
                cond.notify_all()
                return
            if hi <= self.confirmed_step:
                return
            self.confirmed_step = hi
            # (lane, submit_index)-keyed durable hop: the shard's step
            # horizon advanced — ra_trace joins this against
            # engine.submit by step range (docs/INTERNALS.md §10)
            record("engine.confirm", shard=self.idx, step=hi)
            arr = self._appended.get(hi)
            if arr is not None:
                # exact durable tail as of step hi — then re-apply the
                # bases of still-unconfirmed steps: an unconfirmed
                # truncation means indexes above its base are occupied
                # by entries not yet on disk
                confirm = arr.copy()
                for s, base in self._bases.items():
                    if s > hi:
                        confirm = np.minimum(confirm, base)
                self.confirm_upto = confirm
            for s in [s for s in self._appended if s <= hi]:
                del self._appended[s]
                self._blocks.pop(s, None)
                self._bases.pop(s, None)
            # stamped once confirm_upto holds step hi, under the same
            # lock: a dispatch whose sample of the merged horizon
            # (``confirm_sample``) comes after the stamp sees the step
            self.bridge._note_confirmed_steps(self.idx, hi)
            cond.notify_all()

    # -- encode worker ------------------------------------------------------

    def _run(self) -> None:
        cond = self.bridge._cond
        while True:
            with cond:
                cond.wait_for(
                    lambda: self._stop or self._jobs
                    or self._resend_above is not None,
                    timeout=0.25)
                if self._stop:
                    return
                job = self._jobs.popleft() if self._jobs else None
            self._maybe_resend()
            if job is None:
                continue
            step, aux, t_enq = job
            # queue_wait phase stamp: how long the submitted step sat
            # in this shard's encode queue before a worker picked it up
            self.bridge.phases.note("queue_wait",
                                    time.monotonic() - t_enq)
            try:
                self._process(step, aux)
            except Exception as exc:  # noqa: BLE001 — surfaced to callers
                record("engine.crash", shard=self.idx,
                       error=repr(exc)[:200])
                RECORDER.dump("engine_shard_error",
                              what=repr(exc)[:200],
                              where=f"shard{self.idx}",
                              data_dir=self.bridge.dir)
                with cond:
                    self.error = exc
            finally:
                with cond:
                    self.unprocessed -= 1
                    cond.notify_all()

    def _process(self, step: int, aux: dict) -> None:
        lo, hi_l = self.lo, self.hi
        phases = self.bridge.phases
        # wal_encode phase: readback pull + encode + CRC for one step's
        # block on this shard (runs off the dispatch thread)
        with trace.phase_span("ra.wal.encode", phases, "wal_encode", "wal",
                              shard=self.idx, step=step):
            # wal_readback phase: the device-to-host pulls alone, where a
            # data-dependent slice of a device array compiles its program
            # (devicewatch's xla_compiles)
            with trace.phase_span("ra.wal.readback", phases, "wal_readback",
                                  "wal"):
                if aux.get("__mesh__"):
                    # sharded-engine path (ISSUE 11): a worker thread
                    # must NOT launch device computations — slicing a
                    # sharded array compiles+enqueues a multi-device
                    # gather, and concurrent enqueues from encode
                    # workers deadlock against the dispatch thread's
                    # pjit.  The bridge materializes the step's aux to
                    # host ONCE (pure d2h transfers, safe off-thread);
                    # slicing happens in numpy.
                    host = self.bridge._host_aux(aux)
                    hi = host["appended_hi"][lo:hi_l]
                    n_app = host["n_app"][lo:hi_l]
                    n_acc = host["n_acc"][lo:hi_l]
                    full_csum = host["row_csum"]
                    # csum: this shard's logical slice, kept for the
                    # readback_bytes accounting below (the wire moved
                    # the FULL cumsum once per step via _host_aux)
                    csum = full_csum[max(0, lo - 1):hi_l]
                    r0 = int(full_csum[lo - 1]) if lo else 0
                    r1 = int(full_csum[hi_l - 1])
                    flat = host["flat_rows"][r0:r1]
                else:
                    # documented readback point: this worker runs one
                    # step behind dispatch, so the device values are
                    # ready (or the pull overlaps the next dispatch) —
                    # RA02's allowlisted home
                    hi = np.asarray(
                        aux["appended_hi"][lo:hi_l]).astype(np.int32)
                    n_app = np.asarray(
                        aux["n_app"][lo:hi_l]).astype(np.int32)
                    n_acc = np.asarray(
                        aux["n_acc"][lo:hi_l]).astype(np.int32)
                    # only this slice's row-offset boundary values are
                    # needed — pulling the full-N cumsum on every shard
                    # would duplicate the transfer S times
                    csum = np.asarray(
                        aux["row_csum"][max(0, lo - 1):hi_l])
                    r0 = int(csum[0]) if lo else 0
                    r1 = int(csum[-1])
                    # rows [r0, r1) through a window whose length is a
                    # power of two and whose start is data: one
                    # compiled program a length, where a slice at
                    # bounds read from the data compiled one for every
                    # new pair of bounds, inside the serving window
                    # and for most steps once a few hot lanes made a
                    # shard's row count wander (ISSUE 27)
                    flat = _pull_rows(aux["flat_rows"], r0, r1)
            # encode phase (ISSUE 18): just the block encode+CRC, the
            # lane plane's contribution to encode_share_pct (the
            # classic plane's half lands in DurableLog._put_batch)
            with trace.phase_span("ra.wal.encode_block", phases, "encode",
                                  "wal"):
                blk = encode_block_flat(hi, n_app, n_acc, flat,
                                        lane_lo=lo)
        n_s = hi_l - lo
        k = aux["flat_rows"].shape[0] // max(1, self.bridge.n_lanes)
        item = flat.dtype.itemsize * (flat.shape[-1] if flat.ndim > 1
                                      else 1)
        base = hi - n_app
        cond = self.bridge._cond
        with cond:
            ctr = self.bridge.counters
            ctr["readback_bytes"] += (hi.nbytes + n_app.nbytes +
                                      n_acc.nbytes + csum.nbytes +
                                      flat.nbytes)
            # what the pre-compaction full-ring readback moved for the
            # same step slice: the whole [N_s, K, C] host batch
            ctr["readback_bytes_full"] += (hi.nbytes + n_app.nbytes +
                                           n_acc.nbytes + n_s * k * item)
            ctr["encoded_blocks"] += 1
            ctr["encoded_bytes"] += len(blk)
            # transfer-ledger mirror (ISSUE 16): the WAL encode pull is
            # the third d2h budget line of a durable dispatch loop —
            # same bytes as readback_bytes, attributed per site so the
            # device plane's ledger is complete (host int increments
            # only; RA12: no device work on this worker thread)
            devicewatch.record_d2h(
                "wal_readback",
                hi.nbytes + n_app.nbytes + n_acc.nbytes +
                csum.nbytes + flat.nbytes)
            self._appended[step] = hi
            self._blocks[step] = blk
            self._bases[step] = base
            # an election truncation reuses indexes: the durable horizon
            # drops to the step's base until this block itself confirms
            self.confirm_upto = np.minimum(self.confirm_upto, base)
        # sync with any new WAL incarnation BEFORE submitting: a fresh
        # writer accepts any first step, so writing this block ahead of
        # the unconfirmed backlog would leave a step gap in the new file
        # if the WAL dies again before the backlog resends (recovery
        # also guards against the remaining race — _assemble_blocks
        # drops gapped pieces)
        self._maybe_resend()
        try:
            self.wal.write(UID, step, 1, blk)
        except WalDown:
            # block is recorded; the resend path replays it once the
            # supervisor restarts this shard's WAL
            pass

    def _maybe_resend(self) -> None:
        """After a WAL crash+restart (or an out-of-sequence signal),
        resend every unconfirmed block above the shard's durable horizon
        (the resend_from protocol, ra_log.erl:778-793)."""
        cond = self.bridge._cond
        resend_from = None
        with cond:
            if self._resend_above is not None:
                resend_from = self._resend_above
                self._resend_above = None
        if self.wal.generation != self._generation and self.wal.alive:
            self._generation = self.wal.generation
            with cond:
                resend_from = self.confirmed_step if resend_from is None \
                    else min(resend_from, self.confirmed_step)
        if resend_from is None:
            return
        with cond:
            pending = sorted((s, b) for s, b in self._blocks.items()
                             if s > resend_from)
        for s, b in pending:
            try:
                self.wal.write(UID, s, 1, b)
            except WalDown:
                return

    def stop(self) -> None:
        with self.bridge._cond:
            self._stop = True
            self.bridge._cond.notify_all()
        self._thread.join(timeout=5)


class EngineDurability:
    """Host-side bridge: owns the engine's sharded WAL plane (S lane
    shards, each with its own file/writer/fsync and encode worker) and
    the merged confirm feedback arrays."""

    def __init__(self, data_dir: str, n_lanes: int, *, sync_mode: int = 1,
                 write_strategy: str = "default", max_pending: int = 8,
                 wal_max_size: int = 256 * 1024 * 1024,
                 wal_shards: int = 1,
                 wal_batch_bytes: int = 4 * 1024 * 1024,
                 wal_batch_interval_ms: Optional[float] = None,
                 wal_supervise: bool = True) -> None:
        os.makedirs(data_dir, exist_ok=True)
        if not 1 <= wal_shards <= n_lanes:
            raise ValueError(
                f"wal_shards must be in [1, n_lanes]; got {wal_shards}")
        self.dir = data_dir
        self.n_lanes = n_lanes
        self.max_pending = max_pending
        self.wal_shards = wal_shards
        if wal_batch_interval_ms is None:
            # default: no wait.  Group commit still emerges under load
            # (the greedy drain batches every record queued behind the
            # backpressure window); an explicit interval only pays off
            # when the caller KNOWS records arrive faster than fsyncs
            # complete — on boxes with slow/serializing fsync a forced
            # wait just adds a per-step confirm-latency tax.
            wal_batch_interval_ms = 0.0
        self._cond = threading.Condition()
        #: serializes the once-per-step host materialization of mesh
        #: aux (see _host_aux) — NOT self._cond: a d2h transfer can
        #: take milliseconds and must never block the confirm path
        self._readback_lock = threading.Lock()
        self.counters: dict = {f: 0 for f in ENGINE_WAL_FIELDS}
        self.step_seq = 0
        # phase-resolved latency attribution (ISSUE 9): one accumulator
        # for the whole durable plane — the engine adopts it on attach,
        # every WAL shard feeds its fsync/confirm stamps into it, and
        # the bridge stamps queue/encode/e2e edges itself
        from ..telemetry import PhaseStats
        self.phases = PhaseStats()
        #: step -> monotonic submit stamp; popped when the MERGED
        #: confirm horizon covers the step (the commit_e2e phase)
        self._submit_ts: dict = {}
        #: per shard, ([step], [monotonic]): each advance of its
        #: confirm horizon and when it was published (``confirmed_at``,
        #: the durable instant of a block's rows; ISSUE 37)
        self._confirmed_log = [([], []) for _ in range(wal_shards)]
        wal_kwargs = dict(sync_mode=sync_mode,
                          write_strategy=write_strategy,
                          max_size=wal_max_size,
                          max_batch_bytes=wal_batch_bytes,
                          max_batch_interval_ms=wal_batch_interval_ms,
                          phase_stats=self.phases,
                          # every shard's post-mortem bundles land at
                          # the BRIDGE's data dir, not one per shard
                          blackbox_dir=data_dir)
        bounds = [round(i * n_lanes / wal_shards)
                  for i in range(wal_shards + 1)]
        self._shards: list = []
        own_dirs = set()
        for i in range(wal_shards):
            sdir = data_dir if wal_shards == 1 else \
                os.path.join(data_dir, f"shard{i:02d}")
            own_dirs.add(os.path.abspath(os.path.join(sdir, "wal")))
            self._shards.append(
                _WalShard(self, i, bounds[i], bounds[i + 1], sdir,
                          wal_kwargs))
        # foreign-layout recovery: wal dirs left by a run with a
        # different shard count are scanned read-only here and their
        # files retired at the first checkpoint — blocks self-describe
        # their lane slice, so a shard-count change needs no migration.
        # One table PER DIRECTORY: different shards reuse the same step
        # index for different lane slices, so merging them into one
        # idx-keyed table would trip the overwrite-dedup rule across
        # slices and silently drop whole shards.
        self._legacy_tables: list = []
        self._legacy_files: list = []
        for wdir in self._discover_wal_dirs(data_dir):
            if os.path.abspath(wdir) in own_dirs:
                continue
            tables: dict = {}
            for fname in sorted(os.listdir(wdir)):
                if not fname.endswith(".wal"):
                    continue
                path = os.path.join(wdir, fname)
                try:
                    scan_wal_file(path, tables)
                except Exception:
                    logging.getLogger("ra_tpu").warning(
                        "wal recovery: truncated/corrupt tail in %s",
                        path)
                self._legacy_files.append(path)
            self._legacy_tables.append(tables)
        # per-shard WAL supervisor (the ra_log_wal_sup role for the
        # sharded plane): a dead shard batch thread is restarted under
        # an intensity window, the shard worker detects the generation
        # bump and resends its unconfirmed blocks — the merged confirm
        # vector never advanced past them, so nothing reported committed
        # depends on the crashed incarnation (disabled by tests that
        # assert raw WalDown freeze behaviour)
        # post-mortem bundle sources: per-shard durable watermarks +
        # the durability config (last engine wins the shared names; a
        # closed bridge unhooks its own, see close())
        self._bb_config = {
            "data_dir": data_dir, "n_lanes": n_lanes,
            "wal_shards": wal_shards, "sync_mode": sync_mode,
            "write_strategy": write_strategy,
            "max_pending": max_pending,
            "wal_batch_bytes": wal_batch_bytes,
            "wal_batch_interval_ms": wal_batch_interval_ms,
            "wal_supervise": wal_supervise,
        }
        self._bb_watermarks = self._watermark_source
        self._bb_config_src = lambda: self._bb_config
        RECORDER.add_source("engine_wal_watermarks", self._bb_watermarks)
        RECORDER.add_source("engine_wal_config", self._bb_config_src)
        self._sup_stop = threading.Event()
        self._shard_restarts: collections.deque = collections.deque()
        self._sup_thread: Optional[threading.Thread] = None
        if wal_supervise:
            self._sup_thread = threading.Thread(
                target=self._supervise_shards, daemon=True,
                name="ra-engine-wal-sup")
            self._sup_thread.start()

    def _supervise_shards(self) -> None:
        max_r, period = SHARD_RESTART_INTENSITY
        log = logging.getLogger("ra_tpu")
        while not self._sup_stop.wait(0.02):
            for sh in self._shards:
                wal = sh.wal
                if wal._stop or wal.alive:
                    continue
                now = time.monotonic()
                while self._shard_restarts and \
                        now - self._shard_restarts[0] > period:
                    self._shard_restarts.popleft()
                if len(self._shard_restarts) >= max_r:
                    log.error("engine wal supervisor: restart intensity "
                              "exceeded (%d in %.0fs); backing off",
                              max_r, period)
                    record("sup.giveup", plane="engine_wal",
                           shard=sh.idx)
                    RECORDER.dump(
                        "engine_wal_supervisor_giveup",
                        what=f"shard restart intensity exceeded "
                             f"({max_r} in {period:.0f}s)",
                        where=f"shard{sh.idx}", data_dir=self.dir)
                    if self._sup_stop.wait(period):
                        return
                    continue
                self._shard_restarts.append(now)
                log.warning("engine wal supervisor: restarting dead "
                            "WAL shard %d", sh.idx)
                try:
                    wal.restart()
                    record("sup.restart", plane="engine_wal",
                           shard=sh.idx)
                except Exception:
                    log.exception("engine wal supervisor: restart of "
                                  "shard %d failed; will retry", sh.idx)
                    continue
                with self._cond:
                    # wake the shard worker: _maybe_resend sees the
                    # generation bump and replays unconfirmed blocks
                    self._cond.notify_all()

    @staticmethod
    def _discover_wal_dirs(data_dir: str) -> list:
        dirs = []
        top = os.path.join(data_dir, "wal")
        if os.path.isdir(top):
            dirs.append(top)
        try:
            names = sorted(os.listdir(data_dir))
        except OSError:
            names = []
        for name in names:
            w = os.path.join(data_dir, name, "wal")
            if name.startswith("shard") and os.path.isdir(w):
                dirs.append(w)
        return dirs

    # -- compat surface -----------------------------------------------------

    @property
    def wal(self) -> Wal:
        """The first shard's WAL — the whole plane when ``wal_shards=1``
        (the surface the single-shard tests drive kill/restart/flush
        through)."""
        return self._shards[0].wal

    @property
    def wals(self) -> list:
        return [sh.wal for sh in self._shards]

    @property
    def confirm_upto(self) -> np.ndarray:
        """Merged per-lane durable horizon across shards."""
        if len(self._shards) == 1:
            return self._shards[0].confirm_upto
        with self._cond:
            return np.concatenate(
                [sh.confirm_upto for sh in self._shards])

    @property
    def confirmed_step(self) -> int:
        return min(sh.confirmed_step for sh in self._shards)

    def seed(self, prev_hi: np.ndarray, step_seq: int) -> None:
        """Set the post-recovery baseline: everything up to ``prev_hi``
        is durable and recorded through ``step_seq``."""
        prev = prev_hi.astype(np.int32)
        with self._cond:
            self.step_seq = step_seq
            self._submit_ts.clear()  # replay steps are not e2e samples
            for sh in self._shards:
                sh.confirm_upto = prev[sh.lo:sh.hi].copy()
                sh.confirmed_step = step_seq

    # -- phase attribution / live tunables ---------------------------------

    def _note_confirmed_steps(self, shard: int, hi: int) -> None:
        """Log shard ``shard``'s horizon reaching step ``hi``, then pop
        the submit stamps the MERGED confirm horizon now covers and
        record their commit_e2e samples (a step is end-to-end durable
        when EVERY shard's horizon covers it, the merged confirm rule
        the commit quorum gates on).  Called from the shard's WAL
        notify path with the bridge cond held (an RLock), after its
        ``confirm_upto`` took the step: one clock read for both."""
        with self._cond:
            now = time.monotonic()
            steps, ts = self._confirmed_log[shard]
            steps.append(hi)
            ts.append(now)
            if len(steps) > 4096:       # bounded like _submit_ts
                del steps[:2048], ts[:2048]
            if not self._submit_ts:
                return
            m = min(sh.confirmed_step for sh in self._shards)
            for s in [s for s in self._submit_ts if s <= m]:
                self.phases.note("commit_e2e",
                                 now - self._submit_ts.pop(s))
            # a dead shard freezes the merged horizon: stamps would
            # otherwise pile up for the rest of the process — bound the
            # table; dropped stamps just lose samples, never accounting
            while len(self._submit_ts) > 4096:
                self._submit_ts.pop(min(self._submit_ts))

    def confirm_sample(self) -> tuple:
        """What a dispatch feeds its step: ``(confirm_upto, covered,
        t)``, the merged per-lane horizon, the step each shard's part
        of it covers and ``time.monotonic()``, taken together under
        the cond, so a step logged by ``_note_confirmed_steps`` is in
        the sample exactly when its shard's ``covered`` reaches it."""
        with self._cond:
            return (self.confirm_upto,
                    tuple(sh.confirmed_step for sh in self._shards),
                    time.monotonic())

    def confirmed_at(self, need) -> Optional[float]:
        """The instant every shard's horizon had reached its step of
        ``need`` (one a shard; a shard whose step is 0 or below is not
        waited for), on the clock of ``_note_confirmed_steps``: None
        while some shard has not, or its advance is no longer logged."""
        t = None
        with self._cond:
            for (steps, ts), step in zip(self._confirmed_log, need):
                if step <= 0:
                    continue
                i = bisect.bisect_left(steps, step)
                if i == len(steps):
                    return None
                t = ts[i] if t is None else max(t, ts[i])
        return t

    def pending_steps(self) -> int:
        """Dispatched-but-unconfirmed steps on the laggiest shard — the
        durability half of the ingress plane's bounded-queue accounting
        (ISSUE 10): ingress queue depth + this is the node's total
        uncommitted command backlog (IngressPlane.gauges reads it)."""
        return self.step_seq - self.confirmed_step

    def shard_layout(self) -> list:
        """``[[lo, hi], ...]`` lane slice per WAL shard — the bench
        tail's ``wal_shard_layout`` stamp (ISSUE 11 satellite): a
        multichip row must record whether its fsync parallelism was
        per-device (slices matching the mesh's lane sharding) or
        host-defaulted, or cross-round durable comparisons are
        apples-to-oranges."""
        return [[sh.lo, sh.hi] for sh in self._shards]

    def batch_interval_ms(self) -> float:
        """The live WAL group-commit wait budget (uniform across
        shards — the engine_pipeline overview stamps this, rule RA07)."""
        return float(self._shards[0].wal.max_batch_interval_ms)

    def set_batch_interval_ms(self, ms: float) -> None:
        """Autotuner hook: retarget every shard's group-commit wait
        budget.  The WAL batch threads read the interval per group, so
        the change lands at the next batch — no restart, no flush."""
        ms = max(0.0, float(ms))
        for sh in self._shards:
            sh.wal.max_batch_interval_ms = ms
        self._bb_config["wal_batch_interval_ms"] = ms

    # -- submit path (engine dispatch thread — must never host-sync) --------

    def _host_aux(self, aux: dict) -> dict:
        """Host materialization of one step's aux, ONCE per step across
        all shards (first worker converts, the rest reuse the memo).
        Under a mesh the conversion is pure device->host transfers —
        safe from a worker thread, unlike slicing, which would enqueue
        a multi-device computation concurrently with the dispatch
        thread (a runtime deadlock, observed on the forced-host CPU
        client).  The full compacted buffer therefore moves once per
        step instead of S sliced gathers."""
        host = aux.get("__host__")
        if host is not None:
            return host
        with self._readback_lock:
            host = aux.get("__host__")
            if host is None:
                host = {k: np.asarray(aux[k])
                        for k in self._BLOCK_KEYS}
                aux["__host__"] = host
        return host

    def submit(self, aux: dict) -> None:
        """Queue one step's device aux for off-thread encode + WAL write
        on every shard.  No host sync happens here: the shard workers
        pull the compacted readback when the device values are ready."""
        job = {key: aux[key] for key in self._BLOCK_KEYS}
        if _is_multidevice(job["appended_hi"]):
            job["__mesh__"] = True
        t_sub = time.monotonic()
        with self._cond:
            self.step_seq += 1
            step = self.step_seq
            self._submit_ts[step] = t_sub
            for sh in self._shards:
                sh._jobs.append((step, job, t_sub))
                sh.unprocessed += 1
            self._cond.notify_all()
        # host-side boundary event only (step counters — no device
        # value is touched on this thread, rule RA04): commands are
        # joined post-hoc by (lane, submit_index) against the
        # on-device step stamps (docs/INTERNALS.md §10)
        record("engine.submit", step_lo=step, step_hi=step, k=1)

    #: stacked-aux leaves a WAL record needs per inner step (the extra
    #: superstep watermarks — committed_lanes/applied_lanes — are host-
    #: pipelining aids, not durability data)
    _BLOCK_KEYS = ("appended_hi", "n_app", "n_acc", "row_csum",
                   "flat_rows")

    def submit_block(self, aux: dict, k: int) -> None:
        """Queue one fused superstep dispatch's STACKED aux (leading
        [K] axis per leaf, see lockstep._superstep) as ``k``
        consecutive per-inner-step encode jobs on every shard.  The
        leading-axis slices are taken here as device ops (async, no
        host readback — this runs on the engine dispatch thread), so
        each job carries exactly the single-step aux shape and the
        shard workers, WAL record format and confirm protocol are
        unchanged: one RTB block per inner step per shard, confirms
        advance per inner step as each block fsyncs."""
        mesh = _is_multidevice(aux["appended_hi"])
        subs = []
        for j in range(k):
            # leading-axis slices taken HERE, on the dispatch thread:
            # under a mesh these enqueue multi-device gathers, which
            # only the dispatch thread may do (see _host_aux)
            sub = {key: aux[key][j] for key in self._BLOCK_KEYS}
            if mesh:
                sub["__mesh__"] = True
            subs.append(sub)
        t_sub = time.monotonic()
        with self._cond:
            step_lo = self.step_seq + 1
            for sub in subs:
                self.step_seq += 1
                step = self.step_seq
                self._submit_ts[step] = t_sub
                for sh in self._shards:
                    sh._jobs.append((step, sub, t_sub))
                    sh.unprocessed += 1
            step_hi = self.step_seq
            self._cond.notify_all()
        record("engine.submit", step_lo=step_lo, step_hi=step_hi, k=k)

    def flush_all(self, timeout: float = 5.0) -> None:
        """Durability barrier on every shard: drains the encode workers
        first so steps still queued there are written, then flushes
        each shard's WAL."""
        self.drain_all(timeout)
        for sh in self._shards:
            sh.wal.flush(timeout)

    def _raise_shard_error(self) -> None:
        err = next((sh.error for sh in self._shards if sh.error), None)
        if err is not None:
            raise err

    def drain_all(self, timeout: float = 30.0) -> None:
        """Barrier: every submitted step is encoded and handed to its
        shard WAL (not necessarily fsynced — flush the shards for that).
        After this returns, every election truncation's base clamp is
        reflected in ``confirm_upto``."""
        with self._cond:
            ok = self._cond.wait_for(
                lambda: any(sh.error for sh in self._shards)
                or all(sh.unprocessed == 0 for sh in self._shards),
                timeout)
        self._raise_shard_error()
        if not ok:
            raise TimeoutError("WAL encode workers stalled")

    def backpressure(self, timeout: float = 30.0) -> None:
        """Bound the unconfirmed window: wait for WAL confirms when more
        than ``max_pending`` steps are in flight on the laggiest shard
        (the flow control a gen_batch_server gets from its bounded
        mailbox)."""

        def lag() -> int:
            return self.step_seq - min(sh.confirmed_step
                                       for sh in self._shards)

        deadline = time.monotonic() + timeout
        while True:
            with self._cond:
                # sliced wait: WAL thread death never notifies the cond
                self._cond.wait_for(
                    lambda: lag() < self.max_pending
                    or any(sh.error for sh in self._shards)
                    or any(not sh.wal.alive for sh in self._shards),
                    min(0.5, max(0.0, deadline - time.monotonic())))
                under = lag() < self.max_pending
            self._raise_shard_error()
            if under:
                return
            for sh in self._shards:
                if not sh.wal.alive:
                    raise WalDown(
                        f"engine WAL shard {sh.idx} died under "
                        "backpressure; call wal.restart() to resume")
            if time.monotonic() > deadline:
                raise TimeoutError("WAL confirms stalled")

    # -- observability ------------------------------------------------------

    def _watermark_source(self) -> dict:
        """Per-shard durable watermarks for post-mortem bundles: host
        ints/np arrays only (``confirm_upto`` lives on the host side of
        the confirm protocol — no device sync here)."""
        with self._cond:
            return {
                "step_seq": self.step_seq,
                "shards": [{
                    "shard": sh.idx,
                    "lanes": [sh.lo, sh.hi],
                    "confirmed_step": sh.confirmed_step,
                    "jobs_pending": len(sh._jobs),
                    "wal_alive": sh.wal.alive,
                    "confirm_upto_min": int(sh.confirm_upto.min())
                    if sh.confirm_upto.size else 0,
                    "confirm_upto_max": int(sh.confirm_upto.max())
                    if sh.confirm_upto.size else 0,
                } for sh in self._shards],
            }

    def wal_overview(self) -> dict:
        """ENGINE_WAL_FIELDS plus per-shard WAL stats (batch bytes,
        records per fsync, fsync latency p50/p99, confirm lag) — the
        key_metrics merge mirroring the RPC_FIELDS pattern."""
        with self._cond:
            eng = dict(self.counters)
            eng["confirm_lag_steps"] = self.step_seq - min(
                sh.confirmed_step for sh in self._shards)
            shards = []
            for sh in self._shards:
                st = sh.wal.stats()
                st["shard"] = sh.idx
                st["lanes"] = [sh.lo, sh.hi]
                st["confirm_lag_steps"] = \
                    self.step_seq - sh.confirmed_step
                # encode-queue backlog: steps dispatched but not yet
                # picked up by this shard's encode worker — with
                # Wal.stats' queue_depth this completes the per-shard
                # pipeline-depth picture the Observatory/ra_top render
                st["jobs_pending"] = len(sh._jobs)
                shards.append(st)
        return {"engine": eng, "shards": shards,
                # which of the two WAL I/O paths this process runs
                # (ra_tpu.native): fsync numbers from one are not
                # comparable with the other's
                "io_path": "native" if faults.IO.native else "python",
                "disk_faults": faults.disk_fault_counters()}

    # -- checkpoint / recovery ----------------------------------------------

    def checkpoint(self, engine, timeout: float = 30.0) -> str:
        deadline = time.monotonic() + timeout
        self.drain_all(timeout)
        while True:
            with self._cond:
                done = all(sh.confirmed_step >= self.step_seq
                           for sh in self._shards)
            if done:
                break
            for sh in self._shards:
                if not sh.wal.alive:
                    raise WalDown("checkpoint: WAL shard died; "
                                  "wal.restart() and retry")
                try:
                    sh.wal.flush(min(5.0, max(
                        0.1, deadline - time.monotonic())))
                except TimeoutError:
                    pass
            with self._cond:
                self._cond.wait_for(
                    lambda: all(sh.confirmed_step >= self.step_seq
                                for sh in self._shards),
                    min(0.5, max(0.0, deadline - time.monotonic())))
            self._raise_shard_error()
            if time.monotonic() > deadline:
                raise TimeoutError("checkpoint: WAL confirms stalled")
        path = os.path.join(self.dir, "ckpt.npz")
        engine.save(path)
        # the pytree schema rides the meta for post-mortem diagnostics:
        # a reopen under a different engine version can say WHICH field
        # set the archive carries before restore() decides (the archive
        # itself is schema-named since ISSUE 15 and is authoritative)
        from .lockstep import LaneState
        meta = {"step": self.step_seq, "wal_shards": self.wal_shards,
                "schema": list(LaneState._fields)}
        tmp = path + ".meta.tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.dir, "ckpt.meta.json"))
        # roll every shard's current file so its (now-covered) records
        # become prunable, then drop every covered file
        for sh in self._shards:
            sh.wal.rollover()
            sh.wal.flush()
            sh.retirer.prune(self.step_seq)
        self._prune_legacy()
        return path

    def _prune_legacy(self) -> None:
        files, self._legacy_files = self._legacy_files, []
        dirs = set()
        for path in files:
            try:
                os.unlink(path)
            except OSError:
                pass
            dirs.add(os.path.dirname(path))
        for d in dirs:
            parent = os.path.dirname(d)
            try:
                os.rmdir(d)
                if os.path.basename(parent).startswith("shard"):
                    os.rmdir(parent)
            except OSError:
                pass
        self._legacy_tables = []

    def recovered_pieces(self, base_step: int) -> dict:
        """step -> [(lane_lo, hi, n_app, n_acc, rows)] merged from every
        shard's recovered WAL tables plus foreign-layout leftovers;
        ``rows`` a :class:`_PieceRows`."""
        pieces: dict = {}
        tabs = [sh.wal.recovered_table(UID) for sh in self._shards]
        tabs += [t.get(UID, {}) for t in self._legacy_tables]
        for tbl in tabs:
            for s, (_t, blk) in tbl.items():
                if s <= base_step:
                    continue
                pieces.setdefault(s, []).append(
                    decode_block(blk, dense=False))
        return pieces

    def close(self) -> None:
        RECORDER.remove_source("engine_wal_watermarks",
                               self._bb_watermarks)
        RECORDER.remove_source("engine_wal_config", self._bb_config_src)
        self._sup_stop.set()
        if self._sup_thread is not None:
            self._sup_thread.join(timeout=5)
        try:
            self.drain_all(timeout=10.0)
        except Exception:  # noqa: BLE001 — a dead WAL must not block cleanup
            pass
        for sh in self._shards:
            try:
                sh.wal.flush()
            except (WalDown, TimeoutError):
                pass
        for sh in self._shards:
            sh.stop()
            sh.wal.close()


class _StepRows:
    """A step block's rows over the whole fleet, as the pieces that
    survived the contiguity guard: ``shape`` is the dense form's,
    :meth:`take` builds it for the lanes asked for."""

    def __init__(self, n_lanes: int, kmax: int, c: int, dtype) -> None:
        self.shape, self.dtype = (n_lanes, kmax, c), dtype
        self._parts: list = []

    def add(self, lane_lo: int, ok: np.ndarray, rows: _PieceRows) -> None:
        if rows.shape[1]:
            self._parts.append((lane_lo, ok, rows))

    def take(self, lanes: np.ndarray) -> np.ndarray:
        out = np.zeros((len(lanes),) + self.shape[1:], self.dtype)
        for lane_lo, ok, rows in self._parts:
            local = lanes - lane_lo
            mine = (local >= 0) & (local < rows.shape[0])
            mine[mine] = ok[local[mine]]
            out[mine, :rows.shape[1]] = rows.take(local[mine])
        return out


def _assemble_blocks(pieces: dict, n_lanes: int, ckpt_tail: np.ndarray):
    """Stitch per-slice step pieces into full-lane step blocks
    ``(step, hi, n_app, n_acc, rows)``, ``rows`` a :class:`_StepRows`.

    Lanes with no piece at a step (their shard crashed before recording
    it, or a foreign layout covered other slices) carry their tail
    forward with ``n_app=0`` — nothing was durably recorded for them at
    that step, and the merged per-lane confirm rule guarantees nothing
    beyond their last record was ever reported committed.

    Contiguity guard (the engine twin of the classic log's recovery
    clamp): a piece whose append BASE exceeds a lane's carried tail
    records appends above a step gap — a post-restart write that beat
    the unconfirmed-backlog resend into the new WAL file before a
    second crash.  Those appends were never confirmable (the shard's
    confirm slice froze below the gap), so the lane skips the piece
    and carries its tail forward instead of replaying a holed log the
    engine could never converge on."""
    blocks = []
    cur_hi = ckpt_tail.astype(np.int32).copy()
    for s in sorted(pieces):
        ps = pieces[s]
        kmax = max(p[4].shape[1] for p in ps)
        c = ps[0][4].shape[2]
        hi = cur_hi.copy()
        n_app = np.zeros((n_lanes,), np.int32)
        n_acc = np.zeros((n_lanes,), np.int32)
        rows = _StepRows(n_lanes, kmax, c, ps[0][4].dtype)
        for lane_lo, phi, papp, pacc, prows in ps:
            sl = slice(lane_lo, lane_lo + phi.shape[0])
            ok = (phi - papp) <= cur_hi[sl]
            if not ok.all():
                logging.getLogger("ra_tpu").warning(
                    "engine recovery: step %d piece at lanes [%d,%d) "
                    "appends above a gap on %d lane(s); skipped",
                    s, lane_lo, lane_lo + phi.shape[0],
                    int((~ok).sum()))
            hi[sl] = np.where(ok, phi, hi[sl])
            n_app[sl] = np.where(ok, papp, n_app[sl])
            n_acc[sl] = np.where(ok, pacc, n_acc[sl])
            rows.add(lane_lo, ok, prows)
        blocks.append((s, hi, n_app, n_acc, rows))
        cur_hi = hi
    return blocks


def _final_logs(blocks: list, ckpt_tail: np.ndarray):
    """Resolve election truncations across recovered step blocks into the
    surviving per-step entry counts.

    blocks: [(step, hi, n_app, n_acc, rows)] in step order.  Returns
    (surv_counts per block [N], trimmed_tail np[N], final_hi np[N]):
    ``surv_counts[b][i]`` entries of block b survive for lane i (always a
    prefix — truncation removes a suffix of earlier appends), and
    ``trimmed_tail`` is where the checkpoint state itself must be cut
    (a truncation can reach below the checkpoint when unconfirmed
    leader tail existed at checkpoint time)."""
    n = ckpt_tail.shape[0]
    if not blocks:
        return [], ckpt_tail.copy(), ckpt_tail.copy()
    bases = np.stack([hi - n_app for _s, hi, n_app, _a, _r in blocks])
    his = np.stack([hi for _s, hi, _n, _a, _r in blocks])
    # suffix-min of bases strictly after each block: entries above it die
    suffix = np.full((len(blocks) + 1, n), np.iinfo(np.int32).max,
                     np.int32)
    for b in range(len(blocks) - 1, -1, -1):
        suffix[b] = np.minimum(suffix[b + 1], bases[b])
    surv = []
    for b, (_s, hi, n_app, _n_acc, _rows) in enumerate(blocks):
        end = np.minimum(his[b], suffix[b + 1])
        surv.append(np.clip(end - bases[b], 0, n_app).astype(np.int32))
    trimmed_tail = np.minimum(ckpt_tail, suffix[0])
    final_hi = his[-1]
    return surv, trimmed_tail, final_hi


def open_engine(machine, data_dir: str, n_lanes: int, n_members: int = 3,
                *, sync_mode: int = 1, write_strategy: str = "default",
                max_pending: int = 8, wal_shards: int = 1,
                wal_batch_bytes: int = 4 * 1024 * 1024,
                wal_batch_interval_ms: Optional[float] = None,
                wal_supervise: bool = True,
                settle_limit: int = 10_000, **engine_kwargs):
    """Create-or-recover a durable LockstepEngine at ``data_dir``.

    Fresh directory: a new engine wired to ``wal_shards`` new WAL
    shards.  Existing data: restore the checkpoint, merge the surviving
    shard records (any layout), replay them through the jitted step
    (recomputing machine state with the same apply fold), and resume in
    durable mode.  Matches the recovery contract of SURVEY.md §3.4 at
    engine scale: recovery = checkpoint + WAL re-read, deduped by the
    overwrite rule, applied with effects suppressed."""
    import jax
    import jax.numpy as jnp

    from .lockstep import LockstepEngine

    os.makedirs(data_dir, exist_ok=True)
    ckpt = os.path.join(data_dir, "ckpt.npz")
    meta_path = os.path.join(data_dir, "ckpt.meta.json")
    base_step = 0
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            base_step = json.load(f).get("step", 0)

    # the bridge's shard Wals scan surviving files once on construction
    # (scan_wal_file dedups per-index overwrites); the merged piece
    # tables are the step-block source for replay.  No engine writes
    # happen until attach, so constructing it up front is safe.
    dur = EngineDurability(data_dir, n_lanes, sync_mode=sync_mode,
                           write_strategy=write_strategy,
                           max_pending=max_pending,
                           wal_shards=wal_shards,
                           wal_batch_bytes=wal_batch_bytes,
                           wal_batch_interval_ms=wal_batch_interval_ms,
                           wal_supervise=wal_supervise)
    pieces = dur.recovered_pieces(base_step)

    kmax = max((p[4].shape[1] for ps in pieces.values() for p in ps),
               default=0)
    if kmax:
        # the replay apply window must cover the widest recovered block,
        # or ring backpressure would silently clip replayed entries
        engine_kwargs = dict(engine_kwargs)
        engine_kwargs["apply_window"] = max(
            engine_kwargs.get("apply_window") or 0, kmax + 2)
        ring = engine_kwargs.get("ring_capacity", 1024)
        if ring < kmax + 2:
            # the ring-write dummy slot must stay clear of the widest
            # replayed block; reopening with a smaller geometry than
            # the writer would otherwise silently corrupt the replay
            raise ValueError(
                f"ring_capacity {ring} too small to replay recovered "
                f"blocks of width {kmax}; use >= {kmax + 2} (the "
                "engine that wrote this WAL had larger max_step_cmds)")

    eng = LockstepEngine(machine, n_lanes, n_members, **engine_kwargs)
    if os.path.exists(ckpt):
        eng.restore(ckpt)
        # transient failure masks do not survive a node restart: every
        # non-removed member recovers with the node (removed members
        # have voter=False too and stay out).  Revival is by SNAPSHOT
        # INSTALL from the lane leader, vectorized over all revived
        # members — a bare active-flag flip would leave a frozen
        # applied cursor that drags the lane-uniform apply window onto
        # recycled ring slots (silent divergence).
        st = eng.state
        revive = st.voter & ~st.active
        if bool(revive.any()):
            lead = st.leader_slot[:, None]                      # [N,1]
            snap = jnp.take_along_axis(st.applied, lead, axis=1)

            def from_leader(x):
                idx = lead.reshape((n_lanes, 1) + (1,) * (x.ndim - 2))
                idx = jnp.broadcast_to(idx, (n_lanes, 1) + x.shape[2:])
                lx = jnp.take_along_axis(x, idx, axis=1)
                rv = revive.reshape(revive.shape + (1,) * (x.ndim - 2))
                return jnp.where(rv, lx, x)

            st = st._replace(
                mac=jax.tree.map(from_leader, st.mac),
                applied=jnp.where(revive, snap, st.applied),
                commit=jnp.where(revive, snap, st.commit),
                last_index=jnp.where(revive, snap, st.last_index),
                last_written=jnp.where(revive, snap, st.last_written),
                match=jnp.where(revive, 0, st.match),
                next_index=jnp.where(revive, snap + 1, st.next_index))
        eng.state = st._replace(active=st.active | st.voter)

    lane = np.arange(n_lanes)
    st = eng.state
    leader = np.asarray(st.leader_slot)
    ckpt_tail = np.asarray(st.last_index)[lane, leader].astype(np.int32)

    blocks = _assemble_blocks(pieces, n_lanes, ckpt_tail)
    surv, trimmed_tail, final_hi = _final_logs(blocks, ckpt_tail)

    if (trimmed_tail < ckpt_tail).any():
        # a post-checkpoint election truncated into the checkpoint's
        # unconfirmed tail: cut the restored cursors (commit/applied are
        # always below the cut — commit never truncates)
        t = jnp.asarray(trimmed_tail)[:, None]
        st = eng.state
        eng.state = st._replace(
            last_index=jnp.minimum(st.last_index, t),
            last_written=jnp.minimum(st.last_written, t),
            match=jnp.minimum(st.match, t),
            next_index=jnp.minimum(st.next_index, t + 1))

    if blocks:
        kmax = kmax or 1
        C = eng.payload_width
        # the replay's steps donate the state they replace, whatever the
        # engine's own `donate`: nothing else holds the recovered arrays
        # yet, and a fleet that fills its device (20,000 x 5 built on
        # one chip before it is sharded) cannot hold a step's input and
        # output rings and the step's temporaries side by side
        for (s, hi, n_app, n_acc, rows), keep in zip(blocks, surv):
            pad = np.zeros((n_lanes, kmax, C), rows.dtype)
            if rows.shape[1]:
                pad[:, :rows.shape[1]] = rows.take(lane)
            eng.step(keep, pad, donate=True)
        # settle: drain the apply/commit pipeline until every lane's
        # recovered log is fully committed and applied on every live
        # member (recovery commits the whole surviving log: it is on
        # disk, i.e. replicated on every co-hosted member by definition)
        zero_n = np.zeros((n_lanes,), np.int32)
        zero_p = np.zeros((n_lanes, 1, C), eng.payload_dtype)
        for _ in range(settle_limit):
            stn = eng.state
            com = np.asarray(stn.commit)[lane, np.asarray(stn.leader_slot)]
            active = np.asarray(stn.active)
            app = np.where(active, np.asarray(stn.applied),
                           np.iinfo(np.int32).max).min(axis=1)
            if (com >= final_hi).all() and (app >= com).all():
                break
            eng.step(zero_n, zero_p, donate=True)
        else:
            raise RuntimeError("recovery settle did not converge")

    st = eng.state
    leader = np.asarray(st.leader_slot)
    tail = np.asarray(st.last_index)[lane, leader].astype(np.int32)
    last_step = max(pieces) if pieces else base_step
    dur.seed(tail, last_step)
    eng.attach_durability(dur)
    if pieces or os.path.exists(ckpt):
        # an actual recovery happened (checkpoint restore and/or WAL
        # replay): stamp the join-able report next to any post-mortem
        # bundle the crash left (ISSUE 7 — crash + recovery are one
        # incident)
        stamp_recovery(
            {"plane": "engine", "base_step": base_step,
             "replayed_steps": len(pieces),
             "resumed_at_step": last_step,
             "wal_shards": wal_shards,
             "tail_min": int(tail.min()) if tail.size else 0,
             "tail_max": int(tail.max()) if tail.size else 0},
            data_dir=data_dir)
    return eng
