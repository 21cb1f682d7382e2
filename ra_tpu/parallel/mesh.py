"""Device-mesh sharding for the lane engine.

Parallelism axes of this framework (the honest mapping from SURVEY.md §2.4):

* ``lanes`` — cluster-level data parallelism, the reference's "thousands of
  co-hosted clusters per node" (docs/internals/INTERNALS.md:12-19) turned
  into the batch axis.  Lanes are fully independent: sharding them over a
  mesh needs **zero** cross-lane collectives, so throughput scales linearly
  over ICI-connected chips.
* ``members`` — the replication axis.  Sharding member slots across devices
  places each cluster member on a different chip, so the lockstep step's
  cross-member operations (leader gather, match/commit reductions, the
  quorum median) lower to XLA collectives over ICI — the tensorized
  equivalent of the reference shipping #append_entries_rpc{} over Erlang
  distribution (ra_server_proc.erl:1317-1341).

Use a 1-D ``lanes`` mesh for co-hosted deployment (default), or a 2-D
``(members, lanes)`` mesh to emulate/run the distributed deployment where
chips stand in for hosts.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import devicewatch
from ..engine.lockstep import DispatchAheadDriver, LaneState


def lane_mesh(devices=None, member_axis: int = 1) -> Mesh:
    """Build a (members, lanes) mesh.  member_axis=1 gives the pure
    lane-parallel deployment."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    assert n % member_axis == 0, (n, member_axis)
    arr = np.asarray(devices).reshape(member_axis, n // member_axis)
    return Mesh(arr, axis_names=("members", "lanes"))


def state_shardings(mesh: Mesh, state: LaneState) -> LaneState:
    """Sharding pytree for a LaneState, dispatched by field (not rank):
    [N] fields over 'lanes', [N,P] fields over ('lanes','members'), the
    [N,R,C] ring lane-sharded only (entries flow to member chips on demand),
    and machine state over ('lanes','members', replicated...) whatever its
    per-member rank.

    Rule RA15 derives the state schema from this function's ``state``
    annotation and statically requires every ``LaneState`` field to be
    covered by the dispatch below — the generic ``_fields`` loop is
    full coverage, and a by-name special case (``"mac"``/``"telem"``/
    ``"ring"``) naming a non-field is flagged as a stale arm.  The PR 6
    shape (a new pytree field the tree-map didn't cover, rejected by
    ``device_put`` one mesh boot later) cannot reland silently."""
    def by_shape(leaf, member_axis: bool):
        leaf = jax.numpy.asarray(leaf)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        dims = ["lanes"]
        if member_axis and leaf.ndim >= 2:
            dims.append("members")
        dims += [None] * (leaf.ndim - len(dims))
        return NamedSharding(mesh, P(*dims))

    mac_specs = jax.tree.map(lambda l: by_shape(l, member_axis=True),
                             state.mac)
    specs = {}
    for name in LaneState._fields:
        if name == "mac":
            continue
        leaf = getattr(state, name)
        if name == "telem":
            # the telemetry plane is a nested pytree of [N] accumulators
            # (LaneTelemetry): each leaf shards over 'lanes' like any
            # per-lane vector — the device holding a lane holds its
            # telemetry, so the jitted summary's reductions/top_k lower
            # to cross-device collectives (the per-device aggregation +
            # cross-device merge of the sharded observability path)
            specs[name] = jax.tree.map(
                lambda l: by_shape(l, member_axis=False), leaf)
            continue
        # ring [N,R,C] and read_buf [N,Kr,Cq] are LANE-local planes:
        # axis 1 is ring depth / pending-read slots, never members
        member_axis = name not in ("ring", "read_buf")
        specs[name] = by_shape(leaf, member_axis=member_axis)
    return LaneState(mac=mac_specs, **specs)


def shard_engine_state(engine, mesh: Optional[Mesh] = None):
    """Place an engine's state on a mesh; subsequent jitted steps run
    SPMD with XLA-inserted collectives.

    Beyond the state pytree itself (ISSUE 11, the mesh-native pipeline):

    * the engine's cached zero masks (``_zero_fail``/``_zero_elect``/
      ``_zero_confirm``) are re-placed with matching shardings — every
      dispatch consumes them, and leaving them single-device would
      either recompile the step for a mixed-sharding signature or pay a
      broadcast copy per dispatch;
    * ``engine._mesh`` records the mesh so downstream wiring
      (:class:`~ra_tpu.engine.lockstep.DispatchAheadDriver` via
      :func:`mesh_superstep_driver`, ``IngressPlane``) picks up the
      matching :func:`superstep_block_shardings` automatically — the
      SNIPPETS.md pjit rule that out/in axis resources of chained
      jitted calls must MATCH so staged blocks never repartition.
    """
    if mesh is None:
        mesh = lane_mesh()
    shardings = state_shardings(mesh, engine.state)
    engine.state = jax.device_put(engine.state, shardings)
    lane_sh = NamedSharding(mesh, P("lanes"))
    engine._zero_elect = jax.device_put(engine._zero_elect, lane_sh)
    engine._zero_confirm = jax.device_put(engine._zero_confirm, lane_sh)
    engine._zero_fail = jax.device_put(
        engine._zero_fail, NamedSharding(mesh, P("lanes", "members")))
    engine._mesh = mesh
    # transfer ledger (ISSUE 16): the one-time resharding of the full
    # state pytree + zero masks is the mesh path's h2d budget — it
    # must show up ONCE at shard time, never again per dispatch (a
    # per-window h2d delta at this site is the repartition bug RA15
    # guards statically).  .nbytes reads are host metadata.
    devicewatch.record_h2d(
        "mesh_shard",
        sum(getattr(leaf, "nbytes", 0)
            for leaf in jax.tree.leaves(engine.state))
        + engine._zero_elect.nbytes + engine._zero_confirm.nbytes
        + engine._zero_fail.nbytes,
        events=len(jax.tree.leaves(engine.state)) + 3)
    return mesh


def superstep_block_shardings(mesh: Mesh) -> dict:
    """Shardings for the ``[K, ...]`` superstep staging block (the
    dispatch-ahead driver's device_put targets, ISSUE 5).  The leading
    inner-step axis is TIME, not data — it is never sharded; lanes
    shard as everywhere else, so a fused dispatch over a sharded
    engine consumes the staged block with zero resharding copies:

      n_new    int32[K, N]        -> P(None, 'lanes')
      payloads [K, N, Kc, C]      -> P(None, 'lanes', None, None)
      query    bool[K, N]         -> P(None, 'lanes')
      n_read   int32[K, N]        -> P(None, 'lanes')
      read_q   [K, N, Kr, Cq]     -> P(None, 'lanes', None, None)

    and of the flat write block that ``ra_densify`` turns into
    ``payloads`` on the device (ISSUE 26): the row table goes whole to
    every device and the per-lane index with its lanes, so the host
    partitions nothing and each device gathers its own lanes' rows:

      rows     [M, C]             -> P()
      row_base int32[N]           -> P('lanes')
      take     int32[N]           -> P('lanes')

    No ``elect`` entry on purpose: elect schedules are HOST data —
    the engine keeps any-election bookkeeping on the host
    (``LockstepEngine._host_mask``) so the hot path never reads the
    mask back from device; pre-staging it would reintroduce exactly
    that sync.  Rule RA15 pins the other direction: every key the
    dispatch-ahead staging path reads (``shardings.get("n_new")`` in
    ``DispatchAheadDriver._stage``) must have an entry here, so a new
    staged block component cannot silently repartition per dispatch
    (the SNIPPETS.md matching-axis-resources rule, as a lint)."""
    vec = NamedSharding(mesh, P(None, "lanes"))
    lane = NamedSharding(mesh, P("lanes"))
    return {
        "n_new": vec,
        "payloads": NamedSharding(mesh, P(None, "lanes", None, None)),
        "query": vec,
        "n_read": vec,
        "read_q": NamedSharding(mesh, P(None, "lanes", None, None)),
        "rows": NamedSharding(mesh, P()),
        "row_base": lane,
        "take": lane,
    }


def per_device_wal_shards(mesh: Mesh) -> int:
    """WAL shard count for a per-device durable layout: one shard per
    LANE-axis device.  ``EngineDurability`` slices lanes into S equal
    contiguous ranges (``bounds[i] = round(i*N/S)``) — exactly the lane
    slices an even ``P('lanes')`` sharding places per device — so each
    device's committed rows are encoded+fsynced by its own shard and
    fsync parallelism scales with the mesh instead of serializing on
    one writer.  RTB2 recovery merges ANY shard layout, so reopening
    the same dir under a different mesh shape needs no migration."""
    return int(mesh.shape["lanes"])


def mesh_superstep_driver(engine, mesh: Optional[Mesh] = None,
                          max_in_flight: int = 2) -> DispatchAheadDriver:
    """A :class:`DispatchAheadDriver` whose staged blocks are placed
    with :func:`superstep_block_shardings` — the mesh-native form of
    the PR 5 host pipeline: device_put partitions block i+1 across the
    mesh while dispatch i executes, and because the staging shardings
    match the fused step's input shardings the dispatch consumes the
    staged block with zero resharding copies."""
    mesh = mesh or getattr(engine, "_mesh", None)
    if mesh is None:
        mesh = shard_engine_state(engine)
    return DispatchAheadDriver(engine, max_in_flight=max_in_flight,
                               shardings=superstep_block_shardings(mesh))


def ingress_submit_wave(plane, handles, seqnos, payloads):
    """Mesh-side ingress pump: one vectorized submission wave into a
    SHARDED engine's plane — dedup -> admission -> coalesce -> staged
    fused dispatch, returning the per-row status.  All per-session
    work stays inside the plane's vectorized sweeps; lint rule RA08's
    no-per-session-Python gate covers this function and every
    same-module helper it reaches (a mesh-side loop or per-row dict
    here would reintroduce the per-command host work the dense-block
    path removed)."""
    status = plane.submit(handles, seqnos, payloads)
    plane.pump(force=True)
    return status
