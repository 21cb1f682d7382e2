"""The comparison that decides ``correct``.

Every number is an exact count of disagreements with the plain
reference or with a stated guarantee, so every limit is 0.  What is
compared is what the timed path produced: the client's ledger of the
run's operations, the machine state of every active replica as the
window and its drain left it, and what a reopen rebuilds from the WAL.
"""
from __future__ import annotations

import numpy as np

from . import reference


def _against_reference(tag: str, snap: dict, want: dict) -> dict:
    """Leader's state against the reference, then every active replica
    against its leader."""
    lane = np.arange(len(snap["leader"]))
    lead = snap["leader"]
    active = snap["active"]
    out = {}
    cells = 0
    for leaf in ("value", "check", "seq"):
        x = snap[leaf]
        at_leader = x[lane, lead]
        out[f"{tag}_{leaf}_wrong"] = int((at_leader != want[leaf]).sum())
        m = active.reshape(active.shape + (1,) * (x.ndim - 2))
        cells += int((m & (x != at_leader[:, None])).sum())
    out[f"{tag}_replica_cells_wrong"] = cells
    # every active replica has applied its leader's whole log
    tail = snap["last_index"][lane, lead]
    out[f"{tag}_replicas_behind"] = int(
        (active & (snap["applied"] != tail[:, None])).sum())
    return out


def compare(run, snaps: dict) -> dict:
    """name -> (value, limit) for every number compared."""
    f = run.fleet
    n = f.n_ops
    acked = ~np.isnan(f.op_acked[:n])
    sess = f.op_sess[:n]
    # the reference folds every op the client was told is committed
    want = reference.fold(
        int(run.config["clusters"]), int(run.config["dedup_slots"]),
        run.pool, lane=f.lanes[sess[acked]], slot=f.slots[sess[acked]],
        op_id=f.op_id[:n][acked], delta=f.op_delta[:n][acked],
        row=f.op_row[:n][acked], salt=f.op_salt[:n][acked])
    out = {
        "ops_never_acked": int((~acked).sum()),
        "addresses_shared": 0 if reference.addresses_distinct(
            f.lanes, f.slots, int(run.config["dedup_slots"])) else 1,
        "acks_above_fsync": int(run.acks_above_fsync),
    }
    live = snaps["live"]
    out["commit_above_fsync"] = int(
        (live["commit"].max(axis=1) > live["confirm"]).sum())
    out.update(_against_reference("live", live, want))
    out.update(_against_reference("reopen", snaps["reopened"], want))
    return {k: (v, 0) for k, v in out.items()}


def verdict(compared: dict) -> bool:
    return all(value <= limit for value, limit in compared.values())
