"""The partitioned stream's kit: a producer's appends, a consumer's
chunk reads and offset stores on ``ra_tpu.models.stream.StreamLogMachine``,
one stream partition a cluster.

``append`` writes one message (``message_words`` int32 words: the op
id, the session and words mixed from session, op id and a salt) and
goes out as a command ``[1, 0, 0, message words]``; ``read`` returns the
chunk of up to ``chunk`` messages ``lag`` messages before the tail and
goes out as a ``T_READ`` query ``[1, lag]``; ``store`` max-merges a
consumer group's cursor with ``tail - lag`` and goes out as ``[2,
group, lag, zeros]``.  A consumer cannot know an absolute offset before
its read is answered (the fleet mints no operation due on another's
answer), so reads and stores name offsets by their lag behind the tail.
The lag of either is drawn as the mix says: ``live_share`` of them
uniform over ``[0, live_lag)`` (a consumer near the tail), the rest
uniform over the retained tail (a consumer draining a backlog).

The ledger keeps of an op its lag and its salt; the message words are
made where the payload is, and again where the reference checks what a
log holds (``reference.append_words``), never stored.

What ``correct`` holds a run to, beside the harness's own counts
(every limit 0): the leader's ``tail`` is the loaded history plus the
acknowledged appends and ``base`` the retention's; every retained
message is the loaded one at its offset or an acknowledged append of
the partition, none twice, each session's in op-id order; each
group's cursor lies in the range its acknowledged stores allow; every
answered read's messages are the judged log's at its offsets; and every
read's tail lies between what its clock readings allow.  The counts
that need the log are ``state_counts``'s (live and after the reopen),
since the contract hands ``read_counts`` the ledger alone.
"""
from __future__ import annotations

import numpy as np

from .. import leaf_counts
from . import reference
from .machine import StreamLogMachine

_LIVE_SALT = np.uint64(0x6C697665)
_LAG_SALT = np.uint64(0x6C6167)


def _sizes(config: dict) -> tuple:
    words, rem = divmod(int(config["message_bytes"]), 4)
    if rem or words < 1:
        raise ValueError("config: message_bytes must be a multiple of 4")
    return (words, int(config["chunk"]), int(config["retention"]),
            int(config["groups"]))


def build_machine(config: dict):
    words, chunk, retention, groups = _sizes(config)
    if int(config["command_words"]) != 3 + words:
        raise ValueError(
            f"config: command_words must be {3 + words} "
            "([op, a, b] and one message's words)")
    return StreamLogMachine(message_words=words, chunk=chunk,
                            retention=retention, groups=groups,
                            seed=int(config["load_seed"]))


def _unit(h: np.ndarray) -> np.ndarray:
    return (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)


class Operations:
    kinds = ("append", "read", "store")
    reads = ("read",)
    columns = {"lag": np.int32, "salt": np.int32}

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.words, self.chunk, self.retention, self.groups = _sizes(config)
        self.live_lag = int(mix.get("live_lag", 256))
        self.live_share = float(mix.get("live_share", 0.9))

    def content(self, h: np.ndarray, kinds) -> tuple:
        with np.errstate(over="ignore"):
            live = _unit(reference._mix64(h ^ _LIVE_SALT)) < self.live_share
            g = reference._mix64(h ^ _LAG_SALT)
        span = np.where(live, self.live_lag, self.retention)
        lag = np.minimum((_unit(g) * span).astype(np.int64), span - 1)
        salt = ((h >> np.uint64(24)) & np.uint64(0x7FFFFFFF)).astype(np.int32)
        return lag.astype(np.int32), salt

    def payload(self, fleet, idx: np.ndarray) -> np.ndarray:
        kind = fleet.op_kind[idx]
        pay = np.zeros((len(idx), fleet.width), np.int32)
        app = kind == self.kinds.index("append")
        pay[app, 0] = 1
        pay[app, 3:] = reference.append_words(
            fleet.op_sess[idx[app]], fleet.op_id[idx[app]],
            fleet.op_salt[idx[app]], self.words)
        store = kind == self.kinds.index("store")
        pay[store, 0] = 2
        pay[store, 1] = fleet.op_salt[idx[store]] % self.groups
        pay[store, 2] = fleet.op_lag[idx[store]]
        return pay

    def query(self, fleet, idx: np.ndarray) -> np.ndarray:
        q = np.empty((len(idx), 2), np.int32)
        q[:, 0] = 1
        q[:, 1] = fleet.op_lag[idx]
        return q

    def admit(self, fleet, config: dict) -> None:
        if fleet.width != 3 + self.words:
            raise RuntimeError("config: the wire's payload width is not "
                               "the machine's command")


def leaves(mac) -> dict:
    return {"log": mac["log"], "tail": mac["tail"], "base": mac["base"],
            "cursors": mac["cursors"]}


def _of_kind(fleet, name: str) -> np.ndarray:
    return fleet.op_kind[:fleet.n_ops] == Operations.kinds.index(name)


def _clocks(fleet) -> reference.Clocks:
    """Every append that was ever fed to the transport."""
    n = fleet.n_ops
    app = _of_kind(fleet, "append") & ~np.isnan(fleet.op_sent[:n])
    return reference.Clocks(lane=fleet.lanes[fleet.op_sess[:n][app]],
                            sent=fleet.op_sent[:n][app],
                            acked=fleet.op_acked[:n][app])


def expected(config: dict, fleet, acked: np.ndarray) -> dict:
    """``tail`` and ``base`` from the acknowledged appends; and, under
    names no leaf has, what ``state_counts`` judges the log, the
    cursors and the reads' messages by."""
    words, chunk, retention, groups = _sizes(config)
    n_lanes = int(config["clusters"])
    n = fleet.n_ops
    lane = fleet.lanes[fleet.op_sess[:n]]
    done = _of_kind(fleet, "append") & acked
    tail = retention + np.bincount(lane[done], minlength=n_lanes)
    want = {"tail": tail.astype(np.int32),
            "base": np.maximum(tail - retention, 0).astype(np.int32)}
    sess = fleet.op_sess[:n]
    want["@appends"] = reference.Appends(
        words, lane=lane[done], sess=sess[done], op_id=fleet.op_id[:n][done],
        salt=fleet.op_salt[:n][done])
    clocks = _clocks(fleet)
    st = _of_kind(fleet, "store") & acked
    want["@cursors"] = reference.cursor_bounds(
        n_lanes, groups, retention, clocks, lane=lane[st],
        group=fleet.op_salt[:n][st] % groups, lag=fleet.op_lag[:n][st],
        fed=fleet.op_sent[:n][st], seen=fleet.op_acked[:n][st])
    rd = _of_kind(fleet, "read") & acked
    want["@reads"] = {"lane": lane[rd], "lag": fleet.op_lag[:n][rd],
                      "reply": fleet.op_reply[:n][rd]}
    want["@sizes"] = {"retention": retention, "chunk": chunk,
                      "words": words}
    want["@load_seed"] = int(config["load_seed"])
    return want


def state_counts(tag: str, at_leader: dict, want: dict) -> dict:
    out = leaf_counts(tag, at_leader, {"tail": want["tail"],
                                       "base": want["base"]})
    sizes = want["@sizes"]
    seed = want["@load_seed"]
    log, tail, base = at_leader["log"], at_leader["tail"], at_leader["base"]
    counts = reference.held_counts(seed, want["@appends"], log, tail, base,
                                   retention=sizes["retention"],
                                   words=sizes["words"])
    lo, hi = want["@cursors"]
    cur = at_leader["cursors"]
    counts["cursors_outside"] = int(((cur < lo) | (cur > hi)).sum())
    counts.update(reference.read_message_counts(
        seed, log, tail, base, **want["@reads"], **sizes))
    out.update((f"{tag}_{k}", v) for k, v in counts.items())
    return out


def read_counts(config: dict, fleet) -> dict:
    _words, _chunk, retention, _groups = _sizes(config)
    n = fleet.n_ops
    rd = _of_kind(fleet, "read") & ~np.isnan(fleet.op_acked[:n])
    return reference.read_consistency_counts(
        retention, _clocks(fleet), lane=fleet.lanes[fleet.op_sess[:n][rd]],
        lag=fleet.op_lag[:n][rd], first=fleet.op_reply[:n][rd][:, 1],
        fed=fleet.op_sent[:n][rd], seen=fleet.op_acked[:n][rd],
        wm=fleet.op_wm[:n][rd])
