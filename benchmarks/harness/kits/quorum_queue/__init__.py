"""The quorum queue's kit: RabbitMQ PerfTest's work queue on
``ra_tpu.models.quorum_queue.QuorumQueueMachine``, one quorum queue a
cluster, competing consumers pulling their deliveries by reads.

``publish`` writes one message (``message_bytes`` / 4 int32 words: the
op id, the session and words mixed from session, op id and a salt) and
goes out as a command ``[1, 0, 0, message words]``; ``settle``
acknowledges a consumer's ``multi_ack_every`` oldest deliveries at once
(PerfTest's ``--multi-ack-every``) and goes out as ``[2, consumer, n,
zeros]``; ``return`` is a ``basic.nack`` with requeue of the
consumer's ``requeue_n`` oldest, ``[3, consumer, n, zeros]``;
``deliver`` reads the consumer's oldest checked-out messages (up to
ten) and goes out as a ``T_READ`` query ``[1, consumer]``.  The
consumer of each is uniform over the queue's ``consumers``, from the
op's 64 bits.

A consumer cannot name a delivery id before a read has answered it
(the fleet mints no operation due on another's answer), so settles and
returns name "the consumer's oldest n", as PerfTest's cumulative
``multiple`` acknowledgements do.

The ledger keeps of an op its consumer and its salt; the message words
are made where the payload is, and again where the reference checks
what a queue holds (``reference.publish_words``), never stored.

What ``correct`` holds a run to, beside the harness's own counts
(every limit 0), live and after the reopen: every held message is the
loaded one at its ticket or an acknowledged publish of the queue, none
twice, each session's in op-id order; every acknowledged publish is
held or covered by the settle and dead-letter counts; none refused;
each consumer's settled and returned messages, and the queue's
settle, requeue and delivery counts, exactly what its acknowledged
settles and returns named wherever the ledger shows that no clamp can
have bound (``reference.removals_exact``: at the cell's mix, every
queue), and no more than that elsewhere; no consumer past its credit;
every answered read's messages those of their delivery ids, its fresh
ones in ticket order, its first delivery id between what the settles
and returns around it allow.  The counts that need the state are
``state_counts``'s, since the contract hands ``read_counts`` the
ledger alone.
"""
from __future__ import annotations

import numpy as np

from . import reference
from .machine import QuorumQueueMachine

_WHO_SALT = np.uint64(0x636F6E73)


def _sizes(config: dict) -> dict:
    words, rem = divmod(int(config["message_bytes"]), 4)
    if rem or words < 1:
        raise ValueError("config: message_bytes must be a multiple of 4")
    return {"words": words, "capacity": int(config["capacity"]),
            "loaded": int(config["loaded"]),
            "consumers": int(config["consumers"]),
            "prefetch": int(config["prefetch"]),
            "delivery_limit": int(config["delivery_limit"]),
            "settle_n": int(config["multi_ack_every"]),
            "return_n": int(config["requeue_n"])}


def build_machine(config: dict):
    z = _sizes(config)
    if int(config["command_words"]) != 3 + z["words"]:
        raise ValueError(
            f"config: command_words must be {3 + z['words']} "
            "([op, a, b] and one message's words)")
    return QuorumQueueMachine(
        message_words=z["words"], capacity=z["capacity"],
        loaded=z["loaded"], consumers=z["consumers"],
        prefetch=z["prefetch"], delivery_limit=z["delivery_limit"],
        seed=int(config["load_seed"]))


class Operations:
    kinds = ("publish", "settle", "return", "deliver")
    reads = ("deliver",)
    columns = {"consumer": np.int32, "salt": np.int32}

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.z = _sizes(config)

    def content(self, h: np.ndarray, kinds) -> tuple:
        with np.errstate(over="ignore"):
            g = reference._mix64(h ^ _WHO_SALT)
        who = ((g >> np.uint64(33)) % np.uint64(self.z["consumers"])) \
            .astype(np.int32)
        salt = ((h >> np.uint64(24)) & np.uint64(0x7FFFFFFF)).astype(np.int32)
        return who, salt

    def payload(self, fleet, idx: np.ndarray) -> np.ndarray:
        kind = fleet.op_kind[idx]
        pay = np.zeros((len(idx), fleet.width), np.int32)
        pub = kind == self.kinds.index("publish")
        pay[pub, 0] = 1
        pay[pub, 3:] = reference.publish_words(
            fleet.op_sess[idx[pub]], fleet.op_id[idx[pub]],
            fleet.op_salt[idx[pub]], self.z["words"])
        for name, op, n in (("settle", 2, self.z["settle_n"]),
                            ("return", 3, self.z["return_n"])):
            at = kind == self.kinds.index(name)
            pay[at, 0] = op
            pay[at, 1] = fleet.op_consumer[idx[at]]
            pay[at, 2] = n
        return pay

    def query(self, fleet, idx: np.ndarray) -> np.ndarray:
        q = np.empty((len(idx), 2), np.int32)
        q[:, 0] = 1
        q[:, 1] = fleet.op_consumer[idx]
        return q

    def admit(self, fleet, config: dict) -> None:
        if fleet.width != 3 + self.z["words"]:
            raise RuntimeError("config: the wire's payload width is not "
                               "the machine's command")


_LEAVES = ("store", "head", "tail", "out_ticket", "out_count", "lo",
           "next_id", "credit", "turn", "counts")


def leaves(mac) -> dict:
    return {k: mac[k] for k in _LEAVES}


def _of_kind(fleet, name: str) -> np.ndarray:
    return fleet.op_kind[:fleet.n_ops] == Operations.kinds.index(name)


def _who(fleet, config: dict) -> np.ndarray:
    n = fleet.n_ops
    return fleet.lanes[fleet.op_sess[:n]] * int(config["consumers"]) \
        + fleet.op_consumer[:n]


def _clocks(fleet, config: dict, name: str) -> reference.Clocks:
    """Every settle or return that was ever fed to the transport."""
    n = fleet.n_ops
    at = _of_kind(fleet, name) & ~np.isnan(fleet.op_sent[:n])
    return reference.Clocks(who=_who(fleet, config)[at],
                            sent=fleet.op_sent[:n][at],
                            acked=fleet.op_acked[:n][at])


def _per_consumer(fleet, config: dict, mask, n_lanes: int) -> np.ndarray:
    C = int(config["consumers"])
    return np.bincount(_who(fleet, config)[mask],
                       minlength=n_lanes * C).reshape((n_lanes, C))


def expected(config: dict, fleet, acked: np.ndarray) -> dict:
    """Under names no leaf has: what ``state_counts`` judges the held
    messages, the counts and the reads' messages by."""
    z = _sizes(config)
    n_lanes = int(config["clusters"])
    n = fleet.n_ops
    lane = fleet.lanes[fleet.op_sess[:n]]
    sess, op_id = fleet.op_sess[:n], fleet.op_id[:n]
    salt = fleet.op_salt[:n]
    pub = _of_kind(fleet, "publish")
    fed = ~np.isnan(fleet.op_sent[:n])
    sent = pub & fed
    want = {
        "@publishes": reference.Publishes(
            z["words"], lane=lane[pub & acked], sess=sess[pub & acked],
            op_id=op_id[pub & acked], salt=salt[pub & acked]),
        "@publishes_sent": reference.Publishes(
            z["words"], lane=lane[sent], sess=sess[sent], op_id=op_id[sent],
            salt=salt[sent]),
        "@settles": _per_consumer(fleet, config,
                                  _of_kind(fleet, "settle") & acked, n_lanes),
        "@returns": _per_consumer(fleet, config,
                                  _of_kind(fleet, "return") & acked, n_lanes),
        "@loaded": reference.Loaded(int(config["load_seed"]), n_lanes,
                                    z["loaded"], z["words"]),
        "@exact": reference.removals_exact(
            settles=_per_consumer(fleet, config,
                                  _of_kind(fleet, "settle") & fed, n_lanes),
            returns=_per_consumer(fleet, config,
                                  _of_kind(fleet, "return") & fed, n_lanes),
            loaded=z["loaded"], consumers=z["consumers"],
            prefetch=z["prefetch"], settle_n=z["settle_n"],
            return_n=z["return_n"]),
        "@sizes": z, "@load_seed": int(config["load_seed"])}
    rd = _of_kind(fleet, "deliver") & acked
    least, _most = reference.removal_bounds(
        _clocks(fleet, config, "settle"), _clocks(fleet, config, "return"),
        _who(fleet, config)[rd], fleet.op_fed[:n][rd],
        fleet.op_acked[:n][rd], settle_n=z["settle_n"],
        return_n=z["return_n"])
    want["@reads"] = {"lane": lane[rd], "consumer": fleet.op_consumer[:n][rd],
                      "reply": fleet.op_reply[:n][rd], "least": least}
    return want


def state_counts(tag: str, at_leader: dict, want: dict) -> dict:
    z = want["@sizes"]
    counts, (ids, hashes) = reference.state_judgments(
        at_leader, seed=want["@load_seed"], publishes=want["@publishes"],
        loaded=z["loaded"], capacity=z["capacity"], words=z["words"],
        prefetch=z["prefetch"], delivery_limit=z["delivery_limit"],
        settle_n=z["settle_n"], return_n=z["return_n"],
        settles=want["@settles"], returns=want["@returns"],
        exact=want["@exact"])
    rd = want["@reads"]
    counts.update(reference.read_judgments(
        rd["reply"], lane=rd["lane"], consumer=rd["consumer"],
        least=rd["least"], exact=want["@exact"][rd["lane"]],
        chunk=QuorumQueueMachine.CHUNK, words=z["words"],
        loaded=want["@loaded"], publishes_sent=want["@publishes_sent"],
        held_ids=ids, held_hashes=hashes, n_consumers=z["consumers"]))
    return {f"{tag}_{k}": v for k, v in counts.items()}


def read_counts(config: dict, fleet) -> dict:
    z = _sizes(config)
    n = fleet.n_ops
    rd = _of_kind(fleet, "deliver") & ~np.isnan(fleet.op_acked[:n])
    _least, most = reference.removal_bounds(
        _clocks(fleet, config, "settle"), _clocks(fleet, config, "return"),
        _who(fleet, config)[rd], fleet.op_fed[:n][rd],
        fleet.op_acked[:n][rd], settle_n=z["settle_n"],
        return_n=z["return_n"])
    return reference.read_consistency(
        fleet.op_reply[:n][rd], most=most, wm=fleet.op_wm[:n][rd],
        prefetch=z["prefetch"], chunk=QuorumQueueMachine.CHUNK)
