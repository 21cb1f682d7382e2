"""The YCSB record store's kit: core workload B's two operations on
``ra_tpu.models.jit_kv.JitRecordKvMachine``, one store a cluster.

``update`` writes one field (``field_words`` int32 words: the op id and
words mixed from session, op id and a salt) of one record and goes out
as a command ``[1, key, field, value words]``; ``read`` returns the
whole record and goes out as a ``T_READ`` query ``[1, key]``.  The key
of either is drawn as YCSB's ``ScrambledZipfianGenerator`` draws it
(written from memory of YCSB's source, no network here): a zipfian rank
over 10^10 items by Gray et al.'s closed form with the constant the mix
states (``zipf_s``, 0.99) and YCSB's precomputed zeta for it, then
FNV-1a of the rank, modulo the store's records, so that the hot keys
scatter over the key space.  The field is uniform.

The ledger keeps of an op its key, its field and its salt; the value
words are made where the payload is, and again where the reference
checks what a field holds (``reference.value_words``), never stored.
"""
from __future__ import annotations

import numpy as np

from .. import leaf_counts
from . import reference
from .machine import JitRecordKvMachine

#: YCSB ScrambledZipfianGenerator: the zipfian it scrambles is over this
#: many items whatever the store holds, with zeta(ITEM_COUNT, 0.99)
#: precomputed
ITEM_COUNT = 10_000_000_000
ZETAN_099 = 26.46902820178302
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
_FIELD_SALT = np.uint64(0x6669656C64)


def _sizes(config: dict) -> tuple:
    words, rem = divmod(int(config["field_bytes"]), 4)
    if rem or words < 1:
        raise ValueError("config: field_bytes must be a multiple of 4")
    return int(config["records"]), int(config["fields"]), words


def build_machine(config: dict):
    records, fields, words = _sizes(config)
    if int(config["command_words"]) != 3 + words:
        raise ValueError(
            f"config: command_words must be {3 + words} "
            "([op, key, field] and one field's words)")
    return JitRecordKvMachine(records=records, fields=fields,
                              field_words=words,
                              seed=int(config["load_seed"]))


def fnv64(x: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the value's eight octets,
    lowest first, made non-negative."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, _FNV_OFFSET)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (x & np.uint64(0xFF))) * _FNV_PRIME
            x = x >> np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian_rank(u: np.ndarray, theta: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` over ``ITEM_COUNT`` items from
    a uniform ``u`` in [0, 1): Gray et al., "Quickly generating
    billion-record synthetic databases" (SIGMOD 1994)."""
    if abs(theta - 0.99) > 1e-12:
        raise ValueError("kit: YCSB precomputes zeta for 0.99 alone")
    zetan = ZETAN_099
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / ITEM_COUNT) ** (1.0 - theta)) \
        / (1.0 - zeta2 / zetan)
    uz = u * zetan
    rank = (ITEM_COUNT * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    return np.where(uz < 1.0, 0, rank)


class Operations:
    kinds = ("update", "read")
    reads = ("read",)
    columns = {"key": np.int32, "field": np.int32, "salt": np.int32}

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.records, self.fields, self.words = _sizes(config)
        self.theta = float(mix.get("zipf_s", 0.99))

    def content(self, h: np.ndarray, kinds) -> tuple:
        u = (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)
        key = (fnv64(zipfian_rank(u, self.theta))
               % self.records).astype(np.int32)
        with np.errstate(over="ignore"):
            g = reference._mix64(h ^ _FIELD_SALT)
        field = (g % np.uint64(self.fields)).astype(np.int32)
        salt = ((g >> np.uint64(24)) & np.uint64(0x7FFFFFFF)) \
            .astype(np.int32)
        return key, field, salt

    def payload(self, fleet, idx: np.ndarray) -> np.ndarray:
        pay = np.empty((len(idx), fleet.width), np.int32)
        pay[:, 0] = 1
        pay[:, 1] = fleet.op_key[idx]
        pay[:, 2] = fleet.op_field[idx]
        pay[:, 3:] = reference.value_words(
            fleet.op_sess[idx], fleet.op_id[idx], fleet.op_salt[idx],
            self.words)
        return pay

    def query(self, fleet, idx: np.ndarray) -> np.ndarray:
        q = np.empty((len(idx), 2), np.int32)
        q[:, 0] = 1
        q[:, 1] = fleet.op_key[idx]
        return q

    def admit(self, fleet, config: dict) -> None:
        if fleet.width != 3 + self.words:
            raise RuntimeError("config: the wire's payload width is not "
                               "the machine's command")


def leaves(mac) -> dict:
    return {"rec": mac["rec"], "ver": mac["ver"], "sum": mac["sum"]}


def _is_update(fleet) -> np.ndarray:
    return ~fleet.read_kind[fleet.op_kind[:fleet.n_ops]]


def _updates(config: dict, fleet) -> reference.Updates:
    """Every update that was ever fed to the transport."""
    records, fields, words = _sizes(config)
    n = fleet.n_ops
    which = _is_update(fleet) & ~np.isnan(fleet.op_sent[:n])
    sess = fleet.op_sess[:n][which]
    return reference.Updates(
        records, fields, words, lane=fleet.lanes[sess],
        key=fleet.op_key[:n][which], field=fleet.op_field[:n][which],
        sess=sess, op_id=fleet.op_id[:n][which],
        salt=fleet.op_salt[:n][which], sent=fleet.op_sent[:n][which],
        acked=fleet.op_acked[:n][which])


def expected(config: dict, fleet, acked: np.ndarray) -> dict:
    """``ver`` and ``sum`` as the fold of the acknowledged updates; and,
    under names no leaf has, what ``state_counts`` checks the fields
    against: every update that was ever fed, and the load's seed."""
    n = fleet.n_ops
    upd = _is_update(fleet)
    done = upd & acked
    sess = fleet.op_sess[:n][done]
    want = reference.fold(int(config["clusters"]), int(config["records"]),
                          lane=fleet.lanes[sess],
                          key=fleet.op_key[:n][done],
                          op_id=fleet.op_id[:n][done])
    want["@updates"] = _updates(config, fleet)
    want["@load_seed"] = int(config["load_seed"])
    return want


def state_counts(tag: str, at_leader: dict, want: dict) -> dict:
    out = leaf_counts(tag, at_leader, {k: v for k, v in want.items()
                                       if not k.startswith("@")})
    fields = reference.field_counts(want["@load_seed"], want["@updates"],
                                    at_leader["rec"])
    out.update((f"{tag}_{k}", v) for k, v in fields.items())
    return out


def read_counts(config: dict, fleet) -> dict:
    n = fleet.n_ops
    rd = ~_is_update(fleet) & ~np.isnan(fleet.op_acked[:n])
    sess = fleet.op_sess[:n][rd]
    reply = fleet.op_reply[:n][rd]
    return reference.read_counts(
        int(config["load_seed"]), _updates(config, fleet),
        lane=fleet.lanes[sess], key=fleet.op_key[:n][rd],
        fed=fleet.op_sent[:n][rd], seen=fleet.op_acked[:n][rd],
        present=reply[:, 0], wm=fleet.op_wm[:n][rd], reply=reply[:, 1:])
