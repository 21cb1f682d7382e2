"""The plain reference: the same operations folded in numpy.

Imports nothing of ``ra_tpu`` and takes nothing the program computed.
Its inputs are the client's own ledger: for every operation the address
of its session (cluster and dedup slot, as the client learned them at
connect), its op id, its delta and its 256-byte body.  Its output is
what every replica of every cluster has to hold once those operations
have been applied exactly once each: the counter, the body checksum and
the per-slot op-id watermark.
"""
from __future__ import annotations

import numpy as np

WIDTH = 64
BODY = WIDTH - 3
#: the machine's per-position odd weights, stated again here on purpose:
#: the reference shares no code with the machine it checks
WEIGHTS = (2 * np.arange(BODY, dtype=np.int64) + 3)

_MASK31 = np.int32(0x7FFFFFFF)


def make_pool(seed: int, rows: int = 1 << 14) -> np.ndarray:
    """``rows`` bodies of 61 words over [0, 2^31) from the seed: both
    16-bit halves of every word are live."""
    rng = np.random.default_rng([int(seed), 0xB0D1])
    return rng.integers(0, 1 << 31, (rows, BODY), dtype=np.int64) \
        .astype(np.int32)


def body_words(pool: np.ndarray, rows: np.ndarray,
               salts: np.ndarray) -> np.ndarray:
    """An op's body: its pool row with every word xor-ed by the op's
    salt, kept in [0, 2^31).  A gather and a xor, so that the generator
    stays cheap beside the system it loads, and every op's 61 words
    still differ from every other's."""
    salts = np.asarray(salts, np.int32)
    return (pool[rows] ^ salts[:, None]) & _MASK31


def wrap32(x: np.ndarray) -> np.ndarray:
    """int64 -> the int32 the device holds (two's-complement wrap)."""
    return (x & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def fold(n_lanes: int, slots: int, pool: np.ndarray, *, lane, slot, op_id,
         delta, row, salt, block: int = 1 << 18) -> dict:
    """Apply every listed op once.  Returns value[N], check[N] (int32,
    wrapped) and seq[N, slots] (the highest op id applied per slot)."""
    lane = np.asarray(lane, np.int64)
    slot = np.asarray(slot, np.int64)
    if len(lane) and (slot.min() < 0 or slot.max() >= slots):
        raise ValueError("reference: a session's slot is outside the "
                         "machine's slot table")
    value = np.zeros(n_lanes, np.int64)
    check = np.zeros(n_lanes, np.int64)
    seq = np.zeros((n_lanes, slots), np.int64)
    np.add.at(value, lane, np.asarray(delta, np.int64))
    np.maximum.at(seq, (lane, slot), np.asarray(op_id, np.int64))
    for lo in range(0, len(lane), block):
        hi = min(lo + block, len(lane))
        words = body_words(pool, row[lo:hi], salt[lo:hi]).astype(np.int64)
        # mod 2^32 is a ring homomorphism: wrap once per block
        part = (words * WEIGHTS).sum(axis=1) & 0xFFFFFFFF
        np.add.at(check, lane[lo:hi], part)
        check &= 0xFFFFFFFF
    return {"value": wrap32(value), "check": wrap32(check),
            "seq": seq.astype(np.int32)}


def addresses_distinct(lane, slot, slots: int) -> bool:
    """No two sessions share (cluster, slot): the dedup identity the
    comparison rests on."""
    key = np.asarray(lane, np.int64) * slots + np.asarray(slot, np.int64)
    return len(np.unique(key)) == len(key)
