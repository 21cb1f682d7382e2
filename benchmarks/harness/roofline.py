"""The least work the step has to do, whatever implements it.

Counted from the traced window's own counts, not from a pass over the
ring (which an O(K) append would beat, and read over 100%):

* for each acknowledged operation, 3 x 4C bytes: the block row read in,
  the ring row written, the apply window's row read (C = 64 words);
* for each round run, one read and one write of every per-lane and
  per-member int32 vector of ``LaneState`` without the ring.

The step does no floating-point work that the algorithm needs (the
one-hot matmuls are an implementation of a gather), so the bound is the
bytes over the chip's peak bytes/s.
"""
from __future__ import annotations

#: int32 vectors that every round reads and writes, counted once from
#: ``LaneState`` (``ra_tpu/engine/lockstep.py``) as this benchmark found
#: it and fixed here, so that a leaner state reads as a better share and
#: not as less work: 17 [N] fields (term .. read_leased, telemetry and
#: the read buffer left out) and 9 [N, P] fields (last_index,
#: last_written, match, next_index, commit, applied, peer_query and the
#: machine's value and check; the bool masks, the ring and the
#: machine's slot table left out)
PER_LANE_VECTORS = 17
PER_MEMBER_VECTORS = 9


def step_min_bytes(*, ops: int, rounds: int, lanes: int, members: int,
                   words: int = 64) -> float:
    per_op = 3 * 4 * words
    per_round = 2 * 4 * (PER_LANE_VECTORS * lanes
                         + PER_MEMBER_VECTORS * lanes * members)
    return float(ops * per_op + rounds * per_round)


def step_roofline_pct(*, ops: int, rounds: int, lanes: int, members: int,
                      step_device_s: float, peak_bytes_per_s: float,
                      chips: int = 1) -> float:
    """Share of the roofline: least time at peak over the time taken.
    ``step_device_s`` is the mean per chip; the bytes are the whole
    fleet's, divided over the chips."""
    least_s = step_min_bytes(ops=ops, rounds=rounds, lanes=lanes,
                             members=members) / chips / peak_bytes_per_s
    return 100.0 * least_s / step_device_s
