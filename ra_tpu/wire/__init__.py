"""The wire plane (ISSUE 12, ROADMAP item 2 front half): real sockets
— and their in-process loopback twin — into the ingress coalescer.

* :mod:`~ra_tpu.wire.framing` — the byte protocol: version byte,
  fixed-stride DATA records, CREDIT/ACK frames, ONE verdict enum +
  encoder shared with the fifo client's ``StopSending`` ladder.
* :class:`~ra_tpu.wire.server.WireListener` — zero-per-command reader
  + the RA09-gated vectorized sweep feeding ``IngressPlane.submit``.
* :class:`~ra_tpu.wire.client.WireClient` /
  :class:`~ra_tpu.wire.client.LoopbackFleet` — the at-least-once
  client library (pipelined seqnos, credit-driven replay, epoch-bump
  re-enqueue).
* :class:`~ra_tpu.wire.dedup.DedupCounterMachine` — machine-level
  dedup upgrading at-most-once to exactly-once-observable.
* :mod:`~ra_tpu.wire.soak` — the C10k→C1M loopback connection-ladder
  soak (``tools/soak.py --wire``).
"""
from .client import LoopbackFleet, WireClient
from .dedup import DedupCounterMachine
from .framing import (DEFER, DUP, OK, REJECT, SHED, SLOW, STATUS_NAMES,
                      WIRE_VERSION)
from .server import WireListener

__all__ = [
    "WireListener", "WireClient", "LoopbackFleet",
    "DedupCounterMachine", "WIRE_VERSION",
    "OK", "SLOW", "DEFER", "REJECT", "DUP", "SHED", "STATUS_NAMES",
]
