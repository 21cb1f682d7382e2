"""The kit's machine is the program's own: ``QuorumQueueMachine``, the
``JitMachine`` in ``ra_tpu/models/quorum_queue.py`` (a quorum queue of
this shape is what the library's queue machine is for, not a machine of
the benchmark's).
A checkout whose program has none (one older than the machine, with
this kit laid over it) is refused here, when the kit is loaded and
before anything is built."""
from __future__ import annotations

from .. import KitError

try:
    from ra_tpu.models.quorum_queue import QuorumQueueMachine  # noqa: F401
except ImportError as e:
    raise KitError(
        "kit 'quorum_queue': this checkout's ra_tpu has no "
        "models.quorum_queue.QuorumQueueMachine; the program cannot run "
        "this deployment") from e
