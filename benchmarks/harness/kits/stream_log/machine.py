"""The kit's machine is the program's own: ``StreamLogMachine``, the
``JitMachine`` beside ``StreamMachine`` in ``ra_tpu/models/stream.py``
(a partitioned log of this shape is what the library's stream machine
is for, not a machine of the benchmark's).  A checkout whose program
has none (one older than the machine, with this kit laid over it) is
refused here, when the kit is loaded and before anything is built."""
from __future__ import annotations

from .. import KitError

try:
    from ra_tpu.models.stream import StreamLogMachine  # noqa: F401
except ImportError as e:
    raise KitError(
        "kit 'stream_log': this checkout's ra_tpu.models.stream has no "
        "StreamLogMachine; the program cannot run this deployment") from e
