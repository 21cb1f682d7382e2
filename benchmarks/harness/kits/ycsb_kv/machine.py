"""The kit's machine is the program's own: ``JitRecordKvMachine``, the
``JitMachine`` beside ``JitKvMachine`` in ``ra_tpu/models/jit_kv.py``
(a store of this shape is what the library's KV machine is for, not a
machine of the benchmark's).  A checkout whose program has none (one
from before PR 32, with this kit laid over it) is refused here, when
the kit is loaded and before anything is built."""
from __future__ import annotations

from .. import KitError

try:
    from ra_tpu.models.jit_kv import JitRecordKvMachine  # noqa: F401
except ImportError as e:
    raise KitError(
        "kit 'ycsb_kv': this checkout's ra_tpu.models.jit_kv has no "
        "JitRecordKvMachine; the program cannot run this deployment") from e
