"""The control of ``correct``: the program's one place of precision, the
one-hot ring I/O (``ra_tpu/ops/exact.py`` ``split16_matmul``, float32
products at ``Precision.HIGHEST``), computed one step lower, and the
rest of a run as it is.

    python3 benchmarks/control.py --precision high|default  <run.py's arguments>

``high`` is three bfloat16 passes, ``default`` one.  The run prints its
result line as ``run.py`` does: a control has failed where that line
says ``"correct": false``.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def lower_precision(name: str) -> None:
    """Replace ``split16_matmul`` wherever the program bound it."""
    import jax
    import jax.numpy as jnp

    import ra_tpu.engine.lockstep as lockstep
    import ra_tpu.ops.exact as exact

    precision = {"high": jax.lax.Precision.HIGH,
                 "default": jax.lax.Precision.DEFAULT}[name]

    def split16_matmul(onehot_f32, values):
        lo = (values & 0xFFFF).astype(jnp.float32)
        hi = ((values >> 16) & 0xFFFF).astype(jnp.float32)
        glo = jnp.einsum("...ar,...rc->...ac", onehot_f32, lo,
                         precision=precision).astype(jnp.int32)
        ghi = jnp.einsum("...ar,...rc->...ac", onehot_f32, hi,
                         precision=precision).astype(jnp.int32)
        return glo | (ghi << 16)

    exact.split16_matmul = split16_matmul
    lockstep.split16_matmul = split16_matmul


def main(argv) -> int:
    if len(argv) < 2 or argv[0] != "--precision":
        print(__doc__, file=sys.stderr)
        return 2
    lower_precision(argv[1])
    from benchmarks import run
    return run.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
