"""Platform rules shared by every Pallas kernel wrapper.

* **Interpret or compile.**  A kernel runs in the Pallas interpreter
  only where the default backend is the CPU (tests and CPU rehearsals);
  on a TPU it always goes through the Mosaic compiler.  An explicit
  ``interpret=True`` / ``False`` from the caller wins.
* **Row tiling.**  A block's second-minor dimension must be a multiple
  of 8 or the whole axis (the TPU's (8, 128) tile), so the y-tiled
  thermal kernels pick their rows per tile through :func:`row_block`.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag (None = decide from the
    platform: interpreted iff the default backend is the CPU)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def row_block(n: int, block: int) -> int:
    """Rows per tile over an axis of ``n`` rows: the largest multiple of
    8 that is at most ``block`` and divides ``n``, else all ``n`` rows."""
    for by in range(min(block, n) // 8 * 8, 0, -8):
        if n % by == 0:
            return by
    return n
