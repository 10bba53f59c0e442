"""Jitted public wrapper for the AP pass-schedule kernel.

``run_schedule`` dispatches to the Pallas kernel (``backend='pallas'``,
interpret-mode on CPU; compiled on TPU) or to the pure-jnp oracle
(``backend='jnp'``).  Both return identical results — see
tests/test_kernel_ap_match.py for the sweep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ap_match import kernel as _kernel
from repro.kernels.ap_match import ref as _ref


def run_schedule(planes: jax.Array, cmp_cols, cmp_key, w_cols, w_key, *,
                 backend: str = "pallas", block_lanes: int = 512,
                 interpret: bool | None = None):
    """Execute a full AP pass schedule.

    planes : uint32[n_bits, n_lanes]
    cmp_cols/cmp_key : [P, Kc] int32/uint32;  w_cols/w_key : [P, Kw]
    Returns (planes', matched int32[P]).
    """
    cmp_cols = jnp.asarray(cmp_cols, jnp.int32)
    cmp_key = jnp.asarray(cmp_key, jnp.uint32)
    w_cols = jnp.asarray(w_cols, jnp.int32)
    w_key = jnp.asarray(w_key, jnp.uint32)
    if backend == "pallas":
        return _kernel.run_schedule_kernel(
            planes, cmp_cols, cmp_key, w_cols, w_key,
            block_lanes=block_lanes, interpret=interpret)
    elif backend == "jnp":
        return _ref.run_schedule(planes, cmp_cols, cmp_key, w_cols, w_key)
    raise ValueError(f"unknown backend {backend!r}")
