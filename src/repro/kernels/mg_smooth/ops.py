"""Jitted wrapper: the Pallas red-black z-line multigrid smoother.

``rb_line_sweep`` has the exact contract of
:func:`repro.core.multigrid.rb_line_sweep` (the jnp oracle) and slots
into the V-cycle through ``multigrid._resolve_sweep(use_pallas=True)``
— i.e. ``thermal.steady_state(..., solver="mg"/"mgcg",
use_pallas=True)`` smooths every level with this kernel.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.mg_smooth import kernel as _kernel


@functools.partial(jax.jit, static_argnames=("color", "block_y",
                                             "interpret"))
def rb_line_sweep(T: jax.Array, b: jax.Array, F: dict, d_extra,
                  color: int, *, block_y: int = 32,
                  interpret: bool | None = None) -> jax.Array:
    """One red-black z-line Gauss-Seidel half-sweep (Pallas path)."""
    import jax.numpy as jnp
    d_extra = jnp.broadcast_to(jnp.asarray(d_extra, T.dtype), T.shape)
    return _kernel.rb_line_sweep_kernel(
        T, b, F["gx_lf"], F["gx_rt"], F["gy_up"], F["gy_dn"],
        F["gz_up"], F["gz_dn"], F["g_pkg"], d_extra, color=color,
        block_y=block_y, interpret=interpret)
