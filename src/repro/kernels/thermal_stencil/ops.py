"""Jitted wrappers: Pallas thermal stencil + CG solve built on it.

``cg_solve`` mirrors :func:`repro.core.thermal._cg_solve` (Jacobi-
preconditioned CG) with the stencil application replaced by the Pallas
kernel; ``repro.core.thermal.steady_state(use_pallas=True)`` routes here.
Conductances may be scalars or per-layer vectors (see core.thermal).
"""
from __future__ import annotations

import functools

import jax

from repro.core.thermal import _vectors
from repro.kernels.thermal_stencil import kernel as _kernel


def apply_operator(T: jax.Array, g_lat, g_vert, g_pkg, *,
                   block_y: int = 32,
                   interpret: bool | None = None) -> jax.Array:
    """y = G @ T (same contract as core.thermal.apply_operator)."""
    L = T.shape[0]
    g_lat, gv_u, gv_d, g_pkg_vec = _vectors(L, g_lat, g_vert, g_pkg)
    return _kernel.apply_operator_kernel(
        T, g_lat, gv_u, gv_d, g_pkg_vec, block_y=block_y,
        interpret=interpret)


def apply_operator_fields(T: jax.Array, F: dict, *, block_y: int = 32,
                          interpret: bool | None = None) -> jax.Array:
    """Heterogeneous operator (same contract as
    core.thermal.apply_operator_fields)."""
    return _kernel.apply_operator_fields_kernel(
        T, F["gx_lf"], F["gx_rt"], F["gy_up"], F["gy_dn"], F["gz_up"],
        F["gz_dn"], F["g_pkg"], block_y=block_y, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_iter", "block_y",
                                             "interpret"))
def cg_solve_fields_stats(b: jax.Array, F: dict, tol: float = 1e-8,
                          max_iter: int = 8000, block_y: int = 32,
                          interpret: bool | None = None):
    """Jacobi-preconditioned CG on the heterogeneous Pallas stencil.

    Returns ``(x, n_iterations)`` like :func:`repro.core.thermal.pcg`.
    """
    from repro.core.thermal import _diag_fields, pcg
    A = lambda v: apply_operator_fields(v, F, block_y=block_y,
                                        interpret=interpret)
    return pcg(A, 1.0 / _diag_fields(F), b, tol, max_iter)


def cg_solve_fields(b: jax.Array, F: dict, tol: float = 1e-8,
                    max_iter: int = 8000, block_y: int = 32,
                    interpret: bool | None = None) -> jax.Array:
    return cg_solve_fields_stats(b, F, tol, max_iter, block_y,
                                 interpret)[0]


@functools.partial(jax.jit, static_argnames=("max_iter", "block_y",
                                             "interpret"))
def cg_solve(b: jax.Array, diag: jax.Array, g_lat, g_vert, g_pkg,
             tol: float = 1e-8, max_iter: int = 6000,
             block_y: int = 32, interpret: bool | None = None) -> jax.Array:
    """Jacobi-preconditioned CG for G T = b with the Pallas stencil."""
    from repro.core.thermal import pcg
    L = b.shape[0]
    g_lat, gv_u, gv_d, g_pkg_vec = _vectors(L, g_lat, g_vert, g_pkg)
    A = lambda v: _kernel.apply_operator_kernel(
        v, g_lat, gv_u, gv_d, g_pkg_vec, block_y=block_y,
        interpret=interpret)
    return pcg(A, 1.0 / diag, b, tol, max_iter)[0]


