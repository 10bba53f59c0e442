"""Pallas TPU kernel: one red-black z-line Gauss-Seidel half-sweep.

The multigrid smoother (``core/multigrid.rb_line_sweep``) is the hot
loop of every V-cycle: for each in-plane cell of one checkerboard color,
solve the cell's vertical (stack-axis) tridiagonal system exactly with
the lateral neighbors frozen.  This kernel mirrors the jnp oracle
tile-for-tile using the ``kernels/thermal_stencil`` layout: the grid
tiles the y axis, each program holds an (L, BLOCK_Y, nx) tile in VMEM
(full layer depth + full x rows, so the Thomas recursion over the 5-9
layers and the x couplings never leave VMEM), and the y halo comes from
passing T again with clamped i-1 / i+1 index maps.

The Thomas forward/backward recursion unrolls over the static layer
count — short vector ops on [BLOCK_Y, nx] planes, VPU-only, memory-bound
like the stencil kernel.  The checkerboard mask needs the GLOBAL row
index (parity must be consistent across tiles): ``i * BLOCK_Y +
iota_y``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._platform import interpret_mode, row_block


def _rb_line_kernel(t_ref, up_ref, dn_ref, b_ref, gxl_ref, gxr_ref,
                    gyu_ref, gyd_ref, gzu_ref, gzd_ref, gp_ref, de_ref,
                    y_ref, *, color: int, block_y: int, n_blocks: int):
    i = pl.program_id(0)
    T = t_ref[...]                                   # [L, BY, nx]
    L, by, nx = T.shape

    # y halo rows: neighbor tile edge, or own edge at the global boundary
    above = jnp.where(i > 0, up_ref[:, -1:, :], T[:, :1, :])
    below = jnp.where(i < n_blocks - 1, dn_ref[:, :1, :], T[:, -1:, :])
    t_up = jnp.concatenate([above, T[:, :-1, :]], axis=1)
    t_dn = jnp.concatenate([T[:, 1:, :], below], axis=1)
    t_lf = jnp.concatenate([T[:, :, :1], T[:, :, :-1]], axis=2)
    t_rt = jnp.concatenate([T[:, :, 1:], T[:, :, -1:]], axis=2)

    gxl, gxr = gxl_ref[...], gxr_ref[...]
    gyu, gyd = gyu_ref[...], gyd_ref[...]
    gzu, gzd = gzu_ref[...], gzd_ref[...]
    gp, de = gp_ref[...], de_ref[...]

    rhs = b_ref[...] + gxl * t_lf + gxr * t_rt + gyu * t_up + gyd * t_dn
    diag = gxl + gxr + gyu + gyd + gzu + gzd + gp + de
    diag = jnp.where(diag > 0, diag, 1.0)
    lo = -gzu                      # coupling to layer l-1 (zero at l = 0)
    up = -gzd                      # coupling to layer l+1 (zero at L-1)

    # Thomas over the (small, static) layer axis
    cp = [up[0] / diag[0]]
    dp = [rhs[0] / diag[0]]
    for l in range(1, L):
        denom = diag[l] - lo[l] * cp[-1]
        denom = jnp.where(jnp.abs(denom) > 0, denom, 1.0)
        cp.append(up[l] / denom)
        dp.append((rhs[l] - lo[l] * dp[-1]) / denom)
    u = [dp[-1]]
    for l in range(L - 2, -1, -1):
        u.append(dp[l] - cp[l] * u[-1])
    u = jnp.stack(u[::-1], axis=0)

    # global checkerboard parity: (global_y + x) % 2 == color
    gy = i * block_y + jax.lax.broadcasted_iota(jnp.int32, (by, nx), 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, (by, nx), 1)
    mask = ((gy + xx) % 2 == color)[None]
    y_ref[...] = jnp.where(mask, u, T)


@functools.partial(jax.jit, static_argnames=("color", "block_y",
                                             "interpret"))
def rb_line_sweep_kernel(T: jax.Array, b: jax.Array, gx_lf, gx_rt, gy_up,
                         gy_dn, gz_up, gz_dn, g_pkg, d_extra, *,
                         color: int, block_y: int = 32,
                         interpret: bool | None = None) -> jax.Array:
    L, ny, nx = T.shape
    by = row_block(ny, block_y)
    n_blocks = ny // by

    kern = functools.partial(_rb_line_kernel, color=color, block_y=by,
                             n_blocks=n_blocks)
    tile = pl.BlockSpec((L, by, nx), lambda i: (0, i, 0))
    spec_up = pl.BlockSpec((L, by, nx),
                           lambda i: (0, jnp.maximum(i - 1, 0), 0))
    spec_dn = pl.BlockSpec((L, by, nx),
                           lambda i: (0, jnp.minimum(i + 1, n_blocks - 1), 0))
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[tile, spec_up, spec_dn] + [tile] * 9,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, ny, nx), T.dtype),
        interpret=interpret_mode(interpret),
        name="mg_smooth",
    )(T, T, T, b, gx_lf, gx_rt, gy_up, gy_dn, gz_up, gz_dn, g_pkg, d_extra)
