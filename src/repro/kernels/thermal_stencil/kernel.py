"""Pallas TPU kernel: one application of the 3D RC thermal operator  y = G T.

The HotSpot-equivalent steady-state solve (paper §4) is CG on the SPD
conductance system; >95% of its time is the 7-point stencil sweep, so that
sweep is the kernel.  T is [L, ny, nx]: the silicon layers plus the
grid-resolved copper spreader, each with its own lateral sheet conductance
``g_lat[l]`` and per-interface vertical conductances (fed in pre-padded as
gv_up/gv_dn/g_pkg per-layer vectors, so the kernel formula is uniform:

    y = g_lat*(4T - N4) + gv_up*(T - T_above) + gv_dn*(T - T_below) + g_pkg*T

with zero entries encoding the adiabatic top and the lump-coupled bottom).

TPU adaptation: the grid tiles the **y axis**; each program holds an
(L, BLOCK_Y, nx) tile in VMEM — full layer depth and full x rows, so the
vertical and x couplings never leave VMEM.  The y halo comes from passing
the SAME array with index maps i-1 / i+1 (clamped); boundary programs
replicate their own edge row (adiabatic = zero difference), matching the
reference operator exactly.  BLOCK_Y is 8-aligned or the whole axis
(:func:`repro.kernels._platform.row_block`); the per-layer conductance
vectors of the uniform operator ride in SMEM and are read as scalars.
VPU-only elementwise work — memory-bound by design (arithmetic
intensity ~1 flop/byte).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._platform import interpret_mode, row_block


def _neighbors(C, up_ref, dn_ref, n_blocks: int):
    """The six neighbor planes of a tile (edge-replicated at every
    global boundary; the y halo rows come from the adjacent tiles)."""
    i = pl.program_id(0)
    above = jnp.where(i > 0, up_ref[:, -1:, :], C[:, :1, :])
    below = jnp.where(i < n_blocks - 1, dn_ref[:, :1, :], C[:, -1:, :])
    t_up = jnp.concatenate([above, C[:, :-1, :]], axis=1)
    t_dn = jnp.concatenate([C[:, 1:, :], below], axis=1)
    t_lf = jnp.concatenate([C[:, :, :1], C[:, :, :-1]], axis=2)
    t_rt = jnp.concatenate([C[:, :, 1:], C[:, :, -1:]], axis=2)
    l_up = jnp.concatenate([C[:1], C[:-1]], axis=0)
    l_dn = jnp.concatenate([C[1:], C[-1:]], axis=0)
    return t_up, t_dn, t_lf, t_rt, l_up, l_dn


def _stencil_kernel(gl_ref, gu_ref, gd_ref, gp_ref, c_ref, up_ref, dn_ref,
                    y_ref, *, n_blocks: int):
    C = c_ref[...]                                   # [L, BY, nx]
    t_up, t_dn, t_lf, t_rt, l_up, l_dn = _neighbors(C, up_ref, dn_ref,
                                                    n_blocks)
    for l in range(C.shape[0]):                      # per-layer SMEM scalars
        y_ref[l] = gl_ref[l] * (4.0 * C[l] - t_up[l] - t_dn[l] - t_lf[l]
                                - t_rt[l]) \
            + gu_ref[l] * (C[l] - l_up[l]) + gd_ref[l] * (C[l] - l_dn[l]) \
            + gp_ref[l] * C[l]


def _field_kernel(c_ref, up_ref, dn_ref, gxl_ref, gxr_ref, gyu_ref, gyd_ref,
                  gzu_ref, gzd_ref, gp_ref, y_ref, *, n_blocks: int):
    """Heterogeneous per-face conductances (zero face = adiabatic):
    the production operator — silicon exists only over the die footprint,
    the spreader spans the full (die + margin) domain."""
    C = c_ref[...]
    t_up, t_dn, t_lf, t_rt, l_up, l_dn = _neighbors(C, up_ref, dn_ref,
                                                    n_blocks)
    y_ref[...] = gxl_ref[...] * (C - t_lf) + gxr_ref[...] * (C - t_rt) \
        + gyu_ref[...] * (C - t_up) + gyd_ref[...] * (C - t_dn) \
        + gzu_ref[...] * (C - l_up) + gzd_ref[...] * (C - l_dn) \
        + gp_ref[...] * C


def _tile_specs(L: int, by: int, nx: int, n_blocks: int):
    """(own tile, tile above, tile below) BlockSpecs over the y axis."""
    tile = pl.BlockSpec((L, by, nx), lambda i: (0, i, 0))
    spec_up = pl.BlockSpec((L, by, nx),
                           lambda i: (0, jnp.maximum(i - 1, 0), 0))
    spec_dn = pl.BlockSpec((L, by, nx),
                           lambda i: (0, jnp.minimum(i + 1, n_blocks - 1), 0))
    return tile, spec_up, spec_dn


@functools.partial(jax.jit, static_argnames=("block_y", "interpret"))
def apply_operator_fields_kernel(T: jax.Array, gx_lf, gx_rt, gy_up, gy_dn,
                                 gz_up, gz_dn, g_pkg, *, block_y: int = 32,
                                 interpret: bool | None = None) -> jax.Array:
    L, ny, nx = T.shape
    by = row_block(ny, block_y)
    n_blocks = ny // by
    tile, spec_up, spec_dn = _tile_specs(L, by, nx, n_blocks)
    return pl.pallas_call(
        functools.partial(_field_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[tile, spec_up, spec_dn] + [tile] * 7,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, ny, nx), T.dtype),
        interpret=interpret_mode(interpret),
        name="thermal_stencil_fields",
    )(T, T, T, gx_lf, gx_rt, gy_up, gy_dn, gz_up, gz_dn, g_pkg)


@functools.partial(jax.jit, static_argnames=("block_y", "interpret"))
def apply_operator_kernel(T: jax.Array, g_lat: jax.Array, gv_up: jax.Array,
                          gv_dn: jax.Array, g_pkg_vec: jax.Array, *,
                          block_y: int = 32, interpret: bool | None = None
                          ) -> jax.Array:
    L, ny, nx = T.shape
    by = row_block(ny, block_y)
    n_blocks = ny // by
    tile, spec_up, spec_dn = _tile_specs(L, by, nx, n_blocks)
    vec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_stencil_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[vec, vec, vec, vec, tile, spec_up, spec_dn],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, ny, nx), T.dtype),
        interpret=interpret_mode(interpret),
        name="thermal_stencil",
    )(g_lat, gv_up, gv_dn, g_pkg_vec, T, T, T)
