"""Pallas TPU kernel: AP compare + tagged-write pass schedule over bitplanes.

This is the hot loop of the Associative Processor emulation (paper §2.1):
every pass COMPAREs up to Kc bit-columns against a key (AND of per-column
XNORs -> packed TAG) and then WRITEs up to Kw bit-columns of all tagged words.

TPU adaptation of the CAM (DESIGN.md §2): the physical AP activates all
columns of one *word block* simultaneously (columns share match lines).  We
re-block the same layout for the HBM->VMEM hierarchy: the grid tiles the
packed **word axis** (lanes of 32 words), one `(n_bits, BLOCK_LANES)` tile of
the plane array is VMEM-resident per program, and *all* passes stream over it
before it is written back — one HBM round-trip per tile for the entire
schedule, instead of one per pass.  Passes commute across word blocks (all AP
ops are word-parallel; rows never interact), so the loop interchange is exact.

VMEM budget: `n_bits * BLOCK_LANES * 4` bytes for the tile (256 x 512 lanes =
512 KiB) — comfortably inside the ~16 MiB/core VMEM of TPU v5e.  The
schedule tables (columns and key bits, flattened to 1-D int32) ride in
SMEM through scalar prefetch, so every column index is a scalar that
addresses one plane row dynamically.  Per-pass responder counts are
carried as one (1, P) vector and stored once per tile into a
``(n_blocks, 1, P)`` output (a block whose last two dims are the array's).

Padding contract: ``PassSchedule`` pads column tables by repeating entry 0,
which is idempotent for compare and write, so the kernel can loop to the
static Kc/Kw bounds without masking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._platform import interpret_mode

FULL = 0xFFFFFFFF  # python int: avoids capturing a traced const in the kernel


def key_mask(bit) -> jax.Array:
    """A key bit (0/1 scalar) as an all-zeros / all-ones uint32 word."""
    return bit.astype(jnp.uint32) * jnp.uint32(FULL)


def _pass_kernel(cmp_cols_ref, cmp_key_ref, w_cols_ref, w_key_ref,
                 planes_ref, out_planes_ref, matched_ref, *, n_passes: int,
                 kc: int, kw: int):
    # Bring the word-block tile into the output ref; all passes mutate it
    # in place (VMEM-resident RMW), written back to HBM once at the end.
    out_planes_ref[...] = planes_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, n_passes), 1)

    def one_pass(p, matched):
        # ---- COMPARE: TAG <- AND_k XNOR(plane[col_k], key_k)
        tag = jnp.full((1, out_planes_ref.shape[1]), FULL, jnp.uint32)
        for k in range(kc):                      # static unroll over columns
            row = out_planes_ref[pl.ds(cmp_cols_ref[p * kc + k], 1), :]
            tag = tag & ~(row ^ key_mask(cmp_key_ref[p * kc + k]))
        m = jax.lax.population_count(tag).astype(jnp.int32).sum()
        # ---- WRITE: tagged rows take the key bit in each write column
        for k in range(kw):
            rows = pl.ds(w_cols_ref[p * kw + k], 1)
            row = out_planes_ref[rows, :]
            keyb = key_mask(w_key_ref[p * kw + k])
            out_planes_ref[rows, :] = (row & ~tag) | (keyb & tag)
        return jnp.where(slot == p, m, matched)

    matched_ref[...] = jax.lax.fori_loop(
        0, n_passes, one_pass, jnp.zeros((1, n_passes), jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_lanes", "interpret"))
def run_schedule_kernel(planes: jax.Array, cmp_cols: jax.Array,
                        cmp_key: jax.Array, w_cols: jax.Array,
                        w_key: jax.Array, *, block_lanes: int = 512,
                        interpret: bool | None = None):
    """planes: uint32[n_bits, n_lanes] -> (planes', matched int32[P])."""
    n_bits, n_lanes = planes.shape
    P, kc = cmp_cols.shape
    kw = w_cols.shape[1]
    bl = min(block_lanes, n_lanes)
    if n_lanes % bl != 0:
        raise ValueError(f"n_lanes={n_lanes} not a multiple of block={bl}")
    n_blocks = n_lanes // bl

    kern = functools.partial(_pass_kernel, n_passes=P, kc=kc, kw=kw)
    tables = (cmp_cols.astype(jnp.int32).reshape(-1),
              cmp_key.astype(jnp.int32).reshape(-1),
              w_cols.astype(jnp.int32).reshape(-1),
              w_key.astype(jnp.int32).reshape(-1))
    planes_out, matched = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((n_bits, bl), lambda i, *_: (0, i))],
            out_specs=[
                pl.BlockSpec((n_bits, bl), lambda i, *_: (0, i)),
                pl.BlockSpec((None, 1, P), lambda i, *_: (i, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_bits, n_lanes), jnp.uint32),
            jax.ShapeDtypeStruct((n_blocks, 1, P), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
        name="ap_match",
    )(*tables, planes)
    return planes_out, matched.sum(axis=(0, 1))
