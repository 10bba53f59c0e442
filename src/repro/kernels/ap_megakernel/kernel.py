"""Pallas megakernel: a whole AP op group in ONE kernel launch.

Where :mod:`repro.kernels.ap_match` fuses a homogeneous pass *schedule*
(compare + tagged write per row), this kernel executes a full
:class:`~repro.kernels.ap_megakernel.ref.OpGroup` micro-program —
PASS / CMP / CMP_TAG / WRITE ops with response-counter conditions —
while the plane tile stays VMEM-resident across every op: match (masked
compare), conditional write, and the popcount accumulate are fused into
a single launch instead of one XLA op chain per pass.

Tiling contract (see DESIGN.md §3.4):

* **Unconditional groups** (``cond == 0`` everywhere, e.g. bucketed
  pass schedules) tile the packed word axis exactly like ap_match: ops
  commute across word blocks, per-block popcounts are summed outside.
* **Conditional groups** (the sort/knn inner loops) branch on *global*
  responder counts, so the whole lane axis must be resident in one
  program instance (``grid=(1,)``): a block-local popcount would make
  block A take a branch block B skips.  The dispatcher
  (:mod:`.ops`) enforces this; VMEM sizing stays comfortable because
  the AP word is narrow — n_bits x n_lanes x 4 B ≈ 2.5 MiB even at
  1M elements x 20 bit-columns.

The schedule/op tables ride in SMEM through scalar prefetch (flattened
to 1-D int32, as in :mod:`repro.kernels.ap_match`); the per-op responder
counts are a (1, P) vector carried through the op loop, which is also
where a conditional op reads the count it branches on.  On the CPU the
same kernel runs under the Pallas interpreter, which is how tier-1
validates it against :func:`ref.group_scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._platform import interpret_mode
from repro.kernels.ap_match.kernel import FULL, key_mask
from repro.kernels.ap_megakernel.ref import OP_CMP, OP_CMP_TAG, OP_PASS, OP_WRITE


def _group_kernel(op_ref, cond_ref, en_ref, cc_ref, ck_ref, wc_ref, wk_ref,
                  planes_ref, tag_ref, out_planes_ref, out_tag_ref,
                  matched_ref, *, n_ops: int, kc: int, kw: int,
                  conditional: bool):
    # Bring the word-block tile (planes AND persistent tag) into the
    # output refs; every op mutates them in place — one HBM round-trip
    # for the entire group.
    out_planes_ref[...] = planes_ref[...]
    out_tag_ref[...] = tag_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, n_ops), 1)

    def one_op(p, matched):
        opc = op_ref[p]
        # ---- COMPARE: fresh tag <- AND_k XNOR(plane[col_k], key_k)
        t = jnp.full((1, out_planes_ref.shape[1]), FULL, jnp.uint32)
        for k in range(kc):                      # static unroll over columns
            row = out_planes_ref[pl.ds(cc_ref[p * kc + k], 1), :]
            t = t & ~(row ^ key_mask(ck_ref[p * kc + k]))
        cur = out_tag_ref[...]
        t = jnp.where(opc == OP_CMP_TAG, t & cur, t)
        is_wr = opc == OP_WRITE
        wtag = jnp.where(is_wr, cur, t)          # WRITE uses persistent TAG
        m = jax.lax.population_count(wtag).astype(jnp.int32).sum()
        en = en_ref[p] != 0
        if conditional:
            # response-counter predicate: ``matched`` holds this very
            # group's earlier results (single block => global counts)
            cnd = cond_ref[p]
            back = jnp.maximum(p - cnd, 0)
            prev = jnp.sum(jnp.where(slot == back, matched, 0))
            ex = en & ((cnd == 0) | (prev > 0))
        else:
            ex = en
        matched = jnp.where(slot == p, jnp.where(ex, m, 0), matched)
        # ---- WRITE: tagged rows take the key bit in each write column
        do_w = ex & (is_wr | (opc == OP_PASS))
        for k in range(kw):
            rows = pl.ds(wc_ref[p * kw + k], 1)
            row = out_planes_ref[rows, :]
            keyb = key_mask(wk_ref[p * kw + k])
            out_planes_ref[rows, :] = jnp.where(
                do_w, (row & ~wtag) | (keyb & wtag), row)
        do_t = ex & ((opc == OP_CMP) | (opc == OP_CMP_TAG))
        out_tag_ref[...] = jnp.where(do_t, t, cur)
        return matched

    matched_ref[...] = jax.lax.fori_loop(
        0, n_ops, one_op, jnp.zeros((1, n_ops), jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_lanes", "interpret",
                                             "conditional"))
def run_group_kernel(planes: jax.Array, tag: jax.Array, op: jax.Array,
                     cond: jax.Array, enabled: jax.Array, cmp_cols: jax.Array,
                     cmp_key: jax.Array, w_cols: jax.Array, w_key: jax.Array,
                     *, block_lanes: int = 512, interpret: bool | None = None,
                     conditional: bool = False):
    """One megakernel launch -> (planes', tag', matched int32[P]).

    ``conditional`` must be True iff any ``cond > 0`` (static: selects
    the single-block lowering).  Callers go through
    :func:`repro.kernels.ap_megakernel.ops.run_group`, which derives it
    from the host-side OpGroup.
    """
    n_bits, n_lanes = planes.shape
    P, kc = cmp_cols.shape
    kw = w_cols.shape[1]
    bl = n_lanes if conditional else min(block_lanes, n_lanes)
    if n_lanes % bl != 0:
        raise ValueError(f"n_lanes={n_lanes} not a multiple of block={bl}")
    n_blocks = n_lanes // bl

    kern = functools.partial(_group_kernel, n_ops=P, kc=kc, kw=kw,
                             conditional=conditional)
    tables = (op.astype(jnp.int32), cond.astype(jnp.int32),
              enabled.astype(jnp.int32),
              cmp_cols.astype(jnp.int32).reshape(-1),
              cmp_key.astype(jnp.int32).reshape(-1),
              w_cols.astype(jnp.int32).reshape(-1),
              w_key.astype(jnp.int32).reshape(-1))
    lanes = lambda i, *_: (0, i)
    planes_out, tag_out, matched = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((n_bits, bl), lanes),   # planes tile
                      pl.BlockSpec((1, bl), lanes)],       # tag tile
            out_specs=[
                pl.BlockSpec((n_bits, bl), lanes),
                pl.BlockSpec((1, bl), lanes),
                pl.BlockSpec((None, 1, P), lambda i, *_: (i, 0, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_bits, n_lanes), jnp.uint32),
            jax.ShapeDtypeStruct((1, n_lanes), jnp.uint32),
            jax.ShapeDtypeStruct((n_blocks, 1, P), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
        name="ap_megakernel",
    )(*tables, planes, tag.reshape(1, n_lanes))
    return planes_out, tag_out.reshape(n_lanes), matched.sum(axis=(0, 1))
