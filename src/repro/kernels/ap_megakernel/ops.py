"""Dispatch layer for the AP megakernel.

``run_group`` is the one entry point: it executes an
:class:`~repro.kernels.ap_megakernel.ref.OpGroup` against (planes, tag)
via

* ``backend="jnp"``     — the fused-scan reference executor (CPU/GPU),
* ``backend="pallas"``  — the VMEM-resident Pallas kernel
  (``interpret=True`` on CPU),

optionally sharded over the packed word-lane axis with ``mesh=`` (a 1D
``'lanes'`` mesh from :func:`repro.parallel.sharding.ap_mesh`): each
device holds a plane/tag slice, responder popcounts are ``psum``-ed
before any conditional consumes them, so results are bitwise invariant
to the device count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ap_megakernel import ref
from repro.kernels.ap_megakernel.kernel import run_group_kernel
from repro.kernels.ap_megakernel.ref import OpGroup


@jax.jit
def _run_group_jnp(planes, tag, op, cond, enabled, cc, ck, wc, wk):
    return ref.group_scan(planes, tag, (op, cond, cc, ck, wc, wk), enabled)


@functools.lru_cache(maxsize=None)
def _sharded_runner(mesh):
    """jit(shard_map(group_scan)) over the 'lanes' axis, cached per mesh.

    Plane columns and the tag shard over lanes; the op tables are
    replicated; matched/executed come back replicated (the psum inside
    ``group_scan`` makes every shard compute identical counts — integer
    addition is exact in any order, hence device-count invariance).
    """
    def body(planes, tag, op, cond, enabled, cc, ck, wc, wk):
        return ref.group_scan(planes, tag, (op, cond, cc, ck, wc, wk),
                              enabled, axis_name="lanes")

    rep = P()
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "lanes"), P("lanes"), rep, rep, rep, rep, rep,
                  rep, rep),
        out_specs=(P(None, "lanes"), P("lanes"), rep, rep),
        check_vma=False)

    @jax.jit
    def run(planes, tag, op, cond, enabled, cc, ck, wc, wk):
        return mapped(planes, tag, op, cond, enabled, cc, ck, wc, wk)

    return run


def run_group(planes, tag, group: OpGroup, enabled=None, *,
              backend: str = "jnp", mesh=None, block_lanes: int = 512,
              interpret: bool | None = None):
    """Execute one op group -> (planes', tag', matched int32[P]).

    enabled : optional bool[P] dynamic op mask (default: all on)
    mesh    : optional 1D 'lanes' mesh — shards planes/tag over devices
              (jnp backend only; n_lanes must divide evenly)
    """
    op, cond, cc, ck, wc, wk = (jnp.asarray(t) for t in group.tables())
    if enabled is None:
        enabled = jnp.ones(group.n_ops, jnp.bool_)
    else:
        enabled = jnp.asarray(enabled, jnp.bool_)

    if mesh is not None:
        if backend != "jnp":
            raise ValueError(
                f"sharded megakernel execution requires backend='jnp' "
                f"(got {backend!r})")
        n_lanes = planes.shape[1]
        n_shards = mesh.devices.size
        if n_lanes % n_shards != 0:
            raise ValueError(
                f"n_lanes={n_lanes} not divisible by n_shards={n_shards}; "
                f"pick n_words a multiple of {32 * n_shards}")
        planes, tag, matched, _ = _sharded_runner(mesh)(
            planes, tag, op, cond, enabled, cc, ck, wc, wk)
        return planes, tag, matched
    if backend == "pallas":
        return run_group_kernel(
            planes, tag, op, cond, enabled, cc, ck, wc, wk,
            block_lanes=block_lanes, interpret=interpret,
            conditional=group.conditional)
    if backend != "jnp":
        raise ValueError(f"unknown megakernel backend {backend!r}")
    planes, tag, matched, _ = _run_group_jnp(
        planes, tag, op, cond, enabled, cc, ck, wc, wk)
    return planes, tag, matched


#: aliases for scan-embedded use (workloads/_device.py builds its own
#: jitted programs around the raw executor and the cached sharded
#: runner; re-exported so callers don't import ref/privates directly)
group_scan = ref.group_scan
counter_delta = ref.counter_delta
sharded_group_runner = _sharded_runner
