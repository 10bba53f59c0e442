"""Parameter / cache PartitionSpec rules (DP+FSDP over 'data', TP/EP/SP over
'model', 'pod' extending the data axis multi-pod) — plus the sweep-case
batch sharding used by ``repro.sweep.engine`` (bottom of file).

The scheme is Megatron-style 2D:

  column-parallel in-projections  [d, out]   -> P(data, model)
  row-parallel out-projections    [out, d]   -> P(model, data)
  experts                         [E, d, f]  -> P(model, data, None)  (EP)
  embeddings                      [V, d]     -> P(model, data)
  norms / scalars                            -> replicated

FSDP: the 'data' entry on the *other* matrix axis shards params and
optimizer state ZeRO-3 style; XLA all-gathers them per-layer inside the
scan (which pipelines with compute).  KV caches shard batch over 'data' and
SEQUENCE over 'model' (flash-decoding layout; see attention.py).
"""
from __future__ import annotations

import functools as _functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import Sharder

STACK_KEYS = ("layers", "enc_layers", "dense_layers")


def make_sharder(mesh, multi_pod: bool = False) -> Sharder:
    data_axes = ("pod", "data") if multi_pod else "data"
    return Sharder(mesh=mesh, data_axes=data_axes, model_axes="model")


def _rule(path_keys: list[str], ndim: int, data) -> P:
    """PartitionSpec for one param, BEFORE the stacked-layer prefix."""
    name = path_keys[-1]
    in_experts = "experts" in path_keys

    if in_experts:                       # [E, d, f] / [E, f, d]
        if name in ("w_gate", "w_up"):
            return P("model", data, None)
        if name == "w_down":
            return P("model", None, data)
    col = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj_x", "in_proj_z",
           "wq_b", "wkv_b", "dt_proj"}
    row = {"wo", "w_down", "out_proj"}
    if name == "embed":
        return P("model", data)
    if name == "lm_head":
        return P(data, "model")
    if name in col:
        return P(data, "model") if ndim == 2 else P("model")
    if name in row:
        return P("model", data)
    if name in ("bq", "bk", "bv", "b_up", "conv_b", "norm_w"):
        return P("model")
    if name == "conv_w":                 # [K, din]
        return P(None, "model")
    if name in ("x_proj", "A_log"):      # [din, *]
        return P("model", None)
    if name == "D" and ndim == 1:
        return P("model")
    if name == "dt_bias":
        return P("model")
    if name in ("router", "wq_a", "wkv_a", "in_proj_bc", "in_proj_dt"):
        return P(data, None)
    # norms, small vectors, scalars -> replicated
    return P(*([None] * ndim))


def param_specs(cfg: ArchConfig, params_shape: Any, multi_pod: bool = False
                ) -> Any:
    """PartitionSpec pytree matching a params pytree (arrays or SDS)."""
    data = ("pod", "data") if multi_pod else "data"

    def one(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        stacked = any(k in STACK_KEYS for k in keys)
        ndim = len(leaf.shape)
        base_ndim = ndim - 1 if stacked else ndim
        spec = _rule(keys, base_ndim, data)
        # mamba2 dt_bias/A_log/D are [H] per-head (small): replicate
        if keys[-1] in ("dt_bias", "A_log", "D") and cfg.ssm is not None \
                and cfg.ssm.version == 2:
            spec = P(*([None] * base_ndim))
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_specs(cfg: ArchConfig, cache_shape: Any, multi_pod: bool = False
                ) -> Any:
    """PartitionSpecs for serve caches (stacked layer axis leading)."""
    data = ("pod", "data") if multi_pod else "data"

    def one(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        name = keys[-1]
        ndim = len(leaf.shape)
        if name in ("k", "v", "k_q", "v_q"):   # [L, B, W, hkv, dh]
            return P(None, data, "model", None, None)
        if name in ("k_s", "v_s"):       # [L, B, W, hkv] quant scales
            return P(None, data, "model", None)
        if name in ("cross_k", "cross_v"):  # [L, B, F, hkv, dh]
            return P(None, data, None, "model", None)
        if name in ("c_kv", "k_rope"):   # [L, B, S, lora]
            return P(None, data, "model", None)
        if name == "conv":               # [L, B, K-1, din]
            return P(None, data, None, "model")
        if name == "h":                  # [L, B, din, N]
            return P(None, data, "model", None)
        if name in ("slot_pos", "len", "step"):
            return P(*([None] * ndim))
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def to_named(mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# sweep-case batch sharding (the repro.sweep execution mode)
# ---------------------------------------------------------------------------
#
# A sweep batch is embarrassingly parallel over its leading (case) axis:
# every case is an independent closed-loop replay.  ``shard_case_batch``
# wraps the vmapped replay in a ``shard_map`` over a 1D 'cases' mesh, so
# each device runs the per-case program on its slice.  The per-device
# batch is smaller than the unsharded one, and XLA does not promise the
# same reduction order for different batch shapes: on the CPU a batch
# of one turns each batched CG inner product into a plain dot with a
# different summation order.  So a sharded replay agrees with the
# unsharded vmap within SHARD_TOL_C (tests/test_shard_sweep.py), not bit
# for bit, and the sweep cache keys the shard count
# (``repro.sweep.cache.path_for``).

#: stated agreement [°C] of a sharded sweep replay with the unsharded
#: one, on temperatures and Picard residuals: the replay's own
#: per-interval convergence bar (``FeedbackParams.picard_tol_C``)
SHARD_TOL_C = 0.05

def sweep_mesh(n_shards: int | None = None):
    """A 1D mesh of ``n_shards`` local devices over axis 'cases'.

    ``None`` uses every local device.  Raises if more shards are
    requested than devices exist (sharding is an execution detail; it
    must never silently change what runs).
    """
    import numpy as np
    from jax.sharding import Mesh
    devices = jax.devices()
    n = len(devices) if n_shards is None else n_shards
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"n_shards={n} out of range for {len(devices)} local "
            f"device(s)")
    return Mesh(np.asarray(devices[:n]), ("cases",))


@_functools.lru_cache(maxsize=None)
def ap_mesh(n_shards: int | None = None):
    """A 1D mesh of ``n_shards`` local devices over axis 'lanes' — the
    AP bitplane sharding axis (megakernel backend): plane columns and
    the TAG register split over the packed word-lane axis, responder
    popcounts ``psum`` back to every shard.

    Cached so repeated lookups return the *same* Mesh object and the
    jitted sharded runners (``kernels.ap_megakernel.ops``) are reused.
    Validation matches :func:`sweep_mesh`: over-subscription raises.
    """
    import numpy as np
    from jax.sharding import Mesh
    devices = jax.devices()
    n = len(devices) if n_shards is None else n_shards
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"n_shards={n} out of range for {len(devices)} local "
            f"device(s)")
    return Mesh(np.asarray(devices[:n]), ("lanes",))


def pad_case_batch(batch: Any, n_shards: int) -> tuple[Any, int]:
    """Pad every leaf's leading axis to a multiple of ``n_shards`` by
    repeating the last case (dropped again by :func:`unpad_case_batch`).
    Returns ``(padded_batch, original_count)``."""
    counts = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(batch)}
    if len(counts) != 1:
        raise ValueError(f"inconsistent case counts {sorted(counts)}")
    (n,) = counts
    pad = (-n) % n_shards
    if pad == 0:
        return batch, n
    padded = jax.tree_util.tree_map(
        lambda x: jnp.concatenate([x] + [x[-1:]] * pad, axis=0), batch)
    return padded, n


def unpad_case_batch(out: Any, n: int) -> Any:
    """Drop the padding rows added by :func:`pad_case_batch`."""
    return jax.tree_util.tree_map(lambda x: x[:n], out)


def shard_case_batch(fn, mesh):
    """``shard_map`` a batched-pytree function over the 'cases' axis.

    ``fn`` must take ONE pytree whose leaves all carry the case axis
    first, and return a pytree of case-major outputs; the leading axis
    must already be a multiple of the mesh size (:func:`pad_case_batch`).
    """
    spec = P("cases")
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)
