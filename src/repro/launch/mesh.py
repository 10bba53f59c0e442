"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.

Target: TPU v5e, 256 chips/pod (16x16 ICI torus mapped as data x model),
2 pods over DCN for the multi-pod configuration ('pod' extends the data
axis; gradient all-reduce is hierarchical: reduce-scatter over ICI 'data',
all-reduce over DCN 'pod').
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places values
    with ``with_sharding_constraint``, which only Auto axes accept
    (``make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))
