"""Sharded sweep execution: device-count agreement (1 vs N shards give
the same records within ``sharding.SHARD_TOL_C``, and the cache keys
them apart), padding correctness for non-dividing batch sizes, and mesh
validation.

The multi-device cases force 4 XLA host devices in a SUBPROCESS
(``--xla_force_host_platform_device_count`` must be set before jax
initializes, so it cannot run in this process)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.parallel import sharding
from repro.sweep import SweepSpec, run_sweep

_QUICK = dict(workloads=("hist",), sizes=(4096,), n_dram=(1,),
              fb_modes=("open",), grid_n=8, n_intervals=4,
              steps_per_interval=1, n_cg=15)

_SUBPROCESS = r"""
import jax, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.parallel.sharding import SHARD_TOL_C
from repro.sweep import SweepSpec, run_sweep

spec = SweepSpec(workloads=("hist", "sort"), sizes=(4096,), n_dram=(1,),
                 fb_modes=("open",), grid_n=8, n_intervals=4,
                 steps_per_interval=1, n_cg=15)
runs = {n: run_sweep(spec, use_cache=False, n_shards=n)
        for n in (None, 1, 3, 4)}   # 4 cases: 3 shards exercises padding
ref = runs[None]
for n, res in runs.items():
    assert [r.label for r in res.records] == [r.label for r in ref.records]
    for a, b in zip(ref.records, res.records):
        for name in ("peak_C", "min_C", "residual_C"):
            np.testing.assert_allclose(
                getattr(a.report, name), getattr(b.report, name),
                rtol=0, atol=SHARD_TOL_C,
                err_msg=f"n_shards={n} field={name}")
        for name in ("throttle", "refresh_W", "leak_W", "dyn_W"):
            np.testing.assert_allclose(
                getattr(a.report, name), getattr(b.report, name),
                rtol=1e-3, err_msg=f"n_shards={n} field={name}")
print("SHARD-INVARIANCE-OK", spec.content_hash())
"""


def test_single_shard_matches_vmap():
    """n_shards=1 must be bitwise the plain vmap path (runs on the one
    local device; the N-device case is the subprocess test below)."""
    spec = SweepSpec(**_QUICK)
    ref = run_sweep(spec, use_cache=False)
    got = run_sweep(spec, use_cache=False, n_shards=1)
    for a, b in zip(ref.records, got.records):
        for name in ("peak_C", "min_C", "residual_C", "throttle"):
            np.testing.assert_array_equal(getattr(a.report, name),
                                          getattr(b.report, name))


def test_cache_key_ignores_shard_count(tmp_path):
    """Sharding is an execution detail, so the spec and its hash have no
    shard field; the cache path adds the shard count, because sharded
    replays agree with the unsharded one only within SHARD_TOL_C."""
    from repro.sweep import cache
    spec = SweepSpec(**_QUICK)
    assert "shard" not in str(sorted(spec.canonical()))
    assert spec.content_hash() == SweepSpec(**_QUICK).content_hash()
    paths = {cache.path_for(spec, tmp_path, n) for n in (None, 0, 1, 4)}
    assert len(paths) == 3          # None and 0 both mean the plain vmap
    res = run_sweep(spec, cache_dir=tmp_path, n_shards=1)
    assert not res.from_cache
    assert run_sweep(spec, cache_dir=tmp_path, n_shards=1).from_cache
    assert cache.load(spec, tmp_path) is None


def test_sweep_mesh_validates_device_count():
    import jax
    n_dev = len(jax.devices())
    assert sharding.sweep_mesh(n_dev).shape["cases"] == n_dev
    with pytest.raises(ValueError, match="out of range"):
        sharding.sweep_mesh(n_dev + 1)
    with pytest.raises(ValueError, match="out of range"):
        sharding.sweep_mesh(0)


def test_pad_case_batch_roundtrip():
    import jax.numpy as jnp
    batch = (jnp.arange(10).reshape(5, 2), jnp.ones((5, 3)))
    padded, n = sharding.pad_case_batch(batch, 3)
    assert n == 5
    assert all(leaf.shape[0] == 6 for leaf in padded)
    np.testing.assert_array_equal(np.asarray(padded[0][-1]),
                                  np.asarray(padded[0][-2]))
    out = sharding.unpad_case_batch(padded, n)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(batch[0]))
    with pytest.raises(ValueError, match="inconsistent"):
        sharding.pad_case_batch((jnp.ones((5, 2)), jnp.ones((4, 2))), 3)


@pytest.mark.slow
def test_device_count_invariance_subprocess():
    """1 vs 3 vs 4 shards on 4 forced host devices: records agree with
    the unsharded vmap within SHARD_TOL_C (4 shards hold one case each,
    whose CG inner products XLA sums in another order)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD-INVARIANCE-OK" in proc.stdout
