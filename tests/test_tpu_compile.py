"""Compile-only checks against a described TPU v5e (no chip needed).

Each Pallas kernel of a selectable path is compiled by the TPU compiler
at the width its users run — the thermal fields at the solver shoot-out
grid (256^2 die + 64-cell margin = 384^2 x 7 layers), the AP planes at
2^20 words x 256 bits — and must come out as a Mosaic kernel
(``tpu_custom_call``).  The compiler refuses what interpret mode cannot
see: misaligned blocks, scalar stores to VMEM, unsupported reshapes.
Two more checks pin the solvers' f32-exact reductions: their CPU
lowering asks for HIGHEST on every dot, and their TPU compile has no
matmul below HIGHEST.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one given this file
loads the TPU compiler.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import bitplane as bp, isa, multigrid, thermal
from repro.core.engine import APEngine, bucket_schedule
from repro.kernels.ap_match import kernel as ap_match
from repro.kernels.ap_megakernel import OpGroup, kernel as megakernel
from repro.kernels.mg_smooth import kernel as mg_smooth
from repro.kernels.thermal_stencil import kernel as stencil
from repro.stack.spec import dram_on_logic
from repro.workloads import _device
from repro.workloads.sort import plan_bits

N_DIE = 256                 # solver shoot-out die grid (+ N_DIE // 4 margin)
AP_WORDS = 2 ** 20          # the paper's AP width
SORT_WORDS = 2 ** 16        # the sort trace size the smoke runs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache, so keep them out of it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), jnp.asarray(a).dtype, sharding=sharding), tree)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _fields(n: int) -> dict:
    grid = thermal.Grid(die_w=5e-3, ny=n, nx=n, margin=n // 4,
                        spec=dram_on_logic(2))
    return grid.fields()


def _add16_tables():
    """The bucketed 16-bit add schedule (the APEngine pass path)."""
    eng = APEngine(n_words=32)
    a, b = eng.alloc.alloc(16), eng.alloc.alloc(16)
    return bucket_schedule(isa.add(a, b, eng.alloc.alloc(1)))


# ----------------------------------------------------------- thermal

def test_fields_stencil_compiles(one_chip):
    F = _sds(_fields(N_DIE), one_chip)
    assert F["g_pkg"].shape == (7, 384, 384)
    hlo = _compile(lambda T, *f: stencil.apply_operator_fields_kernel(
        T, *f, interpret=False), F["g_pkg"],
        *(F[k] for k in ("gx_lf", "gx_rt", "gy_up", "gy_dn", "gz_up",
                         "gz_dn", "g_pkg")))
    assert "tpu_custom_call" in hlo


def test_uniform_stencil_compiles(one_chip):
    T = jax.ShapeDtypeStruct((7, 384, 384), jnp.float32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((7,), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda T, *g: stencil.apply_operator_kernel(
        T, *g, interpret=False), T, vec, vec, vec, vec)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("level", ["finest", "coarsest"])
def test_mg_smoother_compiles(one_chip, level):
    levels = multigrid.build_levels(_fields(N_DIE), 0.0)
    F, d = levels[0] if level == "finest" else levels[-1]
    assert F["g_pkg"].shape == ((7, 384, 384) if level == "finest"
                                else (7, 6, 6))
    args = _sds([F["g_pkg"], F["g_pkg"]]
                + [F[k] for k in ("gx_lf", "gx_rt", "gy_up", "gy_dn",
                                  "gz_up", "gz_dn", "g_pkg")] + [d],
                one_chip)
    hlo = _compile(lambda *a: mg_smooth.rb_line_sweep_kernel(
        *a, color=1, interpret=False), *args)
    assert "tpu_custom_call" in hlo


def test_steady_host_programs_compile(one_chip):
    """The programs around a 384^2 steady solve: the right-hand side's
    padding, finiteness and norm, then one attempt's health check and
    die window."""
    grid = thermal.Grid(die_w=5e-3, ny=N_DIE, nx=N_DIE, margin=N_DIE // 4,
                        spec=dram_on_logic(2))
    power = jax.ShapeDtypeStruct((grid.n_die_layers, N_DIE, N_DIE),
                                 jnp.float32, sharding=one_chip)
    rhs = thermal._steady_rhs.lower(power, grid.n_layers, grid.margin)
    b, finite, bnorm = (jax.ShapeDtypeStruct(o.shape, o.dtype,
                                             sharding=one_chip)
                        for o in rhs.out_info)
    assert b.shape == (7, 384, 384) and finite.dtype == jnp.bool_
    rhs.compile()
    iters = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    health = thermal._attempt_health.lower(
        b, b, _sds(grid.fields(), one_chip), finite, bnorm, iters, 45.0,
        window=(grid.n_die_layers, grid.margin, N_DIE, N_DIE))
    T, vec = health.out_info
    assert T.shape == (grid.n_die_layers, N_DIE, N_DIE)
    assert vec.shape == (4,) and vec.dtype == jnp.float32
    health.compile()


# ---------------------------------------------------------------- AP

def test_ap_match_compiles(one_chip):
    planes = jax.ShapeDtypeStruct((256, AP_WORDS // 32), jnp.uint32,
                                  sharding=one_chip)
    hlo = _compile(lambda p, *t: ap_match.run_schedule_kernel(
        p, *t, interpret=False), planes, *_sds(_add16_tables(), one_chip))
    assert "tpu_custom_call" in hlo


def _group_args(group: OpGroup, n_bits: int, n_lanes: int, sharding):
    planes = jax.ShapeDtypeStruct((n_bits, n_lanes), jnp.uint32,
                                  sharding=sharding)
    tag = jax.ShapeDtypeStruct((n_lanes,), jnp.uint32, sharding=sharding)
    tables = _sds((group.op, group.cond, np.ones(group.n_ops, bool),
                   group.cmp_cols, group.cmp_key, group.w_cols,
                   group.w_key), sharding)
    return (planes, tag) + tuple(tables)


def test_megakernel_unconditional_compiles(one_chip):
    group = OpGroup.from_schedule(*_add16_tables())
    assert not group.conditional
    hlo = _compile(lambda *a: megakernel.run_group_kernel(
        *a, interpret=False, conditional=False),
        *_group_args(group, 256, AP_WORDS // 32, one_chip))
    assert "tpu_custom_call" in hlo


def test_megakernel_conditional_compiles(one_chip):
    """The sort min-extraction round: whole lane axis in one program."""
    n_bits = plan_bits(8)
    eng = APEngine(n_words=SORT_WORDS, n_bits=n_bits)
    val = eng.alloc.alloc(8)
    active, cand = eng.alloc.alloc(1), eng.alloc.alloc(1)
    group = _device._min_extract_group(isa.copy(cand, active), val, active,
                                       cand, readout=False)
    assert group.conditional
    hlo = _compile(lambda *a: megakernel.run_group_kernel(
        *a, interpret=False, conditional=True),
        *_group_args(group, n_bits, SORT_WORDS // 32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_bits", [16, 40])
def test_word_transposes_fuse(one_chip, n_bits):
    """A field load and read at 2^20 words: the transposes fuse into
    their stores, so no [bits, 32, lanes] temporary reaches HBM."""
    planes = jax.ShapeDtypeStruct((256, AP_WORDS // 32), jnp.uint32,
                                  sharding=one_chip)
    half = jax.ShapeDtypeStruct((AP_WORDS,), jnp.uint32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    load = bp._load_device.lower(planes, half, half if n_bits > 32 else None,
                                 start, n_bits=n_bits).compile()
    read = bp._read_device.lower(planes, start, n_bits=n_bits).compile()
    for compiled in (load, read):
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# -------------------------------------------- f32-exact solver reductions

def _solver_fns():
    return {
        "pcg": lambda b, F: thermal._cg_solve_fields_stats(b, F, 1e-8),
        "mg": lambda b, F: multigrid.mg_solve_fields(b, F, 0.0, 1e-8),
        "mgcg": lambda b, F: multigrid.mgcg_solve_fields(b, F, 0.0, 1e-8),
    }


def test_solver_dots_lower_at_highest_precision():
    """On the CPU lowering, every dot of every steady solver asks for
    HIGHEST (the TPU's default would feed the MXU bf16 operands)."""
    F = _fields(16)
    n_dots = 0
    for name, fn in _solver_fns().items():
        text = jax.jit(fn).lower(F["g_pkg"], F).as_text()
        dots = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots), \
            (name, [ln for ln in dots if "HIGHEST" not in ln][:2])
        n_dots += len(dots)
    assert n_dots >= 6              # pcg's and mgcg's CG inner products


@pytest.mark.parametrize("solver", ["pcg", "mg", "mgcg"])
def test_solver_tpu_matmuls_at_highest(one_chip, solver):
    """Compiled for the v5e, no matmul of a steady solve (the coarse
    Cholesky factor and solve included) runs below HIGHEST."""
    F = _sds(_fields(32), one_chip)
    hlo = _compile(_solver_fns()[solver], F["g_pkg"], F)
    mm = [ln for ln in hlo.splitlines()
          if re.search(r"= \S+ (dot|convolution)\(", ln)]
    assert all("operand_precision={highest,highest}" in ln for ln in mm), \
        [ln.strip()[:200] for ln in mm
         if "operand_precision={highest,highest}" not in ln][:2]
