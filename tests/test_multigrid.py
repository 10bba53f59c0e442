"""Geometric multigrid (core/multigrid.py): Galerkin-product identity,
exact line smoothing, and mg/mgcg-vs-PCG equivalence on the steady,
transient and closed-loop-sweep paths for every stack family
(ISSUE 4 regression pins)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import multigrid as mg
from repro.core import thermal
from repro.stack.spec import PAPER_SPEC, dram_on_logic

STACKS = [PAPER_SPEC, dram_on_logic(1), dram_on_logic(2), dram_on_logic(4)]


def _grid(spec, n=32, margin=8):
    return thermal.Grid(die_w=5e-3, ny=n, nx=n, margin=margin, spec=spec)


def _logic_power(grid, watts=40.0):
    """``watts`` spread over the stack's LOGIC dies (DRAM dies, when
    present, sit at the TOP of the layer order and stay unpowered)."""
    n = grid.ny
    logic = list(grid.stack.logic_layers)
    p = np.zeros((grid.n_die_layers, n, n), np.float32)
    p[logic] = watts / (len(logic) * n * n)
    return p


def test_galerkin_product_identity():
    """The raw coarse operator IS R G P: applying it to any coarse
    vector equals restrict(G(prolong(v))) on the fine grid."""
    grid = _grid(dram_on_logic(2), n=16, margin=4)
    F = grid.fields()
    d = jnp.full(F["g_pkg"].shape, 0.25, jnp.float32)
    Fc, dc = mg.coarsen(F, d)                 # rescale_lateral=False
    rng = np.random.default_rng(0)
    vc = jnp.asarray(rng.normal(size=Fc["g_pkg"].shape).astype(np.float32))
    lhs = mg.operator(vc, Fc, dc)
    rhs = mg.restrict(mg.operator(mg.prolong(vc), F, d))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-5, atol=1e-7)


def test_line_solve_is_exact_per_column():
    """line_solve satisfies its vertical tridiagonal system exactly."""
    grid = _grid(dram_on_logic(1), n=16, margin=4)
    F = grid.fields()
    d = jnp.full(F["g_pkg"].shape, 0.1, jnp.float32)
    rng = np.random.default_rng(1)
    rhs = jnp.asarray(rng.normal(size=F["g_pkg"].shape).astype(np.float32))
    u = mg.line_solve(rhs, F, d)
    diag = jnp.where(mg.diagonal(F, d) > 0, mg.diagonal(F, d), 1.0)
    u_up = jnp.concatenate([jnp.zeros_like(u[:1]), u[:-1]], axis=0)
    u_dn = jnp.concatenate([u[1:], jnp.zeros_like(u[:1])], axis=0)
    resid = diag * u - F["gz_up"] * u_up - F["gz_dn"] * u_dn - rhs
    assert float(jnp.abs(resid).max()) < 1e-4


@pytest.mark.parametrize("spec", STACKS, ids=lambda s: s.name)
@pytest.mark.parametrize("solver", ["mg", "mgcg"])
def test_steady_matches_pcg_all_stacks(spec, solver):
    """Multigrid matches the PCG steady solve within solver tolerance on
    PAPER_SPEC and every DRAM-on-logic stack (the ISSUE 4 pin)."""
    grid = _grid(spec)
    p = _logic_power(grid)
    T_ref = thermal.steady_state(p, grid, solver="pcg")
    T_mg, stats = thermal.steady_state_stats(p, grid, solver=solver)
    assert float(jnp.abs(T_mg - T_ref).max()) < 0.01, spec.name
    # asymptotically faster: a handful of cycles, not hundreds of iters
    assert stats["iterations"] < 40
    # the honest convergence signal: true residual, not iteration count
    assert stats["rel_residual"] < 1e-3


def test_steady_rejects_unknown_solver():
    grid = _grid(PAPER_SPEC, n=8, margin=0)
    with pytest.raises(ValueError, match="unknown solver"):
        thermal.steady_state(_logic_power(grid), grid, solver="bogus")


def test_transient_implicit_mg_matches_pcg():
    """The fixed-cycle MG inner solve reproduces the PCG transient."""
    grid = thermal.Grid(die_w=5e-3, ny=16, nx=16, spec=dram_on_logic(2))
    p = _logic_power(grid)
    T1, pk1 = thermal.transient_solve_implicit(
        p, grid, t_end=0.2, n_steps=32, inner=thermal.InnerSolve("pcg", 80))
    T2, pk2 = thermal.transient_solve_implicit(
        p, grid, t_end=0.2, n_steps=32, inner=thermal.InnerSolve("mg", 3))
    assert float(jnp.abs(T1 - T2).max()) < 0.1
    assert float(jnp.abs(pk1 - pk2).max()) < 0.1


def test_sweep_solver_mg_matches_converged_pcg():
    """Closed-loop sweep with solver="mg" (3 V-cycles/step) lands within
    the Picard bar of a heavily-converged PCG replay — at a fraction of
    the inner-iteration budget."""
    from repro.sweep import SweepSpec, run_sweep
    base = dict(workloads=("hist",), sizes=(4096,), n_dram=(1,),
                fb_modes=("open",), grid_n=8, n_intervals=4,
                steps_per_interval=1)
    ref = run_sweep(SweepSpec(**base, n_cg=400), use_cache=False)
    got = run_sweep(SweepSpec(**base, solver="mg", n_mg=3),
                    use_cache=False)
    for a, b in zip(ref.records, got.records):
        np.testing.assert_allclose(b.report.peak_C, a.report.peak_C,
                                   atol=0.05)


def test_sweep_spec_solver_in_hash_and_validated():
    from repro.sweep import SweepSpec
    base = dict(workloads=("hist",), sizes=(4096,))
    a = SweepSpec(**base)
    b = SweepSpec(**base, solver="mg")
    c = SweepSpec(**base, solver="mg", n_mg=5)
    assert len({a.content_hash(), b.content_hash(), c.content_hash()}) == 3
    with pytest.raises(ValueError, match="unknown solver"):
        SweepSpec(**base, solver="cholesky")
    with pytest.raises(ValueError, match="n_mg"):
        SweepSpec(**base, n_mg=0)


def test_mg_solve_reaches_float32_floor():
    """The stand-alone iteration converges to a tiny true residual and
    reports the cycle count it took."""
    grid = _grid(dram_on_logic(2), n=32, margin=8)
    F = grid.fields()
    p = jnp.pad(jnp.asarray(grid.pad_power(_logic_power(grid))),
                ((0, 0), (8, 8), (8, 8)))
    x, cycles = mg.mg_solve_fields(p, F)
    r = p - mg.operator(x, F, jnp.zeros_like(F["g_pkg"]))
    rel = float(jnp.linalg.norm(r) / jnp.linalg.norm(p))
    assert rel < 1e-3
    assert 1 <= int(cycles) < 40
