"""The steady host path: cached conductance fields and one health fetch
an attempt (``Grid.fields``, ``thermal.steady_state_stats``).

The solver's own programs are unchanged, so a steady map must equal,
bit for bit, the same programs composed by hand on a fresh build of the
fields; the cache must hand back what a fresh build gives and stay at
its bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import thermal
from repro.stack.spec import dram_on_logic

SOLVERS = ("mg", "mgcg", "pcg")
T_AMB = 45.0


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test counts from an empty, disabled registry."""
    prev = obs.is_enabled()
    obs.disable()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if prev else obs.disable)()


def _grid(die_w: float = 2.3e-3, n: int = 16, margin: int = 4):
    return thermal.Grid(die_w=die_w, ny=n, nx=n, margin=margin,
                        spec=dram_on_logic(2))


def _power(grid, seed: int = 0) -> np.ndarray:
    g = np.random.default_rng(seed)
    p = g.uniform(0.0, 2e-3, (grid.n_die_layers, grid.ny, grid.nx))
    p[-1, 4:12, 4:12] += 0.02            # a hot block on the logic die
    return p.astype(np.float32)


def _by_hand(power, grid, solver):
    """The parent's path: pad in eager jnp, the solver's program on a
    fresh (uncached) build of the fields, slice and add eagerly."""
    F = grid._build_fields()
    m = grid.margin
    b = jnp.pad(grid.pad_power(power), ((0, 0), (m, m), (m, m)))
    dT, iters = thermal._solve_fields(b, F, solver, False, 1e-8)
    T = dT[:grid.n_die_layers, m:m + grid.ny, m:m + grid.nx] + T_AMB
    return T, int(iters), b, dT, F


@pytest.mark.parametrize("solver", SOLVERS)
def test_steady_map_is_bit_identical_to_the_solver_by_hand(solver):
    grid = _grid()
    power = _power(grid)
    T, stats = thermal.steady_state_stats(power, grid, t_amb=T_AMB,
                                          solver=solver)
    T_ref, iters, *_ = _by_hand(power, grid, solver)
    assert T.dtype == T_ref.dtype and T.shape == T_ref.shape
    np.testing.assert_array_equal(np.asarray(T), np.asarray(T_ref))
    assert stats["iterations"] == iters
    assert stats["attempts"] == 1 and stats["solved_by"] == solver


@pytest.mark.parametrize("solver", SOLVERS)
def test_rel_residual_matches_the_eager_formula(solver):
    grid = _grid()
    power = _power(grid, seed=1)
    _, stats = thermal.steady_state_stats(power, grid, t_amb=T_AMB,
                                          solver=solver)
    _, _, b, dT, F = _by_hand(power, grid, solver)
    bnorm = float(jnp.linalg.norm(b))
    want = float(jnp.linalg.norm(b - thermal.apply_operator_fields(dT, F))
                 / bnorm)
    assert isinstance(stats["rel_residual"], float)
    # a converged residual is a float32 cancellation near 1e-5 of ||b||:
    # one fused program and the op-by-op formula round its last bits
    # differently, so they agree to 1e-6 of ||b||, not of the residual
    np.testing.assert_allclose(stats["rel_residual"], want, rtol=0,
                               atol=1e-6)
    assert stats["rel_residual"] <= thermal.HEALTH_RTOL


@pytest.mark.parametrize("solver", SOLVERS)
def test_zero_power_returns_ambient_after_one_attempt(solver):
    grid = _grid()
    power = np.zeros((grid.n_die_layers, grid.ny, grid.nx), np.float32)
    T, stats = thermal.steady_state_stats(power, grid, t_amb=T_AMB,
                                          solver=solver)
    assert T.shape == (grid.n_die_layers, grid.ny, grid.nx)
    assert T.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(T), np.float32(T_AMB))
    assert stats == {"iterations": 0, "solver": solver,
                     "rel_residual": 0.0, "attempts": 1,
                     "solved_by": solver}


@pytest.mark.parametrize("solver", SOLVERS)
def test_nonfinite_power_raises_before_any_fallback(solver):
    grid = _grid()
    power = _power(grid)
    power[0, 3, 5] = np.inf
    with obs.scoped():
        with pytest.raises(ValueError, match="non-finite"):
            thermal.steady_state_stats(power, grid, solver=solver)
        fallback = obs.values_by_prefix("thermal/fallback/")
    assert not fallback


def test_cache_hit_returns_the_arrays_of_a_fresh_build():
    grid = _grid(die_w=2.31e-3)
    first, again = grid.fields(), grid.fields()
    fresh = grid._build_fields()
    assert first.keys() == again.keys() == fresh.keys()
    for k in fresh:
        assert again[k] is first[k]              # the cached array itself
        assert again[k].dtype == fresh[k].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(again[k]),
                                      np.asarray(fresh[k]))
    again.pop("g_pkg")                           # a caller's dict is its own
    assert "g_pkg" in grid.fields()


def test_cache_counters_count_one_miss_then_hits():
    grid = _grid(die_w=2.32e-3)
    power = _power(grid)
    with obs.scoped():
        grid.fields()
        for _ in range(3):
            thermal.steady_state_stats(power, grid, solver="mg")
        assert obs.value("thermal/fields/cache_miss") == 1
        assert obs.value("thermal/fields/cache_hit") == 3


def test_cache_stays_at_its_bound_and_evicts_the_least_recent():
    size = thermal.FIELDS_CACHE_SIZE
    grids = [_grid(die_w=2.4e-3 + 1e-6 * i, n=8, margin=2)
             for i in range(size + 2)]
    for g in grids:
        g.fields()
    assert len(thermal._FIELDS_CACHE) == size
    held = {k[0] for k in thermal._FIELDS_CACHE}
    assert held == set(grids[-size:])            # the oldest two went
    oldest = grids[-size]
    with obs.scoped():
        oldest.fields()                          # a hit refreshes it...
        grids[0].fields()                        # ...so this miss evicts
        assert obs.value("thermal/fields/cache_hit") == 1
        assert obs.value("thermal/fields/cache_miss") == 1
    held = {k[0] for k in thermal._FIELDS_CACHE}
    assert len(thermal._FIELDS_CACHE) == size
    assert oldest in held and grids[0] in held
    assert grids[-size + 1] not in held


def test_cache_keys_on_the_default_device():
    grid = _grid(die_w=2.33e-3, n=8, margin=2)
    with obs.scoped():
        grid.fields()
        with jax.default_device(jax.devices()[0]):
            grid.fields()
            grid.fields()
        assert obs.value("thermal/fields/cache_miss") == 2
        assert obs.value("thermal/fields/cache_hit") == 1


def test_fields_built_under_a_trace_cache_concrete_arrays():
    grid = _grid(die_w=2.34e-3, n=8, margin=2)

    @jax.jit
    def total(x):
        return x + sum(v.sum() for v in grid.fields().values())

    traced = float(total(jnp.float32(0.0)))
    F = grid.fields()                            # the one built in the trace
    assert all(isinstance(v, jax.Array) and not isinstance(v, jax.core.Tracer)
               for v in F.values())
    np.testing.assert_allclose(
        traced, sum(float(v.sum()) for v in F.values()), rtol=1e-6)
