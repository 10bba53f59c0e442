"""Co-simulation engine (core/cosim.py + thermal implicit steppers):
implicit-vs-explicit transient agreement, trace-binning energy
conservation, frame synthesis, the vmapped batch driver, and reports."""
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import cosim, thermal


# ------------------------------------------------- implicit transient solver
def test_implicit_matches_explicit_oracle():
    """Acceptance bar: 32x32 grid, peak within 0.1 C of the explicit
    (CFL-bound) oracle at >= 10x fewer time steps."""
    rng = np.random.default_rng(0)
    grid = thermal.Grid(die_w=5e-3, ny=32, nx=32)
    power = grid.pad_power(
        rng.uniform(0, 2e-3, size=(4, 32, 32)).astype(np.float32))
    t_end = 0.05
    n_exp = max(int(t_end / thermal.explicit_dt(grid)), 1)
    T_e, _ = thermal.transient_solve(power, grid, t_end)
    n_imp = max(n_exp // 20, 1)
    assert n_exp / n_imp >= 10
    T_i, peaks = thermal.transient_solve_implicit(power, grid, t_end,
                                                  n_steps=n_imp)
    assert abs(float(jnp.max(T_i)) - float(jnp.max(T_e))) < 0.1
    np.testing.assert_allclose(np.asarray(T_i), np.asarray(T_e), atol=0.1)
    assert peaks.shape == (n_imp,)


def test_implicit_crank_nicolson_also_agrees():
    rng = np.random.default_rng(1)
    grid = thermal.Grid(die_w=4e-3, ny=16, nx=16)
    power = grid.pad_power(
        rng.uniform(0, 1e-3, size=(4, 16, 16)).astype(np.float32))
    t_end = 0.02
    T_e, _ = thermal.transient_solve(power, grid, t_end)
    n_imp = max(int(t_end / thermal.explicit_dt(grid)) // 20, 1)
    T_i, _ = thermal.transient_solve_implicit(power, grid, t_end,
                                              n_steps=n_imp, theta=0.5)
    np.testing.assert_allclose(np.asarray(T_i), np.asarray(T_e), atol=0.1)


def test_transient_implicit_fields_reaches_steady_state():
    """Public fields-operator stepper, driven directly on a margin grid."""
    rng = np.random.default_rng(7)
    grid = thermal.Grid(die_w=3e-3, ny=12, nx=12, margin=3)
    power = rng.uniform(0, 2e-3, size=(4, 12, 12)).astype(np.float32)
    p_dom = jnp.pad(grid.pad_power(power), ((0, 0), (3, 3), (3, 3)))
    T0 = jnp.full(p_dom.shape, thermal.AMBIENT_C, jnp.float32)
    T, peaks = thermal.transient_implicit_fields(
        T0, p_dom, grid.fields(), grid.capacity_field(), dt=0.05,
        n_steps=60, inner=thermal.InnerSolve("pcg", 60))
    T_ss = np.asarray(thermal.steady_state(power, grid))
    die = np.asarray(T)[:4, 3:15, 3:15]
    np.testing.assert_allclose(die, T_ss, atol=0.05)
    assert peaks.shape == (60,)
    assert float(peaks[0]) == pytest.approx(thermal.AMBIENT_C)  # pre-step


@pytest.mark.parametrize("theta", [1.0, 0.5], ids=["euler", "cn"])
@pytest.mark.parametrize("margin", [0, 2])
def test_implicit_lhs_solver_traced_dt_matches_fixed(theta, margin):
    """PCG ``make(dt)`` at a dt traced inside a scan body (the
    variable-dt replay) is the fixed-dt solve made once outside it, bit
    for bit."""
    grid = thermal.Grid(die_w=2.3e-3, ny=8, nx=8, margin=margin)
    cap3 = grid.capacity_field()
    rng = np.random.default_rng(4)
    rhs = jnp.asarray(rng.uniform(0, 1e-3, (3,) + cap3.shape), jnp.float32)

    @partial(jax.jit, static_argnames="traced")
    def steps(F, cap3, rhs, dt, traced):
        make = thermal.implicit_lhs_solver(thermal.fields_operator(F), F,
                                           cap3, theta,
                                           thermal.InnerSolve("pcg", 20))
        fixed = make(dt)

        def body(_, xs):
            r, scale = xs
            return None, (make(dt * scale) if traced else fixed)(r)
        return jax.lax.scan(body, None, (rhs, jnp.ones(rhs.shape[0])))[1]

    args = (grid.fields(), cap3, rhs, 1e-3)
    np.testing.assert_array_equal(np.asarray(steps(*args, traced=True)),
                                  np.asarray(steps(*args, traced=False)))


@pytest.mark.parametrize("kind,iters", [("bogus", 3), ("mgcg", 3),
                                        ("pcg", 0), ("mg", -1)])
def test_inner_solve_rejects_bad_settings(kind, iters):
    with pytest.raises(ValueError, match="unknown inner solve|iters"):
        thermal.InnerSolve(kind, iters)


def test_constant_trace_replay_reaches_steady_state():
    """The fields-operator implicit path, end to end: a constant-activity
    replay must land on the steady-state CG solution."""
    rng = np.random.default_rng(2)
    grid_n, margin = 16, 4
    grid = thermal.Grid(die_w=3e-3, ny=grid_n, nx=grid_n, margin=margin)
    pmap = rng.uniform(0, 5e-3, size=(grid_n, grid_n))
    trace = cosim.PowerTrace(np.ones(30))
    frames = cosim.power_frames(trace, pmap, float(pmap.sum()) * 0.3, grid)
    T_end, peaks, mins = cosim.cosim_transient(
        jnp.asarray(frames), grid.fields(), grid.capacity_field(),
        2.0 / 30, steps_per_interval=4, inner=thermal.InnerSolve("pcg", 60),
        margin=margin, die_n=grid_n)
    power = np.broadcast_to(pmap, (4, grid_n, grid_n)).astype(np.float32)
    T_ss = np.asarray(thermal.steady_state(power, grid))
    for l in range(4):
        assert abs(float(peaks[-1, l]) - T_ss[l].max()) < 0.05
        assert abs(float(mins[-1, l]) - T_ss[l].min()) < 0.05


# ------------------------------------------------------------- power traces
def test_engine_trace_conserves_energy():
    from repro.core.engine import APEngine

    eng = APEngine(n_words=64, n_bits=16)
    eng.bwrite([0, 1], [1, 0])
    eng.compare([0], [1])
    eng.write([1, 2, 3], [1, 1, 0])
    _, bins = eng.power_trace(8)
    assert bins.sum() == pytest.approx(eng.energy)


def test_workload_trace_bins_sum_to_engine_energy():
    """Binned trace == engine.energy for a real pass-schedule workload."""
    from repro.workloads import dmm

    rng = np.random.default_rng(3)
    A = rng.integers(0, 16, (4, 4), dtype=np.uint64)
    B = rng.integers(0, 16, (4, 4), dtype=np.uint64)
    _, ctr = dmm.ap_matmul(A, B, m=4)
    assert ctr["trace_energy"].sum() == pytest.approx(ctr["energy"])
    assert int(ctr["trace_cycles"].max()) <= ctr["cycles"]
    tr = cosim.trace_from_counters(ctr, 16)
    assert tr.activity.shape == (16,)
    assert tr.activity.mean() == pytest.approx(1.0)
    assert (tr.activity >= 0).all()


def test_simd_phase_trace_mean_one():
    dp = cosim.comparable_design_point("dmm")
    from repro.core import models as M
    tr = cosim.simd_phase_trace(M.WORKLOADS["dmm"], dp, 32)
    assert tr.activity.mean() == pytest.approx(1.0)
    assert tr.activity.std() > 0  # it actually alternates


def test_power_frames_conserve_power():
    """mean-over-time of each frame's total == n_si x layer power."""
    grid_n, margin = 8, 2
    grid = thermal.Grid(die_w=2e-3, ny=grid_n, nx=grid_n, margin=margin)
    rng = np.random.default_rng(4)
    pmap = rng.uniform(0, 1e-2, size=(grid_n, grid_n))
    act = rng.uniform(0.2, 2.0, 10)
    trace = cosim.PowerTrace(act / act.mean())
    frames = cosim.power_frames(trace, pmap, float(pmap.sum()) * 0.4, grid)
    n_si = grid.params.n_si_layers
    assert frames.shape == (10, grid.params.n_layers,
                            grid.dom_ny, grid.dom_nx)
    mean_total = frames.sum(axis=(1, 2, 3)).mean()
    assert mean_total == pytest.approx(n_si * pmap.sum(), rel=1e-5)
    assert frames[:, -1].sum() == 0.0        # spreader layer heatless


# --------------------------------------------------------- batched driver
def test_vmapped_cosim_shapes_and_dtypes():
    res = cosim.run_cosim(workloads=("dmm",), grid_n=8, n_intervals=8,
                          t_end=0.1, steps_per_interval=1,
                          inner=thermal.InnerSolve("pcg", 25))
    for machine in ("ap", "simd"):
        r = res["dmm"][machine]
        assert r.peak_C.shape == (8, 4)
        assert r.min_C.shape == (8, 4)
        assert r.peak_C.dtype == np.float32
        assert np.isfinite(r.peak_C).all() and np.isfinite(r.min_C).all()
        assert (r.peak_C >= r.min_C - 1e-4).all()
        assert (r.min_C > 0).all()
    # AP runs cooler than the same-performance SIMD throughout (Fig 10/12)
    assert res["dmm"]["ap"].peak_C.max() < res["dmm"]["simd"].peak_C.max()


@pytest.mark.pallas
def test_cosim_pallas_route_matches_jnp():
    rng = np.random.default_rng(5)
    grid_n, margin = 8, 2
    grid = thermal.Grid(die_w=3e-3, ny=grid_n, nx=grid_n, margin=margin)
    pmap = rng.uniform(0, 5e-3, size=(grid_n, grid_n))
    act = rng.uniform(0.5, 1.5, 6)
    trace = cosim.PowerTrace(act / act.mean())
    frames = jnp.asarray(cosim.power_frames(trace, pmap, 0.0, grid))
    args = (frames, grid.fields(), grid.capacity_field(), 0.02)
    kw = dict(steps_per_interval=2, margin=margin, die_n=grid_n)
    _, pk_j, mn_j = cosim.cosim_transient(
        *args, **kw, inner=thermal.InnerSolve("pcg", 30))
    _, pk_p, mn_p = cosim.cosim_transient(
        *args, **kw, inner=thermal.InnerSolve("pcg", 30, pallas=True))
    np.testing.assert_allclose(np.asarray(pk_j), np.asarray(pk_p),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(mn_j), np.asarray(mn_p),
                               rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------- reports
def test_report_time_above_and_crossing():
    peak = np.array([[50.0, 50.0], [90.0, 60.0], [100.0, 84.9],
                     [80.0, 86.0]], np.float32)
    r = cosim.CosimReport(label="t", interval_s=0.5, peak_C=peak,
                          min_C=peak - 10.0)
    np.testing.assert_allclose(r.time_above(85.0), [1.0, 0.5])
    np.testing.assert_allclose(r.crossing_time(85.0), [1.0, 2.0])
    np.testing.assert_allclose(r.span_C, 10.0)
    never = cosim.CosimReport(label="n", interval_s=0.5,
                              peak_C=peak * 0 + 50.0, min_C=peak * 0 + 49.0)
    assert np.isinf(never.crossing_time(85.0)).all()
    assert never.time_above(85.0).max() == 0.0
