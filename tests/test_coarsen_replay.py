"""Property: coarsened (variable-dt) replay peak-temperature error stays
within the advertised tolerance — ``tol x dc_peak_rise_C`` — against the
exact uniform replay, on both the paper stack and a DRAM-on-logic stack.

The bound is the linear-RC argument of DESIGN.md §9.3: merging intervals
whose activity range is <= tol perturbs the power trajectory pointwise by
at most tol x the modulated map, and a passive RC network's response to a
bounded input perturbation is bounded by its DC gain.  The open-loop
(disabled-feedback) replay IS that linear system, so the property is
exact there; a closed-loop companion test documents that the DTM/refresh
couplings keep the error the same order in practice.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cosim, thermal
from repro.core.floorplan import MM, APFloorplan
from repro.stack import dram, feedback
from repro.stack.spec import PAPER_SPEC, PAPER_STACK, dram_on_logic

GRID_N, MARGIN, T_BASE, T_COARSE = 8, 2, 48, 12
DT = 0.05


def _activity(seed: int, tol: float) -> np.ndarray:
    """Piecewise plateaus + sub-tolerance jitter: mergeable by design,
    with genuine level changes the plan must NOT merge across."""
    rng = np.random.default_rng(seed)
    act = np.repeat(rng.uniform(0.1, 1.0, 6), T_BASE // 6)
    act = act + rng.uniform(-0.3, 0.3, T_BASE) * tol
    return np.clip(act, 0.0, 1.2)


def _case(spec, act):
    dp = cosim.comparable_design_point("dmm")
    fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
    grid = thermal.Grid(die_w=fp.die_w_mm * MM, ny=GRID_N, nx=GRID_N,
                        params=PAPER_STACK, spec=spec, margin=MARGIN)
    dfp = dram.DRAMFloorplan(die_w_mm=fp.die_w_mm)
    pmap = fp.power_map(GRID_N, dp.ap_power_W)
    build = lambda a, traffic=1e10: feedback.stack_power_frames(
        spec, grid, a, pmap, fp.leakage_W(), dfp, traffic)
    return grid, build


def _replay(spec, grid, frames, fb, *, steps, dt_scale=None):
    dyn, l0, r0, lm = frames
    return feedback.closed_loop_replay(
        jnp.asarray(dyn), jnp.asarray(l0), jnp.asarray(r0),
        jnp.asarray(lm), grid.fields(), grid.capacity_field(), DT,
        fb=fb, die_n=GRID_N, n_die=spec.n_die_layers,
        steps_per_interval=steps, inner=thermal.InnerSolve("pcg", 25),
        margin=MARGIN, dt_scale=dt_scale)


def _coarse_vs_exact(spec, act, tol, fb):
    grid, build = _case(spec, act)
    exact = _replay(spec, grid, build(act), fb, steps=1)
    plan = cosim.coarsen_plan(act, tol, max_merge=8).pad_to(T_COARSE)
    coarse = _replay(spec, grid, build(plan.merge(act)), fb, steps=4,
                     dt_scale=jnp.asarray(plan.dt_scale()))
    frames = build(act)[0]
    bound = tol * cosim.dc_peak_rise_C(frames.max(axis=0), grid.fields())
    err = abs(float(np.asarray(exact[1]).max())
              - float(np.asarray(coarse[1]).max()))
    return err, bound, plan


@pytest.mark.parametrize("spec", [PAPER_SPEC, dram_on_logic(2)],
                         ids=["paper", "dram2"])
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1 << 16),
       tol=st.sampled_from((0.05, 0.1, 0.2)))
def test_coarsened_peak_error_within_advertised_bound(spec, seed, tol):
    err, bound, plan = _coarse_vs_exact(
        spec, _activity(seed, tol), tol,
        feedback.FeedbackParams.disabled())
    assert plan.n_base == T_BASE and plan.n_coarse == T_COARSE
    assert err <= bound, (err, bound)


def test_closed_loop_coarsening_stays_small():
    """With DTM/refresh/leakage active the system is no longer linear,
    so the DC bound is not a theorem — but the couplings are weak per
    interval and the error stays the same order (documented §9.3)."""
    tol = 0.1
    err, bound, _ = _coarse_vs_exact(
        dram_on_logic(2), _activity(7, tol), tol,
        feedback.FeedbackParams())
    assert err <= 2.0 * bound, (err, bound)


def test_plan_invariants_and_padding():
    act = _activity(3, 0.1)
    plan = cosim.coarsen_plan(act, 0.1, max_merge=8)
    assert plan.n_base == T_BASE
    assert (plan.reps >= 1).all() and (plan.reps <= 8).all()
    # within-run range respects the tolerance
    edges = np.concatenate([[0], np.cumsum(plan.reps)])
    for i in range(plan.n_coarse):
        seg = act[edges[i]:edges[i + 1]]
        assert seg.max() - seg.min() <= 0.1 + 1e-12
    # merging conserves energy: duration-weighted mean is the plain mean
    merged = plan.merge(act)
    np.testing.assert_allclose(merged @ plan.reps / plan.n_base,
                               act.mean(), rtol=1e-12)
    # expand is the right inverse on run-constant signals
    np.testing.assert_array_equal(plan.merge(plan.expand(merged)), merged)
    # padding only splits runs — same coverage, finer plan
    padded = plan.pad_to(T_BASE)
    assert padded.n_coarse == T_BASE and (padded.reps == 1).all()
    with pytest.raises(ValueError):
        cosim.coarsen_plan(act, -0.1)
    with pytest.raises(ValueError):
        cosim.CoarsePlan(np.array([0, 3]))


def test_variable_dt_matches_fixed_dt_at_unit_scale():
    """dt_scale=ones must reproduce the fixed-step replay bitwise — the
    guarantee that lets the serving path share one code path."""
    spec = dram_on_logic(2)
    act = _activity(1, 0.1)
    grid, build = _case(spec, act)
    fb = feedback.FeedbackParams()
    a = _replay(spec, grid, build(act), fb, steps=1)
    b = _replay(spec, grid, build(act), fb, steps=1,
                dt_scale=jnp.ones(T_BASE))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_variable_dt_rejects_multigrid():
    spec = dram_on_logic(2)
    act = _activity(1, 0.1)
    grid, build = _case(spec, act)
    dyn, l0, r0, lm = build(act)
    with pytest.raises(ValueError, match="solver='pcg'"):
        feedback.closed_loop_replay(
            jnp.asarray(dyn), jnp.asarray(l0), jnp.asarray(r0),
            jnp.asarray(lm), grid.fields(), grid.capacity_field(), DT,
            fb=feedback.FeedbackParams(), die_n=GRID_N,
            n_die=spec.n_die_layers, steps_per_interval=1,
            inner=thermal.InnerSolve("mg", 3), margin=MARGIN,
            dt_scale=jnp.ones(T_BASE))
