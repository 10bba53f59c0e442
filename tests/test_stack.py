"""Heterogeneous 3D-stack subsystem (repro/stack/): spec-built operators
vs the legacy PAPER_STACK path, power-map conservation across grid
resolutions (property tests), JEDEC refresh bins, and the closed-loop
feedback replay (Picard convergence, open-loop equivalence, DTM)."""
import math

import numpy as np
import pytest

import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import cosim, thermal
from repro.core import models as M
from repro.core.constants import AMBIENT_C, DRAM_LIMIT_C
from repro.core.floorplan import MM, APFloorplan, SIMDFloorplan
from repro.stack import dram, feedback
from repro.stack.spec import (LOGIC, PAPER_SPEC, SPREADER, Interface, Layer,
                              StackSpec, dram_on_logic, spec_from_params)


# ------------------------------------------------------------ spec structure
def test_paper_spec_reproduces_legacy_formulas():
    """The generalized spec math == the hand-derived PAPER_STACK values."""
    p = thermal.PAPER_STACK
    s = spec_from_params(p)
    assert s.n_layers == p.n_layers and s.n_die_layers == p.n_si_layers
    np.testing.assert_allclose(
        s.lateral_conductances(),
        [p.k_si * p.t_si] * 4 + [p.k_spreader * p.t_spreader], rtol=1e-12)
    cell_area = 1.37e-8
    r_sisi = p.t_si / p.k_si + p.r_bond          # half-Si + bond + half-Si
    r_tim = 0.5 * p.t_si / p.k_si + p.t_tim / p.k_tim \
        + 0.5 * p.t_spreader / p.k_spreader
    np.testing.assert_allclose(
        s.vertical_conductances(cell_area),
        cell_area / np.array([r_sisi] * 3 + [r_tim]), rtol=1e-12)
    np.testing.assert_allclose(
        s.capacities(cell_area),
        [p.c_si * cell_area * p.t_si] * 4
        + [p.c_cu * cell_area * p.t_spreader], rtol=1e-12)
    area = (7.33e-3) ** 2
    assert s.package_resistance(area) == \
        pytest.approx(thermal.package_resistance(area, p), rel=1e-12)


def test_spec_route_matches_params_route_exactly():
    """Grid(spec=PAPER_SPEC) and Grid(params=PAPER_STACK) are bit-equal."""
    g1 = thermal.Grid(die_w=5e-3, ny=12, nx=12, margin=3)
    g2 = thermal.Grid(die_w=5e-3, ny=12, nx=12, margin=3, spec=PAPER_SPEC)
    c1, c2 = g1.conductances(), g2.conductances()
    for k in c1:
        np.testing.assert_array_equal(np.asarray(c1[k]), np.asarray(c2[k]))
    F1, F2 = g1.fields(), g2.fields()
    for k in F1:
        np.testing.assert_array_equal(np.asarray(F1[k]), np.asarray(F2[k]))
    np.testing.assert_array_equal(np.asarray(g1.capacity_field()),
                                  np.asarray(g2.capacity_field()))
    rng = np.random.default_rng(0)
    power = rng.uniform(0, 2e-3, (4, 12, 12)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(thermal.steady_state(power, g1)),
        np.asarray(thermal.steady_state(power, g2)))


def test_dram_on_logic_structure():
    s = dram_on_logic(2)
    assert s.n_layers == 7 and s.n_die_layers == 6
    assert s.dram_layers == (0, 1)
    assert s.logic_layers == (2, 3, 4, 5)
    assert s.layers[-1].kind == SPREADER
    assert [i.name for i in s.interfaces[:2]] == ["tsv", "tsv"]
    assert dram_on_logic(0) is spec_from_params(thermal.PAPER_STACK)
    np.testing.assert_array_equal(s.layer_mask(LOGIC),
                                  [0, 0, 1, 1, 1, 1, 0])
    # DRAM dies are thin: vertical coupling through them stays finite
    assert np.isfinite(s.vertical_conductances(1e-8)).all()


def test_spec_validation_errors():
    si = Layer("si", LOGIC, 250e-6, 110.0, 1.75e6)
    sp = Layer("spr", SPREADER, 1e-3, 400.0, 3.45e6)
    bond = Interface("bond", 0.7e-6)
    with pytest.raises(ValueError):            # wrong interface count
        StackSpec("bad", (si, sp), ())
    with pytest.raises(ValueError):            # spreader not last
        StackSpec("bad", (sp, si), (bond,))
    with pytest.raises(ValueError):            # spreader in the middle
        StackSpec("bad", (si, sp, sp), (bond, bond))
    with pytest.raises(ValueError):            # bad kind
        Layer("x", "copper", 1e-3, 400.0, 3.45e6)
    with pytest.raises(ValueError):            # negative interface R
        Interface("bad", -1e-6)
    with pytest.raises(ValueError):            # non-positive thickness
        Layer("x", LOGIC, 0.0, 110.0, 1.75e6)


# ------------------------------------------------- power-map conservation
@given(act_W=st.floats(0.05, 20.0), ref_W=st.floats(0.005, 2.0),
       leak_W=st.floats(0.005, 2.0),
       grid_n=st.sampled_from([3, 8, 12, 16, 32]))
@settings(max_examples=25, deadline=None)
def test_dram_power_map_conserves_wattage(act_W, ref_W, leak_W, grid_n):
    fp = dram.DRAMFloorplan(die_w_mm=5.0)
    pm = fp.power_map(grid_n, act_W, ref_W, leak_W)
    assert pm.shape == (grid_n, grid_n)
    assert pm.sum() == pytest.approx(act_W + ref_W + leak_W, rel=1e-9)
    assert (pm >= 0).all()
    assert fp.activate_map(grid_n).sum() == pytest.approx(1.0, rel=1e-9)
    assert fp.refresh_map(grid_n).sum() == pytest.approx(1.0, rel=1e-9)


@given(p_layer=st.floats(4.0, 40.0),
       grid_n=st.sampled_from([8, 16, 32, 64, 192]))
@settings(max_examples=25, deadline=None)
def test_ap_power_map_conserves_wattage(p_layer, grid_n):
    fp = APFloorplan()
    pm = fp.power_map(grid_n, p_layer)
    assert pm.sum() == pytest.approx(p_layer, rel=1e-6)


def test_simd_power_map_conserves_wattage():
    dp = cosim.comparable_design_point("dmm")
    fp = SIMDFloorplan(die_w_mm=math.sqrt(dp.simd_area_mm2))
    wl = M.WORKLOADS["dmm"]
    p_exec, p_sync, _ = M.simd_phase_powers(wl, dp.simd_n_pus)
    # 2/4: degenerate grids (no tiles rasterize -> uniform fallback)
    for grid_n in (2, 4, 8, 16, 32):
        pm = fp.power_map(grid_n, dp)
        assert pm.sum() == pytest.approx(
            p_exec + p_sync + fp.leakage_W(dp), rel=1e-6)


def test_stack_power_inputs_conserve_wattage():
    """Time-mean of dyn + static leak/refresh == logic + DRAM totals."""
    grid_n, margin, n_dram = 8, 2, 2
    spec = dram_on_logic(n_dram)
    dp = cosim.comparable_design_point("dmm")
    fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
    pmap = fp.power_map(grid_n, dp.ap_power_W)
    grid = thermal.Grid(die_w=fp.die_w_mm * MM, ny=grid_n, nx=grid_n,
                        spec=spec, margin=margin)
    rng = np.random.default_rng(1)
    act = rng.uniform(0.3, 1.8, 10)
    trace = cosim.PowerTrace(act / act.mean())
    dfp = dram.DRAMFloorplan(die_w_mm=fp.die_w_mm)
    traffic = M.mem_traffic_bytes_per_s("dmm", dp.ap_n_pus)
    dyn, leak0, ref0, lmask = feedback.stack_power_inputs(
        spec, grid, trace, pmap, fp.leakage_W(), dfp, traffic)
    n_logic = len(spec.logic_layers)
    exp_dyn = n_logic * (pmap.sum() - fp.leakage_W()) \
        + n_dram * dram.activate_io_W(traffic, n_dram)
    assert dyn.sum(axis=(1, 2, 3)).mean() == pytest.approx(exp_dyn, rel=1e-5)
    assert leak0.sum() == pytest.approx(
        n_logic * fp.leakage_W() + n_dram * dfp.leakage_W(), rel=1e-5)
    assert ref0.sum() == pytest.approx(n_dram * dfp.base_refresh_W(),
                                       rel=1e-5)
    assert dyn[:, -1].sum() == 0.0          # spreader heatless
    np.testing.assert_array_equal(lmask, spec.layer_mask(LOGIC))


def test_power_frames_on_heterogeneous_grid_power_logic_only():
    """cosim.power_frames must NOT deposit logic power on DRAM dies."""
    spec = dram_on_logic(2)
    grid = thermal.Grid(die_w=3e-3, ny=8, nx=8, spec=spec, margin=2)
    pmap = np.full((8, 8), 1e-2)
    trace = cosim.PowerTrace(np.ones(4))
    frames = cosim.power_frames(trace, pmap, 0.1 * pmap.sum(), grid)
    assert frames.shape == (4, 7, 12, 12)
    for i in spec.dram_layers:
        assert frames[:, i].sum() == 0.0
    assert frames[:, -1].sum() == 0.0       # spreader heatless
    assert frames.sum() == pytest.approx(
        4 * len(spec.logic_layers) * pmap.sum(), rel=1e-5)


# --------------------------------------------------------- refresh model
def test_refresh_multiplier_bins():
    T = jnp.array([20.0, 84.9, 85.0, 94.9, 95.0, 120.0])
    np.testing.assert_array_equal(np.asarray(dram.refresh_multiplier(T)),
                                  [1.0, 1.0, 2.0, 2.0, 4.0, 4.0])
    assert float(dram.refresh_multiplier(DRAM_LIMIT_C - 1e-3)) == 1.0


def test_activate_io_power_scales_with_traffic_and_dies():
    w1 = dram.activate_io_W(1e10, 1)
    assert w1 == pytest.approx(1e10 * 8 * dram.E_ACT_PJ_PER_BIT * 1e-12)
    assert dram.activate_io_W(1e10, 4) == pytest.approx(w1 / 4)


# ------------------------------------------------------- closed-loop replay
def _open_loop_case(grid_n=8, margin=2, n_intervals=10):
    dp = cosim.comparable_design_point("dmm")
    fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
    pmap = fp.power_map(grid_n, dp.ap_power_W)
    trace = cosim.ap_workload_trace("dmm", n_intervals)
    spec = dram_on_logic(0)
    grid = thermal.Grid(die_w=fp.die_w_mm * MM, ny=grid_n, nx=grid_n,
                        spec=spec, margin=margin)
    return dp, fp, pmap, trace, spec, grid


def test_disabled_feedback_matches_cosim_within_tenth_degree():
    """Acceptance bar: DRAM dies off + feedback off == the homogeneous
    PAPER_STACK cosim replay within 0.1 C."""
    grid_n, margin, n_int = 8, 2, 10
    dp, fp, pmap, trace, spec, grid = _open_loop_case(grid_n, margin, n_int)
    interval_dt = 0.25 / n_int
    kw = dict(steps_per_interval=2, inner=thermal.InnerSolve("pcg", 40),
              margin=margin, die_n=grid_n)
    frames = cosim.power_frames(trace, pmap, fp.leakage_W(), grid)
    _, pk_ref, mn_ref = cosim.cosim_transient(
        jnp.asarray(frames), grid.fields(), grid.capacity_field(),
        interval_dt, **kw)
    dyn, leak0, ref0, lmask = feedback.stack_power_inputs(
        spec, grid, trace, pmap, fp.leakage_W(),
        dram.DRAMFloorplan(die_w_mm=fp.die_w_mm), 0.0)
    assert ref0.sum() == 0.0
    _, pk, mn, res, thr, ref_W, leak_W, dyn_W = feedback.closed_loop_replay(
        jnp.asarray(dyn), jnp.asarray(leak0), jnp.asarray(ref0),
        jnp.asarray(lmask), grid.fields(), grid.capacity_field(),
        interval_dt, fb=feedback.FeedbackParams.disabled(),
        n_die=spec.n_die_layers, **kw)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pk_ref), atol=0.1)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(mn_ref), atol=0.1)
    assert (np.asarray(thr) == 1.0).all()       # DTM never tripped
    assert (np.asarray(ref_W) == 0.0).all()     # no DRAM, no refresh
    # T-independent power: the 2nd Picard iterate reproduces the 1st, so
    # the recorded fixed-point residual is exactly zero
    assert (np.asarray(res) == 0.0).all()


def test_closed_loop_converges_and_feedback_heats():
    """Picard residual meets the documented bar; refresh/leakage feedback
    strictly raises the hot die's temperature on the hot (SIMD) stack."""
    fb = feedback.FeedbackParams(dtm_trip_C=math.inf)   # isolate heating
    inner = thermal.InnerSolve("pcg", 30)
    res = feedback.run_stack_cosim(
        workloads=("dmm",), n_dram=1, grid_n=8, n_intervals=12,
        t_end=0.25, steps_per_interval=1, inner=inner, fb=fb)
    res0 = feedback.run_stack_cosim(
        workloads=("dmm",), n_dram=1, grid_n=8, n_intervals=12,
        t_end=0.25, steps_per_interval=1, inner=inner,
        fb=feedback.FeedbackParams.disabled())
    for machine in ("ap", "simd"):
        r = res["dmm"][machine]
        assert r.converged, r.residual_C.max()
        assert r.residual_C.shape == (12,)
    hot, hot0 = res["dmm"]["simd"], res0["dmm"]["simd"]
    assert hot.peak_C.max() > hot0.peak_C.max() + 1.0
    assert hot.refresh_overhead > 1.2           # JEDEC derating engaged
    assert hot0.refresh_overhead == pytest.approx(1.0)
    cool = res["dmm"]["ap"]
    assert cool.refresh_overhead == pytest.approx(1.0, abs=1e-3)
    assert cool.dram_time_above_limit_s == 0.0
    assert hot.dram_time_above_limit_s > 0.0


def test_dtm_throttle_caps_and_costs_runtime():
    """A low trip point must clamp the AP stack and charge a slowdown."""
    fb_hot = feedback.FeedbackParams(dtm_trip_C=48.0, dtm_ramp_C=2.0,
                                     dtm_floor=0.3)
    run = lambda fb: feedback.run_stack_cosim(
        workloads=("dmm",), n_dram=1, grid_n=8, n_intervals=12,
        t_end=0.25, steps_per_interval=1,
        inner=thermal.InnerSolve("pcg", 30), fb=fb)["dmm"]["ap"]
    r_dtm = run(fb_hot)
    r_free = run(feedback.FeedbackParams(dtm_trip_C=math.inf))
    assert r_dtm.dtm_slowdown > 1.05
    assert r_free.dtm_slowdown == pytest.approx(1.0)
    assert r_dtm.logic_peak_C.max() < r_free.logic_peak_C.max() - 0.5
    assert (r_dtm.throttle >= fb_hot.dtm_floor - 1e-6).all()


def test_run_stack_cosim_batch_shapes_and_ordering():
    res = feedback.run_stack_cosim(
        workloads=("dmm", "fft"), n_dram=2, grid_n=8, n_intervals=8,
        t_end=0.1, steps_per_interval=1,
        inner=thermal.InnerSolve("pcg", 25))
    spec = res["spec"]
    assert spec.n_die_layers == 6
    for w in ("dmm", "fft"):
        for machine in ("ap", "simd"):
            r = res[w][machine]
            assert r.peak_C.shape == (8, 6)
            assert np.isfinite(r.peak_C).all()
            assert (r.peak_C >= r.min_C - 1e-4).all()
            assert (r.peak_C > AMBIENT_C - 1.0).all()
        # AP runs cooler than the same-performance SIMD under DRAM too
        assert res[w]["ap"].dram_peak_C.max() < \
            res[w]["simd"].dram_peak_C.max()


@pytest.mark.pallas
def test_heterogeneous_stack_pallas_matches_jnp():
    """The Pallas stencil is layer-depth generic: a 7-layer DRAM stack
    must solve identically to the jnp oracle."""
    spec = dram_on_logic(2)
    g = thermal.Grid(die_w=5e-3, ny=16, nx=16, margin=4, spec=spec)
    p = np.zeros((6, 16, 16), np.float32)
    p[list(spec.logic_layers)] = 1e-3
    T_j = np.asarray(thermal.steady_state(p, g, use_pallas=False))
    T_p = np.asarray(thermal.steady_state(p, g, use_pallas=True))
    np.testing.assert_allclose(T_j, T_p, rtol=1e-5, atol=1e-3)


def test_steady_state_with_unpowered_dram_dies():
    """DRAM-on-top steady state: DRAM floor temp == top logic die's (heat
    flows down), and the homogeneous result is unchanged underneath."""
    from repro.core.floorplan import thermal_comparison

    res_h = thermal_comparison(grid_ap=32, grid_simd=16, workload="dmm")
    res_d = thermal_comparison(grid_ap=32, grid_simd=16, workload="dmm",
                               stack=dram_on_logic(2))
    spec = dram_on_logic(2)
    for name in ("ap", "simd"):
        peaks_h = res_h[name]["peak_C"]
        peaks_d = res_d[name]["peak_C"]
        assert len(peaks_d) == 6
        # unpowered DRAM adds no heat, only lateral spreading mass on top:
        # it can only COOL the logic peak, and only by a few degrees
        for lh, ld in zip(peaks_h, [peaks_d[i] for i in spec.logic_layers]):
            assert ld <= lh + 0.05
            assert ld > lh - 6.0
        # passive DRAM floats to just under the top logic temperature (it
        # keeps spreading the hot spot laterally, so its own peak is a few
        # degrees BELOW the logic peak, never above)
        top_logic = peaks_d[spec.logic_layers[0]]
        for i in spec.dram_layers:
            assert top_logic - 5.0 < peaks_d[i] <= top_logic + 0.1
        # peaks cool monotonically away from the logic heat source
        assert peaks_d[spec.dram_layers[0]] <= \
            peaks_d[spec.dram_layers[-1]] + 0.1
    # the AP's profile is already near-uniform, so the extra spreader
    # barely matters there — the paper's flatness claim, restated
    assert res_d["ap"]["peak_C"][spec.logic_layers[0]] == \
        pytest.approx(res_h["ap"]["peak_C"][0], abs=0.3)
