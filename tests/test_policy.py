"""The DVFS/DTM policy engine (repro/policy/ + the rewired closed loop).

The load-bearing pin: ``policy="ramp"`` (the default FeedbackParams)
must reproduce the PRE-policy-engine sampled-ramp trajectories
BIT-IDENTICALLY — ``_legacy_closed_loop`` below is that historical scan
body copied verbatim, and every output of the rewired replay is
asserted bitwise equal against it, including a case where the DTM is
actively tripping.  Plus: the ramp_C == 0 step-trip guard, the
FeedbackParams validation contract, controller edge cases (trip at inf,
floor = 1, hysteresis hold band), the DVFS table, and the per-die
rescue that feeds the Pareto bench's verdict flip.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.policy as P
from repro.core import cosim, thermal
from repro.core import models as M
from repro.core.floorplan import MM, APFloorplan, SIMDFloorplan
from repro.policy import PolicyContext
from repro.stack import dram, feedback
from repro.stack.spec import dram_on_logic

GRID_N, MARGIN, N_INT, DT = 8, 2, 10, 0.25 / 10


# ---------------------------------------------------------------------------
# the historical closed loop, copied verbatim from the pre-policy engine
# (git 15aaa8f stack/feedback.py) — the regression oracle
# ---------------------------------------------------------------------------

def _legacy_closed_loop(dyn_frames, leak0, refresh0, logic_mask, F, cap3,
                        interval_dt, theta, t_amb, *,
                        fb: feedback.FeedbackParams,
                        steps_per_interval: int, n_cg: int, n_die: int,
                        margin: int, die_n: int, dt_scale=None):
    A = lambda v: thermal.apply_operator_fields(v, F)
    if dt_scale is None:
        dt = interval_dt / steps_per_interval
        solve = thermal.implicit_lhs_solver(
            A, F, cap3, theta, thermal.InnerSolve("pcg", n_cg))(dt)
        solve_for = lambda _scale: solve
    else:
        diagA = thermal._diag_fields(F)

        def solve_for(scale):
            dt = interval_dt * scale / steps_per_interval
            lhs = lambda v: cap3 / dt * v + theta * A(v)
            Minv = 1.0 / (cap3 / dt + theta * diagA)
            return lambda rhs: thermal.pcg_fixed(lhs, Minv, rhs, n_cg)
    lm3 = logic_mask[:, None, None]

    def interval(dTc, xs):
        P_dyn, scale = xs
        solve = solve_for(scale)
        t_logic = jnp.max(jnp.where(lm3 > 0, dTc + t_amb, -jnp.inf))
        f = jnp.clip(1.0 - (t_logic - fb.dtm_trip_C) / fb.dtm_ramp_C,
                     fb.dtm_floor, 1.0)
        P_base = f * P_dyn

        def picard(_, st):
            dTk, _res, _aux = st
            T = dTk + t_amb
            p_leak = leak0 * jnp.exp(fb.leak_beta * (T - fb.t_ref_C))
            p_ref = refresh0 * dram.refresh_multiplier(T) \
                if fb.refresh_feedback else refresh0
            P = P_base + p_leak + p_ref

            def one(d, _):
                rhs = P - A(d)
                return d + solve(rhs), None

            dTn, _ = jax.lax.scan(one, dTc, None,
                                  length=steps_per_interval)
            return dTn, jnp.max(jnp.abs(dTn - dTk)), \
                (jnp.sum(p_ref), jnp.sum(p_leak))

        init = (dTc, jnp.float32(jnp.inf),
                (jnp.float32(0.0), jnp.float32(0.0)))
        dTn, res, (ref_W, leak_W) = jax.lax.fori_loop(
            0, fb.n_picard, picard, init)
        die = dTn[:n_die, margin:margin + die_n, margin:margin + die_n]
        return dTn, (jnp.max(die, axis=(1, 2)), jnp.min(die, axis=(1, 2)),
                     res, f, ref_W, leak_W)

    dT0 = jnp.zeros_like(dyn_frames[0])
    scales = jnp.ones(dyn_frames.shape[0], dyn_frames.dtype) \
        if dt_scale is None else jnp.asarray(dt_scale, dyn_frames.dtype)
    dT_end, (mx, mn, res, f, ref_W, leak_W) = \
        jax.lax.scan(interval, dT0, (dyn_frames, scales))
    return dT_end + t_amb, mx + t_amb, mn + t_amb, res, f, ref_W, leak_W


# ------------------------------------------------------------ case builders

def _case(machine: str, n_dram: int = 2):
    """Replay inputs for one (machine, stack) case; "simd" runs hot
    enough that the default DTM ramp actively trips."""
    spec = dram_on_logic(n_dram)
    w = "dmm"
    dp = cosim.comparable_design_point(w)
    if machine == "ap":
        fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
        pmap = fp.power_map(GRID_N, dp.ap_power_W)
        leak_W = fp.leakage_W()
        trace = cosim.ap_workload_trace(w, N_INT)
    else:
        fp = SIMDFloorplan(die_w_mm=math.sqrt(dp.simd_area_mm2))
        pmap = fp.power_map(GRID_N, dp)
        leak_W = fp.leakage_W(dp)
        trace = cosim.simd_phase_trace(M.WORKLOADS[w], dp, N_INT)
    grid = thermal.Grid(die_w=fp.die_w_mm * MM, ny=GRID_N, nx=GRID_N,
                        spec=spec, margin=MARGIN)
    dfp = dram.DRAMFloorplan(die_w_mm=fp.die_w_mm)
    traffic = M.mem_traffic_bytes_per_s(w, dp.ap_n_pus)
    dyn, l0, r0, lm = feedback.stack_power_inputs(
        spec, grid, trace, pmap, leak_W, dfp, traffic)
    return spec, grid, (jnp.asarray(dyn), jnp.asarray(l0),
                        jnp.asarray(r0), jnp.asarray(lm))


def _replay(spec, grid, frames, fb, dt_scale=None, **kw):
    return feedback.closed_loop_replay(
        *frames, grid.fields(), grid.capacity_field(), DT, fb=fb,
        die_n=GRID_N, n_die=spec.n_die_layers, steps_per_interval=1,
        inner=thermal.InnerSolve("pcg", 20), margin=MARGIN,
        dt_scale=dt_scale, **kw)


# ---------------------------------------------------------------------------
# THE pin: default policy == historical ramp, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("machine,fb,scaled", [
    ("simd", feedback.FeedbackParams(), False),       # DTM actively trips
    ("ap", feedback.FeedbackParams.disabled(), False),
    ("simd", feedback.FeedbackParams(), True),        # variable-dt path
], ids=["tripping", "disabled", "dt_scale"])
def test_ramp_policy_bit_identical_to_legacy(machine, fb, scaled):
    spec, grid, frames = _case(machine)
    dt_scale = jnp.ones(N_INT) if scaled else None
    new = _replay(spec, grid, frames, fb, dt_scale=dt_scale)
    old = _legacy_closed_loop(
        *frames, grid.fields(), grid.capacity_field(), DT, 1.0,
        feedback.AMBIENT_C, fb=fb, steps_per_interval=1, n_cg=20,
        n_die=spec.n_die_layers, margin=MARGIN, die_n=GRID_N,
        dt_scale=dt_scale)
    assert len(new) == 8 and len(old) == 7
    if machine == "simd" and not scaled:        # the pin must have teeth
        assert float(np.asarray(new[4]).min()) < 1.0
    for x, y in zip(old, new):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_explicit_ramp_policy_matches_default():
    """policy=RampPolicy(dtm fields) is the same controller as
    policy=None — resolved_policy() is a pure re-labeling."""
    spec, grid, frames = _case("simd")
    a = _replay(spec, grid, frames, feedback.FeedbackParams())
    b = _replay(spec, grid, frames, feedback.FeedbackParams(
        policy=P.RampPolicy(trip_C=95.0, ramp_C=10.0, floor=0.25)))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# satellite: ramp_C == 0 is a step trip, not a NaN factory
# ---------------------------------------------------------------------------

def test_step_trip_zero_ramp_is_finite_bang_bang():
    spec, grid, frames = _case("simd")
    fb = feedback.FeedbackParams(dtm_ramp_C=0.0, dtm_trip_C=60.0)
    out = _replay(spec, grid, frames, fb)
    thr = np.asarray(out[4])
    assert np.isfinite(thr).all()
    # bang-bang: every decision is the floor or full duty, and the hot
    # SIMD stack must actually trip
    assert set(np.unique(thr)) <= {np.float32(0.25), np.float32(1.0)}
    assert (thr == 0.25).any()
    for x in out[:4]:
        assert np.isfinite(np.asarray(x)).all()


def test_ramp_duty_step_limit():
    """ramp_duty at ramp_C=0: duty is 1 AT the trip, floor above it —
    the limit of the linear ramp, where the old expression went 0/0."""
    duty = P.ramp_duty(jnp.float32(95.0), 95.0, 0.0, 0.25)
    assert float(duty) == 1.0
    assert float(P.ramp_duty(jnp.float32(95.1), 95.0, 0.0, 0.25)) == 0.25


# ---------------------------------------------------------------------------
# satellite: FeedbackParams / policy parameter validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [dict(dtm_floor=0.0), dict(dtm_floor=-0.1),
                                dict(dtm_floor=1.5)])
def test_feedback_params_rejects_bad_floor(kw):
    with pytest.raises(ValueError, match="dtm_floor"):
        feedback.FeedbackParams(**kw)


@pytest.mark.parametrize("trip", [math.nan, -math.inf])
def test_feedback_params_rejects_non_real_trip(trip):
    with pytest.raises(ValueError, match="dtm_trip_C"):
        feedback.FeedbackParams(dtm_trip_C=trip)


def test_feedback_params_accepts_inf_trip_and_rejects_negative_ramp():
    feedback.FeedbackParams(dtm_trip_C=math.inf)    # legal: never trips
    with pytest.raises(ValueError, match="dtm_ramp_C"):
        feedback.FeedbackParams(dtm_ramp_C=-1.0)


def test_policy_constructors_validate():
    with pytest.raises(ValueError, match="floor"):
        P.RampPolicy(floor=0.0)
    with pytest.raises(ValueError, match="trip_C"):
        P.HysteresisPolicy(trip_C=math.nan)
    with pytest.raises(ValueError, match="band_C"):
        P.DVFSPolicy(band_C=-1.0)
    with pytest.raises(ValueError, match="n_cands"):
        P.PredictivePolicy(n_cands=1)
    with pytest.raises(ValueError, match="unknown policy"):
        P.get("nope")


# ---------------------------------------------------------------------------
# satellite: controller edge cases
# ---------------------------------------------------------------------------

def test_trip_at_inf_never_throttles():
    spec, grid, frames = _case("simd")
    out = _replay(spec, grid, frames,
                  feedback.FeedbackParams(dtm_trip_C=math.inf))
    assert (np.asarray(out[4]) == 1.0).all()


def test_floor_one_is_a_noop_throttle():
    """floor=1.0 clamps the duty to exactly 1 — bitwise the trip-at-inf
    replay (the throttle multiplies by literal 1.0 either way)."""
    spec, grid, frames = _case("simd")
    a = _replay(spec, grid, frames,
                feedback.FeedbackParams(dtm_floor=1.0, dtm_trip_C=50.0))
    b = _replay(spec, grid, frames,
                feedback.FeedbackParams(dtm_trip_C=math.inf))
    assert (np.asarray(a[4]) == 1.0).all()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _hys_ctx(t):
    mask = jnp.array([1.0, 0.0])
    return PolicyContext(layer_T=jnp.array([t, 0.0]), logic_mask=mask,
                         dram_mask=1.0 - mask, predict_hot=None)


def test_hysteresis_holds_inside_band():
    """Within (trip-band, trip] the latch HOLDS: a temperature dwelling
    inside the band cannot flip the duty in either direction."""
    pol = P.HysteresisPolicy(trip_C=90.0, band_C=5.0, floor=0.25)
    s = pol.init_state()
    s, f, _ = pol.act(s, _hys_ctx(80.0))
    assert float(f) == 1.0
    s, f, _ = pol.act(s, _hys_ctx(91.0))        # trips
    assert float(f) == 0.25
    for t in (88.0, 86.0, 89.9, 85.1):          # dwell inside the band
        s, f, _ = pol.act(s, _hys_ctx(t))
        assert float(f) == 0.25                 # held, no oscillation
    s, f, _ = pol.act(s, _hys_ctx(84.9))        # below trip - band
    assert float(f) == 1.0
    for t in (86.0, 89.0):                      # band from below: held
        s, f, _ = pol.act(s, _hys_ctx(t))
        assert float(f) == 1.0


def test_pid_regulates_toward_target():
    """Sustained over-temperature drives the duty down; cooling releases
    it (integral anti-windup keeps it within [floor, 1])."""
    pol = P.PIDPolicy(target_C=90.0, floor=0.25)
    s = pol.init_state()
    duties = []
    for _ in range(10):
        s, f, _ = pol.act(s, _hys_ctx(100.0))
        duties.append(float(f))
    assert duties[-1] <= duties[0] and duties[-1] == 0.25
    for _ in range(60):
        s, f, _ = pol.act(s, _hys_ctx(40.0))
    assert float(f) == 1.0


# ---------------------------------------------------------------------------
# DVFS tables
# ---------------------------------------------------------------------------

def test_dvfs_table_structure():
    for node in P.nodes():
        t = P.build_dvfs_table(node)
        f = [op.f_mhz for op in t.points]
        assert f == sorted(f) and len(set(f)) == len(f)
        ps, fs = t.power_scales(), t.perf_scales()
        assert ps[-1] == 1.0 and fs[-1] == 1.0
        # voltage scaling: power falls FASTER than frequency at every
        # lower operating point — the lever the Pareto bench exploits
        assert all(p < s for p, s in zip(ps[:-1], fs[:-1]))


def test_dvfs_table_validation():
    op = P.OperatingPoint
    with pytest.raises(ValueError, match=">= 2 operating points"):
        P.DVFSTable("x", (op(1000, 1.0),))
    with pytest.raises(ValueError, match="sorted"):
        P.DVFSTable("x", (op(2000, 1.0), op(1000, 0.8)))
    with pytest.raises(ValueError, match="unknown technology node"):
        P.build_dvfs_table("7nm")


def test_dvfs_residency_attribution():
    pol = P.DVFSPolicy()
    fs = pol.table.perf_scales()
    duty = np.array([fs[-1], fs[-1], fs[0], fs[1] + 1e-4])
    res = pol.residency(duty)
    labels = pol.table.labels()
    assert res[labels[-1]] == 2 and res[labels[0]] == 1 \
        and res[labels[1]] == 1
    assert P.RampPolicy().residency(duty) is None


def test_dvfs_policy_steps_one_op_per_interval():
    pol = P.DVFSPolicy(trip_C=85.0, band_C=4.0)
    s = pol.init_state()
    top = pol.table.n_ops - 1
    s, fp, ff = pol.act(s, _hys_ctx(100.0))     # hot: step down once
    assert int(s) == top - 1
    assert float(fp) < float(ff) < 1.0          # f·V² < f at a lower OP
    s, _, _ = pol.act(s, _hys_ctx(83.0))        # in band: hold
    assert int(s) == top - 1
    s, _, _ = pol.act(s, _hys_ctx(60.0))        # cool: step back up
    assert int(s) == top


# ---------------------------------------------------------------------------
# policies inside the replay: per-die rescue + predictive lookahead
# ---------------------------------------------------------------------------

def test_perdie_policy_cools_dram_below_ramp():
    """The per-die controller senses the DRAM dies directly (trip 83 °C)
    and drags logic down with them — the DRAM hot spot must come out
    cooler than under the logic-sensed default ramp."""
    spec, grid, frames = _case("simd")
    dram_l = list(spec.dram_layers)
    pk_ramp = np.asarray(_replay(
        spec, grid, frames, feedback.FeedbackParams())[1])[:, dram_l]
    pk_pd = np.asarray(_replay(
        spec, grid, frames,
        feedback.FeedbackParams(policy=P.PerDiePolicy()))[1])[:, dram_l]
    # compare where control has settled (the final interval): phase
    # spikes land identically under ANY sampled policy — one interval of
    # lag is irreducible — but the regulated level must come out cooler
    assert pk_pd[-1].max() < pk_ramp[-1].max() - 1.0


def test_predictive_policy_cuts_peak_overshoot():
    """Acting on the forecast instead of the measurement shaves the
    reactive ramp's overshoot on the hot stack."""
    spec, grid, frames = _case("simd")
    pk_ramp = np.asarray(_replay(spec, grid, frames,
                                 feedback.FeedbackParams())[1])
    out = _replay(spec, grid, frames, feedback.FeedbackParams(
        policy=P.PredictivePolicy(trip_C=95.0)))
    assert np.asarray(out[1]).max() < pk_ramp.max() - 5.0
    thr = np.asarray(out[4])
    assert (thr >= 0.25).all() and (thr <= 1.0).all()


def test_policy_state_threads_through_scan():
    """A stateful policy (hysteresis) runs jit-compiled end-to-end and
    latches: once tripped on the monotone heat-up it stays at the floor
    until a genuine release crossing."""
    spec, grid, frames = _case("simd")
    fb = feedback.FeedbackParams(policy=P.HysteresisPolicy(
        trip_C=70.0, band_C=5.0, floor=0.25))
    thr = np.asarray(_replay(spec, grid, frames, fb)[4])
    assert set(np.unique(thr)) <= {np.float32(0.25), np.float32(1.0)}
    assert (thr == 0.25).any()


def test_energy_accounting():
    """dyn_W: full duty dissipates the frame power exactly; throttling
    strictly reduces it; energy_per_work_J penalizes the slowdown."""
    spec, grid, frames = _case("simd")
    free = _replay(spec, grid, frames,
                   feedback.FeedbackParams(dtm_trip_C=math.inf))
    hot = _replay(spec, grid, frames, feedback.FeedbackParams())
    dyn_free = np.asarray(free[7])
    np.testing.assert_allclose(
        dyn_free, np.asarray(frames[0]).sum(axis=(1, 2, 3)), rtol=1e-5)
    assert np.asarray(hot[7]).sum() < dyn_free.sum()
    rep = feedback.StackReport(
        label="x", interval_s=DT, spec=spec,
        peak_C=np.asarray(hot[1]), min_C=np.asarray(hot[2]),
        residual_C=np.asarray(hot[3]), throttle=np.asarray(hot[4]),
        refresh_W=np.asarray(hot[5]), leak_W=np.asarray(hot[6]),
        base_refresh_W=1.0, dyn_W=np.asarray(hot[7]))
    assert rep.energy_per_work_J > rep.energy_J > 0.0


# ---------------------------------------------------------------------------
# pareto helpers (doctests cover the arithmetic; pin the API contract)
# ---------------------------------------------------------------------------

def test_pareto_front_mixed():
    pts = [(1.0, 95.0, 5.0),     # fast, hot
           (2.0, 80.0, 4.0),     # slow, cool, efficient
           (2.5, 96.0, 6.0),     # dominated by 0 AND 1? no: hotter+slower
           (1.0, 95.0, 5.0)]     # duplicate of 0 — kept
    assert P.pareto_front(pts) == (0, 1, 3)
    assert P.dominates((1, 1, 1), (2, 2, 2))
    with pytest.raises(ValueError, match="dimension"):
        P.dominates((1.0,), (1.0, 2.0))
