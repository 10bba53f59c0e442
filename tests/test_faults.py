"""Fault injection & graceful degradation (the ISSUE 10 pins).

Four stories, each with its acceptance hook:

- **Fault models** (`repro.faults.models`): spec validation rejects
  nonsense (NaN knobs included), seeded replay is bitwise reproducible
  (hypothesis property + full-replay determinism), and the fault-free
  path is bit-identical to the pre-faults program — pinned by a jaxpr
  test (no ``random`` ops traced when ``FeedbackParams.faults`` is
  None).
- **GuardedPolicy** (`repro.faults.guard`): median-of-K rejects a stuck
  minority, NaN readings hold the last good value, sustained blindness
  on a die layer panics to the fail-safe floor — and a replay-level
  rescue: the naive per-die controller blows the 85 °C ceiling under a
  stuck primary sensor, the guarded wrapper holds it.
- **Solver fallback** (`repro.core.thermal`): a poisoned (forced-NaN)
  multigrid solve is detected by the true-residual health check and
  retried down the chain, with retry counters in the obs registry;
  exhausting the chain is loud, never silent.
- **Failure-isolated sweeps** (`repro.sweep.engine`): a group whose
  replay raises is demoted to NaN records marked FAILED; the other
  groups' results survive and nothing is persisted to the cache.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.core import cosim, thermal
from repro.faults import (GuardedPolicy, PowerFaultSpec, SensorFaultSpec,
                          inject_power_spikes, poison_solver,
                          solver_poisoned)
from repro.policy import POLICIES, PerDiePolicy
from repro.policy.base import Policy, PolicyContext
from repro.stack import feedback
from repro.stack.spec import PAPER_STACK, dram_on_logic
from repro.sweep import SweepSpec, engine, run_sweep

pytestmark = pytest.mark.faults


# ------------------------------------------------------------ spec validation

@pytest.mark.parametrize("kw", [
    {"n_sensors": 0},
    {"noise_C": -1.0},
    {"noise_C": float("nan")},
    {"offset_C": float("inf")},
    {"drift_C": float("nan")},
    {"quant_C": -0.5},
    {"n_stuck": -1},
    {"n_stuck": 4},                      # > n_sensors (default 3)
    {"p_dropout": 1.5},
    {"p_dropout": float("nan")},
])
def test_sensor_spec_rejects_bad_knobs(kw):
    with pytest.raises(ValueError):
        SensorFaultSpec(**kw)


@pytest.mark.parametrize("kw", [
    {"n_spikes": -1},
    {"width": 0},
    {"magnitude": float("nan")},
    {"magnitude": -2.0},
])
def test_power_spec_rejects_bad_knobs(kw):
    with pytest.raises(ValueError):
        PowerFaultSpec(**kw)


def test_spec_is_hashable_static():
    """The spec rides FeedbackParams as a jit static arg: frozen and
    hashable, equal specs hash equal (one compilation per regime)."""
    import dataclasses
    a = SensorFaultSpec(seed=3, noise_C=0.5)
    assert hash(a) == hash(SensorFaultSpec(seed=3, noise_C=0.5))
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.seed = 4
    assert not SensorFaultSpec().randomized
    assert SensorFaultSpec(noise_C=0.1).randomized
    assert SensorFaultSpec(p_dropout=0.1).randomized


# --------------------------------------------------- read() fault semantics

def _scan_read(spec, T_path):
    """Scan spec.read over a [T, L] true-temperature path -> [T, K, L]."""
    def step(state, T):
        state, r = spec.read(state, T)
        return state, r
    _, out = jax.lax.scan(step, spec.init_state(T_path.shape[1]),
                          jnp.asarray(T_path, jnp.float32))
    return np.asarray(out)


def test_stuck_at_latches_first_reading():
    spec = SensorFaultSpec(n_sensors=3, n_stuck=1)
    path = np.stack([np.full(4, 30.0), np.full(4, 90.0)])
    out = _scan_read(spec, path)
    np.testing.assert_array_equal(out[1, 0], 30.0)   # sensor 0 latched
    np.testing.assert_array_equal(out[1, 1:], 90.0)  # the rest track


def test_quantization_snaps_to_step():
    spec = SensorFaultSpec(n_sensors=2, quant_C=0.5)
    out = _scan_read(spec, np.array([[31.26, 47.13]]))
    np.testing.assert_array_equal(out % 0.5, 0.0)
    np.testing.assert_allclose(out[0, 0], [31.5, 47.0])


def test_dropout_returns_nan():
    heavy = _scan_read(SensorFaultSpec(n_sensors=3, p_dropout=0.5),
                       np.full((20, 2), 50.0))
    clean = _scan_read(SensorFaultSpec(n_sensors=3),
                       np.full((20, 2), 50.0))
    assert np.isnan(heavy).any()
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(clean, 50.0)


def test_drift_and_offset_compose():
    spec = SensorFaultSpec(n_sensors=2, drift_C=0.5, offset_C=1.0)
    out = _scan_read(spec, np.full((3, 1), 40.0))
    off = np.asarray(spec.init_state(1).offset)
    # interval t reads true + offset + drift*t, per sensor
    for t in range(3):
        np.testing.assert_allclose(out[t, :, 0], 40.0 + off + 0.5 * t,
                                   rtol=1e-6)


@settings(max_examples=25)
@given(seed=st.integers(0, 2**31 - 1),
       noise=st.floats(0.0, 5.0, allow_nan=False),
       p_drop=st.floats(0.0, 0.9, allow_nan=False),
       n_stuck=st.integers(0, 3))
def test_seeded_read_is_bitwise_reproducible(seed, noise, p_drop, n_stuck):
    """The property the cache/baselines rely on: same spec -> bitwise
    identical fault realizations, replay after replay."""
    spec = SensorFaultSpec(seed=seed, n_sensors=3, noise_C=noise,
                           p_dropout=p_drop, n_stuck=n_stuck)
    path = np.linspace(25.0, 95.0, 6 * 4).reshape(6, 4)
    np.testing.assert_array_equal(_scan_read(spec, path),
                                  _scan_read(spec, path))


def test_different_seeds_differ_when_randomized():
    path = np.full((8, 2), 60.0)
    a = _scan_read(SensorFaultSpec(seed=0, noise_C=1.0), path)
    b = _scan_read(SensorFaultSpec(seed=1, noise_C=1.0), path)
    assert not np.array_equal(a, b)


# ----------------------------------------------------- power-spike injection

def test_power_spikes_deterministic_and_pure():
    dyn = np.ones((10, 2, 3, 3), np.float32)
    spec = PowerFaultSpec(seed=7, n_spikes=3, magnitude=2.5)
    out = inject_power_spikes(dyn, spec)
    np.testing.assert_array_equal(out, inject_power_spikes(dyn, spec))
    np.testing.assert_array_equal(dyn, 1.0)          # input untouched
    spiked = (out[:, 0, 0, 0] == 2.5).sum()
    assert spiked == 3
    np.testing.assert_array_equal(np.unique(out), [1.0, 2.5])
    # n_spikes=0 is the identity; spikes cap at the trace length
    np.testing.assert_array_equal(inject_power_spikes(
        dyn, PowerFaultSpec(n_spikes=0)), dyn)
    all_hit = inject_power_spikes(dyn, PowerFaultSpec(n_spikes=99))
    np.testing.assert_array_equal(all_hit, 2.0)


# ----------------------------------------------------------- GuardedPolicy

def _ctx(layer_T, sensor_T=None, n_layers=None):
    L = len(layer_T) if n_layers is None else n_layers
    return PolicyContext(
        layer_T=jnp.asarray(layer_T, jnp.float32),
        logic_mask=jnp.ones(L, jnp.float32),
        dram_mask=jnp.zeros(L, jnp.float32),
        predict_hot=lambda duty: jnp.zeros_like(jnp.asarray(duty)),
        sensor_T=None if sensor_T is None
        else jnp.asarray(sensor_T, jnp.float32))


def test_guard_needs_n_layers():
    with pytest.raises(ValueError, match="n_layers"):
        GuardedPolicy().init_state()
    st3 = GuardedPolicy().init_state(3)
    assert st3[1].shape == (3,) and st3[2].shape == (3,)


@pytest.mark.parametrize("kw", [
    {"floor": 0.0}, {"floor": 1.5}, {"hold_max": 0},
    {"max_step_C": 0.0}, {"max_step_C": float("nan")},
    {"lo_C": 50.0, "hi_C": 40.0}, {"hi_C": float("inf")},
])
def test_guard_rejects_bad_knobs(kw):
    with pytest.raises(ValueError):
        GuardedPolicy(**kw)


def test_guard_median_rejects_stuck_minority():
    g = GuardedPolicy()
    state = g.init_state(2)
    # primary stuck at ambient, two healthy sensors read 80 C
    sensors = [[25.0, 25.0], [80.0, 80.0], [80.0, 80.0]]
    state, _, _ = g.act(state, _ctx([25.0, 25.0], sensors))
    np.testing.assert_allclose(np.asarray(state[1]), 80.0)
    np.testing.assert_array_equal(np.asarray(state[2]), 0)


def test_guard_nan_holds_last_good_then_panics():
    g = GuardedPolicy(hold_max=2)
    state = g.init_state(1)
    state, _, _ = g.act(state, _ctx([70.0], [[70.0]]))    # good: holds 70
    nan_ctx = _ctx([np.nan], [[np.nan]])
    state, f_p, f = g.act(state, nan_ctx)                 # bad #1: hold
    assert float(state[1][0]) == 70.0 and int(state[2][0]) == 1
    assert float(f) == 1.0
    state, f_p, f = g.act(state, nan_ctx)                 # bad #2: panic
    assert int(state[2][0]) == 2
    assert float(f_p) == float(f) == g.floor


def test_guard_implausible_jump_is_held():
    g = GuardedPolicy(max_step_C=60.0)
    state = g.init_state(1)
    state, _, _ = g.act(state, _ctx([30.0], [[30.0]]))
    state, _, _ = g.act(state, _ctx([130.0], [[130.0]]))  # +100 C in one dt
    assert float(state[1][0]) == 30.0                     # held, not trusted
    state, _, _ = g.act(state, _ctx([140.0], [[140.0]]))  # out of range hi_C?
    assert int(state[2][0]) == 2


def test_guard_fault_free_passthrough():
    """Without sensor_T the guard fuses the one true reading: T_used is
    layer_T exactly, and the inner policy sees the same context."""
    g = GuardedPolicy(inner=PerDiePolicy())
    state = g.init_state(2)
    state, f_p, f = g.act(state, _ctx([50.0, 60.0]))
    np.testing.assert_array_equal(np.asarray(state[1]), [50.0, 60.0])
    ref_state = PerDiePolicy().init_state(2)
    _, rf_p, rf = PerDiePolicy().act(ref_state, _ctx([50.0, 60.0]))
    np.testing.assert_array_equal(np.asarray(f_p), np.asarray(rf_p))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(rf))


def test_guarded_registered_in_policy_registry():
    pol = POLICIES["guarded"]()
    assert isinstance(pol, GuardedPolicy)
    assert pol.name == "guarded-perdie"


# ------------------------------------------------- replay-level integration

_GRID_N = 8
_N_INT = 16


def _sort_ap_case(spec):
    dp = cosim.comparable_design_point("sort", 2 ** 20)
    trace = cosim.ap_workload_trace("sort", _N_INT,
                                   cosim.trace_elems(2 ** 20))
    return [("sort/ap", feedback.assemble_case(
        dp, "sort", "ap", spec, PAPER_STACK, _GRID_N, trace,
        _GRID_N // 4))]


def _replay(case, spec, fb):
    return feedback.replay_cases(
        case, spec, fb, _GRID_N, 0.25 / _N_INT, steps_per_interval=1,
        inner=thermal.InnerSolve("pcg", 25), margin=_GRID_N // 4)["sort/ap"]


def test_no_spec_traces_no_random_ops():
    """FeedbackParams.faults=None must keep the traced program free of
    PRNG ops (the zero-cost pin: the fault-free path is the pre-faults
    program, not a disabled-fault program)."""
    spec = dram_on_logic(1, PAPER_STACK)
    case = _sort_ap_case(spec)
    _, leaves = case[0]
    dyn, l0, r0, lm, F, cap3 = leaves
    kw = dict(die_n=_GRID_N, n_die=spec.n_die_layers,
              steps_per_interval=1, inner=thermal.InnerSolve("pcg", 5),
              margin=_GRID_N // 4)
    clean = str(jax.make_jaxpr(
        lambda *a: feedback.closed_loop_replay(
            *a, 0.02, fb=feedback.FeedbackParams(), **kw))(
        dyn, l0, r0, lm, F, cap3))
    assert "random" not in clean
    faulted = str(jax.make_jaxpr(
        lambda *a: feedback.closed_loop_replay(
            *a, 0.02,
            fb=feedback.FeedbackParams(
                faults=SensorFaultSpec(noise_C=0.5)), **kw))(
        dyn, l0, r0, lm, F, cap3))
    assert "random" in faulted


def test_faulted_replay_is_deterministic():
    spec = dram_on_logic(2, PAPER_STACK)
    case = _sort_ap_case(spec)
    fb = feedback.FeedbackParams(
        policy=PerDiePolicy(),
        faults=SensorFaultSpec(seed=5, noise_C=1.0, p_dropout=0.1))
    a, b = _replay(case, spec, fb), _replay(case, spec, fb)
    np.testing.assert_array_equal(a.peak_C, b.peak_C)
    np.testing.assert_array_equal(a.throttle, b.throttle)


def test_stuck_sensor_rescue():
    """THE acceptance scenario: a stuck-at-ambient primary sensor blinds
    the naive per-die controller (DRAM blows the 85 C ceiling) while the
    guarded wrapper's median still sees the true temperature and holds
    the fault-free trajectory."""
    spec = dram_on_logic(2, PAPER_STACK)
    case = _sort_ap_case(spec)
    stuck = SensorFaultSpec(seed=0, n_sensors=3, n_stuck=1)
    naive = _replay(case, spec, feedback.FeedbackParams(
        policy=PerDiePolicy(), faults=stuck))
    guarded = _replay(case, spec, feedback.FeedbackParams(
        policy=GuardedPolicy(inner=PerDiePolicy()), faults=stuck))
    clean = _replay(case, spec, feedback.FeedbackParams(
        policy=PerDiePolicy()))
    assert clean.dram_time_above_limit_s == 0.0
    assert naive.dram_time_above_limit_s > 0.0          # blind -> blows it
    assert float(naive.throttle.min()) == 1.0           # never throttled
    assert guarded.dram_time_above_limit_s == 0.0       # rescued
    assert float(guarded.dram_peak_C.max()) \
        == pytest.approx(float(clean.dram_peak_C.max()), abs=0.5)


# ------------------------------------------------------- solver fallback

def test_fallback_chain_shapes():
    assert thermal.fallback_chain("mg") == (
        ("mg", 1.0), ("mgcg", 1.0), ("pcg", 1.0), ("pcg", 0.1))
    assert thermal.fallback_chain("pcg") == (("pcg", 1.0), ("pcg", 0.1))
    with pytest.raises(ValueError, match="unknown solver"):
        thermal.fallback_chain("sor")


def test_poison_solver_scoping():
    assert not solver_poisoned("mg")
    with poison_solver("mg", "mgcg"):
        assert solver_poisoned("mg") and solver_poisoned("mgcg")
        with poison_solver("mg"):       # re-entrant: no double-remove
            assert solver_poisoned("mg")
        assert solver_poisoned("mg")
    assert not solver_poisoned("mg") and not solver_poisoned("mgcg")


def _hot_plate():
    g = thermal.Grid(die_w=3e-3, ny=16, nx=16, margin=4)
    p = np.zeros((g.n_die_layers, 16, 16), np.float32)
    p[0, 4:12, 4:12] = 0.05
    return p, g


def test_fallback_recovers_poisoned_solve_with_counters():
    p, g = _hot_plate()
    dT_ref, ref = thermal.steady_state_stats(p, g, solver="mg")
    assert ref["attempts"] == 1 and ref["solved_by"] == "mg"
    with obs.scoped():
        with poison_solver("mg"):
            dT, stats = thermal.steady_state_stats(p, g, solver="mg")
        snap = obs.snapshot()["counters"]
    assert stats["solved_by"] == "mgcg" and stats["attempts"] == 2
    assert stats["solver"] == "mg"               # the REQUESTED solver
    assert stats["rel_residual"] <= thermal.HEALTH_RTOL
    np.testing.assert_allclose(dT, dT_ref, atol=1e-3)
    assert snap["thermal/fallback/engaged"] == 1
    assert snap["thermal/fallback/retries"] == 1
    assert snap["thermal/fallback/recovered"] == 1
    assert snap["thermal/fallback/unhealthy[mg]"] == 1


def test_fallback_exhaustion_is_loud_not_silent():
    p, g = _hot_plate()
    with obs.scoped():
        with poison_solver("mg", "mgcg", "pcg"):
            dT, stats = thermal.steady_state_stats(p, g, solver="mg")
        snap = obs.snapshot()["counters"]
    assert stats["attempts"] == len(thermal.fallback_chain("mg"))
    assert not np.isfinite(np.asarray(dT)).all()  # NaN result, flagged...
    assert not np.isfinite(stats["rel_residual"])
    assert snap["thermal/fallback/exhausted"] == 1


def test_steady_state_rejects_nonfinite_power():
    p, g = _hot_plate()
    p[0, 0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        thermal.steady_state(p, g)


def test_check_finite_power_names_offender():
    with pytest.raises(ValueError, match="dyn_frames.*2 non-finite"):
        feedback.check_finite_power(
            "unit", dyn_frames=np.array([np.nan, np.inf, 1.0]),
            leak0=np.ones(3))
    feedback.check_finite_power("unit", ok=np.ones(3))   # no raise


# ------------------------------------------------- sweep failure isolation

_SWEEP = dict(workloads=("hist",), sizes=(4096,), n_dram=(1,),
              fb_modes=("open", "nodtm"), grid_n=8, n_intervals=4,
              steps_per_interval=1, n_cg=15)


def test_sweep_isolates_failed_group(monkeypatch, tmp_path):
    spec = SweepSpec(**_SWEEP)
    real = engine._run_group

    def sabotaged(spec, points, n_dram, fb_mode, policy, params,
                  n_shards=None):
        if fb_mode == "open":
            raise ValueError("injected group failure")
        return real(spec, points, n_dram, fb_mode, policy, params,
                    n_shards)

    monkeypatch.setattr(engine, "_run_group", sabotaged)
    with obs.scoped():
        res = run_sweep(spec, cache_dir=str(tmp_path), use_cache=True)
        snap = obs.snapshot()["counters"]
    assert snap["sweep/groups_failed"] == 1
    by_mode = {r.point.fb_mode: r for r in res.records}
    assert by_mode["open"].failed and not by_mode["open"].verdict_ok
    assert not by_mode["nodtm"].failed           # isolation: others live
    assert res.n_failed == 2                     # 2 machines x 1 point
    table = res.table()
    assert table.count("FAILED") == 2
    # a failed sweep is never persisted: a rerun must not be served the
    # NaN placeholders from disk
    from repro.sweep import cache
    assert cache.load(spec, str(tmp_path)) is None


def test_sweep_failed_records_never_read_ok():
    rec = engine._failed_group(
        SweepSpec(**_SWEEP), list(SweepSpec(**_SWEEP).points())[:1], 1,
        "open", "ramp", PAPER_STACK, "unit reason")
    for r in rec.values():
        assert r.failed and not r.verdict_ok
        assert not np.isfinite(r.report.peak_C).any()


# -------------------------------------------- device-count invariance (slow)

_SUBPROCESS = r"""
import jax, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.faults import SensorFaultSpec
from repro.policy import PerDiePolicy
from repro.core import cosim, thermal
from repro.stack import feedback
from repro.stack.spec import PAPER_STACK, dram_on_logic

spec = dram_on_logic(2, PAPER_STACK)
dp = cosim.comparable_design_point("sort", 2 ** 20)
trace = cosim.ap_workload_trace("sort", 8, cosim.trace_elems(2 ** 20))
case = [("sort/ap", feedback.assemble_case(
    dp, "sort", "ap", spec, PAPER_STACK, 8, trace, 2))]
fb = feedback.FeedbackParams(
    policy=PerDiePolicy(),
    faults=SensorFaultSpec(seed=3, n_sensors=3, noise_C=0.8,
                           n_stuck=1, p_dropout=0.1))
runs = {n: feedback.replay_cases(case, spec, fb, 8, 0.02,
                                steps_per_interval=1,
                                inner=thermal.InnerSolve("pcg", 15),
                                margin=2, n_shards=n)["sort/ap"]
        for n in (None, 1, 3, 4)}
# device-count invariance: every sharded run is bitwise the 1-shard run
ref = runs[1]
for n in (3, 4):
    for name in ("peak_C", "min_C", "residual_C", "throttle"):
        np.testing.assert_array_equal(
            getattr(runs[n], name), getattr(ref, name),
            err_msg=f"n_shards={n} field={name}")
# and the seeded fault realization (the throttle decisions it drives) is
# invariant even against the UNSHARDED vmap program, whose solver
# arithmetic may round differently under a different XLA fusion
np.testing.assert_array_equal(runs[None].throttle, ref.throttle)
print("FAULT-SHARD-INVARIANCE-OK")
"""


@pytest.mark.slow
def test_faulted_replay_is_device_count_invariant():
    """Seeded faults ride the scan carry, so sharding the case batch
    over 1/3/4 forced host devices must reproduce the single-device
    fault realization bit-for-bit (the test_shard_sweep.py invariance,
    now under an active SensorFaultSpec)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FAULT-SHARD-INVARIANCE-OK" in proc.stdout


# ---------------------------------------------------------- Policy protocol

def test_all_policies_accept_n_layers():
    """Every registered policy must tolerate the widened init_state
    protocol (n_layers positional) — scalar-state controllers ignore
    it, per-layer ones shape their state with it."""
    for name, factory in POLICIES.items():
        factory().init_state(3)                     # no raise is the pin
    assert Policy().init_state() == ()
    assert Policy().init_state(5) == ()
