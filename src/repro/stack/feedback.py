"""Closed-loop temperature↔power co-simulation for heterogeneous stacks.

The open-loop replay (``core/cosim.py``) treats power as a fixed input
trace.  This module closes the loop inside the ``lax.scan`` over trace
intervals through three temperature couplings —

1. **DRAM refresh** — JEDEC bins (``stack.dram.refresh_multiplier``):
   refresh power doubles above 85 °C and doubles again above 95 °C,
   evaluated per cell so a hot bank refreshes harder than a cool one.
2. **Leakage** — exponential in temperature,
   ``leak0 * exp(beta (T − T_ref))``, applied to every die layer.
3. **DTM/DVFS policy** — a sampled controller from the
   ``repro.policy`` family (linear ramp, step trip, hysteresis, PID,
   per-die throttling, discrete DVFS stepping, model-predictive; see
   docs/policies.md).  Each interval the policy reads the measured
   per-layer hot spots and sets a *power* duty (scalar or per-die) that
   scales the dynamic power, plus a *performance* duty f ∈ (0, 1]
   recorded per interval so lost cycles can be accounted as a runtime
   slowdown (mean 1/f).  The default policy is the historical linear
   ramp off ``dtm_trip_C``/``dtm_ramp_C``/``dtm_floor`` — bit-identical
   to the pre-policy-engine throttle (tests/test_policy.py) — and the
   controller state (hysteresis latch, PID integral, DVFS operating
   point) threads through the scan carry, vmapping per design point.

Refresh and leakage are *instantaneous physics*, so they are solved
implicitly by **Picard iteration**: iterate k evaluates them at iterate
k−1's end-of-interval temperature and re-integrates the interval with the
unconditionally-stable theta steps from PR 1 (``thermal.pcg_fixed`` inner
solves).  These couplings are weak over one interval, so the recorded
fixed-point residual ``max |T_k − T_{k−1}|`` contracts below
``picard_tol_C`` (0.05 °C) on EVERY interval — including the violent DTM
bang-bang transients with 80 °C intra-interval swings — within the
default ``n_picard = 6`` (tests and the bench assert it; regime residuals
are ~1e-4…1e-3 °C, the 0.05 °C bar absorbs refresh-bin boundary cells
flipping 2×↔4× between iterates during those transients).  The DTM throttle is deliberately NOT in the fixed point: it is a
sampled controller actuating on the start-of-interval (measured)
temperature — iterating a gain≳1 bang-bang actuator on the unknown end
state has no contractive fixed point and Picard limit-cycles.  The whole
replay is one ``lax.scan`` and vmaps over a batch of (workload × machine)
design points.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import cosim
from repro.core import models as M
from repro.core import thermal
from repro.core.constants import AMBIENT_C, DRAM_LIMIT_C
from repro.core.floorplan import MM, APFloorplan, SIMDFloorplan
from repro.core.thermal import InnerSolve
from repro.faults.models import SensorFaultSpec
from repro.policy import Policy, PolicyContext, RampPolicy
from repro.stack import dram
from repro.stack.spec import (DRAM, LOGIC, PAPER_STACK, StackParams,
                              StackSpec, dram_on_logic)


@dataclasses.dataclass(frozen=True)
class FeedbackParams:
    """Feedback-loop constants (hashable -> usable as a jit static arg).

    ``policy`` selects the DTM/DVFS controller (``repro.policy``); None
    resolves to the classic linear ramp built from the ``dtm_*`` fields
    below, which therefore keep their historical meaning (and their
    bit-identical trajectories)."""
    leak_beta: float = 0.012     # 1/K exponential leakage slope (~2x / 60 K)
    t_ref_C: float = AMBIENT_C   # leakage reference temperature
    n_picard: int = 6            # fixed Picard iterations per interval
    picard_tol_C: float = 0.05   # documented per-step residual bar [°C]
    dtm_trip_C: float = 95.0     # logic hot-spot trip temperature
    dtm_ramp_C: float = 10.0     # °C over which power ramps down to floor
    dtm_floor: float = 0.25      # minimum DTM duty factor
    refresh_feedback: bool = True   # False -> refresh pinned at 1x
    policy: Policy | None = None    # None -> ramp from the dtm_* fields
    faults: SensorFaultSpec | None = None   # None -> perfect sensing;
    #   a spec injects sensor faults into the temperatures the policy
    #   reads (repro.faults; fault state rides the scan carry).  None
    #   keeps the traced program bit-identical to the pre-faults replay
    #   (tests/test_faults.py pins the jaxpr).

    def __post_init__(self):
        if not (0.0 < self.dtm_floor <= 1.0):
            raise ValueError("dtm_floor must lie in (0, 1] (0 breaks the "
                             "mean(1/f) slowdown accounting, > 1 is not "
                             f"a floor); got {self.dtm_floor!r}")
        if math.isnan(self.dtm_trip_C) or self.dtm_trip_C == -math.inf:
            raise ValueError("dtm_trip_C must be a real temperature or "
                             "math.inf (= DTM never trips); got "
                             f"{self.dtm_trip_C!r}")
        if self.dtm_ramp_C < 0:
            raise ValueError("dtm_ramp_C must be >= 0 (0 = step trip); "
                             f"got {self.dtm_ramp_C!r}")

    def resolved_policy(self) -> Policy:
        """The controller the replay actually runs."""
        if self.policy is not None:
            return self.policy
        return RampPolicy(trip_C=self.dtm_trip_C, ramp_C=self.dtm_ramp_C,
                          floor=self.dtm_floor)

    @classmethod
    def disabled(cls) -> "FeedbackParams":
        """Open-loop limit: constant leakage, 1x refresh, no DTM.

        ``n_picard = 2`` (not 1): with temperature-independent power the
        second iterate reproduces the first exactly, so the recorded
        residual is a true fixed-point defect (0) rather than the full
        interval temperature swing a single pass would report.
        """
        return cls(leak_beta=0.0, n_picard=2, dtm_trip_C=math.inf,
                   refresh_feedback=False)


# ---------------------------------------------------------------------------
# closed-loop replay core (scan over intervals; vmappable over design points)
# ---------------------------------------------------------------------------

def _closed_loop(dyn_frames, leak0, refresh0, logic_mask, F, cap3,
                 interval_dt, theta, t_amb, *, fb: FeedbackParams,
                 steps_per_interval: int, inner: InnerSolve,
                 n_die: int, margin: int, die_n: int, dt_scale=None):
    A = thermal.fields_operator(F, inner.pallas)
    make = thermal.implicit_lhs_solver(A, F, cap3, theta, inner)
    if dt_scale is None:
        solve = make(interval_dt / steps_per_interval)
        solve_for = lambda _scale: solve
    else:
        # variable-dt replay (coarsened serving traces): the step size is
        # a traced per-interval quantity, so the solve is made inside the
        # scan body.  The multigrid hierarchy is assembled for ONE dt,
        # hence PCG only.
        if inner.kind != "pcg":
            raise ValueError("variable-dt replay (dt_scale) requires "
                             "solver='pcg'; the multigrid hierarchy is "
                             "built for a fixed step")
        solve_for = lambda scale: make(
            interval_dt * scale / steps_per_interval)
    lm3 = logic_mask[:, None, None]
    # DRAM layers are exactly the refresh-bearing ones (base refresh is
    # strictly positive on every DRAM die) — derived here so per-die
    # policies need no extra replay argument
    dram_mask = (jnp.sum(refresh0, axis=(1, 2)) > 0).astype(
        logic_mask.dtype)
    policy = fb.resolved_policy()
    fspec = fb.faults
    n_layers = int(logic_mask.shape[0])

    def interval(carry, xs):
        # fspec is STATIC (a FeedbackParams field), so the fault-free
        # branch keeps today's carry/body verbatim — a replay without a
        # fault spec traces zero additional operations
        if fspec is None:
            dTc, pstate = carry
        else:
            dTc, pstate, fstate = carry
        P_dyn, scale = xs
        solve = solve_for(scale)
        # The policy actuates on the MEASURED (start-of-interval) hot
        # spots — a real DTM controller reads the previous temperature
        # sample.  Iterating it on the end-of-interval state instead
        # couples a gain->1 bang-bang controller into the fixed point
        # and Picard limit-cycles (~40 C swings); sampled actuation
        # keeps only the weak, contractive couplings (refresh bins,
        # leakage) implicit.
        layer_T = jnp.max(dTc, axis=(1, 2)) + t_amb
        sensor_T = None
        if fspec is not None:
            # what the controller SENSES is the faulted readings: the
            # primary (row 0) replaces layer_T, the full [K, L] array is
            # exposed for hardened policies (GuardedPolicy)
            fstate, sensor_T = fspec.read(fstate, layer_T)
            layer_T = sensor_T[0]
        predict = cosim.interval_forecaster(A, solve, lm3, t_amb)
        ctx = PolicyContext(
            layer_T=layer_T, logic_mask=logic_mask, dram_mask=dram_mask,
            predict_hot=predict(dTc, P_dyn, leak0 + refresh0),
            sensor_T=sensor_T)
        pstate, f_power, f = policy.act(pstate, ctx)
        fp3 = f_power if jnp.ndim(f_power) == 0 else f_power[:, None, None]
        P_base = fp3 * P_dyn

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
        carry = (dTn, pstate) if fspec is None else (dTn, pstate, fstate)
        return carry, (
            jnp.max(die, axis=(1, 2)), jnp.min(die, axis=(1, 2)),
            res, f, ref_W, leak_W, jnp.sum(P_base))

    dT0 = jnp.zeros_like(dyn_frames[0])
    init = (dT0, policy.init_state(n_layers)) if fspec is None \
        else (dT0, policy.init_state(n_layers), fspec.init_state(n_layers))
    scales = jnp.ones(dyn_frames.shape[0], dyn_frames.dtype) \
        if dt_scale is None else jnp.asarray(dt_scale, dyn_frames.dtype)
    (dT_end, *_), (mx, mn, res, f, ref_W, leak_W, dyn_W) = \
        jax.lax.scan(interval, init, (dyn_frames, scales))
    return (dT_end + t_amb, mx + t_amb, mn + t_amb, res, f, ref_W,
            leak_W, dyn_W)


_STATIC = ("fb", "steps_per_interval", "inner", "n_die", "margin", "die_n")


@partial(jax.jit, static_argnames=_STATIC)
def closed_loop_replay(dyn_frames, leak0, refresh0, logic_mask, F: dict,
                       cap3, interval_dt, theta: float = 1.0,
                       t_amb: float = AMBIENT_C, *, fb: FeedbackParams,
                       die_n: int, n_die: int, steps_per_interval: int = 2,
                       inner: InnerSolve = InnerSolve("pcg", 40),
                       margin: int = 0, dt_scale=None):
    """Replay one frame stack with temperature feedback.

    dyn_frames [T, L, NY, NX]: trace-modulated *dynamic* power (logic
    switching + DRAM activate/IO) — NO leakage or refresh baked in;
    leak0 / refresh0 [L, NY, NX]: leakage at ``fb.t_ref_C`` and 1× refresh
    power; logic_mask [L]: 1.0 on layers whose hot spot trips the DTM.
    ``inner`` is the fixed-cost inner solve of each implicit step
    (``thermal.InnerSolve``).

    ``dt_scale`` [T] (optional) stretches interval i to
    ``interval_dt * dt_scale[i]`` — the variable-step replay coarsened
    serving traces use (``cosim.CoarsePlan.dt_scale``).  PCG only: the
    step size becomes a traced quantity, which the fixed multigrid
    hierarchy cannot follow.  The DTM controller then samples at the
    coarsened boundaries (its reaction time follows the local step).

    Returns (T_end [L,NY,NX], peak_C [T,n_die], min_C [T,n_die],
    residual_C [T], throttle [T], refresh_W [T], leak_W [T],
    dyn_W [T]).  ``throttle`` is the policy's *performance* duty (what
    scales runtime); ``dyn_W`` is the policy-scaled dynamic power
    actually dissipated, so refresh + leak + dyn is the stack's total
    draw per interval (the energy axis of the policy Pareto bench).
    """
    return _closed_loop(dyn_frames, leak0, refresh0, logic_mask, F, cap3,
                        interval_dt, theta, t_amb, fb=fb,
                        steps_per_interval=steps_per_interval, inner=inner,
                        n_die=n_die, margin=margin, die_n=die_n,
                        dt_scale=dt_scale)


@partial(jax.jit, static_argnames=_STATIC)
def closed_loop_batch(dyn_frames, leak0, refresh0, logic_mask, F: dict,
                      cap3, interval_dt, theta: float = 1.0,
                      t_amb: float = AMBIENT_C, *, fb: FeedbackParams,
                      die_n: int, n_die: int, steps_per_interval: int = 2,
                      inner: InnerSolve = InnerSolve("pcg", 40),
                      margin: int = 0):
    """vmapped closed-loop replay over a leading design-point batch."""
    fn = partial(_closed_loop, fb=fb,
                 steps_per_interval=steps_per_interval, inner=inner,
                 n_die=n_die, margin=margin, die_n=die_n)
    return jax.vmap(
        lambda fr, l0, r0, lm, Fb, cb: fn(fr, l0, r0, lm, Fb, cb,
                                          interval_dt, theta, t_amb)
    )(dyn_frames, leak0, refresh0, logic_mask, F, cap3)


# ---------------------------------------------------------------------------
# power-input assembly for one (machine, stack) case
# ---------------------------------------------------------------------------

def stack_power_inputs(spec: StackSpec, grid: thermal.Grid,
                       trace: cosim.PowerTrace, logic_pmap: np.ndarray,
                       logic_leak_W: float, dram_fp: dram.DRAMFloorplan,
                       traffic_bytes_per_s: float):
    """Build (dyn_frames, leak0, refresh0, logic_mask) for one stack.

    Logic layers carry the floorplan's dynamic map modulated by the trace
    (the §4 convention: every logic layer the same map); DRAM layers carry
    the traffic-driven activate map modulated by the SAME trace (memory
    traffic follows compute activity) plus their leakage/refresh statics.
    """
    gn = logic_pmap.shape[0]
    L, NY, NX, m = grid.n_layers, grid.dom_ny, grid.dom_nx, grid.margin
    Tn = trace.n_intervals
    act = trace.activity.astype(np.float32)[:, None, None]

    dyn = np.zeros((Tn, L, NY, NX), np.float32)
    leak0 = np.zeros((L, NY, NX), np.float32)
    refresh0 = np.zeros((L, NY, NX), np.float32)

    leak_cell = logic_leak_W / gn ** 2
    dyn_logic = (logic_pmap - leak_cell).astype(np.float32)
    n_dram = len(spec.dram_layers)
    act_map = dram_fp.activate_map(gn) \
        * dram.activate_io_W(traffic_bytes_per_s, n_dram)
    ref_map = dram_fp.refresh_map(gn) * dram_fp.base_refresh_W()
    dram_leak_cell = dram_fp.leakage_W() / gn ** 2

    win = (slice(m, m + gn), slice(m, m + gn))
    for l, layer in enumerate(spec.layers[:-1]):
        if layer.kind == LOGIC:
            dyn[(slice(None), l) + win] = act * dyn_logic
            leak0[(l,) + win] = leak_cell
        elif layer.kind == DRAM:
            dyn[(slice(None), l) + win] = act * act_map
            leak0[(l,) + win] = dram_leak_cell
            refresh0[(l,) + win] = ref_map
    return dyn, leak0, refresh0, spec.layer_mask(LOGIC)


def stack_power_frames(spec: StackSpec, grid: thermal.Grid,
                       activity: np.ndarray, logic_pmap: np.ndarray,
                       logic_leak_W: float, dram_fp: dram.DRAMFloorplan,
                       traffic_bytes_per_s):
    """:func:`stack_power_inputs` for externally-computed interval signals.

    ``activity`` [T] is a raw utilization trace (serving busy fraction;
    NOT mean-normalized like a :class:`~repro.core.cosim.PowerTrace`) —
    logic layers draw ``activity[t] *`` their dynamic map.  DRAM activate
    power follows ``traffic_bytes_per_s``: a scalar is modulated by the
    same activity (the `stack_power_inputs` convention, traffic tracks
    compute), while an array [T] is taken as the per-interval traffic
    verbatim (the serving lowering varies it with the decode batch's
    arithmetic intensity).  Returns the same
    (dyn, leak0, refresh0, logic_mask) tuple.
    """
    gn = logic_pmap.shape[0]
    L, NY, NX, m = grid.n_layers, grid.dom_ny, grid.dom_nx, grid.margin
    act = np.asarray(activity, np.float32)
    if act.ndim != 1:
        raise ValueError("activity must be a 1-D interval signal")
    Tn = act.shape[0]
    n_dram = len(spec.dram_layers)
    traffic = np.asarray(traffic_bytes_per_s, np.float64)
    if traffic.ndim == 0:
        io_W_t = act * dram.activate_io_W(float(traffic), n_dram)
    elif traffic.shape == (Tn,):
        io_W_t = np.array([dram.activate_io_W(float(b), n_dram)
                           for b in traffic], np.float32)
    else:
        raise ValueError("traffic_bytes_per_s must be a scalar or match "
                         "the activity length")

    dyn = np.zeros((Tn, L, NY, NX), np.float32)
    leak0 = np.zeros((L, NY, NX), np.float32)
    refresh0 = np.zeros((L, NY, NX), np.float32)

    leak_cell = logic_leak_W / gn ** 2
    dyn_logic = (logic_pmap - leak_cell).astype(np.float32)
    act_shape = dram_fp.activate_map(gn)
    ref_map = dram_fp.refresh_map(gn) * dram_fp.base_refresh_W()
    dram_leak_cell = dram_fp.leakage_W() / gn ** 2

    win = (slice(m, m + gn), slice(m, m + gn))
    for l, layer in enumerate(spec.layers[:-1]):
        if layer.kind == LOGIC:
            dyn[(slice(None), l) + win] = \
                act[:, None, None] * dyn_logic
            leak0[(l,) + win] = leak_cell
        elif layer.kind == DRAM:
            dyn[(slice(None), l) + win] = \
                io_W_t[:, None, None] * act_shape
            leak0[(l,) + win] = dram_leak_cell
            refresh0[(l,) + win] = ref_map
    return dyn, leak0, refresh0, spec.layer_mask(LOGIC)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackReport:
    """Time-resolved closed-loop summary of one stack replay."""
    label: str
    interval_s: float
    spec: StackSpec
    peak_C: np.ndarray          # [T, n_die]
    min_C: np.ndarray           # [T, n_die]
    residual_C: np.ndarray      # [T] final Picard residual per interval
    throttle: np.ndarray        # [T] DTM duty factor in (0, 1]
    refresh_W: np.ndarray       # [T] total DRAM refresh power
    leak_W: np.ndarray          # [T] total leakage power
    base_refresh_W: float       # 1x refresh total of all DRAM dies
    tol_C: float = FeedbackParams.picard_tol_C   # the run's residual bar
    dyn_W: np.ndarray | None = None   # [T] policy-scaled dynamic power

    @property
    def times(self) -> np.ndarray:
        return self.interval_s * np.arange(1, self.peak_C.shape[0] + 1)

    @property
    def span_C(self) -> np.ndarray:
        return self.peak_C - self.min_C

    def _layer_peak(self, idx: tuple[int, ...]) -> np.ndarray:
        if not idx:
            return np.zeros(self.peak_C.shape[0], self.peak_C.dtype)
        return self.peak_C[:, list(idx)].max(axis=1)

    @property
    def dram_peak_C(self) -> np.ndarray:
        """[T] hottest DRAM cell per interval (zeros if no DRAM dies)."""
        return self._layer_peak(self.spec.dram_layers)

    @property
    def logic_peak_C(self) -> np.ndarray:
        return self._layer_peak(self.spec.logic_layers)

    @property
    def refresh_overhead(self) -> float:
        """Mean refresh power / the 1× (cool-DRAM) refresh power."""
        if self.base_refresh_W <= 0:
            return 1.0
        return float(self.refresh_W.mean() / self.base_refresh_W)

    @property
    def dtm_slowdown(self) -> float:
        """Runtime inflation from throttling: mean(1/f) >= 1."""
        return float(np.mean(1.0 / self.throttle))

    @property
    def energy_J(self) -> float:
        """Total energy over the replay window (dynamic + leak + refresh).

        Requires a replay that recorded ``dyn_W`` (every post-policy-engine
        replay does); older pickled reports raise."""
        if self.dyn_W is None:
            raise ValueError("this report predates dyn_W recording")
        return float(self.interval_s
                     * (self.dyn_W + self.leak_W + self.refresh_W).sum())

    @property
    def energy_per_work_J(self) -> float:
        """Energy divided by the fraction of full-speed work completed —
        the energy-to-solution axis of the policy Pareto bench.  A policy
        that halves power but quarters throughput scores WORSE here."""
        return self.energy_J / float(np.mean(self.throttle))

    def time_above(self, limit_C: float = DRAM_LIMIT_C,
                   layers: tuple[int, ...] | None = None) -> np.ndarray:
        """Seconds each selected layer's peak spent above ``limit_C``."""
        sel = list(layers) if layers is not None \
            else list(range(self.peak_C.shape[1]))
        return self.interval_s * (self.peak_C[:, sel] > limit_C).sum(axis=0)

    @property
    def dram_time_above_limit_s(self) -> float:
        if not self.spec.dram_layers:
            return 0.0
        return float(self.time_above(layers=self.spec.dram_layers).max())

    @property
    def converged(self) -> bool:
        """Did EVERY interval's Picard iteration meet the residual bar?"""
        return bool(self.residual_C.max() <= self.tol_C)


# ---------------------------------------------------------------------------
# per-case assembly (shared by run_stack_cosim and repro.sweep.engine)
# ---------------------------------------------------------------------------

def check_finite_power(what: str, **arrays) -> None:
    """Raise ``ValueError`` if any power input carries non-finite cells.

    NaN/inf power silently propagates into every temperature of a
    replay and from there into verdict tables (NaN compares False
    against the 85 °C ceiling, i.e. reads as OK) — fail at assembly
    instead, naming the offending input.
    """
    for name, arr in arrays.items():
        arr = np.asarray(arr)
        if not np.isfinite(arr).all():
            n_bad = int((~np.isfinite(arr)).sum())
            raise ValueError(
                f"{what}: power input {name!r} has {n_bad} non-finite "
                f"cell(s) (shape {arr.shape}); refusing to replay — "
                "NaN temperatures would silently pass the 85C verdict")


def assemble_case(dp: M.DesignPoint, workload: str, machine: str,
                  spec: StackSpec, params: StackParams, grid_n: int,
                  trace: cosim.PowerTrace, margin: int):
    """Build the closed-loop replay inputs for one (workload, machine) case.

    Returns (dyn, leak0, refresh0, logic_mask, F, cap3) — exactly the
    per-case leaves :func:`closed_loop_batch` stacks over its leading
    batch axis.  ``machine`` is "ap" or "simd"; the DRAM traffic figure
    is shared by construction (``models.mem_traffic_bytes_per_s``).
    """
    wl = M.WORKLOADS[workload]
    traffic = M.mem_traffic_bytes_per_s(workload, dp.ap_n_pus)
    if machine == "ap":
        fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
        pmap = fp.power_map(grid_n, dp.ap_power_W)
        leak_W = fp.leakage_W()
    elif machine == "simd":
        fp = SIMDFloorplan(die_w_mm=math.sqrt(dp.simd_area_mm2))
        pmap = fp.power_map(grid_n, dp)
        leak_W = fp.leakage_W(dp)
    else:
        raise ValueError(f"unknown machine {machine!r}")
    del wl  # the SIMD trace is built by the caller (needs n_intervals)
    grid = thermal.Grid(die_w=fp.die_w_mm * MM, ny=grid_n, nx=grid_n,
                        params=params, spec=spec, margin=margin)
    dfp = dram.DRAMFloorplan(die_w_mm=fp.die_w_mm)
    dyn, l0, r0, lm = stack_power_inputs(spec, grid, trace, pmap, leak_W,
                                         dfp, traffic)
    check_finite_power(f"assemble_case({workload}/{machine})",
                       dyn_frames=dyn, leak0=l0, refresh0=r0)
    return dyn, l0, r0, lm, grid.fields(), grid.capacity_field()


def closed_loop_sharded(dyn_frames, leak0, refresh0, logic_mask, F: dict,
                        cap3, interval_dt, theta: float = 1.0,
                        t_amb: float = AMBIENT_C, *, fb: FeedbackParams,
                        die_n: int, n_die: int,
                        steps_per_interval: int = 2,
                        inner: InnerSolve = InnerSolve("pcg", 40),
                        margin: int = 0, n_shards: int | None = None):
    """:func:`closed_loop_batch` partitioned over local devices.

    The case batch is padded to a multiple of the mesh size (repeating
    the last case; padding rows are dropped from every output) and run
    through ``shard_map`` over a 1D 'cases' mesh
    (``repro.parallel.sharding``).  Each device executes the per-case
    program on its slice.  Results agree with the unsharded vmap within
    ``sharding.SHARD_TOL_C``: a smaller per-device batch may reorder
    XLA's reductions (tests/test_shard_sweep.py).
    """
    from repro.parallel import sharding as shardlib
    mesh = shardlib.sweep_mesh(n_shards)
    batch = (dyn_frames, leak0, refresh0, logic_mask, F, cap3)
    batch, n_cases = shardlib.pad_case_batch(batch, mesh.shape["cases"])

    def fn(tree):
        return closed_loop_batch(
            *tree, interval_dt, theta, t_amb, fb=fb, die_n=die_n,
            n_die=n_die, steps_per_interval=steps_per_interval,
            inner=inner, margin=margin)

    out = shardlib.shard_case_batch(fn, mesh)(batch)
    return shardlib.unpad_case_batch(out, n_cases)


def replay_cases(cases, spec: StackSpec, fb: FeedbackParams, grid_n: int,
                 interval_dt: float, *, theta: float = 1.0,
                 steps_per_interval: int = 2,
                 inner: InnerSolve = InnerSolve("pcg", 40),
                 margin: int | None = None,
                 n_shards: int | None = None) -> dict[str, "StackReport"]:
    """Replay pre-assembled cases as ONE vmapped closed-loop batch.

    ``cases``: sequence of (label, :func:`assemble_case` leaves) — every
    case must share the stack ``spec`` and grid shape.  Returns
    {label: StackReport}.  This is the single lowering both
    :func:`run_stack_cosim` and ``repro.sweep.engine`` go through.
    ``n_shards`` routes through :func:`closed_loop_sharded` (0/None =
    plain vmap on one device).
    """
    margin = grid_n // 4 if margin is None else margin
    labels = [label for label, _ in cases]
    dyns, leaks, refs, masks, Fs, caps = zip(*(leaves for _, leaves in cases))
    Fb = {k: jnp.stack([F[k] for F in Fs]) for k in Fs[0]}
    replay = closed_loop_batch if not n_shards else partial(
        closed_loop_sharded, n_shards=n_shards)
    with obs.span("feedback/replay", cases=len(labels), grid_n=grid_n,
                  solver=inner.kind, n_shards=n_shards or 0):
        _, peaks, mins, res, thr, ref_W, leak_W, dyn_W = replay(
            jnp.asarray(np.stack(dyns)), jnp.asarray(np.stack(leaks)),
            jnp.asarray(np.stack(refs)), jnp.asarray(np.stack(masks)), Fb,
            jnp.stack(caps), interval_dt, theta, fb=fb, die_n=grid_n,
            n_die=spec.n_die_layers, steps_per_interval=steps_per_interval,
            inner=inner, margin=margin)
    base_ref = dram.DRAMFloorplan(die_w_mm=1.0).base_refresh_W() \
        * len(spec.dram_layers)
    with obs.span("feedback/reports", cases=len(labels)):
        with obs.span("sync/reports"):
            outs = jax.device_get((peaks, mins, res, thr, ref_W, leak_W,
                                   dyn_W))
        peaks, mins, res, thr, ref_W, leak_W, dyn_W = outs
        reports = {
            label: StackReport(
                label=label, interval_s=interval_dt, spec=spec,
                peak_C=peaks[i], min_C=mins[i], residual_C=res[i],
                throttle=thr[i], refresh_W=ref_W[i], leak_W=leak_W[i],
                base_refresh_W=base_ref, tol_C=fb.picard_tol_C,
                dyn_W=dyn_W[i])
            for i, label in enumerate(labels)}
    if obs.is_enabled():
        res_h = np.asarray(res, np.float64)
        thr_h = np.asarray(thr, np.float64)
        n_int = res_h.shape[-1] if res_h.ndim else 0
        obs.count("feedback/intervals", len(labels) * n_int)
        obs.count("feedback/picard_iterations",
                  len(labels) * n_int * fb.n_picard)
        obs.count("feedback/throttled_intervals",
                  int((thr_h < 1.0).sum()))
        obs.observe_many("feedback/picard_residual_C",
                         res_h.reshape(len(labels), -1).max(axis=1))
        obs.observe_many("feedback/throttle_duty",
                         thr_h.reshape(len(labels), -1).mean(axis=1))
        pol = fb.resolved_policy()
        obs.observe_many(f"policy/{pol.name}/duty", thr_h.ravel())
        resid = pol.residency(thr_h)
        for op, n in (resid or {}).items():
            obs.count(f"policy/{pol.name}/residency/{op}", n)
    return reports


# ---------------------------------------------------------------------------
# top-level driver: batched AP+DRAM vs SIMD+DRAM closed-loop co-simulation
# ---------------------------------------------------------------------------

def run_stack_cosim(workloads=("dmm", "fft", "bs"), n_dram: int = 2,
                    grid_n: int = 16, n_intervals: int = 32,
                    t_end: float = 0.25, steps_per_interval: int = 2,
                    inner: InnerSolve = InnerSolve("pcg", 40),
                    theta: float = 1.0,
                    fb: FeedbackParams = FeedbackParams(),
                    params: StackParams = PAPER_STACK,
                    n_shards: int | None = None) -> dict:
    """The paper's abstract claim, quantified: for each workload replay the
    AP and the same-performance SIMD under ``n_dram`` stacked DRAM dies
    with closed-loop refresh/leakage/DTM feedback, in ONE vmapped batch.

    Returns ``{workload: {"ap": StackReport, "simd": StackReport},
    "design_points": {...}, "spec": StackSpec, ...}``.
    """
    spec = dram_on_logic(n_dram, params)
    margin = grid_n // 4
    interval_dt = t_end / n_intervals
    n_small = cosim.trace_elems(M.N_DATA)    # shared trace-sizing rule

    cases, dps = [], {}
    for w in workloads:
        dp = cosim.comparable_design_point(w)
        dps[w] = dp
        wl = M.WORKLOADS[w]
        pair = (("ap", cosim.ap_workload_trace(w, n_intervals, n_small)),
                ("simd", cosim.simd_phase_trace(wl, dp, n_intervals)))
        for machine, trace in pair:
            cases.append((f"{w}/{machine}", assemble_case(
                dp, w, machine, spec, params, grid_n, trace, margin)))

    reports = replay_cases(cases, spec, fb, grid_n, interval_dt,
                           theta=theta,
                           steps_per_interval=steps_per_interval,
                           inner=inner, margin=margin, n_shards=n_shards)
    out: dict = {"design_points": dps, "spec": spec,
                 "interval_s": interval_dt, "t_end": t_end, "fb": fb}
    for label, rep in reports.items():
        w, machine = label.split("/")
        out.setdefault(w, {})[machine] = rep
    return out
