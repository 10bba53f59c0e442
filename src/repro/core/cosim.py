"""Power-trace -> transient thermal co-simulation (the CoMeT / Sniper+HotSpot
pattern, arXiv:2109.12405, applied to the paper's AP-vs-SIMD §4 study).

The steady-state comparison (`floorplan.thermal_comparison`) answers "where
does each die settle"; this module answers "what does each die *do on the
way there*" — per-workload hot-spot dynamics, thermal cycling, and the
time-resolved 85 °C 3D-DRAM verdict.

Pipeline (mirrors the performance-simulator -> thermal-model split of CoMeT):

1. **Trace capture** — `APEngine` meters every compare/write pass with its
   exact matched-row energy accounting; `engine.power_trace(n)` bins those
   events into n equal cycle windows (energy-conserving).  The SIMD
   reference gets an analytic two-phase trace from the eq-(14) execute/sync
   decomposition (its instantaneous power alternates between the exec and
   sync levels at the model's duty cycle).
2. **Frame synthesis** — each interval's total dynamic power modulates the
   floorplan's *spatial* power map (leakage stays constant), producing a
   [T, L, NY, NX] power-frame stack over the thermal grid domain.
3. **Replay** — an implicit theta-scheme stepper (`thermal.pcg_fixed` inner
   solves, unconditionally stable, so the step is set by the trace interval
   rather than the explicit CFL bound) scans the frames and records
   per-layer peak/min per interval.  The whole replay is one `lax.scan`
   and vmaps over a batch of (workload x machine) design points.

Time base: small AP kernel instances run in microseconds of engine time
while package thermal constants are ~0.1 s, so the replay *dilates* the
trace onto a configurable `t_end` — the trace supplies the activity
profile's shape, the design point supplies its mean wattage (documented in
README §co-simulation; same epoch-replay convention as HotSpot ptrace).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import models as M
from repro.core import thermal
from repro.core.constants import DRAM_LIMIT_C
from repro.core.floorplan import MM, APFloorplan, SIMDFloorplan
from repro.core.thermal import InnerSolve


# ---------------------------------------------------------------------------
# power traces
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PowerTrace:
    """Per-interval dynamic activity of one die layer (dimensionless).

    ``activity`` has mean 1.0 over the trace, so scaling by a design
    point's per-layer dynamic wattage preserves its time-averaged power.
    ``native_s`` is the engine time the trace actually spans (cycles at
    ``M.AP_CLOCK_HZ``) before replay dilation, 0 for analytic traces.
    """
    activity: np.ndarray
    source: str = ""
    native_s: float = 0.0

    @property
    def n_intervals(self) -> int:
        return int(self.activity.shape[0])


def trace_from_counters(counters: dict, n_intervals: int,
                        source: str = "") -> PowerTrace:
    """Bin a workload's engine events (``counters['trace_*']``) into an
    activity profile.  Energy-conserving: mean(activity) == 1 exactly."""
    from repro.core.engine import bin_energy_trace

    total_cycles = max(int(counters["cycles"]), 1)
    _, bins = bin_energy_trace(counters["trace_cycles"],
                               counters["trace_energy"],
                               total_cycles, n_intervals)
    mean = bins.mean()
    if mean <= 0.0:
        return PowerTrace(np.ones(n_intervals), source,
                          total_cycles / M.AP_CLOCK_HZ)
    return PowerTrace(bins / mean, source, total_cycles / M.AP_CLOCK_HZ)


def trace_elems(size: int) -> int:
    """Small-instance element count for a dataset size: sqrt(N) clamped
    to [32, 2^20].  The lower bound keeps per-phase structure; the upper
    bound has been lifted twice (256 -> 2048 -> 2^20) as the execution
    model sped up: first the device-resident programs removed the
    per-cycle host sync, then the megakernel path's fused op groups and
    bulk host-side accounting (kernels/ap_megakernel, engine
    ``charge_bulk``) made even million-element exact traces tractable —
    the clamp now only bounds trace memory, and binds at dataset sizes
    past 2^40.  The ONE sizing rule shared by every driver (run_cosim,
    run_stack_cosim, repro.sweep) so the same nominal scenario always
    replays the same trace."""
    return int(min(max(math.sqrt(size), 32), 1 << 20))


@functools.lru_cache(maxsize=None)
def ap_workload_trace(workload: str, n_intervals: int = 64,
                      n_elems: int = 64,
                      mode: str = "device") -> PowerTrace:
    """Run a small instance of the named AP workload (any registry entry)
    and bin its measured energy events.  Small instances keep the
    per-phase structure (MAC sweeps, FFT stages, sort extractions) that
    sets the activity shape; ``n_elems`` scales the instance.  ``mode``
    picks the execution path ("device" / "eager" / "megakernel") —
    all three are bit-identical, so it only affects capture speed."""
    from repro.workloads import registry

    ctr = registry.trace_counters(workload, n_elems, mode=mode)
    return trace_from_counters(ctr, n_intervals, source=f"ap:{workload}")


def simd_phase_trace(wl: M.Workload, dp: M.DesignPoint,
                     n_intervals: int = 64,
                     period_intervals: int = 8) -> PowerTrace:
    """Analytic SIMD trace: eq (14) splits runtime into execute and
    synchronize phases; instantaneous dynamic power alternates between the
    two levels at the duty cycle f_run = (1/n) / (1/n + I_s)."""
    p_exec_W, p_sync_W, f_run = M.simd_phase_powers(wl, dp.simd_n_pus)
    # instantaneous levels: average / phase-time-fraction (only the
    # exec:sync ratio matters; the final mean-1 normalization calibrates)
    lvl_exec = p_exec_W / max(f_run, 1e-9)
    lvl_sync = p_sync_W / max(1.0 - f_run, 1e-9)
    act = np.empty(n_intervals)
    for i in range(n_intervals):
        phase = (i % period_intervals) / period_intervals
        act[i] = lvl_exec if phase < f_run else lvl_sync
    return PowerTrace(act / act.mean(), source=f"simd:{wl.name}")


# ---------------------------------------------------------------------------
# frame synthesis
# ---------------------------------------------------------------------------

def power_frames(trace: PowerTrace, pmap: np.ndarray, leak_W: float,
                 grid: thermal.Grid) -> np.ndarray:
    """[T, L, NY, NX] power frames over the full thermal domain.

    ``pmap`` is a floorplan layer map (leakage included, as produced by
    ``*Floorplan.power_map``); leakage stays constant per interval while
    the dynamic remainder is modulated by the trace activity.  Every
    LOGIC layer carries the same map (the §4 convention); DRAM layers of
    a heterogeneous spec, the spreader layer, and the margin ring get
    zero (DRAM power needs its own model —
    ``repro.stack.feedback.stack_power_inputs``).
    """
    grid_n = pmap.shape[0]
    leak_map = np.full_like(pmap, leak_W / pmap.size)
    dyn_map = pmap - leak_map
    frames_2d = leak_map[None] + trace.activity[:, None, None] * dyn_map[None]
    T = trace.n_intervals
    L = grid.n_layers
    m = grid.margin
    out = np.zeros((T, L, grid.dom_ny, grid.dom_nx), np.float32)
    for l in grid.stack.logic_layers:
        out[:, l, m:m + grid_n, m:m + grid_n] = frames_2d
    return out


# ---------------------------------------------------------------------------
# adaptive interval coarsening (multi-hour serving horizons)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoarsePlan:
    """A merge of consecutive base intervals into variable-length coarse
    intervals: ``reps[i]`` base intervals fold into coarse interval i.

    Built by :func:`coarsen_plan` so that the activity range inside each
    run is bounded by the plan's tolerance; the merged power is the run
    MEAN, which conserves energy exactly (equal-length base intervals).
    The replay consumes ``dt_scale`` as the per-interval step multiplier
    (``stack.feedback.closed_loop_replay(..., dt_scale=...)``).
    """
    reps: np.ndarray            # [Tc] int, each >= 1, sum == n_base

    def __post_init__(self):
        reps = np.asarray(self.reps, np.int64)
        if reps.ndim != 1 or reps.size == 0 or (reps < 1).any():
            raise ValueError("reps must be a non-empty 1-D array of "
                             "positive run lengths")
        object.__setattr__(self, "reps", reps)

    @property
    def n_coarse(self) -> int:
        return int(self.reps.size)

    @property
    def n_base(self) -> int:
        return int(self.reps.sum())

    @property
    def ratio(self) -> float:
        """Solver-interval saving vs uniform stepping (>= 1)."""
        return self.n_base / self.n_coarse

    def dt_scale(self) -> np.ndarray:
        """Per-coarse-interval duration in units of the base interval."""
        return self.reps.astype(np.float32)

    def _edges(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.reps)])

    def merge(self, x: np.ndarray) -> np.ndarray:
        """Mean of ``x`` (leading axis = base intervals) over each run —
        the energy-conserving lowering of a base-resolution signal."""
        x = np.asarray(x)
        if x.shape[0] != self.n_base:
            raise ValueError(f"signal has {x.shape[0]} base intervals, "
                             f"plan covers {self.n_base}")
        e = self._edges()
        return np.stack([x[e[i]:e[i + 1]].mean(axis=0)
                         for i in range(self.n_coarse)])

    def expand(self, y: np.ndarray) -> np.ndarray:
        """Inverse resampling: repeat each coarse value over its run."""
        y = np.asarray(y)
        if y.shape[0] != self.n_coarse:
            raise ValueError(f"signal has {y.shape[0]} coarse intervals, "
                             f"plan has {self.n_coarse}")
        return np.repeat(y, self.reps, axis=0)

    def pad_to(self, n: int) -> "CoarsePlan":
        """Split the largest runs until the plan has ``n`` coarse
        intervals (clamped to ``n_base``).  Splitting only ever SHRINKS
        within-run activity ranges, so the plan's error bound still
        holds; use it to bucket plans onto a few lengths so jitted
        replays of different scenarios share compiled programs."""
        n = min(n, self.n_base)
        reps = list(self.reps)
        while len(reps) < n:
            i = int(np.argmax(reps))
            if reps[i] < 2:
                break
            half = reps[i] // 2
            reps[i:i + 1] = [reps[i] - half, half]
        return CoarsePlan(np.asarray(reps, np.int64))


def coarsen_plan(activity: np.ndarray, tol: float,
                 max_merge: int = 64) -> CoarsePlan:
    """Greedy run-merging of a base-resolution activity signal.

    Consecutive intervals join the current run while the run's
    max-min activity range (including the candidate) stays <= ``tol``
    and the run is shorter than ``max_merge`` intervals.  With the
    merged power set to the run mean (:meth:`CoarsePlan.merge`), the
    instantaneous power error of the coarsened trace is bounded by
    ``tol`` activity units, so the replay's temperature error is
    bounded by ``tol`` x the DC thermal gain of the modulated power
    map (:func:`dc_peak_rise_C`; DESIGN.md §9.3) — the linear-RC bound
    the coarsening property test checks.

    ``activity`` may be [T] or [T, K] (K signals coarsened jointly, the
    range criterion applied to the worst signal — e.g. logic utilization
    and DRAM traffic of one serving scenario).
    """
    act = np.asarray(activity, np.float64)
    if act.ndim == 1:
        act = act[:, None]
    if act.ndim != 2 or act.shape[0] == 0:
        raise ValueError("activity must be [T] or [T, K] with T >= 1")
    if tol < 0:
        raise ValueError("tol must be >= 0")
    if max_merge < 1:
        raise ValueError("max_merge must be >= 1")

    reps = []
    run = 1
    lo = act[0].copy()
    hi = act[0].copy()
    for t in range(1, act.shape[0]):
        nlo = np.minimum(lo, act[t])
        nhi = np.maximum(hi, act[t])
        if run < max_merge and float((nhi - nlo).max()) <= tol:
            run += 1
            lo, hi = nlo, nhi
        else:
            reps.append(run)
            run = 1
            lo = act[t].copy()
            hi = act[t].copy()
    reps.append(run)
    return CoarsePlan(np.asarray(reps, np.int64))


def dc_peak_rise_C(frame, F: dict) -> float:
    """Peak steady-state temperature rise of ONE power frame [L,NY,NX].

    The DC gain of the passive RC network: for a linear (open-loop)
    replay, substituting power within a window by a value that deviates
    at most dP pointwise moves the temperature trajectory by at most the
    steady response to dP.  ``tol * dc_peak_rise_C(worst_frame, F)`` is
    therefore a rigorous bound on the coarsened-replay temperature error
    at activity tolerance ``tol`` (coarsen_plan docstring; tested in
    tests/test_coarsen_replay.py)."""
    dT, _ = thermal._solve_fields(jnp.asarray(frame, jnp.float32), F,
                                  solver="pcg", use_pallas=False)
    return float(jnp.max(dT))


def interval_forecaster(A, solve, logic_mask3, t_amb):
    """One-substep RC forecast of the logic hot spot, affine in the duty.

    Built per interval inside the replay scan and handed to predictive
    DTM policies as ``PolicyContext.predict_hot``
    (``repro.policy.PredictivePolicy``): the returned
    ``predict(dT, P_dyn, P_stat)`` closes over the interval's implicit
    step operator and yields ``hot(cands)`` — for duty candidates
    ``cands [K]``, the forecast end-of-substep logic hot spots [K] under
    power ``f·P_dyn + P_stat``.  The theta-step response is affine in
    ``f``, so ALL candidates cost two inner solves:

        dT(f) = dT + solve(P_stat − A dT) + f · solve(P_dyn)

    ``solve`` is the interval's implicit-LHS inner solve (the same
    fixed-cost PCG/multigrid object the replay steps with), so the
    forecast horizon equals one replay substep and the forecast model IS
    the replay's own thermal RC operator — no second model to calibrate.
    """
    def predict(dT, P_dyn, P_stat):
        def hot(cands):
            # solves run lazily, on first call: a replay whose policy
            # never forecasts traces no forecast ops at all
            base = dT + solve(P_stat - A(dT))
            gain = solve(P_dyn)
            fields = base[None] + cands[:, None, None, None] * gain
            return jnp.max(
                jnp.where(logic_mask3 > 0, fields + t_amb, -jnp.inf),
                axis=(1, 2, 3))
        return hot
    return predict


# ---------------------------------------------------------------------------
# implicit replay core (scan over frames; vmappable over design points)
# ---------------------------------------------------------------------------

def _replay(frames, F, cap3, interval_dt, theta, t_amb, *,
            steps_per_interval: int, inner: InnerSolve, n_si: int,
            margin: int, die_n: int):
    A = thermal.fields_operator(F, inner.pallas)
    solve = thermal.implicit_lhs_solver(A, F, cap3, theta, inner)(
        interval_dt / steps_per_interval)

    def interval(dTc, P):
        def one(d, _):
            rhs = P - A(d)
            return d + solve(rhs), None
        dTn, _ = jax.lax.scan(one, dTc, None, length=steps_per_interval)
        die = dTn[:n_si, margin:margin + die_n, margin:margin + die_n]
        return dTn, (jnp.max(die, axis=(1, 2)), jnp.min(die, axis=(1, 2)))

    dT0 = jnp.zeros_like(frames[0])
    dT_end, (mx, mn) = jax.lax.scan(interval, dT0, frames)
    return dT_end + t_amb, mx + t_amb, mn + t_amb


@partial(jax.jit, static_argnames=("steps_per_interval", "inner", "n_si",
                                   "margin", "die_n"))
def cosim_transient(frames, F: dict, cap3, interval_dt,
                    theta: float = 1.0, t_amb: float = thermal.AMBIENT_C, *,
                    die_n: int, steps_per_interval: int = 2,
                    inner: InnerSolve = InnerSolve("pcg", 40),
                    n_si: int = 4, margin: int = 0):
    """Replay one frame stack.  Returns (T_end [L,NY,NX],
    peak_C [T,n_si], min_C [T,n_si]) — peaks/mins over the die footprint
    of the silicon layers only."""
    return _replay(frames, F, cap3, interval_dt, theta, t_amb,
                   steps_per_interval=steps_per_interval, inner=inner,
                   n_si=n_si, margin=margin, die_n=die_n)


@partial(jax.jit, static_argnames=("steps_per_interval", "inner", "n_si",
                                   "margin", "die_n"))
def cosim_transient_batch(frames, F: dict, cap3, interval_dt,
                          theta: float = 1.0,
                          t_amb: float = thermal.AMBIENT_C, *,
                          die_n: int, steps_per_interval: int = 2,
                          inner: InnerSolve = InnerSolve("pcg", 40),
                          n_si: int = 4, margin: int = 0):
    """vmapped replay over a leading batch of design points.

    frames [B,T,L,NY,NX]; each leaf of F and cap3 batched [B,...] (the
    batch shares one grid shape; conductances/capacities differ per die).
    """
    fn = partial(_replay, steps_per_interval=steps_per_interval,
                 inner=inner, n_si=n_si, margin=margin, die_n=die_n)
    return jax.vmap(lambda fr, Fb, cb: fn(fr, Fb, cb, interval_dt, theta,
                                          t_amb))(frames, F, cap3)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CosimReport:
    """Time-resolved thermal summary of one replay."""
    label: str
    interval_s: float
    peak_C: np.ndarray          # [T, n_si]
    min_C: np.ndarray           # [T, n_si]

    @property
    def times(self) -> np.ndarray:
        return self.interval_s * np.arange(1, self.peak_C.shape[0] + 1)

    @property
    def span_C(self) -> np.ndarray:
        return self.peak_C - self.min_C

    @property
    def final_peak_C(self) -> np.ndarray:
        return self.peak_C[-1]

    def time_above(self, limit_C: float = DRAM_LIMIT_C) -> np.ndarray:
        """Seconds each layer spent above ``limit_C`` (per-interval
        granularity, counted on the layer's peak cell)."""
        return self.interval_s * (self.peak_C > limit_C).sum(axis=0)

    def crossing_time(self, limit_C: float = DRAM_LIMIT_C
                      ) -> np.ndarray:
        """First time [s] each layer's peak exceeds ``limit_C`` (inf if
        it never does)."""
        above = self.peak_C > limit_C
        first = np.where(above.any(axis=0), above.argmax(axis=0), -1)
        t = self.times
        return np.where(first >= 0, t[np.maximum(first, 0)], np.inf)


# ---------------------------------------------------------------------------
# top-level driver: batched AP-vs-SIMD per-workload co-simulation
# ---------------------------------------------------------------------------

def comparable_design_point(workload: str | M.Workload,
                            n_ap_start: int = M.N_DATA) -> M.DesignPoint:
    """Largest same-performance AP/SIMD pair that exists for a workload.

    A SIMD can only match AP speedups below its synchronization ceiling
    1/I_s (eq 3).  For dmm/bs the paper's full-size AP (n = 2^20) is
    comparable; for fft and the low-arithmetic-intensity suite workloads
    it is not, so the AP is halved from ``n_ap_start`` (the dataset
    size, paper sizing n_AP = N) until the comparison point exists —
    same-performance remains the invariant.  ``workload`` may be a
    registered name or any :class:`~repro.core.models.Workload` instance
    (e.g. one minted by ``models.derived_workload`` for a serving AI).
    """
    if isinstance(workload, M.Workload):
        wl = workload
    elif workload in M.WORKLOADS:
        wl = M.WORKLOADS[workload]
    else:
        raise ValueError(f"unknown workload {workload!r}; expected one of "
                         f"{sorted(M.WORKLOADS)}")
    n_ap = n_ap_start
    while n_ap >= 1024:
        try:
            return M.design_point(wl, n_ap)
        except ValueError:
            n_ap //= 2
    raise ValueError(f"no comparable design point for {wl.name!r}")

def run_cosim(workloads=("dmm", "fft"), grid_n: int = 32,
              n_intervals: int = 64, t_end: float = 0.25,
              steps_per_interval: int = 2,
              inner: InnerSolve = InnerSolve("pcg", 40),
              theta: float = 1.0,
              stack: thermal.StackParams | None = None) -> dict:
    """The §4 comparison, transient: for each workload, replay the AP's
    measured trace and the SIMD reference's analytic trace through the
    same stack in ONE vmapped batch.  Returns
    ``{workload: {"ap": CosimReport, "simd": CosimReport},
    "design_points": {...}}``.
    """
    stack = stack or thermal.PAPER_STACK
    margin = grid_n // 4
    interval_dt = t_end / n_intervals

    labels, all_frames, all_F, all_cap = [], [], [], []
    dps = {}
    for w in workloads:
        dp = comparable_design_point(w)
        dps[w] = dp
        wl = M.WORKLOADS[w]
        ap_fp = APFloorplan(die_w_mm=math.sqrt(dp.ap_area_mm2))
        simd_fp = SIMDFloorplan(die_w_mm=math.sqrt(dp.simd_area_mm2))
        cases = (
            (f"{w}/ap", ap_fp.power_map(grid_n, dp.ap_power_W),
             ap_fp.leakage_W(), ap_fp.die_w_mm,
             ap_workload_trace(w, n_intervals, trace_elems(M.N_DATA))),
            (f"{w}/simd", simd_fp.power_map(grid_n, dp),
             simd_fp.leakage_W(dp), simd_fp.die_w_mm,
             simd_phase_trace(wl, dp, n_intervals)),
        )
        for label, pmap, leak_W, die_w_mm, trace in cases:
            grid = thermal.Grid(die_w=die_w_mm * MM, ny=grid_n, nx=grid_n,
                                params=stack, margin=margin)
            labels.append(label)
            all_frames.append(power_frames(trace, pmap, leak_W, grid))
            all_F.append(grid.fields())
            all_cap.append(grid.capacity_field())

    frames = jnp.asarray(np.stack(all_frames))
    Fb = {k: jnp.stack([F[k] for F in all_F]) for k in all_F[0]}
    capb = jnp.stack(all_cap)
    _, peaks, mins = cosim_transient_batch(
        frames, Fb, capb, interval_dt, theta,
        steps_per_interval=steps_per_interval, inner=inner,
        n_si=stack.n_si_layers, margin=margin, die_n=grid_n)
    peaks = np.asarray(peaks)
    mins = np.asarray(mins)

    out: dict = {"design_points": dps, "interval_s": interval_dt,
                 "t_end": t_end}
    for i, label in enumerate(labels):
        w, machine = label.split("/")
        out.setdefault(w, {})[machine] = CosimReport(
            label=label, interval_s=interval_dt,
            peak_C=peaks[i], min_C=mins[i])
    return out
