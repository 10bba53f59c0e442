"""Robustness shoot-out: the policy family under sensor faults.

Replays a small scenario grid (the PR-9 verdict-flip point ``sort/ap``
plus the hot ``dmm/simd`` stack, closed loop, dram2) under three
sensing regimes — perfect sensors, a stuck-at primary sensor, and
heavy dropout — once with the naive DRAM-sensing per-die controller
and once with its :class:`repro.faults.GuardedPolicy` wrapper
(median-of-3 fusion, last-good hold, fail-safe floor).

The headline metrics tell the graceful-degradation story end to end:

- ``n_guard_rescued`` — (scenario × fault) cells where the NAIVE
  policy violates the 85 °C DRAM ceiling (or NaNs out entirely) while
  the guarded wrapper holds the ceiling under the *same* fault.  The
  stuck-at cell is the canonical case: the primary sensor latches at
  ~ambient, the naive per-die controller never trips, and the DRAM
  runs to ~95 °C — the guard's median still sees the true temperature
  and throttles exactly like the fault-free replay.
- ``n_naive_lost`` — naive replays whose temperatures go non-finite
  (dropout NaN readings propagate through the duty into the physics).
- ``fallback_attempts`` / ``fallback_recovered`` — a forced-divergence
  steady solve (``poison_solver("mg")``) demonstrably recovered by the
  ``core/thermal.py`` fallback chain, retry counters in the obs
  telemetry (``thermal/fallback/*`` in ``BENCH_faults.json``).
- a transient power-spike injection (``PowerFaultSpec``) on the
  ``sort/ap`` trace, showing the input-fault path raises the peak.

``tools/check_bench.py`` gates ``n_guard_rescued >= 1`` and the
numbers behind the stuck-sensor story (``baseline.json``, section
"faults").  Metrics land in ``BENCH_faults.json``.
"""
import argparse
import sys
import time

try:                                    # python -m benchmarks.run ...
    from benchmarks._record import Recorder
except ImportError:                     # python benchmarks/bench_*.py
    from _record import Recorder

import numpy as np

from repro.core import cosim, thermal
from repro.core import models as M
from repro.faults import (GuardedPolicy, PowerFaultSpec, SensorFaultSpec,
                          inject_power_spikes, poison_solver)
from repro.policy import PerDiePolicy
from repro.stack import feedback
from repro.stack.spec import PAPER_STACK, dram_on_logic

GRID_N = 8
N_INTERVALS = 16
INNER = thermal.InnerSolve("pcg", 25)
T_END = 0.25

#: the swept sensing regimes (None = perfect sensors, the reference)
FAULTS: dict[str, SensorFaultSpec | None] = {
    "none": None,
    "stuck": SensorFaultSpec(seed=0, n_sensors=3, n_stuck=1),
    "dropout": SensorFaultSpec(seed=0, n_sensors=3, p_dropout=0.4),
}


def _cases(margin: int, spec):
    """The two quick scenarios, as pre-assembled replay cases."""
    out = []
    for wl, mc in (("sort", "ap"), ("dmm", "simd")):
        dp = cosim.comparable_design_point(wl, 2 ** 20)
        w = M.WORKLOADS[wl]
        trace = cosim.ap_workload_trace(
            wl, N_INTERVALS, cosim.trace_elems(2 ** 20)) \
            if mc == "ap" else cosim.simd_phase_trace(w, dp, N_INTERVALS)
        out.append((f"{wl}/{mc}", feedback.assemble_case(
            dp, wl, mc, spec, PAPER_STACK, GRID_N, trace, margin)))
    return out


def _verdict(rep) -> str:
    if not np.isfinite(rep.peak_C).all():
        return "FAILED"
    return "OK" if rep.dram_time_above_limit_s == 0.0 else "BLOCKED"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke lane (same grid today; keys the lane)")
    args = ap.parse_args(argv)
    del args

    rec = Recorder("faults")
    spec = dram_on_logic(2, PAPER_STACK)
    margin = GRID_N // 4
    interval_dt = T_END / N_INTERVALS
    cases = _cases(margin, spec)
    policies = {"naive": PerDiePolicy(),
                "guarded": GuardedPolicy(inner=PerDiePolicy())}

    t0 = time.time()
    results: dict[tuple[str, str, str], object] = {}
    for fname, fspec in FAULTS.items():
        for pname, pol in policies.items():
            fb = feedback.FeedbackParams(policy=pol, faults=fspec)
            reps = feedback.replay_cases(
                cases, spec, fb, GRID_N, interval_dt,
                steps_per_interval=1, inner=INNER, margin=margin)
            for label, rep in reps.items():
                results[(label, fname, pname)] = rep
    scenarios = [label for label, _ in cases]
    print(f"faults sweep: {len(scenarios)} scenarios x {len(FAULTS)} "
          f"sensing regimes x {len(policies)} policies in "
          f"{time.time() - t0:.1f}s")

    print(f"\n  {'scenario':<10}{'fault':<9}{'policy':<9}"
          f"{'dram_C':>8}{'slow_x':>8}  verdict")
    n_rescued = n_lost = 0
    for label in scenarios:
        for fname in FAULTS:
            verdicts = {}
            for pname in policies:
                rep = results[(label, fname, pname)]
                v = _verdict(rep)
                verdicts[pname] = v
                if pname == "naive" and v == "FAILED":
                    n_lost += 1
                peak = float(rep.dram_peak_C.max())
                slow = rep.dtm_slowdown
                print(f"  {label:<10}{fname:<9}{pname:<9}"
                      f"{peak:>8.1f}{slow:>8.3f}  {v}")
            if fname != "none" and verdicts["naive"] != "OK" \
                    and verdicts["guarded"] == "OK":
                n_rescued += 1
                print(f"  RESCUED: {label} under {fname}: naive "
                      f"{verdicts['naive']} -> guarded OK")
    print(f"\n# {n_rescued} (scenario x fault) cell(s) rescued by the "
          f"guard; {n_lost} naive replay(s) lost to NaN")
    rec.add(n_scenarios=len(scenarios), n_faults=len(FAULTS),
            n_guard_rescued=n_rescued, n_naive_lost=n_lost)

    # ---- the gated numbers behind the stuck-sensor story ----
    for pname in policies:
        rep = results[("sort/ap", "stuck", pname)]
        rec.add(**{f"sort_ap_stuck_{pname}_dram_peak_C":
                   float(rep.dram_peak_C.max()),
                   f"sort_ap_stuck_{pname}_slowdown_x": rep.dtm_slowdown})

    # ---- transient power-spike injection on the input trace ----
    label, leaves = cases[0]                       # sort/ap
    dyn, l0, r0, lm, F, cap3 = leaves
    spiked = inject_power_spikes(
        dyn, PowerFaultSpec(seed=0, n_spikes=2, magnitude=3.0))
    fb = feedback.FeedbackParams(policy=policies["naive"])
    base, bump = (feedback.replay_cases(
        [(label, (d, l0, r0, lm, F, cap3))], spec, fb, GRID_N,
        interval_dt, steps_per_interval=1, inner=INNER,
        margin=margin)[label] for d in (dyn, spiked))
    delta = float(bump.dram_peak_C.max() - base.dram_peak_C.max())
    print(f"# power spike (2 intervals x3): sort/ap dram peak "
          f"{base.dram_peak_C.max():.1f} -> {bump.dram_peak_C.max():.1f} C"
          f" (+{delta:.1f})")
    rec.add(spike_peak_delta_C=delta)

    # ---- solver fallback chain: forced divergence, then recovery ----
    g = thermal.Grid(die_w=3e-3, ny=16, nx=16, margin=4)
    p = np.zeros((g.n_die_layers, 16, 16), np.float32)
    p[0, 4:12, 4:12] = 0.05
    _, healthy = thermal.steady_state_stats(p, g, solver="mg")
    with poison_solver("mg"):
        _, stats = thermal.steady_state_stats(p, g, solver="mg")
    print(f"# fallback: mg poisoned -> solved_by={stats['solved_by']} "
          f"after {stats['attempts']} attempts "
          f"(rel_residual {stats['rel_residual']:.2g}; healthy run: "
          f"{healthy['attempts']} attempt)")
    rec.add(fallback_attempts=stats["attempts"],
            fallback_recovered=int(stats["solved_by"] != "mg"
                                   and stats["rel_residual"]
                                   <= thermal.HEALTH_RTOL),
            healthy_attempts=healthy["attempts"])
    return rec.finish()


if __name__ == "__main__":
    main(sys.argv[1:])
