"""Paper §4 / Figs 10, 12, 13: AP vs SIMD 4-layer-stack thermal comparison.

Three sections:

1. steady state (the paper's own experiment),
2. solver shoot-out — the same fine-grid steady solve through every
   backend in ``thermal.SOLVERS`` (Jacobi-PCG, stand-alone multigrid,
   MG-preconditioned CG) with wall-clock, iteration counts and
   cross-backend agreement; run at >= 256^2 so the asymptotic gap is
   visible (the multigrid acceptance evidence, ISSUE 4), and
3. transient co-simulation — per-workload power traces replayed through
   the implicit stepper, reporting time-resolved peaks and the per-layer
   time spent above the 85 °C 3D-DRAM ceiling, plus the implicit
   solver's step-count advantage over the explicit oracle.

``--quick`` shrinks the steady/transient grids for the CI smoke lane
(the solver section keeps its 256^2 grid — that IS the point).  Metrics
land in ``BENCH_thermal.json`` (see ``benchmarks/_record.py``).
"""
import argparse
import time

try:                                    # python -m benchmarks.run ...
    from benchmarks._record import Recorder
except ImportError:                     # python benchmarks/bench_*.py
    from _record import Recorder

from repro.core.floorplan import thermal_comparison


def steady_section(rec: Recorder, grid_ap: int, grid_simd: int) -> None:
    res = thermal_comparison(grid_ap=grid_ap, grid_simd=grid_simd,
                             workload="dmm")
    dp = res["design_point"]
    print(f"design point: S={dp.speedup:.0f}  "
          f"AP {dp.ap_power_W:.2f}W/layer @{dp.ap_area_mm2:.1f}mm^2  "
          f"SIMD {dp.simd_power_W:.2f}W/layer @{dp.simd_area_mm2:.1f}mm^2")
    print("layer,ap_peak_C,ap_span_C,simd_peak_C,simd_min_C")
    for l in range(4):
        print(f"{l},{res['ap']['peak_C'][l]:.1f},{res['ap']['span_C'][l]:.2f},"
              f"{res['simd']['peak_C'][l]:.1f},{res['simd']['min_C'][l]:.1f}")
    ap_ok = max(res["ap"]["peak_C"]) < 85.0
    simd_ok = res["simd"]["min_C"][0] < 85.0
    print(f"3D-DRAM (85C limit): AP {'OK' if ap_ok else 'BLOCKED'} / "
          f"SIMD {'OK' if simd_ok else 'BLOCKED'}   "
          f"(paper: AP 55C OK, SIMD 98-128C blocked)")
    rec.add(ap_peak_C=max(res["ap"]["peak_C"]),
            ap_span_C=res["ap"]["span_C"][0],
            simd_peak_C=res["simd"]["peak_C"][0],
            simd_min_C=res["simd"]["min_C"][0],
            ap_dram_ok=ap_ok, simd_dram_blocked=not simd_ok)


def solver_section(rec: Recorder, n: int) -> None:
    """PCG vs multigrid vs MG-CG on one fine-grid steady solve."""
    import numpy as np

    from repro.core import thermal
    from repro.stack.spec import dram_on_logic

    print()
    print(f"steady-state solver shoot-out ({n}x{n} die grid + margin, "
          f"2xDRAM-on-logic stack)")
    spec = dram_on_logic(2)
    grid = thermal.Grid(die_w=5e-3, ny=n, nx=n, margin=n // 4, spec=spec)
    power = np.zeros((grid.n_die_layers, n, n), np.float32)
    # 40 W over the LOGIC dies (they sit below the stacked DRAM)
    power[list(spec.logic_layers)] = 40.0 / (len(spec.logic_layers) * n * n)

    results = {}
    print("solver,iterations,wall_s,peak_C,maxdiff_vs_pcg_C,rel_residual")
    for solver in thermal.SOLVERS:
        T, stats = thermal.steady_state_stats(power, grid, solver=solver)
        T.block_until_ready()               # compile outside the timing
        t0 = time.time()
        T, stats = thermal.steady_state_stats(power, grid, solver=solver)
        T.block_until_ready()
        wall = time.time() - t0
        results[solver] = (np.asarray(T), stats["iterations"], wall)
        diff = float(np.abs(np.asarray(T) - results["pcg"][0]).max())
        print(f"{solver},{stats['iterations']},{wall:.3f},"
              f"{float(T.max()):.2f},{diff:.2e},"
              f"{stats['rel_residual']:.2e}")
        rec.add(**{f"steady_{solver}_iters_{n}": stats["iterations"],
                   f"steady_{solver}_wall_s_{n}": wall,
                   f"steady_{solver}_maxdiff_C_{n}": diff,
                   f"steady_{solver}_relres_{n}": stats["rel_residual"]})
    wall_pcg = results["pcg"][2]
    for solver in ("mg", "mgcg"):
        speedup = wall_pcg / results[solver][2]
        print(f"# {solver} speedup over pcg at {n}^2: {speedup:.1f}x")
        rec.add(**{f"steady_{solver}_speedup_{n}": speedup})


def cosim_section(rec: Recorder, grid_n: int, n_intervals: int,
                  workloads) -> None:
    import math

    from repro.core import cosim, thermal
    from repro.core.floorplan import MM
    from repro.sweep import SweepSpec, run_sweep

    print()
    print(f"transient co-simulation (grid {grid_n}, {n_intervals} intervals, "
          f"implicit theta-scheme)")
    t_end = 0.25
    steps_per_interval = 2
    # the bare 4-layer logic stack, open loop, as one declarative sweep
    spec = SweepSpec(workloads=tuple(workloads), sizes=(2 ** 20,),
                     n_dram=(0,), fb_modes=("open",), grid_n=grid_n,
                     n_intervals=n_intervals, t_end=t_end,
                     steps_per_interval=steps_per_interval)
    res = run_sweep(spec, use_cache=False)
    # implicit step-count advantage vs the CFL-bound explicit oracle, on
    # the exact grids simulated (the AP and SIMD dies of the first workload)
    dp = cosim.comparable_design_point(workloads[0])
    n_imp = n_intervals * steps_per_interval
    for machine, area in (("ap", dp.ap_area_mm2), ("simd", dp.simd_area_mm2)):
        grid = thermal.Grid(die_w=math.sqrt(area) * MM, ny=grid_n, nx=grid_n,
                            margin=grid_n // 4)
        n_exp = max(int(t_end / thermal.explicit_dt(grid)), 1)
        print(f"steps ({workloads[0]}/{machine} die): explicit oracle "
              f"{n_exp}, implicit {n_imp} ({n_exp / n_imp:.0f}x fewer)")
        rec.add(**{f"implicit_step_advantage_{machine}": n_exp / n_imp})
    # one host-stepped implicit solve through the instrumented scan so the
    # telemetry snapshot carries per-step true residuals
    # (thermal/transient/*); the vmapped sweep replay above is fully
    # device-resident and records interval counts only
    import numpy as np
    probe = np.zeros((1, grid_n, grid_n), np.float32)
    probe[0, grid_n // 2, grid_n // 2] = 0.5
    _, pk, _ = thermal.transient_solve_implicit(
        probe, grid, t_end=t_end, n_steps=n_imp,
        inner=thermal.InnerSolve("pcg", 40), with_residuals=True)
    rec.add(transient_probe_peak_C=float(pk[-1].max()))
    print("workload,machine,layer,peak_max_C,peak_final_C,span_max_C,"
          "time_above_85C_s")
    for r_ in res.records:
        r = r_.report
        above = r.time_above()
        for l in range(r.peak_C.shape[1]):
            print(f"{r_.point.workload},{r_.machine},{l},"
                  f"{r.peak_C[:, l].max():.1f},{r.peak_C[-1, l]:.1f},"
                  f"{r.span_C[:, l].max():.2f},{above[l]:.3f}")
    for w in workloads:
        by_mc = {r_.machine: r_ for r_ in res.records
                 if r_.point.workload == w}
        print(f"# {w}: AP above-85C {by_mc['ap'].time_above_limit_s:.3f}s / "
              f"SIMD above-85C {by_mc['simd'].time_above_limit_s:.3f}s "
              f"of {t_end:.2f}s")
        rec.add(**{f"cosim_{w}_ap_above85_s":
                   by_mc["ap"].time_above_limit_s,
                   f"cosim_{w}_simd_above85_s":
                   by_mc["simd"].time_above_limit_s,
                   f"cosim_{w}_ap_peak_C":
                   float(by_mc["ap"].report.peak_C.max())})
    rec.add(cosim_cases=len(res.records))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small grids/intervals (CI smoke lane)")
    ap.add_argument("--solver-grid", type=int, default=256,
                    help="grid for the solver shoot-out (>= 256 is the "
                         "acceptance evidence)")
    args = ap.parse_args(argv)
    rec = Recorder("thermal")
    if args.quick:
        steady_section(rec, grid_ap=64, grid_simd=32)
        solver_section(rec, n=args.solver_grid)
        cosim_section(rec, grid_n=16, n_intervals=24,
                      workloads=("dmm", "fft"))
    else:
        steady_section(rec, grid_ap=128, grid_simd=64)
        solver_section(rec, n=args.solver_grid)
        cosim_section(rec, grid_n=32, n_intervals=64,
                      workloads=("dmm", "fft"))
    return rec.finish()


if __name__ == "__main__":
    main()
