"""Benchmark driver — one section per paper table/figure + the roofline
deliverable.

    PYTHONPATH=src python -m benchmarks.run [--quick] [section ...]

Every section's ``main(argv)`` records machine-readable metrics and
writes ``BENCH_<name>.json`` next to its text table
(``benchmarks/_record.py``); ``--quick`` forwards the CI smoke-lane
flag to each section.  ``tools/check_bench.py`` gates the JSON
artifacts against ``benchmarks/baseline.json``.
"""
import argparse
import sys
import time

from repro import compile_cache

from benchmarks import (bench_ap_backend, bench_cycles, bench_faults,
                        bench_policy, bench_roofline, bench_serving,
                        bench_speedup_power, bench_stack, bench_sweep,
                        bench_thermal, bench_workloads)

SECTIONS = {
    "cycles": ("§2.2 cycle-count claims", bench_cycles.main),
    "speedup_power": ("Figs 6/7 speedup & power vs area",
                      bench_speedup_power.main),
    "workloads": ("§3.1 workloads on the AP emulator",
                  bench_workloads.main),
    "thermal": ("§4 thermal comparison (Figs 10/12/13) + solver "
                "shoot-out", bench_thermal.main),
    "stack": ("abstract claim: AP+DRAM vs SIMD+DRAM closed-loop "
              "stacks (refresh/leakage/DTM feedback)",
              bench_stack.main),
    "sweep": ("scenario sweep: workloads x sizes x stacks through the "
              "cached vmapped path", bench_sweep.main),
    "policy": ("DTM/DVFS policy shoot-out: Pareto frontiers + verdict "
               "flips over the policy axis", bench_policy.main),
    "serving": ("LLM-serving traffic -> thermal co-simulation "
                "(SLA + coarsening headline)", bench_serving.main),
    "faults": ("fault injection: sensor faults vs GuardedPolicy, "
               "power spikes, solver fallback chain", bench_faults.main),
    "roofline": ("§Roofline per-cell terms (dry-run artifacts)",
                 bench_roofline.main),
    "ap_backend": ("paper-technique x assigned archs (AP vs TPU)",
                   bench_ap_backend.main),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="forward the CI smoke-lane flag to every section")
    ap.add_argument("sections", nargs="*", choices=[[]] + list(SECTIONS),
                    help="sections to run (default: all)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    wanted = args.sections or list(SECTIONS)
    section_argv = ["--quick"] if args.quick else []
    for name in wanted:
        title, fn = SECTIONS[name]
        print(f"\n===== {name}: {title} =====", flush=True)
        t0 = time.time()
        fn(section_argv)
        print(f"----- {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
