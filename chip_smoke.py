"""Bring-up smoke of the closed-loop AP/thermal simulator on a TPU.

    python chip_smoke.py              # one chip: phases a-e
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

Runs the main path once, in this one process, through the entry points
a user calls, at the sizes its users run:

a. device      — the default device must be a TPU (no CPU fallback);
b. steady      — ``thermal.steady_state_stats`` on the 2xDRAM-on-logic
                 stack at the solver shoot-out grid (256^2 die + 64-cell
                 margin = 384^2 field) with pcg/mg/mgcg, and pcg/mg on
                 the Pallas kernels; each solve must be done by the
                 requested solver at attempt 1 and agree with a
                 multigrid solve on this process's CPU within 1e-3 C;
c. ap          — hist at 2^20 and sort at 2^16 words in megakernel mode
                 bit-identical to device mode, and one pass schedule on a
                 2^20-word APEngine through each Pallas backend
                 bit-identical to the jnp backend;
d. sweep       — a live closed-loop sweep (dmm, sort at 2^20 on
                 2xDRAM, 64^2 grid, mg inner solve): no failed group, every
                 report converged, dmm reads AP OK / SIMD BLOCKED;
e. serving     — stablelm-1.6b (full config widths) under diurnal
                 traffic for one simulated hour on a 32^2 grid: finite.

Every phase calls its work twice and prints the first call's seconds
(compilation included) and the second's (warm), both ended by
``block_until_ready`` — information, not a benchmark.  ``--chips 4``
runs only the multi-device paths against their single-device reference:
phase d with ``n_shards=4`` (agreement within ``SHARD_TOL_C``) and the
2^16-word megakernel sort with 4 lane shards (bit for bit).

Nothing is read from ``.sweep_cache/`` or from any file this run did
not write.  The script exits non-zero at the first failed check; its
last line on success is the JSON contract line
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

STEADY_N = 256            # die cells per side (bench_thermal solver grid)
STEADY_TOL_C = 1e-3       # agreement with the CPU multigrid solve
AP_WORDS = 2 ** 20        # the paper's AP width
SORT_WORDS = 2 ** 16
RESID_C = 0.05            # the Picard bar every sweep interval must meet


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def twice(fn):
    """Call ``fn`` twice -> (second result, first s, second s); both
    calls end in ``block_until_ready`` on the returned pytree."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def same_counters(a: dict, b: dict) -> list[str]:
    """Names of the counter entries that differ (arrays bit for bit)."""
    import numpy as np
    bad = sorted(set(a) ^ set(b))
    for k in set(a) & set(b):
        if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
            bad.append(k)
    return bad


# ---------------------------------------------------------------- phases

def phase_steady(n: int = STEADY_N) -> None:
    import jax
    import numpy as np
    from repro.core import thermal
    from repro.stack.spec import dram_on_logic

    spec = dram_on_logic(2)
    grid = thermal.Grid(die_w=5e-3, ny=n, nx=n, margin=n // 4, spec=spec)
    power = np.zeros((grid.n_die_layers, n, n), np.float32)
    power[list(spec.logic_layers)] = 40.0 / (len(spec.logic_layers) * n * n)
    print(f"[b] steady solves: 2xDRAM-on-logic, {n}^2 die + {n // 4} "
          f"margin = {grid.dom_ny}^2 x {grid.n_layers} field", flush=True)

    cpu = jax.devices("cpu")[0]
    ref_iters = {}
    with jax.default_device(cpu):
        for solver in thermal.SOLVERS:
            t0 = time.perf_counter()
            T, st = thermal.steady_state_stats(power, grid, solver=solver)
            T = np.asarray(T)
            ref_iters[solver] = st["iterations"]
            if solver == "mg":
                T_ref = T
            print(f"[b] cpu {solver}: iterations={st['iterations']} "
                  f"attempts={st['attempts']} "
                  f"s={time.perf_counter() - t0:.3f}", flush=True)

    for solver, pallas in (("pcg", False), ("mg", False), ("mgcg", False),
                           ("pcg", True), ("mg", True)):
        (T, st), s_first, s_warm = twice(
            lambda: thermal.steady_state_stats(power, grid, solver=solver,
                                               use_pallas=pallas))
        diff = float(np.abs(np.asarray(T) - T_ref).max())
        path = "pallas" if pallas else "jnp"
        print(f"[b] tpu {solver}/{path}: attempts={st['attempts']} "
              f"solved_by={st['solved_by']} iterations={st['iterations']} "
              f"(cpu {ref_iters[solver]}) rel_residual="
              f"{st['rel_residual']:.3e} maxdiff_vs_cpu_mg_C={diff:.3e} "
              f"peak_C={float(np.max(T)):.4f} first_s={s_first:.3f} "
              f"warm_s={s_warm:.3f}", flush=True)
        check(st["attempts"] == 1 and st["solved_by"] == solver,
              f"steady {solver}/{path} needed a fallback: {st}")
        check(diff <= STEADY_TOL_C,
              f"steady {solver}/{path} differs from the CPU mg solve by "
              f"{diff:.3e} C > {STEADY_TOL_C}")


def phase_ap(hist_words: int = AP_WORDS, sort_words: int = SORT_WORDS,
             engine_words: int = AP_WORDS) -> None:
    import numpy as np
    from repro.core import isa
    from repro.core.engine import APEngine
    from repro.workloads import registry

    for name, n in (("hist", hist_words), ("sort", sort_words)):
        ref = registry.trace_counters(name, n, mode="device")
        got, s_first, s_warm = twice(
            lambda: registry.trace_counters(name, n, mode="megakernel"))
        bad = same_counters(ref, got)
        print(f"[c] {name} n={n}: megakernel vs device cycles="
              f"{got['cycles']} energy={got['energy']!r} trace_events="
              f"{len(got['trace_cycles'])} mismatched={bad} "
              f"first_s={s_first:.3f} warm_s={s_warm:.3f}", flush=True)
        check(not bad, f"{name}: megakernel differs from device in {bad}")

    rng = np.random.default_rng(0)
    av = rng.integers(0, 1 << 16, engine_words, dtype=np.uint64)
    bv = rng.integers(0, 1 << 16, engine_words, dtype=np.uint64)

    def add_on(backend):
        eng = APEngine(n_words=engine_words, backend=backend)
        a, b = eng.alloc.alloc(16, "a"), eng.alloc.alloc(16, "b")
        carry = eng.alloc.alloc(1, "carry")
        eng.load(a, av)
        eng.load(b, bv)
        isa.run_add(eng, a, b, carry)
        return np.asarray(eng.planes), eng.counters(), eng.peek(b)

    ref_planes, ref_ctr, ref_sum = add_on("jnp")
    check(np.array_equal(ref_sum, (av + bv) & 0xFFFF), "jnp add is wrong")
    for backend in ("megakernel_pallas", "pallas"):
        (planes, ctr, _), s_first, s_warm = twice(lambda: add_on(backend))
        bad = same_counters(ref_ctr, ctr)
        same = np.array_equal(planes, ref_planes)
        print(f"[c] APEngine n_words={engine_words} add16 {backend} vs jnp: "
              f"planes_equal={same} counters_mismatched={bad} "
              f"cycles={ctr['cycles']} first_s={s_first:.3f} "
              f"warm_s={s_warm:.3f}", flush=True)
        check(same and not bad, f"{backend} add differs from jnp")


def sweep_spec(grid_n: int = 64, n_intervals: int = 64,
               size: int = AP_WORDS):
    from repro.sweep import SweepSpec
    return SweepSpec(workloads=("dmm", "sort"), sizes=(size,),
                     n_dram=(2,), grid_n=grid_n, n_intervals=n_intervals,
                     n_picard=20, solver="mg", ap_backend="megakernel")


def check_sweep(res) -> None:
    from repro import obs
    check(res.n_failed == 0, f"{res.n_failed} FAILED sweep record(s)")
    check(obs.value("sweep/groups_failed") == 0, "a sweep group failed")
    check(not res.from_cache, "sweep was served from a cache")
    for r in res.records:
        rep = r.report
        check(rep.converged and float(rep.residual_C.max()) < RESID_C,
              f"{r.label}: residual {float(rep.residual_C.max()):.3g} C")
    verdicts = {r.machine: r.verdict_ok for r in res.records
                if r.point.workload == "dmm"}
    check(verdicts == {"ap": True, "simd": False},
          f"dmm must read AP OK / SIMD BLOCKED, got {verdicts}")


def phase_sweep(**kw) -> None:
    from repro.sweep import run_sweep
    spec = sweep_spec(**kw)
    res, s_first, s_warm = twice(lambda: run_sweep(spec, use_cache=False))
    print(f"[d] sweep grid={spec.grid_n} intervals={spec.n_intervals} "
          f"solver={spec.solver} records={len(res.records)} "
          f"failed={res.n_failed} first_s={s_first:.3f} "
          f"warm_s={s_warm:.3f}", flush=True)
    for line in res.table().splitlines():
        print(f"[d] {line}")
    check_sweep(res)
    print("[d] dmm: AP OK / SIMD BLOCKED", flush=True)


def phase_serving(grid_n: int = 32, horizon_s: float = 3600.0) -> None:
    import numpy as np
    from repro.serving import ServingScenario, TrafficSpec, \
        run_serving_cosim
    sc = ServingScenario(config="stablelm-1.6b",
                         traffic=TrafficSpec(shape="diurnal",
                                             horizon_s=horizon_s),
                         grid_n=grid_n)
    reps, s_first, s_warm = twice(lambda: run_serving_cosim(sc))
    for machine, r in reps.items():
        st = r.stack
        vals = np.concatenate([st.peak_C.ravel(), st.residual_C.ravel(),
                               st.throttle.ravel(), r.latency_s.ravel(),
                               [r.mean_qps, r.throttle_residual]])
        finite = bool(np.isfinite(vals).all())
        print(f"[e] serving {r.label}: qps={r.mean_qps:.4f} "
              f"intervals={r.n_coarse}/{r.n_base} "
              f"dram_peak_C={float(st.dram_peak_C.max()):.3f} "
              f"p99_s={r.p99_s:.4f} finite={finite} first_s={s_first:.3f} "
              f"warm_s={s_warm:.3f}", flush=True)
        check(finite, f"serving {r.label} has non-finite results")
    check(set(reps) == {"ap", "simd"}, f"machines {sorted(reps)}")


def phase_sharded(n_shards: int = 4, sort_words: int = SORT_WORDS,
                  **kw) -> None:
    import numpy as np
    from repro.parallel.sharding import SHARD_TOL_C
    from repro.sweep import run_sweep
    from repro.workloads import sort

    spec = sweep_spec(**kw)
    ref = run_sweep(spec, use_cache=False)
    check_sweep(ref)
    got, s_first, s_warm = twice(
        lambda: run_sweep(spec, use_cache=False, n_shards=n_shards))
    check_sweep(got)
    worst = {}
    for a, b in zip(ref.records, got.records):
        check(a.label == b.label, f"record order {a.label} != {b.label}")
        for name in ("peak_C", "min_C", "residual_C", "throttle",
                     "refresh_W", "leak_W", "dyn_W"):
            x, y = getattr(a.report, name), getattr(b.report, name)
            d = float(np.abs(x - y).max())
            worst[name] = max(worst.get(name, 0.0), d)
            if name in ("peak_C", "min_C", "residual_C"):
                check(d <= SHARD_TOL_C, f"{a.label} {name} off by {d}")
            else:
                check(np.allclose(x, y, rtol=1e-3, atol=0),
                      f"{a.label} {name} off by {d}")
    print(f"[d4] sweep n_shards={n_shards} vs unsharded: max_abs_diff="
          f"{json.dumps(worst)} tol_C={SHARD_TOL_C} first_s={s_first:.3f} "
          f"warm_s={s_warm:.3f}", flush=True)

    x = np.random.default_rng(0).integers(0, 256, sort_words,
                                          dtype=np.uint64)
    ref_out, ref_ctr = sort.ap_sort(x, m=8, mode="megakernel")
    (out, ctr), s_first, s_warm = twice(
        lambda: sort.ap_sort(x, m=8, mode="megakernel", n_shards=n_shards))
    bad = same_counters(ref_ctr, ctr)
    same = np.array_equal(out, ref_out) and np.array_equal(out, np.sort(x))
    print(f"[c4] ap_sort n={sort_words} megakernel n_shards={n_shards} vs "
          f"unsharded: sorted_equal={same} counters_mismatched={bad} "
          f"cycles={ctr['cycles']} first_s={s_first:.3f} "
          f"warm_s={s_warm:.3f}", flush=True)
    check(same and not bad, "sharded sort differs from unsharded")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[a] device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"[a] FAIL: no TPU (default platform is {dev.platform!r}); "
              f"this smoke has no CPU fallback", flush=True)
        return 2
    if len(devices) < args.chips:
        print(f"[a] FAIL: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", flush=True)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache, obs
    print(f"[a] compilation cache: {compile_cache.enable()}", flush=True)
    obs.enable(reset=True)

    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("steady", phase_steady), ("ap", phase_ap),
               ("sweep", phase_sweep), ("serving", phase_serving)])
    t_all = time.perf_counter()
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s",
                  flush=True)
        check(obs.value("thermal/fallback/engaged") == 0,
              "a thermal solve fell back to another solver")
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(f"[all] phases passed in {time.perf_counter() - t_all:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
